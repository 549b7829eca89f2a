"""Deadline-compliant serving: FIFO vs EDF on a mixed-criticality mix.

Two tenants share a 2-stage PHAROS pipeline:
- ``perception`` — heavyweight inference, relaxed deadline,
- ``safety``     — lightweight inference, tight deadline (the paper's
  smart-transportation safety monitor).

Under FIFO the safety task queues behind perception layers; under EDF
the scheduler preempts perception *inside a layer* at a tile-window
boundary (the preemptible-matmul mechanism), spilling the fp32 partial
accumulator and resuming later — deadline misses drop accordingly.

Run: ``PYTHONPATH=src python examples/serve_edf.py``
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.pipeline.serve import PharosServer, ServeTask
from repro.compile_cache import enable_compile_cache


def mk_weights(dims, seed):
    key = jax.random.PRNGKey(seed)
    out = []
    for (k_dim, n_dim) in dims:
        key, sub = jax.random.split(key)
        out.append(
            jax.random.normal(sub, (k_dim, n_dim), jnp.float32)
            / jnp.sqrt(k_dim)
        )
    return tuple(out)


def main():
    perception = ServeTask(
        "perception",
        mk_weights([(512, 1024), (1024, 1024), (1024, 512)], 0),
        stage_of_layer=(0, 0, 1),
        period=0.08,
        input_rows=1024,
    )
    safety = ServeTask(
        "safety",
        mk_weights([(128, 256), (256, 128)], 1),
        stage_of_layer=(0, 1),
        period=0.02,
        deadline=0.012,
        input_rows=128,
    )

    for policy in ("fifo", "edf"):
        srv = PharosServer(
            [perception, safety], n_stages=2, policy=policy, window_tiles=2
        )
        rep = srv.run(horizon_s=2.0)
        print(f"\n== {policy.upper()} ==")
        for name in ("perception", "safety"):
            r = rep.response_times[name]
            if not r:
                continue
            arr = np.asarray(r)
            misses = rep.deadline_misses[name]
            print(
                f"  {name:11s} jobs={len(r):4d} "
                f"mean={1e3*arr.mean():7.2f}ms p99={1e3*np.quantile(arr,0.99):7.2f}ms "
                f"max={1e3*arr.max():7.2f}ms deadline_misses={misses}"
            )
        print(f"  preemptions={rep.preemptions} "
              f"windows={rep.windows_executed}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
