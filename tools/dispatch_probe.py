#!/usr/bin/env python3
"""Host cost of one call from the serving thread, by kind of call.

    python tools/dispatch_probe.py [--calls 5000] [--dim 2048]

Times ``--calls`` back-to-back calls of each kind on JAX's default
backend and prints host microseconds per call (the loop's time, up to
the last result being ready, over the calls):

- ``asarray``: eager ``jnp.asarray(int)``, the start a window
  transferred from the host before launch plans;
- ``zeros``: eager ``jnp.zeros((128, dim))``, the accumulator a layer
  allocated before launch plans;
- ``jit_noop``: a jitted identity on a device scalar;
- ``window_device_start`` / ``window_numpy_start``:
  ``matmul_window_call`` on a decode window, ``(128, dim) @ (dim,
  dim)`` four tiles wide, its start a device scalar or a NumPy int;
- ``served_launch``: the same window through `_run_window` and its
  `LaunchPlan`, as `PharosServer` launches it.

The last line of standard output is the result as one JSON object.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

BLOCK = (128, 128, 128)
WINDOW_TILES = 4


def per_call_us(fn, calls: int) -> float:
    """Host microseconds per call of ``fn`` over ``calls`` calls, after
    a warm-up call; the last result is waited for."""
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e6


def probe(calls: int = 5000, dim: int = 2048) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.preemptible_matmul import grid_geometry, pick_window
    from repro.kernels.preemptible_matmul.kernel import matmul_window_call
    from repro.pipeline.serve import _run_window

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (128, dim), jnp.float32)
    b = jax.random.normal(k2, (dim, dim), jnp.float32)
    c = jnp.zeros((128, dim), jnp.float32)
    _, n_n, k_steps, total = grid_geometry(128, dim, dim, BLOCK)
    window = pick_window(total, WINDOW_TILES)
    kw = dict(block=BLOCK, window=window, n_tiles_n=n_n, k_steps=k_steps)
    start = jax.device_put(np.int32(0))
    noop = jax.jit(lambda x: x)
    kinds = {
        "asarray": lambda: jnp.asarray(0, jnp.int32),
        "zeros": lambda: jnp.zeros((128, dim), jnp.float32),
        "jit_noop": lambda: noop(start),
        "window_device_start": lambda: matmul_window_call(start, a, b, c, **kw),
        "window_numpy_start": lambda: matmul_window_call(
            np.int32(0), a, b, c, **kw
        ),
        "served_launch": lambda: _run_window(
            a, b, c, 0, block=BLOCK, window=window, backend="pallas"
        )[0],
    }
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "calls": calls,
        "dim": dim,
        "us_per_call": {k: per_call_us(fn, calls) for k, fn in kinds.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=5000)
    ap.add_argument("--dim", type=int, default=2048)
    args = ap.parse_args(argv)
    res = probe(args.calls, args.dim)
    for k, us in res["us_per_call"].items():
        print(f"{k:>22}: {us:.3f} us a call")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
