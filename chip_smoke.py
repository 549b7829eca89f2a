#!/usr/bin/env python3
"""Chip smoke test: PHAROS's serving path end to end on one TPU.

One chip (the default) runs two phases through the entry points a user
calls — ``TrafficGateway`` -> ``PharosServer(backend="pallas")`` -> the
compiled preemptible-matmul Pallas windows:

- serve: the ``copilot_decode`` scenario as the DSE places it on
  ``paper_platform(16)`` (3 stages, EDF): a DeiT-T safety monitor next
  to stablelm-1.6b decode (batch 8, 2k context), built at full model
  widths (``max_dim=None``: 128 input rows, about 8.1 GB of fp32
  surrogate weights, built once). A `CostModel` is calibrated on the
  chip, periods move onto the wall timebase by the measured/modeled
  WCET ratio, and the gateway serves on a `WallClock` with calibrated
  admission until every tenant meets its job quota (or a wall limit);
- correctness: on the same server, one more job per tenant is compared
  with a plain ``jnp`` chain of the same weights at
  ``Precision.HIGHEST``.

``--chips 4`` runs only the SPMD stage pipeline (`pipeline_backbone`,
full-width stablelm-1.6b, 6 of its 24 layers per chip on a 4-chip
``stage`` mesh) and compares it with `reference_backbone` on one chip.

Run from the repository root on a TPU host::

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the four-chip pipeline only

Exit code 0 only when every phase passed; the last line of stdout is
then ``{"ok": true, "device": {...}}``. Without a TPU it exits non-zero
and prints no result. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SCENARIO = "copilot_decode"
#: completed jobs each tenant must reach in the serve phase
QUOTAS = {"deit_t": 20, "stablelm_1_6b:decode": 3}
#: the serve phase stops here even if a quota is still open (and fails)
WALL_LIMIT_S = 240.0
#: correctness bound, max |served - reference| / max |reference|. Both
#: sides are fp32 (the window kernel and the reference contract at
#: ``Precision.HIGHEST``) and differ only in accumulation order: a
#: K-term fp32 dot product carries ~sqrt(K) * 2**-24 relative rounding,
#: under 1e-5 for K <= 11264, and over the 121-layer decode chain those
#: differences add like a random walk to ~1e-4. 1e-3 leaves 10x margin
#: and still fails a silent bf16 contraction: with the window's dot at
#: ``Precision.DEFAULT``, or its operands cast to bf16, one v5e read
#: 6.9e-3 (DeiT) and 2.1e-2 (decode) at full width, against 2.7e-7 and
#: 2.3e-6 for the sound kernel (``tests/test_chip_smoke.py`` keeps the
#: bf16-operand control on the CPU).
REL_ERR_BOUND = 1e-3
#: pipeline-vs-reference bound for ``--chips 4``: both run the same
#: bf16 blocks, so they differ only where XLA fuses the two programs
#: differently (a few bf16 roundings, 2**-8 each, across 24 layers); a
#: stage or microbatch out of order gives O(1) error instead.
PIPE_REL_ERR_BOUND = 5e-2
#: TPU kinds whose peaks the exec model (`repro.core.perfmodel.hardware`)
#: describes — measured/modeled WCET ratios mean something only there
V5E_KINDS = ("TPU v5 lite", "TPU v5e")


def device_info() -> tuple[str, str, int]:
    """``(platform, device_kind, device count)`` as JAX reports them."""
    import jax

    devs = jax.devices()
    return devs[0].platform, devs[0].device_kind, len(devs)


def peak_bytes() -> int | None:
    """``peak_bytes_in_use`` of the first device (None where the backend
    does not report memory statistics, as on the CPU)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def build_tenants(max_dim: int | None = None, seed: int = 0):
    """``copilot_decode`` on ``paper_platform(16)`` and its serve tasks
    on the model timebase (weights materialized once, here)."""
    from repro.core.perfmodel.hardware import paper_platform
    from repro.traffic.scenarios import build, get_scenario

    built = build(get_scenario(SCENARIO), paper_platform(16), seed=seed)
    return built, built.serve_tasks(max_dim=max_dim)


def serve_phase(
    built,
    serve_model,
    *,
    quotas=QUOTAS,
    wall_limit_s: float = WALL_LIMIT_S,
    seed: int = 0,
    log=print,
):
    """Calibrate, rescale and serve through the gateway on a wall clock.

    Returns ``(server, result)``; ``result["ok"]`` is False when a tenant
    was refused admission or missed its completion quota.
    """
    from repro.conformance import ConformanceConfig, wall_timebase
    from repro.pipeline.serve import PharosServer
    from repro.traffic.admission import (
        AdmissionController,
        calibrated_requests,
    )
    from repro.traffic.clock import WallClock
    from repro.traffic.gateway import TrafficGateway

    n_stages = built.design.n_stages
    policy = built.scenario.policy
    probe = PharosServer(serve_model, n_stages, policy=policy, backend="pallas")
    t0 = time.perf_counter()
    probe.warmup()
    warmup_s = time.perf_counter() - t0
    # the wall-clock conformance case's timebase: measured utilization
    # at most 1/headroom of the modeled one on every stage
    t0 = time.perf_counter()
    wall = wall_timebase(
        built, probe,
        reps=3, headroom=ConformanceConfig().wall_scale_headroom, seed=seed,
    )
    calibrate_s = time.perf_counter() - t0
    tasks = wall.tasks
    log(
        f"serve: {n_stages} stages, {policy}; warm-up {warmup_s:.3f} s, "
        f"calibration {calibrate_s:.3f} s; periods "
        + ", ".join(f"{t.name}={t.period:.6g} s" for t in tasks)
    )

    clock = WallClock()
    server = PharosServer(
        tasks, n_stages, policy=policy, backend="pallas",
        clock=clock.now, sleep=clock.sleep,
    )
    gateway = TrafficGateway(
        server,
        AdmissionController([0.0] * n_stages, preemptive=(policy == "edf")),
        calibrated_requests(wall.measured, wall.requests),
        wall.arrivals,
        clock=clock,
    )
    quota = [quotas[t.name] for t in tasks]
    t0 = time.perf_counter()
    gateway.begin_run(wall_limit_s, warmup=True)
    serve_warmup_s = time.perf_counter() - t0
    while True:
        rel = gateway.release_due()
        if rel >= wall_limit_s or all(
            n >= q for n, q in zip(server.completed_per_task, quota)
        ):
            break
        if not server.step():
            clock.sleep(1e-4)
    report = gateway.finish_run()
    sr = report.server_report

    ok = True
    tenants = {}
    for t, q, dec in zip(tasks, quota, report.decisions):
        pct = sr.response_percentiles(t.name, (50, 99))
        jobs = len(sr.response_times[t.name])
        tenants[t.name] = {
            "admitted": dec.admitted,
            "jobs": jobs,
            "quota": q,
            "p50_s": pct["p50"],
            "p99_s": pct["p99"],
            "deadline_s": t.deadline,
            "misses": sr.deadline_misses[t.name],
        }
        passed = dec.admitted and jobs >= q
        ok &= passed
        log(
            f"  {t.name}: jobs={jobs} (quota {q}) p50={pct['p50']:.6g} s "
            f"p99={pct['p99']:.6g} s deadline={t.deadline:.6g} s "
            f"misses={sr.deadline_misses[t.name]}"
            + ("" if passed else "  FAIL")
        )
    result = {
        "ok": ok,
        "tenants": tenants,
        "preemptions": sr.preemptions,
        "windows": sr.windows_executed,
        "served_s": rel,
        "warmup_s": warmup_s,
        "serve_warmup_s": serve_warmup_s,
        "calibrate_s": calibrate_s,
        "wcet_ratio": wall.wcet_ratio,
        "period_scale": wall.period_scale,
        "peak_bytes_in_use": peak_bytes(),
    }
    log(
        f"  preemptions={sr.preemptions} windows={sr.windows_executed} "
        f"served {rel:.3f} s; serve warm-up {serve_warmup_s:.3f} s; "
        f"peak_bytes_in_use={result['peak_bytes_in_use']}"
    )
    return server, result


def reference_chain(x, weights):
    """The served computation in plain ``jnp``: ``x @ W1 @ ... @ Wn``."""
    import jax
    import jax.numpy as jnp

    for w in weights:
        x = jnp.dot(
            x, w,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    return x


def correctness_phase(
    server, *, limit_s: float = WALL_LIMIT_S, bound=REL_ERR_BOUND, log=print
):
    """Serve one more job per tenant on ``server`` and compare each
    output with `reference_chain`. Returns ``{"ok", "rel_err"}``."""
    import jax.numpy as jnp

    jobs = [server.submit(i) for i in range(len(server.tasks))]
    t0 = time.perf_counter()
    while any(j.done_at is None for j in jobs):
        if time.perf_counter() - t0 > limit_s:
            raise TimeoutError("correctness jobs did not finish in time")
        server.step()
    ok = True
    errs = {}
    for job in jobs:
        t = server.tasks[job.task_id]
        ref = reference_chain(server.inputs[job.task_id], t.weights)
        out = job.x
        finite = bool(jnp.all(jnp.isfinite(out)))
        err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        passed = finite and out.shape == ref.shape and err <= bound
        ok &= passed
        errs[t.name] = err
        log(
            f"  correctness {t.name}: {len(t.weights)} layers, out "
            f"{tuple(out.shape)}, max rel err {err:.3e} (bound {bound:g})"
            + ("" if passed else "  FAIL")
        )
    return {"ok": ok, "rel_err": errs}


def pipeline_phase(
    cfg=None,
    *,
    n_stages: int = 4,
    n_micro: int = 4,
    batch: int = 1,
    seq: int = 2048,
    seed: int = 0,
    bound=PIPE_REL_ERR_BOUND,
    log=print,
):
    """`pipeline_backbone` on an ``n_stages``-device ``stage`` mesh vs
    `reference_backbone` on one device; block weights are created
    sharded, so each stage's repeats live only on its own device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.stablelm_1_6b import CONFIG
    from repro.models import lm
    from repro.pipeline.executor import (
        make_stage_mesh,
        pipeline_backbone,
        reference_backbone,
    )

    cfg = cfg or CONFIG
    mesh = make_stage_mesh(n_stages)
    devices = list(mesh.devices.flat)
    t0 = time.perf_counter()
    blocks = jax.jit(
        lambda k: lm.init_params(k, cfg)["blocks"],
        out_shardings=NamedSharding(mesh, P("stage")),
    )(jax.random.PRNGKey(seed))
    jax.block_until_ready(blocks)
    init_s = time.perf_counter() - t0
    total = sum(x.nbytes for x in jax.tree.leaves(blocks))
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(blocks):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    placed = all(held[d] == total // n_stages for d in devices)
    for d in devices:
        stats = d.memory_stats() or {}
        log(
            f"  {d}: block weights {held[d]} B of {total} B, "
            f"bytes_in_use={stats.get('bytes_in_use')}"
        )

    micro = jax.random.normal(
        jax.random.PRNGKey(seed + 1),
        (n_micro, batch, seq, cfg.d_model),
        jnp.bfloat16,
    )
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        out = jax.block_until_ready(
            jax.jit(pipeline_backbone(cfg, mesh, n_stages))(blocks, micro)
        )
    pipe_s = time.perf_counter() - t0
    one = jax.device_put(blocks, devices[0])
    ref = reference_backbone(cfg, {"blocks": one}, micro)
    out32 = jax.device_put(out, devices[0]).astype(jnp.float32)
    ref32 = ref.astype(jnp.float32)
    finite = bool(jnp.all(jnp.isfinite(out32)))
    err = float(jnp.max(jnp.abs(out32 - ref32)) / jnp.max(jnp.abs(ref32)))
    ok = placed and finite and out.shape == ref.shape and err <= bound
    log(
        f"  pipeline {cfg.name}: {cfg.n_layers} layers over {n_stages} "
        f"stages, micro {tuple(micro.shape)}; init {init_s:.3f} s, "
        f"first call (compile + run) {pipe_s:.3f} s; max rel err vs "
        f"one-device reference {err:.3e} (bound {bound:g}); weights on "
        f"every stage: {placed}" + ("" if ok else "  FAIL")
    )
    return {"ok": ok, "rel_err": err, "held_bytes": [held[d] for d in devices]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
    )
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the SPMD stage pipeline across four chips",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    platform, kind, count = device_info()
    print(f"device: platform={platform} kind={kind!r} count={count}")
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}", file=sys.stderr)
        return 1
    if kind not in V5E_KINDS:
        print(
            f"chip_smoke: unknown TPU kind {kind!r}; the exec model "
            f"describes {V5E_KINDS}",
            file=sys.stderr,
        )
        return 1
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {count} found", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        ok = pipeline_phase(seed=args.seed)["ok"]
    else:
        t0 = time.perf_counter()
        built, serve_model = build_tenants(None, args.seed)
        n_params = sum(w.size for t in serve_model for w in t.weights)
        print(
            f"build: {SCENARIO}, "
            + ", ".join(f"{t.name} {len(t.weights)} layers" for t in serve_model)
            + f"; {n_params} fp32 weights in {time.perf_counter() - t0:.3f} s"
        )
        server, served = serve_phase(built, serve_model, seed=args.seed)
        print(f"  measured/modeled WCET ratio ({kind}): {served['wcet_ratio']:.6g}")
        checked = correctness_phase(server)
        print(f"  peak_bytes_in_use={peak_bytes()}")
        ok = served["ok"] and checked["ok"]
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": platform, "kind": kind, "count": count},
    }))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    sys.exit(main())
