"""Admission-control micro-benchmark -> BENCH_admission.json.

Two hot paths of the traffic subsystem:

1. **Admit-check latency** — `AdmissionController.check` is the per
   tenancy-change fast path; it must be O(stages), independent of how
   many tenants are resident. We time it across resident-set sizes and
   compare against the full re-analysis (rebuild `SegmentTable` +
   `srt_schedulable`), whose cost grows with the tenant count.
2. **Gateway release jitter** — how late the `TrafficGateway` releases
   jobs relative to their scheduled arrival times on a virtual-clock
   serving run (jitter is bounded by the serving quantum) and on a
   wall-clock run of the release loop.

Run: ``PYTHONPATH=src python benchmarks/admission_bench.py [--quick]``
Writes ``experiments/benchmarks/BENCH_admission.json``.
"""
from __future__ import annotations

import json
import os
import random
import statistics
import sys
import time

from repro.core.rt.schedulability import srt_schedulable
from repro.core.rt.task import LayerDesc, SegmentTable, Task, TaskSet, Workload
from repro.traffic import (
    AdmissionController,
    PeriodicArrivals,
    PoissonArrivals,
    TaskRequest,
    TrafficGateway,
    VirtualClock,
)
from repro.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join("experiments", "benchmarks")


def _percentiles(xs):
    xs = sorted(xs)

    def pct(p):
        return xs[min(len(xs) - 1, int(p * len(xs)))]

    return {
        "mean": statistics.fmean(xs),
        "p50": pct(0.50),
        "p99": pct(0.99),
        "max": xs[-1],
    }


def _mk_controller(n_tenants: int, n_stages: int, rng: random.Random):
    ctl = AdmissionController([0.001] * n_stages, preemptive=True)
    for j in range(n_tenants):
        base = tuple(
            rng.uniform(0.001, 0.5 / max(1, n_tenants)) for _ in range(n_stages)
        )
        ctl.admit(TaskRequest(f"t{j}", base, period=rng.uniform(0.5, 2.0)))
    return ctl


def bench_admit_check(quick: bool) -> dict:
    rng = random.Random(0)
    reps = 200 if quick else 2000
    out = {}
    for n_tenants in (4, 16, 64) if quick else (4, 16, 64, 256):
        n_stages = 4
        ctl = _mk_controller(n_tenants, n_stages, rng)
        probes = [
            TaskRequest(
                f"p{j}",
                tuple(rng.uniform(0.001, 0.05) for _ in range(n_stages)),
                period=rng.uniform(0.5, 2.0),
            )
            for j in range(64)
        ]
        # incremental O(stages) check
        inc_ns = []
        for i in range(reps):
            p = probes[i % len(probes)]
            t0 = time.perf_counter_ns()
            ctl.check(p)
            inc_ns.append(time.perf_counter_ns() - t0)
        # full re-analysis: rebuild table + taskset + Eq. 3
        w = Workload("w", (LayerDesc("l", 8, 8, 8),))
        full_ns = []
        for i in range(max(20, reps // 10)):
            p = probes[i % len(probes)]
            t0 = time.perf_counter_ns()
            reqs = list(ctl.admitted) + [p]
            table = SegmentTable(
                base=[list(r.base) for r in reqs],
                overhead=list(ctl.overheads),
            )
            ts = TaskSet(
                tasks=tuple(
                    Task(workload=w, period=r.period, name=r.name)
                    for r in reqs
                )
            )
            srt_schedulable(table, ts, preemptive=True)
            full_ns.append(time.perf_counter_ns() - t0)
        inc, full = _percentiles(inc_ns), _percentiles(full_ns)
        out[f"tenants_{n_tenants}"] = {
            "incremental_check_ns": inc,
            "full_reanalysis_ns": full,
            "speedup_mean": full["mean"] / inc["mean"],
        }
    return out


def bench_gateway_jitter(quick: bool) -> dict:
    """Release jitter on a virtual-clock serving run with real GEMMs."""
    import jax
    import jax.numpy as jnp

    from repro.pipeline.serve import PharosServer, ServeTask

    def weights(dims, key):
        k = jax.random.PRNGKey(key)
        ws = []
        for (K, N) in dims:
            k, s = jax.random.split(k)
            ws.append(
                jax.random.normal(s, (K, N), jnp.float32) / jnp.sqrt(K)
            )
        return tuple(ws)

    dt = 1e-3
    tasks = [
        ServeTask(
            "a", weights([(128, 128), (128, 128)], 0), (0, 1), period=0.01
        ),
        ServeTask(
            "b", weights([(128, 128), (128, 128)], 1), (0, 1), period=0.02
        ),
    ]
    reqs = [
        TaskRequest("a", (dt, dt), period=0.01),
        TaskRequest("b", (dt, dt), period=0.02),
    ]
    clk = VirtualClock()
    srv = PharosServer(tasks, 2, clock=clk.now, sleep=clk.sleep)
    gw = TrafficGateway(
        srv,
        AdmissionController([0.0, 0.0]),
        reqs,
        [PeriodicArrivals(period=0.01), PoissonArrivals(rate=40.0, seed=2)],
        clock=clk,
    )
    horizon = 0.5 if quick else 2.0
    t_wall = time.perf_counter()
    rep = gw.run(horizon, virtual_dt=dt)
    wall_s = time.perf_counter() - t_wall
    jitters = [j for t in rep.tenants for j in t.release_jitter]
    return {
        "virtual_dt_s": dt,
        "horizon_virtual_s": horizon,
        "wall_seconds": wall_s,
        "jobs_released": rep.total_released(),
        "release_jitter_s": _percentiles(jitters or [0.0]),
    }


def main() -> None:
    quick = "--quick" in sys.argv
    payload = {
        "bench": "admission",
        "quick": quick,
        "admit_check": bench_admit_check(quick),
        "gateway": bench_gateway_jitter(quick),
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, "BENCH_admission.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps(payload, indent=2))
    print(f"\nwrote {path}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
