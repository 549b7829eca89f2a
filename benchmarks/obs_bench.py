"""Observability benchmark -> BENCH_obs.json.

CI-enforces the two tracing contracts of `repro.obs`:

- **zero emission when off** — tracing is opt-in everywhere; a DES run
  and a full virtual `TrafficGateway` run handed a *disabled*
  `TraceRecorder` must emit **exactly zero** events (every layer
  resolves the handle once and never calls a disabled recorder);
- **<5% DES slowdown when on** — the tentpole's overhead budget:
  paired, interleaved DES timings (tracing off vs on, the median
  of per-rep paired ratios, GC isolated so allocator pauses don't land on one arm) on
  the ``sensor_fusion`` window-preemption case must stay within
  ``MAX_OVERHEAD_FRAC``. One retry absorbs a host load spike landing
  mid-measurement (the same policy the wall-clock conformance case
  uses); two consecutive failures fail CI.

On top of the gates, the bench exercises the whole observability
surface once so the artifact doubles as a worked example:

- `MetricsRegistry.from_trace` snapshot (tardiness / response
  percentiles, preemption + xi counters, backlog gauges) with the
  Eq. 3 per-stage slack gauges filled from the admitted tenant set
  (`AdmissionController.headroom_report`);
- the Chrome-trace exporter (`write_chrome_trace`) on the DES stream —
  the written file loads in Perfetto / ``chrome://tracing``;
- a `trace_diff` self-check: a stream diffed against itself must be
  ``identical``; the same stream with one completion nudged past the
  tolerance must report exactly that event as the first divergence.

Run: ``PYTHONPATH=src python benchmarks/obs_bench.py [--quick]``
Writes ``experiments/benchmarks/BENCH_obs.json`` (and the demo trace
``experiments/benchmarks/TRACE_obs_demo.json``); exits non-zero if
either tracing contract is violated.
"""
from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import replace as dc_replace
from statistics import median

from repro.conformance import CostModel
from repro.core.perfmodel.hardware import paper_platform
from repro.core.rt.task import SegmentTable
from repro.obs import (
    MetricsRegistry,
    TraceRecorder,
    trace_diff,
    write_chrome_trace,
)
from repro.scheduler.des import simulate_taskset
from repro.traffic.scenarios import build, get_scenario
from repro.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join("experiments", "benchmarks")

#: the tentpole's enabled-tracing overhead budget on the DES
MAX_OVERHEAD_FRAC = 0.05


def _des_inputs(built, horizon_periods: float):
    """The window-preemption DES inputs the conformance case runs."""
    serve_tasks, requests, _arr = built.serve_bundle(
        period_scale=1.0, seed=0, max_dim=512
    )
    cm = CostModel.from_exec_model(
        built.design, list(built.workloads), serve_tasks
    )
    table = SegmentTable(
        base=cm.segment_table().base, overhead=[0.0] * cm.n_stages
    )
    horizon = horizon_periods * max(t.period for t in built.taskset.tasks)
    traces = built.des_arrivals(horizon)
    return table, cm, horizon, traces, requests


def _run_des(built, table, cm, horizon, traces, trace):
    return simulate_taskset(
        table,
        built.taskset,
        "edf",
        horizon=horizon,
        arrivals=traces,
        chunk_schedules=cm.chunk_schedule(),
        preemption="window",
        trace=trace,
    )


def bench_overhead(quick: bool) -> tuple[dict, bool]:
    """Paired DES timings, tracing off vs on, interleaved so host
    drift hits both arms equally; the reported overhead is the
    median of the per-rep paired ratios (each rep's pair runs
    back-to-back, so host speed drift cancels within the pair —
    per-arm aggregates don't have that property). Runs the
    ``sensor_fusion`` case — the registry's heaviest DES (most
    scheduling decisions per run), so the ratio is measured where
    per-event cost matters most and the per-rep run is long enough
    that timer noise does not swamp a percent-level budget. GC is
    collected and paused around each timed run: a generational pass
    triggered by the event buffer would otherwise bill an arbitrary
    arm for unrelated garbage. A measurement exceeding the budget is
    retried once (host load spikes are noise, not instrumentation
    cost); two consecutive failures count. Timings use CPU time
    (`time.process_time`): the instrumentation budget is CPU cost, and
    wall clock on a contended host charges scheduler preemptions to
    whichever arm they land on."""
    import gc

    built = build(
        get_scenario("sensor_fusion"), paper_platform(16), beam_width=4
    )
    horizon_periods = 30.0 if quick else 60.0
    reps = 11 if quick else 15
    table, cm, horizon, traces, _req = _des_inputs(built, horizon_periods)
    # warm both paths (JIT-free, but first-touch allocations matter)
    _run_des(built, table, cm, horizon, traces, None)
    _run_des(built, table, cm, horizon, traces, TraceRecorder())

    def measure() -> tuple[float, float, float, int]:
        t_off, t_on, ratios, n_events = [], [], [], 0
        for _ in range(reps):
            gc.collect()
            gc.disable()
            try:
                t0 = time.process_time()
                _run_des(built, table, cm, horizon, traces, None)
                off = time.process_time() - t0
                rec = TraceRecorder()
                t0 = time.process_time()
                _run_des(built, table, cm, horizon, traces, rec)
                on = time.process_time() - t0
            finally:
                gc.enable()
            t_off.append(off)
            t_on.append(on)
            ratios.append((on - off) / off)
            n_events = len(rec.events)
        # the estimator is the MEDIAN OF PAIRED PER-REP RATIOS: each
        # rep's off/on pair runs back-to-back, so the host's
        # seconds-scale speed drift (frequency scaling, neighbors)
        # cancels within the pair; independent per-arm minima/medians
        # can land in different machine states and swing points in
        # either direction (measured on this very case)
        return median(ratios), median(t_off), median(t_on), n_events

    attempts = []
    ok = False
    for attempt in range(2):
        overhead, off_s, on_s, n_events = measure()
        ok = overhead < MAX_OVERHEAD_FRAC
        attempts.append(
            {
                "attempt": attempt,
                "des_off_s": off_s,
                "des_on_s": on_s,
                "overhead_frac": overhead,
            }
        )
        print(
            f"overhead[{attempt}]: des off={1e3 * off_s:.2f}ms "
            f"on={1e3 * on_s:.2f}ms ({n_events} events) -> "
            f"{100 * overhead:+.2f}% "
            f"(budget {100 * MAX_OVERHEAD_FRAC:.0f}%) "
            f"{'OK' if ok else 'VIOLATED'}"
        )
        if ok:
            break
        print(
            f"tracing overhead {100 * overhead:.2f}% exceeds the "
            f"{100 * MAX_OVERHEAD_FRAC:.0f}% budget"
            + ("; retrying once" if attempt == 0 else "; giving up"),
            file=sys.stderr,
        )
    return {
        "scenario": "sensor_fusion",
        "reps": reps,
        "horizon_periods": horizon_periods,
        "events_per_run": n_events,
        "attempts": attempts,
        "overhead_frac": attempts[-1]["overhead_frac"],
        "budget_frac": MAX_OVERHEAD_FRAC,
        "ok": ok,
    }, ok


def bench_zero_emission(built, quick: bool) -> tuple[dict, bool]:
    """A disabled recorder through the DES *and* a full virtual gateway
    run (admission, rate limiting, shedding paths armed) must stay
    empty."""
    from repro.traffic import RateLimiter
    from repro.traffic.clock import VirtualClock
    from repro.traffic.gateway import TrafficGateway
    from repro.traffic.shedding import get_policy
    from repro.pipeline.serve import PharosServer

    table, cm, horizon, traces, requests = _des_inputs(
        built, 20.0 if quick else 40.0
    )
    off = TraceRecorder(enabled=False)
    _run_des(built, table, cm, horizon, traces, off)
    des_events = len(off.events)

    serve_tasks, requests, arrivals = built.serve_bundle(
        period_scale=1.0, seed=0, max_dim=512
    )
    from repro.traffic.admission import AdmissionController

    clk = VirtualClock()
    srv = PharosServer(
        serve_tasks,
        built.design.n_stages,
        policy="edf",
        cost_model=cm,
        clock=clk.now,
        sleep=clk.sleep,
        trace=off,
    )
    gw = TrafficGateway(
        srv,
        AdmissionController(
            [0.0] * built.design.n_stages, preemptive=True
        ),
        requests,
        arrivals,
        shedding=get_policy("reject_newest"),
        ratelimit=RateLimiter.for_requests(requests, burst_periods=3.0),
        clock=clk,
        trace=off,
    )
    gw.run(horizon)
    total = len(off.events)
    ok = total == 0 and des_events == 0
    print(
        f"zero-emission: disabled recorder collected {total} events "
        f"{'OK' if ok else 'VIOLATED'}"
    )
    if not ok:
        print(
            f"disabled tracing emitted {total} events (must be 0)",
            file=sys.stderr,
        )
    return {"events_while_disabled": total, "ok": ok}, ok


def bench_surface(built, quick: bool) -> dict:
    """One worked pass over metrics, export and diff."""
    from repro.traffic.admission import AdmissionController

    table, cm, horizon, traces, requests = _des_inputs(
        built, 20.0 if quick else 40.0
    )
    rec = TraceRecorder()
    _run_des(built, table, cm, horizon, traces, rec)

    # metrics: trace-derived registry + Eq. 3 slack gauges from the
    # admitted tenant set
    reg = MetricsRegistry.from_trace(rec.events)
    admission = AdmissionController(
        [0.0] * built.design.n_stages, preemptive=True
    )
    for r in requests:
        admission.admit(r)
    hr = admission.headroom_report()
    reg.set_eq3_slacks([s.slack for s in hr.stages])
    snapshot = reg.snapshot()

    # Chrome export (Perfetto-loadable)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    trace_path = os.path.join(RESULTS_DIR, "TRACE_obs_demo.json")
    doc = write_chrome_trace(rec.events, trace_path)

    # diff self-check: identical against itself ...
    same = trace_diff(rec, rec)
    assert same.identical, f"self-diff not identical: {same.summary()}"
    # ... and a single nudged completion is *the* reported divergence
    completes = [e for e in rec.events if e.kind == "complete"]
    victim = completes[len(completes) // 2]
    perturbed = [
        dc_replace(e, t=e.t + 1.0) if e is victim else e
        for e in rec.events
    ]
    skewed = trace_diff(rec.events, perturbed, time_tol=1e-6)
    assert not skewed.identical, "perturbed diff claims identical"
    assert skewed.divergence is not None
    assert skewed.divergence.task == victim.task, (
        f"divergence blamed {skewed.divergence.task}, "
        f"nudged {victim.task}"
    )
    print(f"diff self-check: {same.summary()} / {skewed.summary()}")

    return {
        "metrics_snapshot": snapshot,
        "eq3_stage_slacks": [s.slack for s in hr.stages],
        "chrome_trace_path": trace_path,
        "chrome_trace_events": len(doc["traceEvents"]),
        "diff_identical": same.summary(),
        "diff_perturbed": skewed.summary(),
    }


def main() -> None:
    quick = "--quick" in sys.argv
    built = build(
        get_scenario("steady_city"), paper_platform(16), beam_width=4
    )
    zero, zero_ok = bench_zero_emission(built, quick)
    over, over_ok = bench_overhead(quick)
    payload = {
        "bench": "obs",
        "quick": quick,
        "zero_emission": zero,
        "overhead": over,
        "surface": bench_surface(built, quick),
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, "BENCH_obs.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nwrote {path}")
    if not (zero_ok and over_ok):
        print("OBSERVABILITY CONTRACT VIOLATED", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
