"""Cross-layer conformance sweep -> BENCH_conformance.json.

Runs the `repro.conformance` harness over the registry's
contract-honouring scenarios x {fifo, edf} and records, per case and
per task, the three layers' responses (analytic bound, DES max,
virtual-runtime max), the verdict chain, and every ordering violation.
A clean run — the acceptance gate — has **zero** violations: the
analytic bound dominates the DES, the DES dominates the executing
runtime (within the tie-breaking tolerance), and no layer's
schedulability verdict inverts.

The sweep runs with ``record_traces`` on: every case row carries its
host ``wall_seconds`` and a ``trace_diff`` verdict (``identical`` or
the first divergent event) from the `repro.obs` schedule traces — the
bench asserts the verdict exists for every registry case.

Seven CI-enforced invariants ride on top of the sweep:

- **tightened tolerance** — the window-boundary DES must hold a
  DES-vs-runtime tolerance *strictly below* the PR-2 values that
  absorbed the idealized-DES deferral gap (asserted against
  `PR2_TOL_REL` / `PR2_QUANTUM_SLACK`), and — now that the DES adopts
  the runtime's simultaneous-event tie-breaking — strictly below the
  pre-alignment `PR3_QUANTUM_SLACK` too;
- **sharded cases** — `run_sharded_case` places ``sharded_city``
  across K pipeline shards (every placement policy) and holds every
  shard to the full three-layer contract plus a bit-exact per-shard
  admission verdict;
- **DSE case** — `run_dse_case` pushes the search's claimed-feasible
  designs through all three layers and serves the scenario on a
  DSE-provisioned 2-shard `ShardedGateway` (zero violations required);
- **shedding cases** — `run_shedding_case` drives overdriven
  scenarios with identical drop-shedding armed in DES and runtime and
  matches the surviving jobs by release time;
- **migration cases** — `run_migration_case` live-migrates
  ``sharded_city`` tenants between co-simulated elastic shards
  (slack-aware and explicit targets, both policies) and fails CI on
  any deadline violation during a handover, any DES/runtime
  survivor-set disagreement, or a re-home without a committed Eq. 3
  proof;
- **mode-switch cases** — `run_mode_switch_case` drives the
  mixed-criticality ``av_stack`` scenario with twin `ModeController`s
  armed in DES and runtime; CI fails on any HI-class guarantee miss
  across a transition, on survivor-set disagreement, and on a
  committed switch whose Eq. 3 re-proof failed;
- **wall-clock case** — `run_wallclock_case` drives the gateway on the
  real clock against the calibrated `CostModel` in calibrated-admission
  mode (tenancy admitted against measured WCETs; one retry absorbs a
  host throttle landing mid-run; two consecutive failures fail CI).

Also times a wall-clock WCET calibration pass (`CostModel.calibrate`)
on the ``steady_city`` serve bundle and reports measured-vs-modeled
segment WCET ratios — the "measured, not modeled" serve-path numbers
the ROADMAP asked for.

Run: ``PYTHONPATH=src python benchmarks/conformance_bench.py [--quick]``
Writes ``experiments/benchmarks/BENCH_conformance.json``; exits
non-zero on any conformance violation so CI enforces the ordering.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

from repro.conformance import (
    DEFAULT_SCENARIOS,
    POLICIES,
    PR2_QUANTUM_SLACK,
    PR2_TOL_REL,
    PR3_QUANTUM_SLACK,
    ConformanceConfig,
    CostModel,
    run_conformance,
    run_dse_case,
    run_mode_switch_case,
    run_sharded_case,
    run_shedding_case,
    run_wallclock_case,
)
from repro.core.perfmodel.hardware import paper_platform
from repro.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join("experiments", "benchmarks")


def _num(x: float):
    """inf-safe JSON scalar."""
    return None if not math.isfinite(x) else x


def bench_conformance(quick: bool, prebuilt: dict) -> tuple[dict, bool]:
    # record_traces: every case carries a DES-vs-runtime trace_diff —
    # the bench asserts a verdict (identical or first-divergence) is
    # present for every registry case, so a tripped tolerance always
    # arrives with its pinpointed divergent event
    cfg = ConformanceConfig(
        horizon_periods=24.0 if quick else 60.0, record_traces=True
    )
    # CI invariant: the window-boundary DES must run under a strictly
    # tighter DES-vs-runtime tolerance than the idealized-preemption
    # DES of PR 2 needed — loosening it back is a regression
    assert cfg.tol_rel < PR2_TOL_REL, (
        f"tol_rel {cfg.tol_rel} regressed to >= PR-2's {PR2_TOL_REL}"
    )
    assert cfg.quantum_slack < PR2_QUANTUM_SLACK, (
        f"quantum_slack {cfg.quantum_slack} regressed to >= "
        f"PR-2's {PR2_QUANTUM_SLACK}"
    )
    # ...and, since the DES adopted the runtime's simultaneous-event
    # tie-breaking, strictly tighter than the pre-alignment slack too
    assert cfg.quantum_slack < PR3_QUANTUM_SLACK, (
        f"quantum_slack {cfg.quantum_slack} regressed to >= "
        f"the pre-tie-break-alignment {PR3_QUANTUM_SLACK}"
    )
    t0 = time.perf_counter()
    report = run_conformance(
        DEFAULT_SCENARIOS,
        POLICIES,
        platform=paper_platform(16),
        cfg=cfg,
        prebuilt=prebuilt,
    )
    elapsed = time.perf_counter() - t0
    cases = []
    for c in report.cases:
        assert c.trace_diff is not None, (
            f"{c.scenario}/{c.policy}: record_traces produced no "
            "trace_diff verdict"
        )
        cases.append(
            {
                "scenario": c.scenario,
                "policy": c.policy,
                "analysis_schedulable": c.analysis_schedulable,
                "des_schedulable": c.des_schedulable,
                "server_bounded": c.server_bounded,
                "wall_seconds": c.wall_seconds,
                "trace_diff": c.trace_diff.summary(),
                "tasks": [
                    {
                        "task": t.task,
                        "analytic_bound_s": _num(t.analytic_bound),
                        "des_max_s": t.des_max,
                        "des_jobs": t.des_jobs,
                        "server_max_s": t.server_max,
                        "server_jobs": t.server_jobs,
                        "in_flight": t.in_flight,
                        "des_over_bound": _num(
                            t.des_max / t.analytic_bound
                            if t.analytic_bound > 0
                            and math.isfinite(t.analytic_bound)
                            else float("inf")
                        ),
                        "server_over_des": (
                            t.server_max / t.des_max
                            if t.des_max > 0
                            else None
                        ),
                    }
                    for t in c.tasks
                ],
                "violations": [str(v) for v in c.violations],
            }
        )
    payload = {
        "horizon_periods": cfg.horizon_periods,
        "wall_seconds": elapsed,
        "cases": cases,
        "total_violations": len(report.violations),
    }
    print(report.summary())
    return payload, report.ok


def bench_sharded(quick: bool, built) -> tuple[dict, bool]:
    """The sharded conformance cases: `sharded_city` placed across K
    pipeline shards, every shard held to the full three-layer contract
    plus the bit-exact per-shard admission check. K=1 anchors the
    equivalence (it *is* `run_case` plus the admission check)."""
    cfg = ConformanceConfig(horizon_periods=24.0 if quick else 40.0)
    placements = (
        ("least_loaded",)
        if quick
        else ("hash_by_tenant", "least_loaded", "slack_aware")
    )
    cases = []
    ok = True
    for policy in POLICIES:
        for shards, placement in [(1, "least_loaded")] + [
            (2, p) for p in placements
        ]:
            res = run_sharded_case(
                built, policy, shards=shards, placement=placement, cfg=cfg
            )
            ok = ok and res.ok
            cases.append(
                {
                    "scenario": res.scenario,
                    "policy": res.policy,
                    "shards": res.n_shards,
                    "placement": res.placement,
                    "assignment": list(res.assignment),
                    "shard_cases": [
                        {
                            "shard_scenario": c.scenario,
                            "analysis_schedulable": c.analysis_schedulable,
                            "des_schedulable": c.des_schedulable,
                            "server_bounded": c.server_bounded,
                            "violations": [str(v) for v in c.violations],
                        }
                        for c in res.cases
                    ],
                    "violations": [str(v) for v in res.violations],
                }
            )
            print(
                f"sharded {res.scenario:12s} {res.policy:4s} "
                f"K={res.n_shards} {res.placement:14s} "
                f"assign={res.assignment} viol={len(res.violations)}"
            )
    return {"cases": cases}, ok


def bench_dse(quick: bool) -> tuple[dict, bool]:
    """The DSE conformance case: the search's claimed-feasible designs
    pushed through analysis/DES/runtime, and the best design
    provisioned into a 2-shard `ShardedGateway` that must serve the
    scenario's traffic with zero violations — the acceptance gate of
    the DSE -> serving bridge."""
    cfg = ConformanceConfig(horizon_periods=16.0 if quick else 24.0)
    res = run_dse_case(
        "sharded_city",
        "edf",
        shards=2,
        check_top=1 if quick else 2,
        cfg=cfg,
    )
    print(
        f"dse {res.scenario:12s} {res.policy:4s} claimed={res.n_claimed} "
        f"checked={[round(u, 4) for u in res.checked_utils]} "
        f"K={res.n_shards} {res.placement} admitted={res.admitted} "
        f"released={res.released} viol={len(res.violations)}"
    )
    payload = {
        "scenario": res.scenario,
        "policy": res.policy,
        "method": res.method,
        "claimed_feasible": res.n_claimed,
        "checked_utils": list(res.checked_utils),
        "shards": res.n_shards,
        "placement": res.placement,
        "assignment": list(res.assignment),
        "admitted": res.admitted,
        "released": res.released,
        "cases": [
            {
                "analysis_schedulable": c.analysis_schedulable,
                "des_schedulable": c.des_schedulable,
                "server_bounded": c.server_bounded,
                "violations": [str(v) for v in c.violations],
            }
            for c in res.cases
        ],
        "violations": [str(v) for v in res.violations],
    }
    return payload, res.ok


def bench_shedding(quick: bool, prebuilt: dict) -> tuple[dict, bool]:
    """Overload conformance: overdriven scenarios with the same (drop)
    shedding machinery armed in DES and runtime — surviving jobs
    matched by release, verdict chain enforced."""
    from repro.core.perfmodel.hardware import paper_platform
    from repro.traffic.scenarios import build, get_scenario

    cfg = ConformanceConfig(horizon_periods=24.0 if quick else 60.0)
    scenarios = ("overload_2x", "noisy_neighbor")
    policies = ("reject_newest",) if quick else (
        "reject_newest",
        "shed_by_value",
    )
    cases = []
    ok = True
    for name in scenarios:
        built = prebuilt.get(name) or build(
            get_scenario(name), paper_platform(16), beam_width=4
        )
        prebuilt[name] = built
        for shed_policy in policies:
            res = run_shedding_case(
                built, "edf", shed_policy=shed_policy, cfg=cfg
            )
            ok = ok and res.ok
            des_shed, srv_shed = res.total_shed()
            cases.append(
                {
                    "scenario": res.scenario,
                    "policy": res.policy,
                    "shed_policy": res.shed_policy,
                    "analysis_schedulable": res.analysis_schedulable,
                    "des_overloaded": res.des_overloaded,
                    "server_bounded": res.server_bounded,
                    "des_shed": des_shed,
                    "server_shed": srv_shed,
                    "tasks": [
                        {
                            "task": t.task,
                            "des_completed": t.des_completed,
                            "des_shed": t.des_shed,
                            "server_completed": t.server_completed,
                            "server_shed": t.server_shed,
                            "matched_jobs": t.matched_jobs,
                            "des_max_s": t.des_max,
                            "server_max_s": t.server_max,
                            "in_flight": t.in_flight,
                        }
                        for t in res.tasks
                    ],
                    "violations": [str(v) for v in res.violations],
                }
            )
            print(
                f"shedding {res.scenario:14s} {shed_policy:16s} "
                f"shed des/srv={des_shed}/{srv_shed} "
                f"viol={len(res.violations)}"
            )
    return {"cases": cases}, ok


def bench_migration(quick: bool, built) -> tuple[dict, bool]:
    """Live-migration conformance: `run_migration_case` re-homes
    ``sharded_city`` tenants between co-simulated elastic shards and
    holds the run to zero deadline violations during any handover,
    exact DES/runtime survivor-set agreement on every tenant, and a
    committed Eq. 3 proof behind every re-home. One slack-aware and one
    explicit-target migration per policy."""
    from repro.conformance import run_migration_case
    from repro.traffic.migration import MigrationPlan

    cfg = ConformanceConfig(horizon_periods=20.0 if quick else 40.0)
    cases = []
    ok = True
    policies = ("edf",) if quick else POLICIES
    for policy in policies:
        for label, plans in (
            ("slack_aware", None),
            (
                "explicit",
                [
                    MigrationPlan(
                        tenant=built.requests[0].name,
                        at=0.25 * cfg.horizon_periods
                        * max(r.period for r in built.requests),
                        target=1,
                    )
                ],
            ),
        ):
            res = run_migration_case(
                built, policy, shards=2, plans=plans, cfg=cfg
            )
            ok = ok and res.ok
            cases.append(
                {
                    "scenario": res.scenario,
                    "policy": res.policy,
                    "plan": label,
                    "shards": res.n_shards,
                    "commits": res.commits,
                    "aborts": res.aborts,
                    "final_assignment": [
                        list(x) for x in res.final_assignment
                    ],
                    "tenants": [
                        {
                            "tenant": t.tenant,
                            "migrated": t.migrated,
                            "donor": t.donor,
                            "target": t.target,
                            "committed": t.committed,
                            "held": t.held,
                            "runtime_survivors": t.runtime_survivors,
                            "des_survivors": t.des_survivors,
                            "runtime_misses": t.runtime_misses,
                            "des_misses": t.des_misses,
                        }
                        for t in res.tenants
                    ],
                    "violations": [str(v) for v in res.violations],
                }
            )
            print(
                f"migration {res.scenario:12s} {res.policy:4s} "
                f"{label:12s} commits={res.commits} aborts={res.aborts} "
                f"viol={len(res.violations)}"
            )
    return {"cases": cases}, ok


def bench_mode_switch(quick: bool, prebuilt: dict) -> tuple[dict, bool]:
    """Mixed-criticality mode-switch conformance: the ``av_stack``
    scenario (overdriven LO infotainment next to HI perception) with
    twin `ModeController`s armed in DES and runtime. The CI gate fails
    on *any* HI-class guarantee miss across a transition — a HI job
    exceeding the survivor set's Eq. 3 bound plus the transition
    allowance — and on survivor-set disagreement between the layers."""
    from repro.core.perfmodel.hardware import paper_platform
    from repro.traffic.scenarios import build, get_scenario

    cfg = ConformanceConfig(horizon_periods=24.0 if quick else 60.0)
    configs = (
        (("degrade", "edf"), ("drop", "edf"))
        if quick
        else (
            ("degrade", "edf"),
            ("degrade", "fifo"),
            ("drop", "edf"),
            ("drop", "fifo"),
        )
    )
    built = prebuilt.get("av_stack") or build(
        get_scenario("av_stack"), paper_platform(16), beam_width=4
    )
    prebuilt["av_stack"] = built
    cases = []
    ok = True
    for action, policy in configs:
        res = run_mode_switch_case(built, policy, action=action, cfg=cfg)
        des_miss, srv_miss = res.hi_miss_totals()
        ok = ok and res.ok
        cases.append(
            {
                "scenario": res.scenario,
                "policy": res.policy,
                "action": res.action,
                "analysis_schedulable": res.analysis_schedulable,
                "hi_proof_schedulable": res.hi_proof_schedulable,
                "survivors": list(res.survivors),
                "des_switches": len(res.des_switches),
                "server_switches": len(res.server_switches),
                "hi_misses_des": des_miss,
                "hi_misses_server": srv_miss,
                "tasks": [
                    {
                        "task": t.task,
                        "criticality": t.criticality,
                        "des_completed": t.des_completed,
                        "des_shed": t.des_shed,
                        "des_degraded": t.des_degraded,
                        "des_misses": t.des_misses,
                        "server_completed": t.server_completed,
                        "server_shed": t.server_shed,
                        "server_degraded": t.server_degraded,
                        "server_misses": t.server_misses,
                        "matched_jobs": t.matched_jobs,
                        "des_max_s": t.des_max,
                        "server_max_s": t.server_max,
                    }
                    for t in res.tasks
                ],
                "violations": [str(v) for v in res.violations],
            }
        )
        print(
            f"mode {res.scenario:10s} {action:8s}/{policy:4s} "
            f"switches des/srv={len(res.des_switches)}/"
            f"{len(res.server_switches)} "
            f"hi_miss={des_miss}/{srv_miss} "
            f"survivors={list(res.survivors)} viol={len(res.violations)}"
        )
    return {"cases": cases}, ok


def bench_calibration(quick: bool, built) -> dict:
    """Wall-clock WCET calibration on the steady_city serve bundle."""
    from repro.pipeline.serve import PharosServer
    from repro.traffic.clock import VirtualClock

    serve_tasks, _reqs, _arr = built.serve_bundle(period_scale=1.0)
    clk = VirtualClock()
    srv = PharosServer(
        serve_tasks,
        built.design.n_stages,
        clock=clk.now,
        sleep=clk.sleep,
    )
    t0 = time.perf_counter()
    measured = CostModel.calibrate(srv, reps=2 if quick else 5)
    calib_s = time.perf_counter() - t0
    modeled = CostModel.from_exec_model(
        built.design, list(built.workloads), serve_tasks
    )
    rows = []
    for i, t in enumerate(serve_tasks):
        for k in range(built.design.n_stages):
            b_meas = measured.segment_cost(i, k)
            b_model = modeled.segment_cost(i, k)
            if b_model > 0:
                rows.append(
                    {
                        "task": t.name,
                        "stage": k,
                        "measured_s": b_meas,
                        "modeled_s": b_model,
                        "ratio": b_meas / b_model,
                    }
                )
    return {
        "calibration_wall_seconds": calib_s,
        "segments": rows,
        "note": (
            "measured = host wall-clock window probes (jnp backend); "
            "modeled = TPU exec-model latency — the ratio is the "
            "host/TPU speed gap, stable within a run"
        ),
    }


def bench_wallclock(quick: bool, built) -> tuple[dict, bool]:
    """The calibrated wall-clock case (gateway on the real clock vs the
    measured `CostModel`), with one retry: a CPU-quota throttle or load
    spike landing mid-run inflates every wall number at once, which is
    host noise, not a model defect. Two failures in a row count.

    One `TraceRecorder` is shared across both attempts with
    ``annotate(attempt=n)``, so a throttle-discarded first attempt's
    schedule events stay in the trace (per-attempt event counts land in
    the payload) instead of vanishing with the retry."""
    from repro.obs import TraceRecorder

    cfg = ConformanceConfig(
        wall_horizon_periods=8.0 if quick else 12.0,
        wall_reps=2 if quick else 3,
        # ROADMAP's calibrated-admission mode: tenancy admission runs
        # against the measured WCET contracts on this host
        calibrated_admission=True,
    )
    recorder = TraceRecorder()
    attempts = []
    ok = False
    for attempt in range(2):
        recorder.annotate(attempt=attempt)
        events_before = len(recorder.events)
        t0 = time.perf_counter()
        case = run_wallclock_case(built, "edf", cfg=cfg, trace=recorder)
        attempts.append(
            {
                "attempt": attempt,
                "trace_events": len(recorder.events) - events_before,
                "policy": case.policy,
                "admission_mode": case.admission_mode,
                "period_scale": case.period_scale,
                "horizon_s": case.horizon_s,
                "margin": case.margin,
                "wall_seconds": time.perf_counter() - t0,
                "tasks": [
                    {
                        "task": t.task,
                        "measured_median_s": t.measured_median,
                        "measured_max_s": t.measured_max,
                        "jobs": t.jobs,
                        "predicted_des_max_s": t.predicted_des_max,
                        "predicted_bound_s": _num(t.predicted_bound),
                        "in_flight": t.in_flight,
                    }
                    for t in case.tasks
                ],
                "violations": [str(v) for v in case.violations],
            }
        )
        for row in case.tasks:
            print(
                f"wall[{attempt}] {row.task:16s} "
                f"median={1e3 * row.measured_median:7.3f}ms "
                f"max={1e3 * row.measured_max:7.3f}ms "
                f"bound={1e3 * row.predicted_bound:7.3f}ms "
                f"jobs={row.jobs}"
            )
        if case.ok:
            ok = True
            break
        if attempt == 0:
            print("wall-clock case violated; retrying once", file=sys.stderr)
        else:
            print("wall-clock case violated twice; giving up", file=sys.stderr)
    return {"attempts": attempts, "ok": ok}, ok


def main() -> None:
    from repro.traffic.scenarios import build, get_scenario

    quick = "--quick" in sys.argv
    # steady_city's DSE result is shared by the sweep, calibration and
    # the wall-clock case; sharded_city backs the sharded cases
    steady = build(
        get_scenario("steady_city"), paper_platform(16), beam_width=4
    )
    sharded_city = build(
        get_scenario("sharded_city"), paper_platform(16), beam_width=4
    )
    conf, ok = bench_conformance(quick, {"steady_city": steady})
    sharded, sharded_ok = bench_sharded(quick, sharded_city)
    dse, dse_ok = bench_dse(quick)
    shedding, shedding_ok = bench_shedding(quick, {})
    migration, migration_ok = bench_migration(quick, sharded_city)
    modes, modes_ok = bench_mode_switch(quick, {})
    wall, wall_ok = bench_wallclock(quick, steady)
    payload = {
        "bench": "conformance",
        "quick": quick,
        "conformance": conf,
        "sharded": sharded,
        "dse": dse,
        "shedding": shedding,
        "migration": migration,
        "mode_switch": modes,
        "wallclock": wall,
        "calibration": bench_calibration(quick, steady),
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, "BENCH_conformance.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nwrote {path}")
    if (
        not ok
        or not sharded_ok
        or not dse_ok
        or not shedding_ok
        or not migration_ok
        or not modes_ok
        or not wall_ok
    ):
        print("CONFORMANCE VIOLATIONS DETECTED", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
