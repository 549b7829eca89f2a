"""Multi-gateway sharding benchmark -> BENCH_shard.json.

Scales the ``multi_tenant_rush`` scenario past one pipeline's Eq. 3
budget by replicating its tenant set (distinct names, re-seeded
traffic) and serves it on K = 1, 2, 4 `ShardedGateway` shards under
each placement policy, with the per-tenant token buckets armed and
disarmed, reporting per (K, placement, ratelimit):

- **admit rate**  — admitted tenants / total tenants: the replicated
  mix overcommits a single pipeline, so per-shard admission must turn
  tenants away at small K and admits more as capacity is added;
- **miss rate**   — deadline misses / completed jobs across shards;
- **shed fraction** — shedding-policy drops / scheduled releases (the
  scenario's MMPP camera and Poisson segmentation tenants are
  overdriven 3x, so backlog-triggered shedding engages on the shards
  that host them — unless the rate limiter trims them first);
- **response percentiles** — per-tenant p50/p95/p99 response times via
  the shared `ServerReport.response_percentiles` helper;
- **rate-limited fraction** — releases refused by the per-tenant token
  buckets (value-weighted, armed in front of every shard's admission).
  The armed rows show the tentpole division of labour: the bucket
  absorbs the contract violation up front, shedding drops to ~0 and
  the miss rate falls with it.

A second, elastic section ramps the tenant population up and back down
(25% -> 50% -> 100% -> 50% -> 25%) and compares the `Autoscaler` (K
free to grow/shrink inside [1, max K], emptiest shard drained before
removal) against static fleets at each K over the identical phases;
the bench gates on the autoscaled admit rate matching or beating every
static K.

Each shard runs deterministically (cost-model `PharosServer` on a
`VirtualClock`), so every number here is bit-reproducible.

Run: ``PYTHONPATH=src python benchmarks/shard_bench.py [--quick]``
Writes ``experiments/benchmarks/BENCH_shard.json``.
"""
from __future__ import annotations

import json
import os
import sys
import time

from repro.core.perfmodel.hardware import paper_platform
from repro.traffic import RateLimiter, ShardedGateway
from repro.traffic.autoscale import Autoscaler, RampPhase
from repro.traffic.scenarios import (
    BuiltScenario,
    build,
    get_scenario,
    replicate,
)
from repro.traffic.shedding import get_policy
from repro.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join("experiments", "benchmarks")

SCENARIO = "multi_tenant_rush"
PLACEMENTS = ("hash_by_tenant", "least_loaded", "slack_aware")


def ramp_phases(
    population: BuiltScenario, quick: bool
) -> tuple[RampPhase, ...]:
    """Tenant-count ramp over the replicated population: 25% -> 50% ->
    100% -> 50% -> 25% of the tenants arrive/depart across epochs (the
    quick sweep trims to the up-leg).  Each epoch runs long enough for
    the per-shard backlog dynamics to engage."""
    n = len(population.requests)
    duration = 8.0 * max(r.period for r in population.requests)
    fracs = (0.25, 0.5, 1.0) if quick else (0.25, 0.5, 1.0, 0.5, 0.25)
    phases = []
    for frac in fracs:
        count = max(1, round(frac * n))
        phases.append(
            RampPhase(duration=duration, active=tuple(range(count)))
        )
    return tuple(phases)


def run_ramp_point(
    population: BuiltScenario,
    phases: tuple[RampPhase, ...],
    min_shards: int,
    max_shards: int,
) -> dict:
    t0 = time.perf_counter()
    scaler = Autoscaler(
        population, min_shards=min_shards, max_shards=max_shards
    )
    report = scaler.run_ramp(phases)
    elapsed = time.perf_counter() - t0
    return {
        "min_shards": min_shards,
        "max_shards": max_shards,
        "admit_rate": report.admit_rate(),
        "max_shards_used": report.max_shards_used(),
        "shard_counts": report.shard_counts(),
        "final_assignment": {
            str(k): v for k, v in report.final_assignment().items()
        },
        "epochs": [
            {
                "t_start": ep.t_start,
                "n_shards": ep.n_shards,
                "active": ep.tenant_count(),
                "admitted": ep.admitted_count(),
                "rehomed": ep.rehomed,
                "grew": ep.grew,
                "shrank": ep.shrank,
            }
            for ep in report.epochs
        ],
        "wall_seconds": elapsed,
    }


def run_point(
    built: BuiltScenario,
    shards: int,
    placement: str,
    horizon_periods: float,
    ratelimit: bool,
) -> dict:
    gw = ShardedGateway.from_built(
        built,
        shards=shards,
        placement=placement,
        shedding=get_policy("reject_newest"),
        make_ratelimit=(
            (
                lambda reqs: RateLimiter.for_requests(
                    reqs, burst_periods=3.0, value_weighted=True
                )
            )
            if ratelimit
            else None
        ),
    )
    horizon = horizon_periods * max(r.period for r in built.requests)
    t0 = time.perf_counter()
    report = gw.run(horizon)
    elapsed = time.perf_counter() - t0
    assert gw.verify(), "a shard's cached Eq. 3 verdict diverged"

    tenants = report.tenants
    admitted = report.admitted_count()
    scheduled = sum(t.scheduled for t in tenants)
    shed = report.total_shed()
    rate_limited = report.total_rate_limited()
    completed = 0
    misses = 0
    # per-tenant response-time percentiles via the shared
    # `ServerReport.response_percentiles` helper (nearest-rank, the
    # same summary `repro.obs.MetricsRegistry` reports)
    response_pctl: dict[str, dict[str, float]] = {}
    for rep in report.reports:
        if rep is None:
            continue
        sr = rep.server_report
        completed += sr.jobs_completed
        misses += sum(sr.deadline_misses.values())
        for name, times in sr.response_times.items():
            if times:
                response_pctl[name] = sr.response_percentiles(name)
    return {
        "shards": shards,
        "placement": placement,
        "ratelimit": ratelimit,
        "assignment": list(report.plan.assignment),
        "tenants": len(tenants),
        "admitted": admitted,
        "admit_rate": admitted / len(tenants),
        "scheduled_releases": scheduled,
        "completed": completed,
        "deadline_misses": misses,
        "miss_rate": (misses / completed) if completed else None,
        "response_percentiles_s": response_pctl,
        "shed": shed,
        "shed_fraction": (shed / scheduled) if scheduled else None,
        "rate_limited": rate_limited,
        "rate_limited_fraction": (
            rate_limited / scheduled if scheduled else None
        ),
        # per-replica remaining capacity (the shard-aware headroom
        # report): slacks + the worst admitted tenant's rate multiplier
        "headroom": [
            None
            if hr is None
            else {
                "shard": hr.shard,
                "tenants": list(hr.tenants),
                "stage_slacks": list(hr.stage_slacks),
                "bottleneck": hr.bottleneck,
                "min_tenant_rate_multiplier": (
                    min(hr.tenant_rate_multipliers.values())
                    if hr.tenant_rate_multipliers
                    else None
                ),
            }
            for hr in report.headrooms
        ],
        "wall_seconds": elapsed,
    }


def main() -> None:
    quick = "--quick" in sys.argv
    copies = 2
    ks = (1, 2) if quick else (1, 2, 4)
    # backlog needs ~25+ periods to trip the shedding monitor even at
    # 3x overdrive, so quick mode keeps the full horizon and economizes
    # on the K sweep instead
    horizon_periods = 40.0

    built = build(
        get_scenario(SCENARIO), paper_platform(16), beam_width=4
    )
    population = replicate(built, copies)
    points = []
    for k in ks:
        for placement in PLACEMENTS:
            for ratelimit in (False, True):
                pt = run_point(
                    population, k, placement, horizon_periods, ratelimit
                )
                points.append(pt)
                nan = float("nan")

                def _f(x):
                    return nan if x is None else x

                print(
                    f"K={pt['shards']} {pt['placement']:14s} "
                    f"rl={'on ' if ratelimit else 'off'} "
                    f"admit={pt['admit_rate']:.2f} "
                    f"miss={_f(pt['miss_rate']):.3f} "
                    f"shed={_f(pt['shed_fraction']):.3f} "
                    f"ratelimited={_f(pt['rate_limited_fraction']):.3f}"
                )

    # scale sanity: adding shards must never admit fewer tenants under
    # the load-aware placements (hash placement is load-blind and gets
    # no monotonicity promise)
    for placement in ("least_loaded", "slack_aware"):
        for ratelimit in (False, True):
            rates = [
                p["admit_rate"]
                for p in points
                if p["placement"] == placement
                and p["ratelimit"] == ratelimit
            ]
            assert all(
                b >= a - 1e-12 for a, b in zip(rates, rates[1:])
            ), f"admit rate regressed with K under {placement}: {rates}"

    # elastic ramp gate: the autoscaler (K free to move in
    # [1, max(ks)]) must admit at least as many tenant-phases as every
    # static fleet run over the same ramp with the same epoch
    # machinery.  It can: any placement a static K proves, the
    # autoscaler can reach by growing to that K, and shrink only fires
    # when every evicted tenant re-proves elsewhere.
    phases = ramp_phases(population, quick)
    auto_pt = run_ramp_point(population, phases, 1, max(ks))
    print(
        f"ramp auto     K<={max(ks)} admit={auto_pt['admit_rate']:.2f} "
        f"shards={auto_pt['shard_counts']}"
    )
    static_pts = []
    for k in ks:
        pt = run_ramp_point(population, phases, k, k)
        static_pts.append(pt)
        print(
            f"ramp static   K={k}  admit={pt['admit_rate']:.2f} "
            f"shards={pt['shard_counts']}"
        )
        assert auto_pt["admit_rate"] >= pt["admit_rate"] - 1e-12, (
            f"autoscaled admit rate {auto_pt['admit_rate']} fell below "
            f"static K={k} ({pt['admit_rate']})"
        )

    payload = {
        "bench": "shard",
        "quick": quick,
        "scenario": SCENARIO,
        "copies": copies,
        "horizon_periods": horizon_periods,
        "points": points,
        "ramp": {
            "phases": [
                {"duration_s": p.duration, "active": len(p.active)}
                for p in phases
            ],
            "autoscaled": auto_pt,
            "static": static_pts,
        },
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, "BENCH_shard.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nwrote {path}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
