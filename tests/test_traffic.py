"""Traffic & admission-control subsystem tests.

The three contract properties from the subsystem's design:
(a) sporadic arrivals with inter-arrival == period reproduce the
    periodic DES results *exactly*;
(b) the admission controller's O(stages) incremental verdict matches a
    full `srt_schedulable` re-analysis on every decision — in
    particular it never admits a task the full re-check would reject;
(c) shedding keeps admitted tenants' response times bounded under 2x
    overload (DES- and gateway-level).
"""
import math
import random

import pytest

from repro.core.rt.schedulability import (
    max_admissible_rate,
    max_utilization,
    srt_schedulable,
    stage_slacks,
    stage_utilizations,
    task_rate_sensitivity,
)
from repro.core.rt.task import LayerDesc, SegmentTable, Task, TaskSet, Workload
from repro.scheduler.des import SimConfig, SimTask, simulate, simulate_taskset
from repro.traffic import (
    AdmissionController,
    BacklogMonitor,
    MMPPArrivals,
    PeriodicArrivals,
    PoissonArrivals,
    SporadicArrivals,
    TaskRequest,
    TraceArrivals,
    merge_arrivals,
)
from repro.traffic.shedding import DROP, SUBMIT, get_policy


def _placeholder_taskset(reqs):
    w = Workload("w", (LayerDesc("l", 8, 8, 8),))
    return TaskSet(
        tasks=tuple(
            Task(workload=w, period=r.period, deadline=r.deadline, name=r.name)
            for r in reqs
        )
    )


# ---------------------------------------------------------------------------
# arrival models
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "proc",
    [
        PeriodicArrivals(period=0.1, phase=0.03),
        SporadicArrivals(min_gap=0.1, jitter=0.4, seed=7),
        PoissonArrivals(rate=25.0, seed=7),
        MMPPArrivals(rates=(5.0, 40.0), dwells=(1.0, 0.25), seed=7),
        TraceArrivals(times=(0.0, 0.01, 0.5, 0.52, 2.0)),
    ],
)
def test_arrivals_deterministic_sorted_prefix_stable(proc):
    a = proc.arrivals(5.0)
    assert a == proc.arrivals(5.0)  # deterministic
    assert a == sorted(a)
    assert all(t >= 0.0 for t in a)
    assert all(t < 5.0 for t in a)
    longer = proc.arrivals(9.0)
    assert longer[: len(a)] == a  # prefix-stable
    assert proc.analysis_period() > 0


def test_trace_with_simultaneous_arrivals_has_no_sporadic_bound():
    # a zero min gap means no positive inter-arrival bound exists: the
    # trace cannot be provisioned as sporadic (TaskRequest rejects it)
    proc = TraceArrivals(times=(0.0, 0.5, 0.5, 1.0))
    assert proc.analysis_period() == 0.0
    with pytest.raises(ValueError, match="period"):
        TaskRequest("t", (0.1,), period=proc.analysis_period())


def test_arrival_rates_roughly_match():
    h = 400.0
    for proc, rate in [
        (PoissonArrivals(rate=10.0, seed=1), 10.0),
        (SporadicArrivals(min_gap=0.05, jitter=1.0, seed=1), 10.0),
        (MMPPArrivals(rates=(4.0, 16.0), dwells=(1.0, 1.0), seed=1), 10.0),
    ]:
        n = len(proc.arrivals(h))
        assert n == pytest.approx(rate * h, rel=0.15)
        assert proc.mean_rate() == pytest.approx(rate, rel=1e-9)


def test_merge_arrivals_interleaves_sorted():
    a = PeriodicArrivals(period=0.3)
    b = PeriodicArrivals(period=0.5, phase=0.1)
    sched = merge_arrivals([a, b], 3.0)
    assert [t for t, _ in sched] == sorted(t for t, _ in sched)
    assert sum(1 for _, i in sched if i == 0) == len(a.arrivals(3.0))
    assert sum(1 for _, i in sched if i == 1) == len(b.arrivals(3.0))


# ---------------------------------------------------------------------------
# (a) sporadic@period == periodic, exactly, in the DES
# ---------------------------------------------------------------------------
def test_sporadic_zero_jitter_reproduces_periodic_des_exactly():
    rng = random.Random(0)
    for trial in range(5):
        n_tasks = rng.randint(1, 3)
        tasks_periodic, arrivals = [], []
        horizon = 30.0
        for i in range(n_tasks):
            period = rng.uniform(0.3, 1.2)
            segs = tuple(
                (k, rng.uniform(0.01, period / (2 * n_tasks)))
                for k in range(rng.randint(1, 3))
            )
            phase = rng.uniform(0.0, 0.2)
            tasks_periodic.append(
                SimTask(segments=segs, period=period, phase=phase)
            )
            arrivals.append(
                SporadicArrivals(
                    min_gap=period, jitter=0.0, phase=phase, seed=i
                ).arrivals(horizon)
            )
        tasks_explicit = [
            SimTask(
                segments=t.segments,
                period=t.period,
                arrivals=tuple(arr),
            )
            for t, arr in zip(tasks_periodic, arrivals)
        ]
        for policy in ("fifo", "edf"):
            cfg = SimConfig(policy=policy, horizon=horizon)
            r_per = simulate(tasks_periodic, cfg)
            r_exp = simulate(tasks_explicit, cfg)
            assert r_per.response_times == r_exp.response_times, (
                trial,
                policy,
            )
            assert r_per.schedulable == r_exp.schedulable
            assert r_per.preemptions == r_exp.preemptions


def test_des_explicit_burst_arrivals_supported():
    # back-to-back arrivals (gap 0) and long silences both simulate
    t = SimTask(
        segments=((0, 0.05),),
        period=0.5,
        arrivals=(0.0, 0.0, 0.0, 5.0, 5.01),
    )
    res = simulate([t], SimConfig(policy="fifo", horizon=20.0))
    assert res.jobs_released == 5
    assert res.jobs_completed == 5
    assert res.schedulable


def test_des_rejects_bad_arrival_sequences():
    with pytest.raises(ValueError, match="non-decreasing"):
        SimTask(segments=((0, 0.1),), period=1.0, arrivals=(1.0, 0.5))
    with pytest.raises(ValueError, match="non-negative"):
        SimTask(segments=((0, 0.1),), period=1.0, arrivals=(-0.1,))


# ---------------------------------------------------------------------------
# (b) admission: incremental verdict == full re-analysis, every decision
# ---------------------------------------------------------------------------
def _random_request(rng, n_stages, name):
    base = [0.0] * n_stages
    for k in range(n_stages):
        if rng.random() < 0.7:
            base[k] = rng.uniform(0.001, 0.2)
    if not any(base):
        base[rng.randrange(n_stages)] = rng.uniform(0.001, 0.2)
    return TaskRequest(
        name=name,
        base=tuple(base),
        period=rng.uniform(0.2, 2.0),
        value=rng.uniform(0.1, 5.0),
    )


def _full_recheck(ctl, candidate):
    """Ground truth: rebuild the table with the candidate appended and
    run the offline Eq. 3 test."""
    reqs = list(ctl.admitted) + [candidate]
    table = SegmentTable(
        base=[list(r.base) for r in reqs],
        overhead=list(ctl.overheads),
    )
    return srt_schedulable(
        table, _placeholder_taskset(reqs), preemptive=ctl.preemptive
    )


def test_admission_incremental_matches_full_reanalysis_every_decision():
    rng = random.Random(42)
    for trial in range(8):
        n_stages = rng.randint(1, 4)
        overheads = [rng.uniform(0.0, 0.01) for _ in range(n_stages)]
        ctl = AdmissionController(overheads, preemptive=bool(trial % 2))
        for j in range(40):
            req = _random_request(rng, n_stages, f"t{trial}_{j}")
            full = _full_recheck(ctl, req)
            dec = ctl.admit(req)
            # incremental verdict == full re-analysis, both directions
            assert dec.admitted == full, (trial, j, dec.reason)
            assert ctl.verify()
            # occasionally churn tenants to exercise cache rebuilds
            if ctl.admitted and rng.random() < 0.25:
                victim = rng.choice(ctl.admitted).name
                ctl.release(victim)
                assert ctl.verify()


def test_admission_never_admits_past_cap():
    ctl = AdmissionController([0.0], preemptive=False)
    assert ctl.admit(TaskRequest("a", (0.5,), period=1.0)).admitted
    assert ctl.admit(TaskRequest("b", (0.5,), period=1.0)).admitted
    dec = ctl.check(TaskRequest("c", (0.001,), period=1.0))
    assert not dec.admitted
    assert "stage 0" in dec.reason
    # the cache did not absorb the rejected candidate
    assert ctl.utilizations() == (1.0,)


def test_admission_best_effort_consumes_no_budget():
    ctl = AdmissionController([0.0, 0.0])
    dec = ctl.admit(
        TaskRequest("be", (1.0, 1.0), period=0.1, best_effort=True)
    )
    assert dec.admitted and not dec.guaranteed
    assert ctl.utilizations() == (0.0, 0.0)
    assert ctl.best_effort[0].name == "be"


def test_admission_headroom_and_max_rate():
    ctl = AdmissionController([0.0, 0.0], preemptive=False)
    ctl.admit(TaskRequest("a", (0.2, 0.4), period=1.0))
    probe = (0.1, 0.2)
    r_max = ctl.max_rate(probe)
    assert r_max == pytest.approx(min(0.8 / 0.1, 0.6 / 0.2))
    # admitting just under the max rate succeeds, just over fails
    ok = ctl.check(
        TaskRequest("u", probe, period=1.0 / (r_max * 0.999))
    )
    bad = ctl.check(
        TaskRequest("o", probe, period=1.0 / (r_max * 1.001))
    )
    assert ok.admitted and not bad.admitted
    hr = ctl.headroom_report(probe=probe)
    assert hr.probe_max_rate == pytest.approx(r_max)
    assert hr.bottleneck == 1
    assert hr.tenant_rate_multipliers["a"] == pytest.approx(
        1.0 + 0.6 / 0.4
    )


def test_admission_controller_duplicate_and_missing_names():
    ctl = AdmissionController([0.0])
    ctl.admit(TaskRequest("a", (0.1,), period=1.0))
    with pytest.raises(ValueError, match="duplicate"):
        ctl.admit(TaskRequest("a", (0.1,), period=1.0))
    # the refused duplicate never reached the audit log or the cache
    assert len(ctl.decisions) == 1
    assert ctl.utilizations() == (0.1,)
    with pytest.raises(KeyError):
        ctl.release("nope")


# ---------------------------------------------------------------------------
# core.rt headroom helpers
# ---------------------------------------------------------------------------
def test_core_rt_headroom_helpers():
    w = Workload("w", (LayerDesc("l", 8, 8, 8),))
    table = SegmentTable(
        base=[[0.2, 0.0], [0.1, 0.3]], overhead=[0.0, 0.0]
    )
    ts = TaskSet(
        tasks=(
            Task(workload=w, period=1.0, name="a"),
            Task(workload=w, period=1.0, name="b"),
        )
    )
    utils = stage_utilizations(table, ts, False)
    assert utils == pytest.approx([0.3, 0.3])
    assert stage_slacks(table, ts, False) == pytest.approx([0.7, 0.7])
    # candidate active on both stages: rate bound is the tighter stage
    r = max_admissible_rate(table, ts, [0.1, 0.35], False)
    assert r == pytest.approx(min(0.7 / 0.1, 0.7 / 0.35))
    # task b can scale until stage 1 saturates: 1 + 0.7/0.3
    sens = task_rate_sensitivity(table, ts, False)
    assert sens[1] == pytest.approx(1.0 + 0.7 / 0.3)
    # scaling task b's rate by its sensitivity saturates exactly
    ts2 = TaskSet(
        tasks=(
            ts.tasks[0],
            Task(workload=w, period=1.0 / sens[1], name="b"),
        )
    )
    assert max_utilization(table, ts2, False) == pytest.approx(1.0)
    with pytest.raises(ValueError, match="length"):
        max_admissible_rate(table, ts, [0.1], False)


def test_task_rate_sensitivity_below_one_when_infeasible():
    # on an already-overloaded stage the multiplier is the rate
    # *reduction* restoring Eq. 3, not a claim the current rate fits
    w = Workload("w", (LayerDesc("l", 8, 8, 8),))
    table = SegmentTable(base=[[0.75], [0.75]], overhead=[0.0])
    ts = TaskSet(
        tasks=(
            Task(workload=w, period=1.0, name="a"),
            Task(workload=w, period=1.0, name="b"),
        )
    )
    assert not srt_schedulable(table, ts, preemptive=False)
    sens = task_rate_sensitivity(table, ts, False)
    # u = 1.5; scaling one task by 1 + (1-1.5)/0.75 = 1/3 restores u=1
    assert sens == pytest.approx([1.0 / 3.0, 1.0 / 3.0])


# ---------------------------------------------------------------------------
# (c) shedding bounds response under 2x overload — DES level
# ---------------------------------------------------------------------------
def test_shedding_restores_boundedness_under_2x_overload_des():
    """2x-overdriven Poisson traffic overloads the DES; shedding back to
    the provisioned rate (drop every other arrival — what the gateway's
    policies do online) keeps admitted response times bounded."""
    w = Workload("w", (LayerDesc("l", 8, 8, 8),))
    table = SegmentTable(base=[[0.4], [0.35]], overhead=[0.0])
    period = 1.0
    ts = TaskSet(
        tasks=(
            Task(workload=w, period=period, name="keep"),
            Task(workload=w, period=period, name="overdriven"),
        )
    )
    assert srt_schedulable(table, ts, preemptive=False)
    horizon = 400.0
    keep_arr = PeriodicArrivals(period=period).arrivals(horizon)
    over_arr = PoissonArrivals(rate=2.0 / period, seed=5).arrivals(horizon)

    overloaded = simulate_taskset(
        table,
        ts,
        "fifo",
        horizon=horizon,
        arrivals=[keep_arr, over_arr],
    )
    assert not overloaded.schedulable  # analysis contradicted

    shed_arr = over_arr[::2]  # shed half: back inside the contract
    shed = simulate_taskset(
        table,
        ts,
        "fifo",
        horizon=horizon,
        arrivals=[keep_arr, shed_arr],
    )
    assert shed.schedulable
    assert max(shed.max_response) < 20 * period
    assert max(overloaded.max_response) > max(shed.max_response)


# ---------------------------------------------------------------------------
# backlog monitor + policies (unit level)
# ---------------------------------------------------------------------------
def test_backlog_monitor_hysteresis():
    mon = BacklogMonitor(margin=2.0, fallback=6)
    lim = mon.limit_for(float("inf"), 0.1)
    assert lim == 6
    lim2 = mon.limit_for(0.35, 0.1)  # bound/period=3.5 -> ceil(2*4.5)=9
    assert lim2 == 9
    assert not mon.observe(0, 5, 6)
    assert mon.observe(0, 7, 6)  # crosses the limit -> engage
    assert mon.observe(0, 5, 6)  # still above half -> stays engaged
    assert not mon.observe(0, 3, 6)  # below half -> disengage
    assert not mon.any_engaged()


def test_shedding_policies_pick_expected_victims():
    ctl = AdmissionController([0.0], preemptive=False)
    reqs = [
        TaskRequest("first", (0.2,), period=1.0, value=5.0),
        TaskRequest("second", (0.2,), period=1.0, value=0.5),
    ]
    for r in reqs:
        ctl.admit(r)
    overloaded = [0, 1]
    # reject-newest sheds the later admission only
    rn = get_policy("reject_newest")
    assert rn.classify(0, overloaded, ctl, reqs) == SUBMIT
    assert rn.classify(1, overloaded, ctl, reqs) == DROP
    # shed-by-value sheds the low-value tenant only
    sv = get_policy("shed_by_value")
    assert sv.classify(0, overloaded, ctl, reqs) == SUBMIT
    assert sv.classify(1, overloaded, ctl, reqs) == DROP
    # degrade demotes rather than drops
    dg = get_policy("degrade_best_effort")
    assert dg.classify(1, overloaded, ctl, reqs) == "best_effort"
    # tenants inside their envelope are never shed
    assert sv.classify(0, [1], ctl, reqs) == SUBMIT
    with pytest.raises(KeyError, match="unknown shedding policy"):
        get_policy("nope")


def test_value_density_sheds_safety_tenants_last():
    """Value-ordered shedding is strict: with every tenant overloaded,
    only the single cheapest value-density tenant is a victim, and the
    victim order walks up the density ladder — the safety (highest
    value) tenant falls last."""
    ctl = AdmissionController([0.0], preemptive=False)
    reqs = [
        TaskRequest("safety", (0.1,), period=1.0, value=10.0),
        TaskRequest("mid", (0.1,), period=1.0, value=2.0),
        TaskRequest("cheap", (0.1,), period=1.0, value=0.3),
    ]
    for r in reqs:
        ctl.admit(r)
    sv = get_policy("shed_by_value")
    verdicts = [sv.classify(i, [0, 1, 2], ctl, reqs) for i in range(3)]
    assert verdicts == [SUBMIT, SUBMIT, DROP]
    # once the cheapest drains out of the overloaded set, the next
    # rung up becomes the victim; safety only when it stands alone
    assert sv.classify(1, [0, 1], ctl, reqs) == DROP
    assert sv.classify(0, [0, 1], ctl, reqs) == SUBMIT
    assert sv.classify(0, [0], ctl, reqs) == DROP


def test_equal_density_victim_is_deterministic():
    """Ties in value density resolve to the lowest admission index,
    and repeated classification never flips the victim."""
    ctl = AdmissionController([0.0], preemptive=False)
    reqs = [
        TaskRequest(f"t{i}", (0.1,), period=1.0, value=1.0)
        for i in range(3)
    ]
    for r in reqs:
        ctl.admit(r)
    for policy_name, victim_verdict in (
        ("shed_by_value", DROP),
        ("degrade_best_effort", "best_effort"),
    ):
        pol = get_policy(policy_name)
        for _ in range(5):
            verdicts = [
                pol.classify(i, [0, 1, 2], ctl, reqs) for i in range(3)
            ]
            assert verdicts == [victim_verdict, SUBMIT, SUBMIT]


def test_degrade_picks_same_victim_as_shed_but_demotes():
    ctl = AdmissionController([0.0], preemptive=False)
    reqs = [
        TaskRequest("keep", (0.2,), period=1.0, value=5.0),
        TaskRequest("victim", (0.2,), period=1.0, value=0.5),
    ]
    for r in reqs:
        ctl.admit(r)
    sv = get_policy("shed_by_value")
    dg = get_policy("degrade_best_effort")
    assert sv.drops and not dg.drops
    for i in range(2):
        shed_v = sv.classify(i, [0, 1], ctl, reqs)
        deg_v = dg.classify(i, [0, 1], ctl, reqs)
        # same victim selection, different disposition
        assert (shed_v == DROP) == (deg_v == "best_effort")
        assert (shed_v == SUBMIT) == (deg_v == SUBMIT)
