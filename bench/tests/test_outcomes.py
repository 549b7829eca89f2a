"""What a window's jobs count as: ``failed`` holds the jobs that were not
served (refused, shed, rate-limited, never finished); a job served after
its deadline is late, and ``hi_on_time`` leaves it out."""
from types import SimpleNamespace as NS

import pytest

import harness

T0 = 100.0
DUE = [0.0, 0.1, 0.2, 0.3]


def _window(*, shed=0, admitted=True, late_s=0.05):
    """One HI periodic tenant (deadline 0.1 s) beside one closed-loop LO
    tenant. Job 0 and job 2 finish on time, job 1 ``late_s`` after its
    deadline, job 3 never."""
    recs = [
        harness.JobRecord(k, 0, T0 + t, False, T0 + t + 0.1, done, k)
        for k, (t, done) in enumerate([
            (0.0, T0 + 0.05),
            (0.1, T0 + 0.2 + late_s),
            (0.2, T0 + 0.25),
        ])
    ]
    live = [NS(uid=3, task_id=0, release=T0 + 0.3, best_effort=False,
               abs_deadline=T0 + 0.4)]
    s = NS(
        dep=NS(names=["cam", "lm"], criticality=["HI", "LO"]),
        decisions=[NS(request=NS(name="cam"), admitted=admitted),
                   NS(request=NS(name="lm"), admitted=True)],
        tracker=NS(records=recs, live=live, due={3: 3}),
        closed=[1],
        schedules=[DUE, []],
    )
    w = NS(
        report=NS(tenants=[NS(name="cam", shed=shed, rate_limited=0),
                           NS(name="lm", shed=0, rate_limited=0)]),
        closed_submitted={1: 5},
        t0=T0, t_end=T0 + 0.4, t_drained=T0 + 1.0,
    )
    return harness.outcomes(s, w)


def test_late_job_is_counted_late_and_not_failed():
    out = _window()
    assert out["attempted"] == len(DUE) + 5
    assert out["failed"] == 1  # job 3, never finished
    assert out["late"] == 1  # job 1
    assert (out["hi_due"], out["hi_on_time"]) == (4, 2)


@pytest.mark.parametrize("late_s", [0.001, 1.0])
def test_how_late_does_not_move_failed(late_s):
    assert _window(late_s=late_s)["failed"] == 1


def test_shed_and_refused_jobs_fail():
    assert _window(shed=2)["failed"] == 3
    # a refused tenant's every due job fails
    assert _window(admitted=False)["failed"] >= len(DUE)


def _empty_context():
    return NS(
        trace=None, traced_windows=[], traced_windows_counted=0,
        release_jitter_s=[], hi_queue_s=[], hi_resp_s=[],
        step_busy_s=0.0, windows_in_window=0, percentile=harness.percentile,
    )


PER_LAYER = [m["name"] for m in harness.load_benchmark()["per_layer"]]


@pytest.mark.parametrize("metric", PER_LAYER)
def test_reader_that_finds_nothing_returns_none(metric):
    assert harness.load_reader(metric)(_empty_context()) is None


def test_hi_response_tail_reads_the_95th_percentile_in_ms():
    ctx = _empty_context()
    ctx.hi_resp_s = [k / 1000 for k in range(1, 101)]  # 1..100 ms
    got = harness.load_reader("server.hi_resp_p95_ms")(ctx)
    assert got == pytest.approx(95.0)
