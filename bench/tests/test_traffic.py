"""The benchmark's traffic is a function of the cell file and the seed
alone: never of the program's measured speed."""
import pytest

import harness
import traffic

SEED = 2**31 + 12345


CELL = {
    "cam": {"arrival": "periodic", "period_s": 0.05},
    "lidar": {"arrival": "periodic", "period_s": 0.15},
    "info": {"arrival": "poisson", "rate_hz": 20.0},
    "lm": {"arrival": "closed"},
}


def test_periodic_tenants_share_a_seeded_phase():
    a = traffic.schedules(CELL, 10.0, SEED)
    b = traffic.schedules(CELL, 10.0, SEED)
    c = traffic.schedules(CELL, 10.0, SEED + 1)
    assert a == b and a["cam"] != c["cam"]
    assert abs(len(a["cam"]) - 200) <= 1 and abs(len(c["cam"]) - len(a["cam"])) <= 1
    assert all(abs(y - x - 0.05) < 1e-9 for x, y in zip(a["cam"], a["cam"][1:]))
    assert a["lidar"][0] == a["cam"][0] < 0.05
    assert a["lm"] == []


def test_poisson_schedule_has_one_count_for_every_seed():
    runs = [traffic.schedules(CELL, 10.0, s)["info"] for s in (1, 2, SEED)]
    assert {len(r) for r in runs} == {200}
    assert all(r == sorted(r) and 0 <= r[0] and r[-1] < 10.0 for r in runs)
    assert runs[0] != runs[1]


def test_poisson_tenants_draw_independent_streams():
    two = dict(CELL, info2=CELL["info"])
    s = traffic.schedules(two, 5.0, 7)
    assert s["info"] != s["info2"]
    assert traffic.schedules(CELL, 5.0, 7)["info"] == s["info"]


def test_unknown_kind_fails():
    with pytest.raises(ValueError):
        traffic.schedules({"x": {"arrival": "bursty"}}, 5, 1)


@pytest.fixture(scope="module")
def av_stack():
    config = harness.load_spec("configs", "av_stack")
    return harness.build_deployment(config, SEED, max_dim=128)


def test_schedule_ignores_calibrated_wcets(av_stack, monkeypatch):
    """Two calibrations 1000x apart give the same releases and the same
    contract periods; only the WCETs handed to admission differ."""
    from repro.conformance import CostModel

    cell = harness.load_spec("workloads", "av_stack.edf")
    real = CostModel.calibrate
    seen = []
    for ratio in (1.0, 1000.0):
        monkeypatch.setattr(
            CostModel, "calibrate",
            classmethod(lambda cls, server, reps=3, r=ratio: real.__func__(
                cls, server, reps=1).scaled(r)),
        )
        s = harness.prepare(av_stack, cell, SEED, 10.0)
        seen.append((
            [a.times for a in s.gateway.arrivals],
            [r.period for r in s.gateway.requests],
            [r.base for r in s.gateway.requests],
        ))
    (t1, p1, b1), (t2, p2, b2) = seen
    assert t1 == t2 and p1 == p2
    assert b1 != b2
    assert sum(len(t) for t in t1) > 0
