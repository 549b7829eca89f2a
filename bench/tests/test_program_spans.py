"""The program's host spans inside ``server.step``: idle gaps charged on
down to the innermost span that overlaps them, and the per-window
readers of the span counters."""
import json
import time
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import harness
import program_spans as ps
import trace_reduce as tr

SPANS = ("gateway.release_due", "server.step", "client.idle")


def _trace():
    """Window [0, 1000]. One step [100, 900] holds schedule [100, 150],
    acc_init [150, 200], launch [200, 800] and sync [850, 900]; the
    device runs [0, 120] and [780, 860]."""
    host = [
        ["bench.window", 0, 1000],
        ["gateway.release_due", 0, 100],
        ["server.step", 100, 800],
        ["pharos.schedule", 100, 50],
        ["pharos.acc_init", 150, 50],
        ["pharos.window_launch", 200, 600],
        ["pharos.layer_sync", 850, 50],
        ["client.idle", 900, 100],
    ]
    ops = [["%matmul_window_call.1 = f32[128,2048]", 0, 120],
           ["%copy.4 = f32[128,2048]", 780, 80]]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
    ]}


def test_idle_in_step_splits_what_idle_gaps_gives_server_step():
    s = tr.summarize(_trace(), SPANS)
    # idle [120, 780] overlaps server.step most, and in it the launch;
    # idle [860, 1000] overlaps client.idle most
    assert s["idle_by_span"] == {
        "server.step": pytest.approx(660e-9),
        "client.idle": pytest.approx(140e-9),
    }
    assert dict(ps.idle_in_step(_trace(), SPANS)) == {
        "pharos.window_launch": pytest.approx(660e-9),
        "client.idle": pytest.approx(140e-9),
    }


def test_a_gap_goes_down_to_the_innermost_span_that_overlaps_it():
    spans = [(0, 10, "outer"), (2, 4, "a"), (4, 6, "b"), (4, 5, "b1"),
             (12, 14, "c")]
    assert ps.charge_innermost(
        [(1, 3), (3, 7), (9, 13), (20, 22)], spans
    ) == {
        "a": 2,  # outer 2, then a 1 of its children
        "b1": 4,  # outer 4, then b 2 (a 1), then b1 inside b
        "outer": 4,  # outer 1 and c 1 tie: the earlier; no child overlaps
        tr.NO_SPAN: 2,
    }


def test_over_spans_that_do_not_nest_it_is_charge_gaps():
    trace = json.loads(
        (Path(__file__).parent / "data" / "trace_small.json").read_text())
    lo, hi = tr.window(trace)
    (dev,) = [p for p in tr.device_planes(trace)]
    idle = tr.gaps(tr.merge(tr.device_ops(dev), lo, hi), lo, hi)
    spans = tr.host_spans(trace, set(SPANS))
    assert ps.charge_innermost(idle, spans) == tr.charge_gaps(idle, spans)


READERS = {
    "server.sched_us_per_window": "pharos.schedule",
    "server.acc_init_us_per_window": "pharos.acc_init",
    "server.launch_us_per_window": "pharos.window_launch",
    "server.sync_us_per_window": "pharos.layer_sync",
}
#: 4,000 windows launched in the window; per span (count, total_s,
#: max_s, t_of_max)
COUNTERS = {
    "pharos.schedule": (12000, 0.2, 0.001, 3.0),
    "pharos.acc_init": (100, 0.01, 0.002, 4.0),
    "pharos.window_launch": (4000, 2.8, 0.11, 5.0),
    "pharos.layer_sync": (100, 0.04, 0.003, 6.0),
}
EXPECTED_US = {
    "pharos.schedule": 50.0,
    "pharos.acc_init": 2.5,
    "pharos.window_launch": 700.0,
    "pharos.layer_sync": 10.0,
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_turns_span_counters_into_us_per_window(metric):
    read = harness.load_reader(metric)
    span = READERS[metric]
    assert read(NS(program_spans=COUNTERS)) == pytest.approx(EXPECTED_US[span])
    # nothing to read: no counters, a program without the span, no launch
    assert read(NS(program_spans={})) is None
    assert read(NS()) is None
    assert read(NS(program_spans={
        n: v for n, v in COUNTERS.items() if n != span})) is None
    idle = dict(COUNTERS, **{"pharos.window_launch": (0, 0.0, 0.0, None)})
    assert read(NS(program_spans=idle)) is None


def test_every_span_reader_is_in_the_benchmark():
    per_layer = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    for metric in READERS:
        m = per_layer[metric]
        assert (m["source"], m["layer"], m["unit"]) == (
            "program_span", "server loop", "us")
    assert set(READERS.values()) == set(ps.PROGRAM_SPANS)


def _traced_run(cell, seed, **kw):
    return harness.run_cell(
        cell, seed, 1.0, True, t_start=time.perf_counter(),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        max_dim=128, **kw,
    )


def test_traced_run_reports_the_span_metrics():
    """A ``--trace 1`` run of a cell in which every step runs a window:
    the four span metrics are read from the run's gateway report, they
    time parts of the steps that ``server.us_per_window`` times whole,
    and the log lists each span's longest call. (On the CPU the profiler
    sees no device, so the trace's own metrics stay out of the line.)"""
    lines = []
    res = _traced_run("copilot_decode.edf", 2**31 + 4343, log=lines.append)
    got = {m: res["metrics"][m]["value"] for m in READERS}
    assert got["server.launch_us_per_window"] > 0
    assert got["server.acc_init_us_per_window"] > 0
    step = res["metrics"]["server.us_per_window"]["value"]
    assert sum(got.values()) <= 1.05 * step
    (spans,) = [m for m in lines if m.startswith("program spans in the window")]
    for name in ps.PROGRAM_SPANS:
        assert f"({name}, " in spans
    assert list(res)[-1] == "checks"


def test_idle_in_step_joins_the_breakdown_of_a_profiled_run(monkeypatch):
    """Where the profile holds device operations, a traced run's result
    line carries ``idle_in_step`` beside the harness's breakdown (the
    run's profile read as the small trace above)."""
    monkeypatch.setattr(tr, "load_xplane", lambda path: _trace())
    res = _traced_run("av_stack.edf", 2**31 + 4344, log=lambda m: None)
    assert dict(res["breakdown"]["idle_in_step"]) == {
        "pharos.window_launch": pytest.approx(660e-9),
        "client.idle": pytest.approx(140e-9),
    }
    assert list(res["breakdown"]) == ["device_ops", "idle_gaps", "idle_in_step"]
    assert list(res)[-1] == "checks"
