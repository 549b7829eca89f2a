"""Busy union, idle share, kernel time and idle gaps by host span, on a
small trace in the benchmark's plain form."""
import json
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).parent / "data" / "trace_small.json"
SPANS = ("gateway.release_due", "server.step", "client.idle")


@pytest.fixture
def trace():
    return json.loads(DATA.read_text())


def test_busy_union_and_idle_share(trace):
    s = tr.summarize(trace, SPANS)
    # window [1000, 11000]; ops [1800, 4500] and [9000, 11000] (clipped)
    assert s["window_s"] == pytest.approx(10000e-9)
    assert s["busy_s"] == pytest.approx(4700e-9)
    assert s["idle_share"] == pytest.approx(0.53)


def test_kernel_time_by_name(trace):
    """Operations are named without layout and operands, so the calls of
    one kernel at one shape add up under one name."""
    s = tr.summarize(trace, SPANS)
    name = "%matmul_window_call.1 = f32[128,2048]"
    assert s["op_s"][name] == pytest.approx(3500e-9)
    assert s["device_ops"][0] == (name, pytest.approx(3500e-9))
    assert "early" not in s["op_s"]


def test_idle_gaps_go_to_the_host_span_they_overlap_most(trace):
    s = tr.summarize(trace, SPANS)
    # gaps [1000,1800] -> release_due 500 vs step 300: release_due;
    # [4500,9000] -> idle 3000 vs step 1000 vs step(1500..5000) 500: idle
    assert s["idle_by_span"] == {
        "gateway.release_due": pytest.approx(800e-9),
        "client.idle": pytest.approx(4500e-9),
    }
    assert s["idle_gaps"][0][0] == "client.idle"


def test_merge_and_gaps():
    busy = tr.merge([(0, 5, "a"), (3, 8, "b"), (10, 12, "c")], 1, 11)
    assert busy == [(1, 8), (10, 11)]
    assert tr.gaps(busy, 0, 15) == [(0, 1), (8, 10), (11, 15)]


def test_a_trace_without_device_operations_is_refused(trace):
    trace["planes"] = [p for p in trace["planes"] if p["name"] == "/host:CPU"]
    with pytest.raises(ValueError, match="no device operations"):
        tr.summarize(trace, SPANS)
    trace["planes"] = []
    with pytest.raises(ValueError, match="bench.window"):
        tr.summarize(trace, SPANS)


def test_extract_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("server.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = tr.load_xplane(path)
    lo, hi = tr.window(data)
    assert hi > lo
    spans = tr.host_spans(data, {"server.step"})
    assert len(spans) == 1 and lo <= spans[0][0] <= spans[0][1] <= hi
