"""Host spans inside `PharosServer.step`: the four ``pharos.*`` spans land
on the profiler's host plane and their counters agree with the server;
with tracing off, or on the cost-model path, nothing is timed."""
import jax
import jax.numpy as jnp
import pytest

from repro.conformance import CostModel
from repro.obs import TraceRecorder
from repro.pipeline import PharosServer, ServeTask
from repro.pipeline.serve import (
    SPAN_ACC_INIT,
    SPAN_LAYER_SYNC,
    SPAN_SCHEDULE,
    SPAN_WINDOW_LAUNCH,
)
from repro.traffic import (
    AdmissionController,
    PeriodicArrivals,
    TaskRequest,
    TrafficGateway,
)
from repro.traffic.clock import VirtualClock

SPANS = (SPAN_SCHEDULE, SPAN_ACC_INIT, SPAN_WINDOW_LAUNCH, SPAN_LAYER_SYNC)
#: 256 input rows: two windows a layer on the jnp backend
ROWS = 256


def _task():
    k = jax.random.PRNGKey(0)
    ws = []
    for K, N in [(128, 256), (256, 256), (256, 128)]:
        k, s = jax.random.split(k)
        ws.append(jax.random.normal(s, (K, N), jnp.float32) / jnp.sqrt(K))
    # layers 0 and 1 on stage 0, layer 2 forwarded to stage 1
    return ServeTask("t", tuple(ws), (0, 0, 1), period=1.0, input_rows=ROWS)


class CountingClock:
    """A clock that advances by one on every read, and counts them."""

    def __init__(self):
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return float(self.reads)


def _serve(srv, jobs=2):
    """Release ``jobs`` jobs and step until all are done; returns the
    number of `step` calls."""
    for _ in range(jobs):
        srv.submit(0, 0.0)
    steps = 0
    while srv.report.jobs_completed < jobs:
        srv.step()
        steps += 1
    return steps


def _boom(*a, **kw):
    raise AssertionError("TraceAnnotation constructed")


def test_spans_land_on_the_host_plane_and_count_what_the_server_ran(tmp_path):
    rec = TraceRecorder()
    srv = PharosServer([_task()], 2, policy="edf", trace=rec)
    srv.warmup()
    windows0 = srv.report.windows_executed
    jax.profiler.start_trace(str(tmp_path))
    try:
        steps = _serve(srv)
    finally:
        jax.profiler.stop_trace()
    windows = srv.report.windows_executed - windows0
    layers = 2 * len(srv.tasks[0].weights)
    stats = rec.span_stats()
    assert set(stats) == set(SPANS)
    assert stats[SPAN_WINDOW_LAUNCH].count == windows == 2 * layers
    assert stats[SPAN_ACC_INIT].count == layers
    assert stats[SPAN_LAYER_SYNC].count == layers
    assert stats[SPAN_SCHEDULE].count == steps * len(srv.stages)
    launch = stats[SPAN_WINDOW_LAUNCH]
    assert 0 < launch.max_s <= launch.total_s
    assert launch.t_of_max is not None

    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                host[e.name] = host.get(e.name, 0) + 1
    assert {n: host.get(n, 0) for n in SPANS} == {
        n: stats[n].count for n in SPANS
    }


def test_tracing_off_constructs_no_annotation_and_reads_no_clock(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _boom)
    clock = CountingClock()
    srv = PharosServer(
        [_task()], 2, policy="edf", clock=clock, sleep=lambda dt: None,
    )
    srv.warmup()
    steps = _serve(srv)
    assert srv.report.jobs_completed == 2
    # the clock is read once a step and once a finished layer, never
    # per window
    assert clock.reads == steps + 2 * len(srv.tasks[0].weights)


def test_cost_model_path_times_no_span(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _boom)
    cm = CostModel(
        layer_costs=((0.1, 0.2, 0.1),),
        layer_windows=((2, 2, 2),),
        stage_of_layer=((0, 0, 1),),
        n_stages=2,
    )
    clk = VirtualClock()
    rec = TraceRecorder()
    srv = PharosServer(
        [_task()], 2, policy="edf", cost_model=cm,
        clock=clk.now, sleep=clk.sleep, trace=rec,
    )
    rep = srv.run(3.0)
    assert rep.jobs_completed == 3
    assert rec.span_stats() == {}
    assert rec.counts()["complete"] == 3


def test_gateway_run_reports_the_spans_of_its_horizon():
    """Windows before `begin_run` and after the horizon (a drain) are not
    the run's: `GatewayReport.host_spans` counts those in between."""
    clk = VirtualClock()
    rec = TraceRecorder()
    srv = PharosServer(
        [_task()], 2, policy="edf", clock=clk.now, sleep=clk.sleep, trace=rec,
    )
    srv.warmup()
    _serve(srv, jobs=1)  # served before the run
    gw = TrafficGateway(
        srv,
        AdmissionController([0.0, 0.0]),
        [TaskRequest("t", (1e-3, 1e-3), period=0.25, value=1.0)],
        [PeriodicArrivals(period=0.25)],
        clock=clk,
    )
    gw.open()
    gw.begin_run(1.0, warmup=False)
    w0 = srv.report.windows_executed
    while gw.release_due() < 1.0:
        if not srv.step():
            clk.advance(0.01)
    windows = srv.report.windows_executed - w0
    done = srv.report.jobs_completed
    srv.submit(0, clk.now())  # served past the horizon, as in a drain
    while srv.report.jobs_completed == done:
        srv.step()
    rep = gw.finish_run()
    assert windows > 0
    assert rep.host_spans[SPAN_WINDOW_LAUNCH].count == windows
    assert set(rep.host_spans) == set(SPANS)
    assert rec.span_stats()[SPAN_WINDOW_LAUNCH].count > windows


def test_a_server_without_spans_reports_none_to_its_gateway():
    clk = VirtualClock()
    srv = PharosServer([_task()], 2, policy="edf", clock=clk.now, sleep=clk.sleep)
    gw = TrafficGateway(
        srv,
        AdmissionController([0.0, 0.0]),
        [TaskRequest("t", (1e-3, 1e-3), period=0.25, value=1.0)],
        [PeriodicArrivals(period=0.25)],
        clock=clk,
    )
    assert srv.host_span_stats() == {}
    assert gw.run(0.5, warmup=False).host_spans == {}


@pytest.mark.parametrize("clear", [False, True])
def test_span_counters_accumulate_and_clear(clear):
    rec = TraceRecorder()
    clock = CountingClock()
    calls = []
    a = rec.span("x", clock)
    b = rec.span("x", clock)  # one name, one set of counters
    a._annotation = b._annotation = lambda name: _Annotation(calls, name)
    a._profiling = b._profiling = lambda: True
    with a:
        pass
    b.start()
    clock()  # the second call lasts two ticks
    b.stop()
    if clear:
        rec.clear_spans()
        with a:
            pass
    st = rec.span_stats()["x"]
    assert calls == ["x"] * (3 if clear else 2)
    if clear:
        assert (st.count, st.total_s, st.max_s, st.t_of_max) == (1, 1.0, 1.0, 6.0)
    else:
        assert (st.count, st.total_s, st.max_s, st.t_of_max) == (2, 3.0, 2.0, 3.0)
    # with no profiler collecting, a call is counted but not annotated
    a._profiling = lambda: False
    with a:
        pass
    assert len(calls) == (3 if clear else 2)
    assert rec.span_stats()["x"].count == st.count + 1
    assert TraceRecorder(enabled=False).span("x", clock) is None


class _Annotation:
    def __init__(self, calls, name):
        calls.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None
