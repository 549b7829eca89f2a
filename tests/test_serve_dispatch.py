"""One dispatch per tile window: after `PharosServer.warmup` a serving
step launches every window through its layer's `LaunchPlan`, with every
operand already on the device, and computes what it computed when each
window transferred its start and each layer allocated its accumulator
from the host."""
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.preemptible_matmul import matmul, matmul_window
from repro.obs import TraceRecorder
from repro.pipeline import PharosServer, ServeTask
from repro.pipeline.serve import _jnp_row_strip, _run_window, launch_plan
from repro.traffic.clock import VirtualClock

BLOCK = (128, 128, 128)
BACKENDS = ["jnp", "pallas"]


def _w(K, N, key):
    return jax.random.normal(jax.random.PRNGKey(key), (K, N), jnp.float32) / np.sqrt(K)


def _heavy():
    """256 rows: layer 0 is 8 tiles (two row strips on the jnp backend),
    forwarded from stage 0 to stage 1 for layer 1."""
    return ServeTask(
        "heavy", (_w(128, 512, 1), _w(512, 256, 2)), (0, 1),
        period=10.0, input_rows=256,
    )


def _urgent():
    return ServeTask("urgent", (_w(128, 128, 3),), (0,), period=1.0, input_rows=128)


def _server(backend, tasks, *, trace=None):
    clk = VirtualClock()
    return PharosServer(
        tasks, 2, policy="edf", backend=backend, window_tiles=1,
        clock=clk.now, sleep=clk.sleep, trace=trace,
    )


def _guarded_step(srv):
    with jax.transfer_guard("disallow"):
        return srv.step()


def _serve_with_midlayer_preemption(srv):
    """The heavy job runs one window, the urgent job (earlier deadline)
    then preempts it in the middle of layer 0; every step runs with host
    transfers disallowed. Returns the two jobs."""
    heavy = srv.submit(0, 0.0)
    assert _guarded_step(srv)
    assert 0 < heavy.next_tile < 8
    urgent = srv.submit(1, 0.0)
    while srv.report.jobs_completed < 2:
        _guarded_step(srv)
    assert srv.report.preemptions == 1
    return heavy, urgent


def _plain_chain(x, weights, backend):
    """Each layer whole: ``jnp.dot`` at HIGHEST (the jnp backend's row
    strips are its rows) or the kernel's unpreempted `matmul`."""
    for w in weights:
        if backend == "jnp":
            x = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
        else:
            x = matmul(x, w, block=BLOCK)
    return x


def _launched_as_before(x, weights, backend):
    """Window by window, as windows were launched before launch plans:
    the start transferred from the host per window, a fresh
    ``jnp.zeros`` accumulator per layer."""
    for w in weights:
        M, K = x.shape
        N = w.shape[1]
        n_n = N // BLOCK[2]
        c = jnp.zeros((M, N), jnp.float32)
        if backend == "jnp":
            for row in range(M // BLOCK[0]):
                c = _jnp_row_strip(x, w, c, jnp.int32(row), bm=BLOCK[0])
        else:
            tile = 0
            while tile < (M // BLOCK[0]) * n_n:
                c, tile = matmul_window(x, w, c, tile, block=BLOCK, window_tiles=1)
        x = c
    return x


@pytest.mark.parametrize("backend", BACKENDS)
def test_warm_server_steps_without_host_transfers_and_outputs_are_unchanged(backend):
    srv = _server(backend, [_heavy(), _urgent()])
    srv.warmup()
    heavy, urgent = _serve_with_midlayer_preemption(srv)
    assert srv.report.launch_plan_misses == 0
    for job in (heavy, urgent):
        x0, ws = srv.inputs[job.task_id], srv.tasks[job.task_id].weights
        out = np.asarray(job.x)
        assert np.array_equal(out, np.asarray(_plain_chain(x0, ws, backend)))
        assert np.array_equal(out, np.asarray(_launched_as_before(x0, ws, backend)))


@pytest.mark.parametrize("backend", BACKENDS)
def test_dispatch_is_marked_resumed_only_in_the_middle_of_a_layer(backend):
    rec = TraceRecorder()
    srv = _server(backend, [_heavy(), _urgent()], trace=rec)
    srv.warmup()
    _serve_with_midlayer_preemption(srv)
    dispatches = [
        (e.task, e.stage, bool((e.attrs or {}).get("resumed")))
        for e in rec.events
        if e.kind == "dispatch"
    ]
    # heavy starts, urgent preempts it, heavy resumes mid-layer 0, then
    # starts layer 1 afresh on stage 1
    assert dispatches == [
        ("heavy", 0, False),
        ("urgent", 0, False),
        ("heavy", 0, True),
        ("heavy", 1, False),
    ]


def _finish(srv, jobs, step):
    while any(j.done_at is None for j in jobs):
        step()


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_server_plans_each_geometry_it_first_meets_while_serving_once(backend):
    """Without `warmup`, a layer geometry is planned on first use and
    counted; a tenant served later counts only its new geometries."""
    # shares the heavy tenant's (256, 128, 512) first layer, adds one
    late = ServeTask(
        "late", (_w(128, 512, 4), _w(512, 384, 5)), (0, 0),
        period=1.0, input_rows=256,
    )
    srv = _server(backend, [_heavy(), late])
    _finish(srv, [srv.submit(0, 0.0), srv.submit(0, 0.0)], srv.step)
    assert srv.report.launch_plan_misses == 2
    job = srv.submit(1, 0.0)
    _finish(srv, [job], srv.step)
    assert srv.report.launch_plan_misses == 3
    x0 = srv.inputs[1]
    want = _plain_chain(x0, late.weights, backend)
    assert np.array_equal(np.asarray(job.x), np.asarray(want))
    # every geometry planned: both tenants serve without host transfers
    _finish(srv, [srv.submit(1, 0.0), srv.submit(0, 0.0)], lambda: _guarded_step(srv))
    assert srv.report.launch_plan_misses == 3


@pytest.mark.parametrize("backend", BACKENDS)
def test_plans_are_shared_by_geometry_and_start_on_window_boundaries(backend):
    geometry = (256, 128, 512, BLOCK, backend, 4 if backend == "jnp" else 3)
    plan = launch_plan(*geometry)
    assert plan is launch_plan(*geometry)
    assert plan.total == 8
    # pallas rounds 3 down to a divisor of 8; jnp serves a tile row
    assert plan.window == (4 if backend == "jnp" else 2)
    want = [0, 1] if backend == "jnp" else [0, 2, 4, 6]
    assert [int(s) for s in plan.starts] == want
    a, b = _w(256, 128, 6), _w(128, 512, 7)
    c = jnp.zeros((256, 512), jnp.float32)
    with pytest.raises(ValueError, match="multiple"):
        _run_window(a, b, c, 1, block=BLOCK, window=plan.window, backend=backend)


def test_dispatch_probe_reports_every_kind_of_call(capsys):
    path = Path(__file__).resolve().parents[1] / "tools" / "dispatch_probe.py"
    spec = importlib.util.spec_from_file_location("dispatch_probe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(["--calls", "3", "--dim", "128"]) == 0
    res = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert set(res["us_per_call"]) == {
        "asarray", "zeros", "jit_noop", "window_device_start",
        "window_numpy_start", "served_launch",
    }
    assert all(us > 0 for us in res["us_per_call"].values())
