"""One run of one benchmark cell, driven by the files the cell names.

A cell (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json``: the deployment's tenants, platform and
sizes), a scheduling policy and each tenant's traffic in absolute
seconds. Each tenant of the configuration is served as its model
(``"model": "<name>"``, ``models/<name>.py``; `DEFAULT_MODEL` where it
names none), which builds, counts and checks it (`load_model`).
`run_cell` then:

1. builds the deployment: the DSE design of the configuration's
   tenants (`repro.traffic.scenarios.build`), and each tenant at full
   width through its model, from the seed, on the device;
2. sets up as a user would: `PharosServer.warmup`,
   `CostModel.calibrate`, and the gateway's admission on the
   calibrated WCETs (all of it counts in ``setup_s``);
3. serves for ``seconds`` through `TrafficGateway.begin_run` /
   `release_due`, `PharosServer.step` and `finish_run`, on a
   `WallClock`; open-loop tenants are released by the gateway on the
   cell's schedule, closed-loop tenants are submitted as best-effort
   jobs when their previous job completes; each job reads the input
   its model gives its ordinal (its place among the tenant's jobs);
4. waits for every job due in the window, then compares the served
   outputs with the model's plain reference of their ordinals;
5. returns the result line of the benchmark's contract.

With ``trace`` it also records the server's schedule events and a
profiler trace of a steady part of the window, and reads the cell's
per-layer metrics from them through ``metrics/<metric>.py``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import random
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace

import jax

import program_spans
import reference
import trace_reduce
import traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: the host spans a traced run writes around its calls into each layer
SPANS = (
    "gateway.release_due",
    "server.step",
    "client.submit",
    "client.idle",
)
#: where `load_model` looks for ``<name>.py``, in this order
MODEL_DIRS = [BENCH / "models"]
#: the model of a tenant that names none
DEFAULT_MODEL = "gemm_chain"
#: a finished job's output is kept for the check when it is this small;
#: larger ones are sampled
SMALL_OUTPUT_BYTES = 1 << 20
#: at most this many large outputs kept per tenant
MAX_LARGE_KEPT = 12
#: how long the run waits past the window for jobs due in it
DRAIN_LIMIT_S = 60.0
#: idle poll of the serving loop
IDLE_SLEEP_S = 1e-4
#: a ``step`` call this long is logged as a stall
STALL_S = 0.05


# ---------------------------------------------------------------------------
# the files
# ---------------------------------------------------------------------------
def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_spec(kind: str, name: str) -> dict:
    """``<kind>/<name>.json`` under the benchmark's directory."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def cell_metrics(bench: dict, cell: str, key: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
    return [
        m for m in bench[key]
        if "workloads" not in m or cell in m["workloads"]
    ]


def load_reader(metric: str):
    """``metrics/<metric>.py``'s ``read(ctx) -> float | None``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_model(name: str):
    """The model module ``<name>.py`` of the first of `MODEL_DIRS` that
    holds one, loaded once per process (a model module may build on
    another through this). Its contract:

    ``build(specs, *, key, rows, block, stages, workloads, max_dim)``
        returns one tenant object per entry of ``specs``, the
        configuration's tenants that name this model, in its order.
        ``key`` is the seed's PRNG key, the same for every model of a
        configuration (a model draws from keys it folds from it);
        ``rows`` the configuration's rows; ``block`` the server's tile
        block; ``stages`` per tenant the stage of each layer of its DSE
        workload; ``workloads`` per tenant that workload
        (`repro.traffic.scenarios.BuiltScenario.workloads`);
        ``max_dim`` a width cap for CPU tests (None on the chip). What
        the reference needs is made on the device here.

    A tenant object has:

    - ``layers``: the layers of a job (progress ``(layers, 0)`` is done);
    - ``output_bytes``: the bytes of a job's output, for the keep rule;
    - ``task(name, period, deadline)``: the program's task object;
    - ``job_input(k)``: the input of the tenant's ``k``-th job (from 0);
    - ``output(job)``: the array a finished job produced;
    - ``reference(k)``: what job ``k`` must produce, computed plainly;
    - ``control(k)``: the same one precision down (`control.py`), and
      optionally ``witness(k)``, another path to it that `control.py`
      prints beside it;
    - ``flops_done(progress)``: FLOPs a job has completed at progress
      ``(layer, next_tile)``;
    - ``windows_between(window_tiles, a, b)``: ``(kernel, flops, bytes,
      gemm)`` of each window a job ran from progress ``a`` to ``b``,
      ``gemm`` the ``(M, K, N, start, tiles)`` of a window of the GEMM
      window kernel (None for another kernel).
    """
    if not name or not all(c.isalnum() or c in "_.-" for c in name):
        raise ValueError(f"bad model name {name!r}")
    key = "bench_model_" + name.replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    path = next((d / f"{name}.py" for d in MODEL_DIRS
                 if (d / f"{name}.py").is_file()), None)
    if path is None:
        raise FileNotFoundError(f"no model file {name}.py in {MODEL_DIRS}")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (copied from `repro.obs.metrics`)."""
    vals = sorted(values)
    if not vals:
        return math.nan
    return vals[max(1, math.ceil(q / 100.0 * len(vals))) - 1]


def seed_key(seed: int):
    """A PRNG key from any non-negative whole-number seed."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed % 2**32), seed // 2**32
    )


# ---------------------------------------------------------------------------
# the deployment: design and tenants
# ---------------------------------------------------------------------------
@dataclass
class Deployment:
    config: dict
    built: object  # repro.traffic.scenarios.BuiltScenario
    names: list[str]
    criticality: list[str]
    #: per tenant, the tenant object its model built (`load_model`)
    tenants: list
    block: tuple[int, int, int]


def build_deployment(config: dict, seed: int, *, max_dim=None,
                     log=lambda msg: None) -> Deployment:
    from repro.core.perfmodel import hardware
    from repro.traffic.scenarios import (
        ArrivalSpec,
        TenantSpec,
        TrafficScenario,
        build,
    )

    tenants = []
    for t in config["tenants"]:
        dse = t["dse"]
        tenants.append(TenantSpec(
            workload=t["workload"],
            ratio=dse["ratio"],
            arrival=ArrivalSpec(**dse.get("arrival", {})),
            value=t["value"],
            name=t["name"],
            batch=t.get("batch", 1),
            seq=t.get("seq", 2048),
            criticality=t["criticality"],
        ))
    scenario = TrafficScenario(
        name=config["name"], description=config["name"],
        tenants=tuple(tenants),
    )
    factory, *args = config["platform"]
    t = time.perf_counter()
    built = build(scenario, getattr(hardware, factory)(*args))
    log(f"design search {time.perf_counter() - t:.3f} s")
    from repro.pipeline.serve import DEFAULT_BLOCK

    block = tuple(DEFAULT_BLOCK)
    stages = []
    for i in range(len(tenants)):
        st = []
        for k in range(built.design.n_stages):
            st += [k] * built.design.splits[k][i]
        stages.append(tuple(st))
    key = seed_key(seed)
    models = [t.get("model", DEFAULT_MODEL) for t in config["tenants"]]
    made = [None] * len(models)
    for name in dict.fromkeys(models):
        idx = [i for i, m in enumerate(models) if m == name]
        out = load_model(name).build(
            [config["tenants"][i] for i in idx],
            key=key, rows=config["rows"], block=block,
            stages=[stages[i] for i in idx],
            workloads=[built.workloads[i] for i in idx],
            max_dim=max_dim,
        )
        for i, tenant in zip(idx, out, strict=True):
            made[i] = tenant
    return Deployment(
        config=config,
        built=built,
        names=[t["name"] for t in config["tenants"]],
        criticality=[t["criticality"] for t in config["tenants"]],
        tenants=made,
        block=block,
    )


# ---------------------------------------------------------------------------
# the server with its jobs in view
# ---------------------------------------------------------------------------
def _recording_server_class():
    from repro.pipeline.serve import PharosServer

    class RecordingServer(PharosServer):
        """`PharosServer` that gives every job it is given (the
        gateway's releases and the client's) the input its tenant's
        model gives the job's ordinal, ``k`` for the tenant's ``k``-th
        job, and hands the job and ``k`` to ``on_submit``."""

        on_submit = None

        def __init__(self, tasks, *args, models, **kw):
            super().__init__(tasks, *args, **kw)
            self.models = models
            self.submitted = [0] * len(tasks)

        def submit(self, task_id, release=None, *, best_effort=False):
            k = self.submitted[task_id]
            self.submitted[task_id] += 1
            self.inputs[task_id] = self.models[task_id].job_input(k)
            job = super().submit(task_id, release, best_effort=best_effort)
            if self.on_submit is not None:
                self.on_submit(job, k)
            return job

    return RecordingServer


@dataclass
class JobRecord:
    uid: int
    task: int
    release: float
    best_effort: bool
    abs_deadline: float
    done_at: float | None
    #: the job's position in its tenant's schedule (None: closed loop)
    due: int | None


class Tracker:
    """Follows every job through the window: keeps a record of each,
    the outputs the check samples with their ordinals (``(k, output)``
    per tenant), and which jobs EDF preempted in the
    middle of a layer. `poll` runs after every ``step``: a job that a
    step preempted did not run again in that step, so its ``next_tile``
    is where the preemption cut its layer."""

    def __init__(self, server, dep: Deployment, seed: int):
        self.server = server
        self.dep = dep
        self.rng = random.Random(f"{seed}:sample")
        self.live: list = []
        #: uid -> position in the tenant's schedule, of released jobs
        self.due: dict[int, int] = {}
        self._due_at: list[dict[float, int]] = [{} for _ in dep.names]
        self.records: list[JobRecord] = []
        self.outputs: dict[int, list] = {i: [] for i in range(len(dep.names))}
        #: uid -> ordinal among its tenant's jobs, of live jobs
        self.ordinal: dict[int, int] = {}
        self.midlayer: set[int] = set()
        self.midlayer_kept = 0
        self._seen_pre: dict[int, int] = {}
        self._done = 0
        self._pre = 0
        self._large_kept = [0] * len(dep.names)
        self._small = [t.output_bytes <= SMALL_OUTPUT_BYTES for t in dep.tenants]
        server.on_submit = self._on_submit

    def arm(self, t0: float, schedules: list[list[float]]) -> None:
        """Match released jobs to their schedule positions: the gateway
        releases a job due at ``t`` of a run begun at ``t0`` with the
        release time ``t0 + t``."""
        self._due_at = [{t0 + t: k for k, t in enumerate(sch)} for sch in schedules]

    def _on_submit(self, job, ordinal: int) -> None:
        self.ordinal[job.uid] = ordinal
        k = self._due_at[job.task_id].get(job.release)
        if k is not None:
            self.due[job.uid] = k
        self.live.append(job)

    def poll(self) -> None:
        rep = self.server.report
        if rep.preemptions != self._pre:
            self._pre = rep.preemptions
            for j in self.live:
                if j.preemptions != self._seen_pre.get(j.uid, 0):
                    self._seen_pre[j.uid] = j.preemptions
                    if j.next_tile > 0:
                        self.midlayer.add(j.uid)
        if rep.jobs_completed != self._done:
            self._done = rep.jobs_completed
            still = []
            for j in self.live:
                if j.done_at is None:
                    still.append(j)
                else:
                    self._finish(j)
            self.live[:] = still

    def _finish(self, j) -> None:
        mid = j.uid in self.midlayer
        self.records.append(JobRecord(
            j.uid, j.task_id, j.release, j.best_effort, j.abs_deadline,
            j.done_at, self.due.get(j.uid),
        ))
        t = j.task_id
        k = self.ordinal.pop(j.uid)
        keep = self._small[t]
        if not keep and self._large_kept[t] < MAX_LARGE_KEPT:
            keep = self.rng.random() < 0.5 or mid
            self._large_kept[t] += keep
        if keep:
            self.outputs[t].append((k, self.dep.tenants[t].output(j)))
            self.midlayer_kept += mid

    def progress(self) -> dict[int, tuple[int, tuple[int, int]]]:
        """``uid -> (task, (layer, next_tile))`` of the live jobs."""
        return {j.uid: (j.task_id, (j.layer, j.next_tile)) for j in self.live}

    def sync(self) -> None:
        """Wait until the device has run every window issued so far."""
        jax.block_until_ready(
            [j.c_acc for j in self.live if j.c_acc is not None]
        )


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclass
class Session:
    dep: Deployment
    cell: dict
    server: object
    gateway: object
    clock: object
    tracker: Tracker
    schedules: list[list[float]]
    closed: list[int]
    decisions: list
    recorder: object = None
    stalls: list = field(default_factory=list)


def prepare(dep: Deployment, cell: dict, seed: int, horizon_s: float, *,
            trace=False, log=lambda msg: None):
    """Server, calibration, contracts, schedules and admission: the
    set-up a user pays before serving."""
    from repro.conformance import CostModel
    from repro.obs import TraceRecorder
    from repro.traffic.admission import AdmissionController, TaskRequest
    from repro.traffic.arrival import TraceArrivals
    from repro.traffic.clock import WallClock
    from repro.traffic.gateway import TrafficGateway
    from repro.traffic.modes import ModeController

    policy = cell["policy"]
    specs = [cell["tenants"][n] for n in dep.names]
    tasks = [
        t.task(
            n,
            period=s.get("period_s", s.get("contract_period_s")),
            deadline=s.get("deadline_s", 0.0),
        )
        for n, t, s in zip(dep.names, dep.tenants, specs)
    ]
    clock = WallClock()
    recorder = TraceRecorder() if trace else None
    n_stages = dep.built.design.n_stages
    server = _recording_server_class()(
        tasks, n_stages, policy=policy, backend="pallas",
        clock=clock.now, sleep=clock.sleep, trace=recorder,
        models=dep.tenants,
    )
    server.inputs = [t.job_input(0) for t in dep.tenants]
    t = time.perf_counter()
    server.warmup()
    t_warm = time.perf_counter() - t
    measured = CostModel.calibrate(server, reps=3)
    log(f"warm-up {t_warm:.3f} s, calibration "
        f"{time.perf_counter() - t - t_warm:.3f} s")
    by_name = traffic.schedules(cell["tenants"], horizon_s, seed)
    schedules = [by_name[n] for n in dep.names]
    requests, arrivals, closed = [], [], []
    for i, (n, s) in enumerate(zip(dep.names, specs)):
        is_closed = s["arrival"] == "closed"
        if is_closed:
            closed.append(i)
        requests.append(TaskRequest(
            name=n,
            base=tuple(measured.segment_cost(i, k) for k in range(n_stages)),
            period=tasks[i].period,
            deadline=tasks[i].deadline,
            value=dep.config["tenants"][i]["value"],
            best_effort=is_closed,
            criticality=dep.criticality[i],
        ))
        arrivals.append(TraceArrivals(
            times=tuple(schedules[i]), provisioned_period=tasks[i].period,
        ))
    admission = AdmissionController([0.0] * n_stages, preemptive=policy == "edf")
    modes = None
    if cell.get("modes"):
        modes = ModeController(admission, requests, **cell["modes"])
    gateway = TrafficGateway(
        server, admission, requests, arrivals, modes=modes, clock=clock,
    )
    decisions = gateway.open()
    tracker = Tracker(server, dep, seed)
    return Session(
        dep=dep, cell=cell, server=server, gateway=gateway, clock=clock,
        tracker=tracker, schedules=schedules, closed=closed,
        decisions=decisions, recorder=recorder,
    )


@dataclass
class Window:
    seconds: float
    t0: float
    t_end: float
    t_drained: float
    compiles: int
    windows: int
    step_busy_s: float
    #: submissions per closed-loop tenant
    closed_submitted: dict
    #: FLOPs of the LO tenants' windows run in the window
    lo_flops: int
    report: object = None  # GatewayReport
    traced: dict = field(default_factory=dict)


class _Compiles:
    """Counts backend compilations while armed, and keeps the garbage
    collector's passes and JAX's timed events that last ``STALL_S`` or
    more, as ``(what, perf_counter at the end, seconds)``: the run logs
    them beside its stalls."""

    def __init__(self):
        self.armed = False
        self.n = 0
        self.slow: list[tuple[str, float, float]] = []
        self._gc_start = 0.0
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on)
        gc.callbacks.append(self._on_gc)

    def _on(self, event, duration, **kw):
        if not self.armed:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
        if duration >= STALL_S:
            self.slow.append((event, time.perf_counter(), duration))

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self.armed:
            d = time.perf_counter() - self._gc_start
            if d >= STALL_S:
                self.slow.append((f"gc generation {info['generation']}",
                                  time.perf_counter(), d))


def serve(s: Session, seconds: float, *, trace_dir=None, trace_s=3.0,
          compiles: _Compiles | None = None) -> Window:
    """Serve the cell for ``seconds``; with ``trace_dir``, profile the
    device over the last ``trace_s`` seconds of the window (the trace is
    written once the jobs due in the window have finished, so writing
    it delays none of them; the reduction reads the window alone)."""
    server, gw, clock, tr = s.server, s.gateway, s.clock, s.tracker
    tracing = trace_dir is not None
    span = jax.profiler.TraceAnnotation if tracing else (
        lambda name: contextlib.nullcontext()
    )
    closed = {
        i: [None] * s.cell["tenants"][s.dep.names[i]].get("outstanding", 1)
        for i in s.closed
    }
    closed_submitted = {i: 0 for i in s.closed}
    step_busy = 0.0
    traced: dict = {}
    profiling = False
    prof_from = max(0.0, seconds - trace_s)
    window_span = None
    stalls: list[tuple[float, float]] = []

    gc.collect()
    gc.freeze()
    if compiles is not None:
        compiles.armed = True
    gw.begin_run(seconds, warmup=False)
    t0 = gw._run.t0  # the time the gateway's releases are counted from
    tr.arm(t0, s.schedules)
    perf = time.perf_counter
    while True:
        with span("gateway.release_due"):
            rel = gw.release_due()
        if rel >= seconds:
            break
        if tracing and not profiling and rel >= prof_from:
            tr.sync()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            window_span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            window_span.__enter__()
            traced = {
                "progress": tr.progress(),
                "records": len(tr.records),
                "windows": server.report.windows_executed,
            }
            profiling = True
        for i, jobs in closed.items():
            for k, job in enumerate(jobs):
                if job is None or job.done_at is not None:
                    with span("client.submit"):
                        jobs[k] = server.submit(i, clock.now(), best_effort=True)
                    closed_submitted[i] += 1
        a = perf()
        with span("server.step"):
            ran = server.step()
        a = perf() - a
        if ran:
            step_busy += a
        if a > STALL_S:
            stalls.append((rel, a))
        tr.poll()
        if not ran:
            with span("client.idle"):
                clock.sleep(IDLE_SLEEP_S)
    t_end = clock.now()
    tr.poll()
    windows = server.report.windows_executed
    if profiling:
        tr.sync()
        traced["progress_end"] = tr.progress()
        traced["records_end"] = len(tr.records)
        traced["windows_end"] = server.report.windows_executed
        window_span.__exit__(None, None, None)
    lo_flops = _lo_flops(s)
    # every job due in the window is waited for; the clients stop
    limit = clock.now() + DRAIN_LIMIT_S
    while tr.live and clock.now() < limit:
        if not server.step():
            clock.sleep(IDLE_SLEEP_S)
        tr.poll()
    t_drained = clock.now()
    if profiling:
        jax.profiler.stop_trace()
    if compiles is not None:
        compiles.armed = False
    gc.unfreeze()
    report = gw.finish_run()
    s.stalls = stalls
    return Window(
        seconds=seconds, t0=t0, t_end=t_end, t_drained=t_drained,
        compiles=compiles.n if compiles is not None else -1,
        windows=windows, step_busy_s=step_busy,
        closed_submitted=closed_submitted, lo_flops=lo_flops,
        report=report, traced=traced,
    )


def _lo_flops(s: Session) -> int:
    """FLOPs of every LO tenant's windows so far (no job predates the
    window, so this is the window's work)."""
    dep, tr = s.dep, s.tracker
    lo = {i for i, c in enumerate(dep.criticality) if c != "HI"}
    total = 0
    for r in tr.records:
        if r.task in lo:
            t = dep.tenants[r.task]
            total += t.flops_done((t.layers, 0))
    for j in tr.live:
        if j.task_id in lo:
            total += dep.tenants[j.task_id].flops_done((j.layer, j.next_tile))
    return total


def traced_windows(s: Session, w: Window) -> list[tuple]:
    """``(kernel, flops, bytes, gemm)`` of every window run while traced,
    from each job's progress (its model's ``windows_between``)."""
    t = w.traced
    if "progress_end" not in t:
        return []
    tenants, wt = s.dep.tenants, s.server.window_tiles
    jobs = dict(t["progress_end"])
    for r in s.tracker.records[t["records"]:t["records_end"]]:
        jobs[r.uid] = (r.task, (tenants[r.task].layers, 0))
    out = []
    for uid, (task, end) in jobs.items():
        start = t["progress"].get(uid, (task, (0, 0)))[1]
        out += tenants[task].windows_between(wt, start, end)
    return out


# ---------------------------------------------------------------------------
# what the window did
# ---------------------------------------------------------------------------
def outcomes(s: Session, w: Window) -> dict:
    """End-to-end numbers of the window and the counts of the line."""
    dep, tr = s.dep, s.tracker
    stats = {st.name: st for st in w.report.tenants}
    admitted = {d.request.name: d.admitted for d in s.decisions}
    by_task: dict[int, list[JobRecord]] = {i: [] for i in range(len(dep.names))}
    for r in tr.records:
        by_task[r.task].append(r)
    for j in tr.live:  # unfinished after the drain limit
        by_task[j.task_id].append(JobRecord(
            j.uid, j.task_id, j.release, j.best_effort, j.abs_deadline, None,
            tr.due.get(j.uid),
        ))
    attempted = failed = late = hi_due = hi_on_time = 0
    hi_resp, missed = [], []
    for i, name in enumerate(dep.names):
        st = stats[name]
        hi = dep.criticality[i] == "HI"
        if i in s.closed:
            attempted += w.closed_submitted[i]
            failed += 0 if admitted[name] else 1
            continue
        due = s.schedules[i]
        attempted += len(due)
        refused = len(due) if not admitted[name] else st.shed + st.rate_limited
        failed += refused
        recs = [r for r in by_task[i] if r.due is not None]
        # a job that never finished failed; one that finished after its
        # deadline was served late: ``hi_on_time`` counts it, as a host
        # pause of a tenth of a second makes some job late on any load
        failed += sum(1 for r in recs if r.done_at is None)
        late += sum(
            1 for r in recs
            if not r.best_effort and r.done_at is not None
            and r.done_at > r.abs_deadline
        )
        if hi:
            hi_due += len(due)
            hi_on_time += sum(
                1 for r in recs
                if not r.best_effort and r.done_at is not None
                and r.done_at <= r.abs_deadline
            )
            hi_resp += [
                (r.done_at if r.done_at is not None else w.t_drained) - r.release
                for r in recs
            ]
            missed += [
                (name, r.release - w.t0, (r.done_at or w.t_drained) - r.release)
                for r in recs
                if r.best_effort or r.done_at is None or r.done_at > r.abs_deadline
            ]
            released = {r.due for r in recs}
            hi_resp += [
                w.t_drained - (w.t0 + t) for k, t in enumerate(due)
                if k not in released
            ]
    return {
        "attempted": attempted,
        "failed": failed,
        "late": late,
        "hi_due": hi_due,
        "hi_on_time": hi_on_time,
        "hi_resp_s": hi_resp,
        "hi_missed": missed,
        "window_s": w.t_end - w.t0,
    }


def check(s: Session, limits: dict) -> tuple[bool, dict]:
    """Compare each sampled output of ordinal ``k`` with its model's
    ``reference(k)``; returns ``(correct, {name: {"value", "limit"}})``."""
    dep, tr = s.dep, s.tracker
    checks = {}
    for i, name in enumerate(dep.names):
        outs = tr.outputs[i]
        t = dep.tenants[i]
        err = max(
            (reference.max_rel_err([out], t.reference(k)) for k, out in outs),
            default=math.inf,
        )
        lim = limits["rel_err"][name]
        checks[f"rel_err.{name}"] = _check(err, "<=", lim)
        checks[f"jobs_compared.{name}"] = _check(len(outs), ">=", 1)
    checks["midlayer_preempted_compared"] = _check(tr.midlayer_kept, ">=", 1)
    checks["jobs_unfinished"] = _check(len(tr.live), "<=", 0)
    return all(c["ok"] for c in checks.values()), checks


def _check(value, op: str, limit) -> dict:
    ok = value <= limit if op == "<=" else value >= limit
    return {"value": value, "limit": limit, "op": op, "ok": bool(ok)}


def _device_info() -> dict:
    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    peak = max(
        ((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs),
        default=stats.get("peak_bytes_in_use", 0),
    )
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": int(peak or 0),
    }


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, peaks: dict, max_dim=None, log=None,
             cell: dict | None = None) -> dict:
    """One run of ``cell_name`` (its file, or ``cell`` in its place):
    the contract's result line as a dict (``checks`` last)."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    bench = load_benchmark()
    cell = cell or load_spec("workloads", cell_name)
    config = load_spec("configs", cell["config"])
    readers = {
        m["name"]: load_reader(m["name"])
        for m in cell_metrics(bench, cell_name, "per_layer")
    } if trace else {}
    compiles = _Compiles()
    log(f"{time.perf_counter() - t_start:.3f} s to reach the device")
    dep = build_deployment(config, seed, max_dim=max_dim, log=log)
    log(f"built {cell['config']}: {dep.built.design.n_stages} stages, "
        + ", ".join(f"{n} {t.layers} layers" for n, t in zip(dep.names, dep.tenants))
        + f"; {time.perf_counter() - t_start:.3f} s since start")
    s = prepare(dep, cell, seed, seconds, trace=trace, log=log)
    for d in s.decisions:
        if not d.admitted and d.request.criticality == "HI":
            raise RuntimeError(f"HI tenant {d.request.name} refused: {d.reason}")
    trace_dir = None
    if trace:
        trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; serving {seconds} s")
    w = serve(
        s, seconds, trace_dir=trace_dir, trace_s=min(3.0, seconds / 3.0),
        compiles=compiles,
    )
    out = outcomes(s, w)
    device = _device_info()
    log(f"window {out['window_s']:.3f} s, drained {w.t_drained - w.t_end:.3f} s "
        f"later; windows {w.windows}; compiles in window {w.compiles}; "
        f"attempted {out['attempted']} failed {out['failed']} late {out['late']}; "
        f"preemptions {s.server.report.preemptions}, mid-layer "
        f"{len(s.tracker.midlayer)}")
    log("step calls over 50 ms (s into the window, s): " + ", ".join(
        f"({t:.3f}, {d:.3f})" for t, d in s.stalls[:10]))
    log("GC passes and JAX events over 50 ms (what, s into the window at "
        "their end, s): " + ", ".join(
            f"({n}, {t - w.t0:.3f}, {d:.3f})" for n, t, d in compiles.slow[:10]))
    worst = sorted(out["hi_resp_s"])[-3:]
    log(f"HI responses: p95 {percentile(out['hi_resp_s'], 95) * 1e3:.3f} ms, worst "
        + ", ".join(f"{r * 1e3:.3f}" for r in worst)
        + " ms; missed (tenant, due s, response ms): "
        + ", ".join(f"({n}, {t:.3f}, {r * 1e3:.3f})" for n, t, r in out["hi_missed"][:10]))
    ctx = None
    summary = None
    if trace:
        summary = _summarize(trace_dir, log)
        ctx = _context(s, w, out, summary, peaks, config, cell, cell_name)
        program_spans.log_longest_calls(ctx.program_spans, w.t0, log)
        log(f"traced windows: {len(ctx.traced_ops)} from the jobs' progress "
            f"({len(ctx.traced_windows)} GEMM), {ctx.traced_windows_counted} "
            "by the server")
    metrics = {}
    if not trace:
        hi = [r * 1e3 for r in out["hi_resp_s"]]
        values = {
            "setup_s": (setup_s, "s"),
            "hi_resp_p50_ms": (percentile(hi, 50), "ms"),
            "hi_on_time": (out["hi_on_time"] / out["hi_due"] if out["hi_due"] else math.nan, "share"),
            "lo_gflop_per_s": (w.lo_flops / out["window_s"] / 1e9, "GFLOP/s"),
        }
        for m in cell_metrics(bench, cell_name, "end_to_end"):
            v, _ = values[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, cell_name, "per_layer"):
            v = readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    # the check runs on the benchmark's weights once the program is gone
    limits = cell["limits"]
    s.server = s.gateway = s.recorder = s.tracker.server = None
    gc.collect()
    correct, checks = check(s, limits)
    result = {
        "correct": bool(correct),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {
            "device_ops": [[n, v] for n, v in summary["device_ops"]],
            "idle_gaps": [[n, v] for n, v in summary["idle_gaps"]],
            "idle_in_step": summary["idle_in_step"],
        }
    result["checks"] = checks
    return result


def _summarize(trace_dir: Path, log):
    """`trace_reduce.summarize` of the run's profile, with
    ``idle_in_step``: its idle time charged down to the program's spans
    (`program_spans.idle_in_step`); None where the profile holds no
    device operation. The trace is deleted."""
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    try:
        if not files:
            log("no profiler trace written")
            return None
        data = trace_reduce.load_xplane(files[-1])
        try:
            summary = trace_reduce.summarize(data, SPANS)
            summary["idle_in_step"] = program_spans.idle_in_step(data, SPANS)
            return summary
        except ValueError as e:
            log(f"trace: {e}")
            return None
    finally:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)


def _context(s, w, out, summary, peaks, config, cell, cell_name):
    """What the per-layer metric readers read."""
    rel_jitter = [j for st in w.report.tenants for j in st.release_jitter]
    hi_names = {n for n, c in zip(s.dep.names, s.dep.criticality) if c == "HI"}
    queue = []
    if s.recorder is not None:
        rel, first = {}, {}
        for e in s.recorder.events:
            if e.task not in hi_names or e.layer != "runtime":
                continue
            key = (e.task, e.release)
            if e.kind == "release":
                rel.setdefault(key, e.t)
            elif e.kind == "dispatch":
                first.setdefault(key, e.t)
        queue = [first[k] - rel[k] for k in rel if k in first]
    windows = traced_windows(s, w) if summary is not None else []
    spans = w.report.host_spans or {}
    return SimpleNamespace(
        cell=cell, cell_name=cell_name, config=config, peaks=peaks,
        block=s.dep.block, release_jitter_s=rel_jitter,
        step_busy_s=w.step_busy_s, windows_in_window=w.windows,
        hi_queue_s=queue, hi_resp_s=out["hi_resp_s"],
        trace=summary,
        # every window run while traced, as (kernel, flops, bytes)
        traced_ops=[(k, f, b) for k, f, b, _ in windows],
        # the GEMM windows among them, as (M, K, N, start, tiles)
        traced_windows=[g for *_, g in windows if g is not None],
        traced_windows_counted=(
            w.traced.get("windows_end", 0) - w.traced.get("windows", 0)
        ),
        # the kernel of ``traced_windows``
        kernel=load_model(DEFAULT_MODEL).KERNEL,
        # the program's spans over the window (`GatewayReport.host_spans`)
        program_spans={
            n: (st.count, st.total_s, st.max_s, st.t_of_max)
            for n, st in spans.items()
        },
        percentile=percentile,
    )
