"""PHAROS serving runtime: per-stage FIFO/EDF scheduling with
tile-window preemption — the paper's §3.2 control flow executing real
compute.

Entities map 1:1 onto the paper's hardware (Fig. 2):

- ``ServeTask``     — a task: an ordered GEMM chain (the DNN layers),
                      period/deadline, and a layer->stage map obeying
                      the pipelined-topology constraint.
- ``StageRuntime``  — one accelerator: a job pool (FIFO deque / EDF
                      heap), a progress table (per-job, per-layer
                      `MatmulProgress`), and the window executor.
- ``PharosServer``  — the decentralized control flow: jobs released by
                      period, forwarded stage->stage when their segment
                      completes (the HLS FIFO streams), preempted
                      between tile windows when EDF priority demands.

Preemption fidelity: a job is only ever interrupted at a *window*
boundary — the running window always completes (``e_tile``), the fp32
partial accumulator already lives in the job's buffer (``e_store``),
and resumption re-streams the operand tiles (``e_load``) — exactly the
Eq. 5 cost structure, realized by `kernels.preemptible_matmul`.

Window executors: ``backend="jnp"`` (jitted masked-GEMM windows — fast,
used by examples/benchmarks) or ``backend="pallas"`` (the real kernel:
compiled on the TPU, interpreted on the CPU — see
`repro.kernels.backend.resolve_interpret`).

Wall-clock stamps are honest: dispatch is asynchronous, so a layer's
hand-off or completion is stamped only after its accumulator is ready
on the device (``block_until_ready``), never at enqueue.

Launch: every window is one jitted dispatch whose operands already live
on the device (`LaunchPlan`: the start scalars are made once per layer
geometry, and a layer's first window reads the geometry's zero
accumulator), so after `PharosServer.warmup` a serving step transfers
nothing from the host.
"""
from __future__ import annotations

import heapq
import itertools
import time
from collections import deque
from collections.abc import Callable
from dataclasses import dataclass, field, replace
from functools import cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.preemptible_matmul import grid_geometry, pick_window
from repro.kernels.preemptible_matmul.kernel import matmul_window_call

DEFAULT_BLOCK = (128, 128, 128)

#: host spans of the wall-clock serving path (`TraceRecorder.span`),
#: disjoint: the EDF preemption check, pool pop and dispatch event; a
#: layer's progress-table reset (`_start_layer`); one window's plan
#: lookup and jit dispatch (`_exec_window`); the wait for a finished
#: layer's accumulator
SPAN_SCHEDULE = "pharos.schedule"
SPAN_ACC_INIT = "pharos.acc_init"
SPAN_WINDOW_LAUNCH = "pharos.window_launch"
SPAN_LAYER_SYNC = "pharos.layer_sync"

#: Degenerate safety tick (seconds): the smallest forced clock advance
#: of a serving loop iteration that made no progress — no window ran
#: and the next modeled event is not in the future (a float-equality
#: corner the event-driven advance cannot cross on its own). Advancing
#: by this epsilon guarantees a zero-progress step still terminates
#: instead of spinning; it is far below any modeled window cost, so it
#: never perturbs response times. Shared with the gateway's
#: cost-driven loop (`repro.traffic.gateway`).
DEGENERATE_SAFETY_TICK_S = 1e-9


def window_plan(
    M: int, N: int, K: int, *, block, backend: str, window_tiles: int
) -> tuple[int, int]:
    """(window size, window count) the executor runs for one
    ``(M,K) @ (K,N)`` layer — the single source of truth for window
    geometry, shared by the server's launch plans, the cost-model
    validation in `PharosServer.__init__` and
    `repro.conformance.CostModel`. The jnp
    backend serves one output-tile row per window (exact-FLOP fast
    path); the pallas backend honours the configured tile count."""
    _, n_n, _, total = grid_geometry(M, N, K, block)
    window = n_n if backend == "jnp" else pick_window(total, window_tiles)
    return window, -(-total // window)


# ---------------------------------------------------------------------------
# window executors
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n_tiles_m", "n_tiles_n", "block", "window"))
def _jnp_window(a, b, c_acc, start, *, n_tiles_m, n_tiles_n, block, window):
    """Masked-GEMM window: same tile semantics as the Pallas kernel."""
    bm, _, bn = block
    full = jnp.dot(
        a.astype(jnp.float32), b.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    flat = jnp.arange(n_tiles_m * n_tiles_n).reshape(n_tiles_m, n_tiles_n)
    active = (flat >= start) & (flat < start + window)
    mask = jnp.repeat(jnp.repeat(active, bm, 0), bn, 1)
    return c_acc + jnp.where(mask, full, 0.0)


@partial(jax.jit, static_argnames=("bm",))
def _jnp_row_strip(a, b, c_acc, row, *, bm):
    """Fast exact path for window == one row of output tiles: compute
    ``a[row*bm:(row+1)*bm] @ b`` only (the window's actual FLOPs)."""
    a_strip = jax.lax.dynamic_slice_in_dim(a, row * bm, bm, 0)
    strip = jnp.dot(
        a_strip.astype(jnp.float32), b.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return jax.lax.dynamic_update_slice_in_dim(
        c_acc, jax.lax.dynamic_slice_in_dim(c_acc, row * bm, bm, 0) + strip,
        row * bm, 0,
    )


@dataclass(frozen=True)
class LaunchPlan:
    """How every tile window of one layer geometry is launched: one
    jitted dispatch whose operands all live on the device.

    Window ``i`` starts at tile ``i * window`` (`pick_window` divides
    the tile count, and the scheduler preempts only at window
    boundaries), and hands the executable ``starts[i]``, an int32
    device scalar made once with the plan: its first tile, or its tile
    row on the jnp row-strip path."""

    total: int  # output tiles of the layer
    window: int  # tiles a window
    starts: tuple[jax.Array, ...]
    #: ``(a, b, c_acc, start scalar) -> c_acc'``, one jitted dispatch
    launch: Callable


@cache
def launch_plan(M, K, N, block, backend, window) -> LaunchPlan:
    """The plan of an ``(M,K) @ (K,N)`` layer run ``window`` tiles at a
    time (the pallas backend takes the largest divisor of the tile count
    up to ``window``, `pick_window`). Kept for the process: a plan holds
    a few bytes of device scalars, shared by every server of the
    geometry."""
    n_m, n_n, k_steps, total = grid_geometry(M, N, K, block)
    if backend == "pallas":
        window = pick_window(total, window)
        starts = range(0, total, window)
        kernel = partial(
            matmul_window_call,
            block=block, window=window, n_tiles_n=n_n, k_steps=k_steps,
        )

        def launch(a, b, c_acc, start):
            return kernel(start, a, b, c_acc)
    elif window == n_n:
        starts = range(n_m)  # the row strip takes its tile row
        launch = partial(_jnp_row_strip, bm=block[0])
    else:
        starts = range(0, total, window)
        launch = partial(
            _jnp_window,
            n_tiles_m=n_m, n_tiles_n=n_n, block=block, window=window,
        )
    return LaunchPlan(
        total=total,
        window=window,
        starts=tuple(jax.device_put([np.int32(s) for s in starts])),
        launch=launch,
    )


def _run_window(a, b, c_acc, start, *, block, window, backend):
    """Run the window of ``a @ b`` that starts at tile ``start`` (a
    multiple of the window) into ``c_acc``; returns ``(c_acc',
    next_tile)``. One jitted dispatch through the geometry's
    `launch_plan`: no operand comes from the host."""
    plan = launch_plan(a.shape[0], a.shape[1], b.shape[1], block, backend, window)
    i, off = divmod(start, plan.window)
    if off:
        raise ValueError(
            f"window start {start} is not a multiple of {plan.window}"
        )
    c = plan.launch(a, b, c_acc, plan.starts[i])
    return c, min(start + plan.window, plan.total)


# ---------------------------------------------------------------------------
# tasks / jobs
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ServeTask:
    """A periodic inference task: GEMM-chain layers mapped to stages."""

    name: str
    weights: tuple  # tuple of (K, N) jnp weight matrices, chained
    stage_of_layer: tuple[int, ...]  # non-decreasing (pipelined topology)
    period: float  # seconds
    deadline: float = 0.0  # 0 -> implicit
    input_rows: int = 128  # M of the chain input

    def __post_init__(self):
        if len(self.weights) != len(self.stage_of_layer):
            raise ValueError("one stage per layer required")
        if any(
            b < a
            for a, b in zip(self.stage_of_layer, self.stage_of_layer[1:])
        ):
            raise ValueError("stage map must be non-decreasing (no backtrack)")
        if self.deadline == 0.0:
            object.__setattr__(self, "deadline", self.period)

    def rescaled(self, factor: float) -> "ServeTask":
        """The same task on another timebase: period and deadline scaled
        by ``factor``, the weight arrays shared (not copied)."""
        return replace(
            self, period=self.period * factor, deadline=self.deadline * factor
        )


class Job:
    """One released inference + its progress-table rows.

    ``best_effort`` jobs carry an infinite absolute deadline: EDF orders
    them after every guaranteed job and they never count as deadline
    misses — the degraded service class the traffic layer's shedding
    policies demote to under overload.
    """

    _ids = itertools.count()

    def __init__(
        self,
        task_id: int,
        task: ServeTask,
        release: float,
        x0,
        *,
        best_effort: bool = False,
    ):
        self.uid = next(Job._ids)
        self.task_id = task_id
        self.release = release
        self.best_effort = best_effort
        self.abs_deadline = (
            float("inf") if best_effort else release + task.deadline
        )
        self.layer = 0  # next/current layer index
        self.x = x0  # current activation (input of self.layer)
        self.c_acc = None  # partial fp32 accumulator of current layer
        self.next_tile = 0
        self.done_at: float | None = None
        self.preemptions = 0

    def __repr__(self):
        return f"Job(t{self.task_id}#{self.uid} layer={self.layer})"


class StageRuntime:
    """One accelerator: job pool + running-job slot (paper Fig. 2).

    Best-effort jobs are genuinely demoted under both policies: EDF
    orders their infinite deadline after every guaranteed job, and FIFO
    keeps them in a second queue served only when no guaranteed job is
    waiting.
    """

    def __init__(self, idx: int, policy: str):
        self.idx = idx
        self.policy = policy
        self.fifo: deque[Job] = deque()
        self.fifo_be: deque[Job] = deque()  # best-effort background
        self.edf: list[tuple[float, int, Job]] = []
        self.running: Job | None = None
        # cost-model (virtual-time) mode: end of the window in flight
        self.busy_until = 0.0

    def jobs(self) -> list[Job]:
        """Every job currently resident on this stage (pool + running)."""
        out = list(self.fifo) + list(self.fifo_be)
        out += [j for _, _, j in self.edf]
        if self.running is not None:
            out.append(self.running)
        return out

    def push(self, job: Job) -> None:
        if self.policy == "fifo":
            (self.fifo_be if job.best_effort else self.fifo).append(job)
        else:
            heapq.heappush(self.edf, (job.abs_deadline, job.uid, job))

    def pop(self) -> Job | None:
        if self.policy == "fifo":
            if self.fifo:
                return self.fifo.popleft()
            return self.fifo_be.popleft() if self.fifo_be else None
        return heapq.heappop(self.edf)[2] if self.edf else None

    def head_deadline(self) -> float:
        return self.edf[0][0] if self.edf else float("inf")

    def busy(self) -> bool:
        return (
            self.running is not None
            or bool(self.fifo)
            or bool(self.fifo_be)
            or bool(self.edf)
        )


@dataclass
class ServerReport:
    response_times: dict[str, list[float]]
    #: release times of the completed jobs, aligned 1:1 with
    #: ``response_times`` — the join key for matching "the same job"
    #: across runs whose shed sets differ (conformance under overload)
    completed_releases: dict[str, list[float]]
    deadline_misses: dict[str, int]
    preemptions: int
    jobs_completed: int
    jobs_released: int
    windows_executed: int
    #: layer geometries the server first met while serving, whose launch
    #: plan and zero accumulator it built then (a geometry `warmup` did
    #: not see): 0 when warm-up covered every layer served
    launch_plan_misses: int = 0
    #: released-but-unfinished jobs per task at the last
    #: `PharosServer.finalize_report` — the same number the gateway's
    #: backlog monitor polls via `pending`, so overload verdicts and
    #: conformance checks read one counter
    in_flight: dict[str, int] = field(default_factory=dict)

    def max_response(self, name: str) -> float:
        r = self.response_times.get(name, [])
        return max(r) if r else 0.0

    def total_in_flight(self) -> int:
        return sum(self.in_flight.values())

    def response_percentiles(
        self, name: str, qs=(50, 95, 99)
    ) -> dict[str, float]:
        """Nearest-rank response-time percentiles of one tenant
        (`repro.obs.metrics.percentile` — the one shared
        implementation)."""
        from repro.obs.metrics import percentile_summary

        return percentile_summary(self.response_times.get(name, []), qs)

    def tardiness_percentiles(
        self, name: str, deadline: float, qs=(50, 95, 99)
    ) -> dict[str, float]:
        """Per-tenant tardiness (``max(0, response - deadline)``)
        percentiles against the given relative deadline."""
        from repro.obs.metrics import percentile_summary

        return percentile_summary(
            [
                max(0.0, r - deadline)
                for r in self.response_times.get(name, [])
            ],
            qs,
        )


class PharosServer:
    """Decentralized pipelined serving with FIFO/EDF + preemption.

    ``clock``/``sleep`` are injectable (defaults: wall clock). All
    timestamps inside one serving step come from the same clock, so a
    virtual clock (repro.traffic.clock.VirtualClock) makes the runtime
    fully deterministic for tests and for the traffic gateway.

    ``cost_model`` (repro.conformance.CostModel) switches virtual-time
    service from wall-side quantization to model-driven timing: every
    executed tile window occupies its stage for exactly the model's
    per-window WCET on the injected clock, preemption waits for the
    window boundary, and completions are stamped at the modeled finish
    time. Requires an injected (virtual) clock — advancing a wall clock
    by modeled WCETs would be meaningless.

    ``trace`` (a `repro.obs.TraceRecorder`) captures the runtime's
    schedule as structured events — release / dispatch /
    preempt_store / preempt_load (xi = 0: the virtual executor keeps
    accumulators resident, nothing spills) / segment_end / complete /
    deadline_miss — stamped on the injected clock; ``trace_shard`` tags
    every event with the shard index when the server backs one
    `ShardedGateway` replica. None (the default) emits nothing. Without
    a ``cost_model`` the recorder also times the four ``SPAN_*`` host
    spans of every served window (`TraceRecorder.span`).
    """

    def __init__(
        self,
        tasks: list[ServeTask],
        n_stages: int,
        *,
        policy: str = "edf",
        block=DEFAULT_BLOCK,
        window_tiles: int = 4,
        backend: str = "jnp",
        seed: int = 0,
        clock=None,
        sleep=None,
        cost_model=None,
        trace=None,
        trace_shard: int = -1,
    ):
        if policy not in ("fifo", "edf"):
            raise ValueError(policy)
        if cost_model is not None:
            if clock is None:
                raise ValueError(
                    "cost_model-driven serving needs an injected "
                    "(virtual) clock"
                )
            if cost_model.n_tasks != len(tasks) or any(
                len(cost_model.layer_costs[i]) != len(t.weights)
                for i, t in enumerate(tasks)
            ):
                raise ValueError("cost model does not match the task set")
            # window counts must match the executor's real geometry or
            # per-window charges silently mis-time the whole run
            for i, t in enumerate(tasks):
                for j, w in enumerate(t.weights):
                    K, N = w.shape
                    _, expect = window_plan(
                        t.input_rows, N, K,
                        block=block, backend=backend,
                        window_tiles=window_tiles,
                    )
                    have = cost_model.layer_windows[i][j]
                    if have != expect:
                        raise ValueError(
                            f"cost model window count for task {i} "
                            f"layer {j} is {have}, executor runs "
                            f"{expect}"
                        )
        self.tasks = tasks
        self.policy = policy
        self.block = block
        self.window_tiles = window_tiles
        self.backend = backend
        self.cost_model = cost_model
        # rtlint: disable=clock-domain -- injectable wall-clock defaults
        # for live serving; the DES and tests inject virtual clocks
        self.clock = clock if clock is not None else time.perf_counter
        # rtlint: disable=clock-domain -- same: live-serving default
        self.sleep = sleep if sleep is not None else time.sleep
        # schedule-trace handle (repro.obs.TraceRecorder), resolved
        # once: disabled tracing emits nothing and costs nothing
        self._tr = (
            trace
            if trace is not None and getattr(trace, "enabled", False)
            else None
        )
        self._tr_shard = trace_shard
        # host spans of the wall-clock path, resolved once like `_tr`;
        # the cost-model path times nothing
        spans = (
            [
                self._tr.span(name, self.clock)
                for name in (
                    SPAN_SCHEDULE, SPAN_ACC_INIT,
                    SPAN_WINDOW_LAUNCH, SPAN_LAYER_SYNC,
                )
            ]
            if self._tr is not None and cost_model is None
            else [None] * 4
        )
        (
            self._sp_schedule, self._sp_acc_init,
            self._sp_launch, self._sp_sync,
        ) = spans
        self._missed_in_flight: set[int] = set()
        self.released_per_task = [0] * len(tasks)
        self.completed_per_task = [0] * len(tasks)
        self.stages = [StageRuntime(k, policy) for k in range(n_stages)]
        #: ``(M, K, N) -> (LaunchPlan, zero accumulator)`` of every layer
        #: geometry the server runs; the zeros are each first window's
        #: ``c_acc``, never written (the windows do not donate it)
        self._plans: dict[tuple[int, int, int], tuple] = {}
        key = jax.random.PRNGKey(seed)
        self.inputs = []
        for t in tasks:
            key, sub = jax.random.split(key)
            k_dim = t.weights[0].shape[0]
            self.inputs.append(
                jax.random.normal(sub, (t.input_rows, k_dim), jnp.float32)
            )
        self.report = ServerReport(
            response_times={t.name: [] for t in tasks},
            completed_releases={t.name: [] for t in tasks},
            deadline_misses={t.name: 0 for t in tasks},
            preemptions=0,
            jobs_completed=0,
            jobs_released=0,
            windows_executed=0,
        )

    # ------------------------------------------------------------------
    def _start_layer(self, job: Job) -> None:
        """Enter the job's current layer: reset its progress-table row.
        ``c_acc`` stays None (nothing issued in this layer yet): the
        layer's first window makes the accumulator."""
        sp = self._sp_acc_init
        if sp is not None:
            sp.start()
        job.next_tile = 0
        if sp is not None:
            sp.stop()

    def _new_plan(self, M: int, K: int, N: int) -> tuple:
        """Build, keep and return the ``(LaunchPlan, zeros)`` of a layer
        geometry (its window from `window_plan`)."""
        window, _ = window_plan(
            M, N, K,
            block=self.block, backend=self.backend,
            window_tiles=self.window_tiles,
        )
        entry = (
            launch_plan(M, K, N, self.block, self.backend, window),
            jnp.zeros((M, N), jnp.float32),
        )
        self._plans[(M, K, N)] = entry
        return entry

    def _plan(self, job: Job) -> tuple:
        """``(LaunchPlan, zeros)`` of the job's current layer; a
        geometry warm-up did not see is planned now and counted in
        ``report.launch_plan_misses``."""
        M, K = job.x.shape
        N = self.tasks[job.task_id].weights[job.layer].shape[1]
        entry = self._plans.get((M, K, N))
        if entry is None:
            self.report.launch_plan_misses += 1
            entry = self._new_plan(M, K, N)
        return entry

    def _finish_layer_or_forward(self, job: Job, now: float) -> None:
        """Layer done: advance; forward to next stage / complete job."""
        t = self.tasks[job.task_id]
        job.x = job.c_acc  # fp32 activation chains to the next GEMM
        job.c_acc = None
        prev_stage = t.stage_of_layer[job.layer]
        job.layer += 1
        if job.layer >= len(t.weights):
            job.done_at = now
            self.report.jobs_completed += 1
            self.completed_per_task[job.task_id] += 1
            rt = now - job.release
            self.report.response_times[t.name].append(rt)
            self.report.completed_releases[t.name].append(job.release)
            missed = (
                now > job.abs_deadline
                and job.uid not in self._missed_in_flight
            )
            if missed:
                # not already counted by a mid-run finalize_report
                self.report.deadline_misses[t.name] += 1
            if self._tr is not None:
                # response/tardiness/missed derive from (t, release,
                # deadline) at read time — same complete-event schema
                # as the DES; completed-job misses are not separately
                # emitted (only in-flight ones at finalize are)
                self._tr.emit(
                    "complete", now, "runtime", t.name,
                    prev_stage, self._tr_shard, release=job.release,
                    attrs={"deadline": job.abs_deadline},
                )
            return
        nxt = t.stage_of_layer[job.layer]
        self._start_layer(job)
        if nxt == prev_stage:
            # same accelerator: continue immediately (still its segment)
            self.stages[nxt].running = job
        else:
            # release to successor via the inter-stage FIFO (paper §3.2)
            if self._tr is not None:
                self._tr.emit(
                    "segment_end", now, "runtime", t.name,
                    prev_stage, self._tr_shard, release=job.release,
                )
            self.stages[nxt].push(job)

    def _preempt_if_due(self, st: StageRuntime, now: float) -> None:
        """EDF preemption check between windows (tile boundary)."""
        if (
            self.policy == "edf"
            and st.running is not None
            and st.head_deadline() < st.running.abs_deadline
        ):
            preempted = st.running
            preempted.preemptions += 1
            self.report.preemptions += 1
            if self._tr is not None:
                name = self.tasks[preempted.task_id].name
                # xi = 0: the virtual executor's accumulator stays
                # resident, so the boundary preemption spills nothing
                # (the conformance premise — raw-WCET comparison)
                self._tr.emit(
                    "preempt_store", now, "runtime", name,
                    st.idx, self._tr_shard, release=preempted.release,
                    attrs={"xi": 0.0},
                )
                self._tr.emit(
                    "preempt_load", now, "runtime", name,
                    st.idx, self._tr_shard, release=preempted.release,
                    attrs={"xi": 0.0},
                )
            st.push(preempted)  # progress table keeps (layer, next_tile)
            st.running = None

    def _emit_dispatch(self, st: StageRuntime, now: float) -> None:
        """Trace a stage server picking a job (fresh or resumed)."""
        if self._tr is None:
            return
        job = st.running
        self._tr.emit(
            "dispatch", now, "runtime",
            self.tasks[job.task_id].name,
            st.idx, self._tr_shard, release=job.release,
            # past the layer's first tile => mid-layer resume
            attrs={"resumed": True} if job.next_tile > 0 else None,
        )

    def _exec_window(self, job: Job) -> int:
        """Execute one tile window of ``job``'s current layer; returns
        the layer's total tile count."""
        plan, zeros = self._plan(job)
        job.c_acc, job.next_tile = _run_window(
            job.x,
            self.tasks[job.task_id].weights[job.layer],
            zeros if job.c_acc is None else job.c_acc,
            job.next_tile,
            block=self.block,
            window=plan.window,
            backend=self.backend,
        )
        self.report.windows_executed += 1
        return plan.total

    def _step_stage(self, st: StageRuntime, now: float) -> bool:
        """Run one tile window on stage ``st``. Returns True if it ran."""
        sched, launch, sync = self._sp_schedule, self._sp_launch, self._sp_sync
        if sched is not None:
            sched.start()
        self._preempt_if_due(st, now)
        if st.running is None:
            st.running = st.pop()
            if st.running is not None:
                self._emit_dispatch(st, now)
        if sched is not None:
            sched.stop()
        job = st.running
        if job is None:
            return False
        if job.c_acc is None and job.layer == 0:  # a fresh job
            self._start_layer(job)
        if launch is not None:
            launch.start()
        total = self._exec_window(job)
        if launch is not None:
            launch.stop()
        if job.next_tile >= total:
            st.running = None
            # Completion is stamped off the *injected* clock (the window
            # just executed, so re-read rather than reuse loop-entry
            # `now`) — keeps all timestamps on one timebase — and only
            # once the layer's accumulator is ready: the dispatch is
            # asynchronous, so reading the clock first would stamp the
            # enqueue instead of the device's completion.
            if sync is not None:
                sync.start()
            jax.block_until_ready(job.c_acc)
            if sync is not None:
                sync.stop()
            self._finish_layer_or_forward(job, self.clock())
        return True

    def _step_stage_virtual(self, st: StageRuntime, now: float) -> bool:
        """Cost-model stepping: the stage is occupied until the modeled
        end of the window in flight; compute runs eagerly at window
        start, completion bookkeeping is stamped at ``busy_until``."""
        job = st.running
        if job is not None:
            if now < st.busy_until - 1e-18:
                return False  # mid-window in virtual time
            if job.next_tile >= self._plan(job)[0].total:
                st.running = None
                self._finish_layer_or_forward(job, st.busy_until)
                # a same-stage next layer re-occupies `running`; a
                # forwarded/finished job frees the stage for the pool
        self._preempt_if_due(st, now)
        if st.running is None:
            st.running = st.pop()
            if st.running is None:
                return False
            self._emit_dispatch(st, now)
            if st.running.c_acc is None:
                self._start_layer(st.running)
        job = st.running
        self._exec_window(job)
        st.busy_until = now + self.cost_model.window_cost(
            job.task_id, job.layer
        )
        return True

    # ------------------------------------------------------------------
    # traffic-layer API: explicit release / single-step / backlog probes
    # ------------------------------------------------------------------
    def submit(
        self,
        task_id: int,
        release: float | None = None,
        *,
        best_effort: bool = False,
    ) -> Job:
        """Release one job of ``task_id`` (the TrafficGateway entry
        point; `run` uses it for its own periodic releases)."""
        t = self.tasks[task_id]
        job = Job(
            task_id,
            t,
            self.clock() if release is None else release,
            self.inputs[task_id],
            best_effort=best_effort,
        )
        self.stages[t.stage_of_layer[0]].push(job)
        self.report.jobs_released += 1
        self.released_per_task[task_id] += 1
        if self._tr is not None:
            # stamped at the *clock* instant of submission (monotone
            # within the stream); `release` carries the nominal stamp —
            # the cross-layer join key
            self._tr.emit(
                "release", self.clock(), "runtime", t.name,
                t.stage_of_layer[0], self._tr_shard,
                release=job.release,
                attrs={"best_effort": True} if best_effort else None,
            )
        return job

    def step(self) -> bool:
        """Run at most one tile window on every stage; True if any ran."""
        ran = False
        now = self.clock()
        stepper = (
            self._step_stage_virtual
            if self.cost_model is not None
            else self._step_stage
        )
        for st in self.stages:
            ran |= stepper(st, now)
        return ran

    def next_completion_time(self) -> float:
        """Earliest modeled window-boundary across busy stages (inf when
        every stage is idle) — the event a cost-model-driven caller
        should advance its virtual clock to."""
        ends = [
            st.busy_until for st in self.stages if st.running is not None
        ]
        return min(ends) if ends else float("inf")

    def pending(self, task_id: int) -> int:
        """Jobs of ``task_id`` released but not yet completed."""
        return (
            self.released_per_task[task_id]
            - self.completed_per_task[task_id]
        )

    def queue_depths(self) -> list[int]:
        """Per-stage backlog (pool + in-flight) — the observable the
        traffic layer checks against the analysis."""
        return [
            len(st.fifo)
            + len(st.fifo_be)
            + len(st.edf)
            + (1 if st.running else 0)
            for st in self.stages
        ]

    # ------------------------------------------------------------------
    def warmup(self) -> None:
        """Plan and pre-compile every layer geometry the run will use —
        JIT stalls inside the serving loop would otherwise blow every
        deadline in the first hyperperiod, and planning there would
        transfer start scalars from the host."""
        for i, t in enumerate(self.tasks):
            x = self.inputs[i]
            for w in t.weights:
                (M, K), N = x.shape, w.shape[1]
                plan, zeros = self._plans.get((M, K, N)) or self._new_plan(
                    M, K, N
                )
                c, _ = _run_window(
                    x, w, zeros, 0,
                    block=self.block, window=plan.window, backend=self.backend,
                )
                jax.block_until_ready(c)
                x = c  # chain shapes like the real execution

    def host_span_stats(self) -> dict:
        """A copy of the counters of the ``SPAN_*`` host spans, by name
        (`TraceRecorder.span_stats`); empty where the server times none."""
        return {} if self._sp_launch is None else self._tr.span_stats()

    def clear_host_spans(self) -> None:
        """Zero the ``SPAN_*`` counters (`TraceRecorder.clear_spans`)."""
        if self._sp_launch is not None:
            self._tr.clear_spans()

    def finalize_report(self, now: float | None = None) -> ServerReport:
        """Horizon-end accounting: expose per-task in-flight counts and
        count deadline misses of jobs still executing past their
        absolute deadline — an overloaded run would otherwise report
        zero misses because unfinished jobs were never examined.
        Idempotent: each in-flight job is counted as a miss once."""
        now = self.clock() if now is None else now
        self.report.in_flight = {
            t.name: self.pending(i) for i, t in enumerate(self.tasks)
        }
        for st in self.stages:
            for job in st.jobs():
                if (
                    now > job.abs_deadline
                    and job.uid not in self._missed_in_flight
                ):
                    self._missed_in_flight.add(job.uid)
                    name = self.tasks[job.task_id].name
                    self.report.deadline_misses[name] += 1
                    if self._tr is not None:
                        self._tr.emit(
                            "deadline_miss", now, "runtime", name,
                            st.idx, self._tr_shard,
                            release=job.release,
                            attrs={"in_flight": True},
                        )
        return self.report

    def run(self, horizon_s: float) -> ServerReport:
        """Serve for ``horizon_s`` clock seconds (periodic releases)."""
        self.warmup()
        t0 = self.clock()
        next_release = [t0 for _ in self.tasks]
        while True:
            now = self.clock()
            if now - t0 >= horizon_s:
                break
            for i, t in enumerate(self.tasks):
                while next_release[i] <= now:
                    self.submit(i, next_release[i])
                    next_release[i] += t.period
            ran = self.step()
            if self.cost_model is not None:
                # event-driven virtual time: jump to the next modeled
                # window boundary or the next periodic release
                nxt = min(
                    self.next_completion_time(),
                    min(next_release),
                    t0 + horizon_s,
                )
                now2 = self.clock()
                if nxt > now2:
                    self.sleep(nxt - now2)
                elif not ran:
                    self.sleep(DEGENERATE_SAFETY_TICK_S)
            elif not ran:
                self.sleep(1e-4)  # idle
        return self.finalize_report(t0 + horizon_s)
