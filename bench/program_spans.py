"""The program's host spans inside ``server.step``: `PharosServer`'s
four ``pharos.*`` spans (`repro.obs` `TraceRecorder.span`), for the
``metrics/server.*_us_per_window.py`` readers and for the breakdown of
the device's idle time by the innermost span.

The readers read the span counters of the served window: the gateway
zeroes them at `begin_run` and copies them as its horizon is reached
(`GatewayReport.host_spans`), the extent of ``step_busy``. The reader
context of `harness.run_cell` carries them as ``program_spans``
(``name -> (count, total_s, max_s, t_of_max)``). A program without the
spans gives nothing, and the readers None.

A traced run's result line carries ``breakdown["idle_in_step"]``: the
idle time of the traced window charged by `charge_innermost` over the
benchmark's spans and `PROGRAM_SPANS` (`idle_in_step`). Run as a
script, this is ``run.py`` with ``--trace 1``::

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

from collections import defaultdict

import trace_reduce as tr

#: the program's spans, each nested in the benchmark's ``server.step``
PROGRAM_SPANS = (
    "pharos.schedule",
    "pharos.acc_init",
    "pharos.window_launch",
    "pharos.layer_sync",
)
#: the span whose calls are the windows the per-window metrics divide by
LAUNCH_SPAN = "pharos.window_launch"


# ---------------------------------------------------------------------------
# the readers' counters
# ---------------------------------------------------------------------------
def counters(ctx) -> dict:
    """``name -> (count, total_s, max_s, t_of_max)`` of the program's
    spans over the served window; empty where there are none."""
    return getattr(ctx, "program_spans", None) or {}


def span_us_per_window(ctx, name: str):
    """Host microseconds per tile window in span ``name``: its total
    over the window, over the window's `LAUNCH_SPAN` calls; None where
    there is no such span or no window was launched."""
    spans = counters(ctx)
    if name not in spans or not spans.get(LAUNCH_SPAN, (0,))[0]:
        return None
    return spans[name][1] / spans[LAUNCH_SPAN][0] * 1e6


def log_longest_calls(spans: dict, t0: float, log) -> None:
    """Log, beside the run's step stalls, each span's calls, total s,
    and longest call as (s into the window begun at ``t0``, s)."""
    if not spans:
        return
    log("program spans in the window (span, calls, total s, s into the "
        "window of the longest call, longest s): " + ", ".join(
            f"({n}, {c}, {tot:.6f}, {t - t0:.3f}, {d:.6f})"
            for n, (c, tot, d, t) in sorted(spans.items()) if c))


# ---------------------------------------------------------------------------
# idle time by the innermost span
# ---------------------------------------------------------------------------
def charge_innermost(idle, spans) -> dict[str, int]:
    """Idle nanoseconds per host span name: each gap goes to the span
    that overlaps it most, as in `trace_reduce.charge_gaps`, then on down
    to the span nested in that one that overlaps it most, to the
    innermost span that overlaps it (``(none)`` where none does). Over
    spans that do not nest, this is `charge_gaps`; with the program's
    spans nested in ``server.step`` it splits what `charge_gaps` gives
    ``server.step``. ``idle`` is sorted, as `trace_reduce.gaps` gives
    it."""
    order = sorted(spans)
    by = defaultdict(int)
    live: list[tuple[int, int, str]] = []
    k = 0
    for gs, ge in idle:
        while k < len(order) and order[k][0] < ge:
            live.append(order[k])
            k += 1
        live = [sp for sp in live if sp[1] > gs]
        name, pool = tr.NO_SPAN, live
        while pool:
            best, best_ov = None, 0
            for sp in pool:
                ov = min(sp[1], ge) - max(sp[0], gs)
                if ov > best_ov:
                    best, best_ov = sp, ov
            if best is None:
                break
            s0, e0, name = best
            pool = [
                sp for sp in pool
                if s0 <= sp[0] and sp[1] <= e0 and (sp[0], sp[1]) != (s0, e0)
            ]
        by[name] += ge - gs
    return dict(by)


def idle_in_step(trace: dict, span_names, top: int = 10) -> list:
    """``[[span, s], ...]``: the idle time of the traced window, as
    `trace_reduce.summarize` finds it, charged by `charge_innermost` over
    ``span_names`` and `PROGRAM_SPANS`, averaged over the device
    planes."""
    lo, hi = tr.window(trace)
    planes = [p for p in tr.device_planes(trace) if tr.device_ops(p)]
    if not planes:
        raise ValueError("no device operations in the trace")
    spans = tr.host_spans(trace, set(span_names) | set(PROGRAM_SPANS))
    by = defaultdict(int)
    for p in planes:
        ops = [o for o in tr.device_ops(p) if o[1] > lo and o[0] < hi]
        busy = tr.merge(ops, lo, hi)
        for n, v in charge_innermost(tr.gaps(busy, lo, hi), spans).items():
            by[n] += v
    s = {n: v * 1e-9 / len(planes) for n, v in by.items()}
    return [[n, v] for n, v in sorted(s.items(), key=lambda kv: -kv[1])[:top]]


if __name__ == "__main__":
    import sys

    import run

    sys.exit(run.main([*sys.argv[1:], "--trace", "1"]))
