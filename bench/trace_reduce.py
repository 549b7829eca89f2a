"""Reduce a profiler trace to the device's busy and idle time.

`extract` turns ``jax.profiler.ProfileData`` into plain data,
``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}``, which is also the format of the recorded trace
under ``tests/data``. Everything else works on that form:

- device operations are the events of each ``/device:...`` plane's
  ``XLA Ops`` line (all its lines where it has none);
- host spans are events of the host plane named by the benchmark's
  ``jax.profiler.TraceAnnotation`` calls, on the same clock;
- the traced window is the host span `WINDOW`; busy time is the union
  of the device operations clipped to it, idle time the rest;
- each idle gap is charged to the host span that overlaps it most
  (``(none)`` where the host was in no span of the benchmark's).
"""
from __future__ import annotations

from collections import defaultdict

#: host span around the traced part of a run's window
WINDOW = "bench.window"
#: the line of a device plane that holds one event per operation
OPS_LINE = "XLA Ops"
NO_SPAN = "(none)"


def extract(profile) -> dict:
    """Plain-data copy of a ``jax.profiler.ProfileData``."""
    planes = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            lines.append({
                "name": line.name,
                "events": [
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                ],
            })
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_xplane(path) -> dict:
    from jax.profiler import ProfileData

    return extract(ProfileData.from_file(str(path)))


def _is_device(plane: dict) -> bool:
    return plane["name"].startswith("/device:")


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if _is_device(p)]


def short_name(name: str) -> str:
    """An HLO operation's name and result shape, without its layout and
    operands: ``%copy.4 = f32[128,2048]``."""
    return name.split("{", 1)[0].strip()


def device_ops(plane: dict) -> list[tuple[int, int, str]]:
    """``(start, end, short name)`` of the plane's operations, by start."""
    lines = [l for l in plane["lines"] if l["name"] == OPS_LINE]
    lines = lines or plane["lines"]
    out = [
        (s, s + d, short_name(n)) for l in lines for n, s, d in l["events"]
    ]
    return sorted(out)


def host_spans(trace: dict, names=None) -> list[tuple[int, int, str]]:
    """Host-plane events (``names`` only, when given), by start."""
    out = []
    for p in trace["planes"]:
        if _is_device(p):
            continue
        for l in p["lines"]:
            for n, s, d in l["events"]:
                if names is None or n in names:
                    out.append((s, s + d, n))
    return sorted(out)


def window(trace: dict) -> tuple[int, int]:
    spans = host_spans(trace, {WINDOW})
    if not spans:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    return spans[0][0], spans[-1][1]


def merge(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of ``(start, end, ...)`` intervals clipped to ``[lo, hi]``."""
    out: list[list[int]] = []
    for iv in sorted(intervals):
        s, e = max(iv[0], lo), min(iv[1], hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """The complement of merged ``busy`` inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def charge_gaps(idle, spans) -> dict[str, int]:
    """Idle nanoseconds per host span name: each gap goes to the span
    that overlaps it most."""
    by = defaultdict(int)
    for gs, ge in idle:
        best, best_ov = NO_SPAN, 0
        for s, e, n in spans:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = n, ov
        by[best] += ge - gs
    return dict(by)


def summarize(trace: dict, span_names, top: int = 10) -> dict:
    """Busy, idle and per-operation time of the traced window, averaged
    over the device planes.

    Returns ``{"window_s", "busy_s", "idle_share", "op_s": {name: s},
    "idle_by_span": {span: s}, "device_ops": [[name, s], ...],
    "idle_gaps": [[span, s], ...]}``.
    """
    lo, hi = window(trace)
    planes = [p for p in device_planes(trace) if device_ops(p)]
    if not planes:
        raise ValueError("no device operations in the trace")
    spans = host_spans(trace, set(span_names))
    busy_ns, op_ns, idle_ns = 0, defaultdict(int), defaultdict(int)
    for p in planes:
        ops = [o for o in device_ops(p) if o[1] > lo and o[0] < hi]
        busy = merge(ops, lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        for s, e, n in ops:
            op_ns[n] += min(e, hi) - max(s, lo)
        for n, v in charge_gaps(gaps(busy, lo, hi), spans).items():
            idle_ns[n] += v
    k = len(planes)
    window_s = (hi - lo) * 1e-9
    busy_s = busy_ns * 1e-9 / k
    op_s = {n: v * 1e-9 / k for n, v in op_ns.items()}
    idle_s = {n: v * 1e-9 / k for n, v in idle_ns.items()}
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "op_s": op_s,
        "idle_by_span": idle_s,
        "device_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(idle_s.items(), key=lambda kv: -kv[1])[:top],
    }

