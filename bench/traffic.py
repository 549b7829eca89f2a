"""Arrival schedules for the benchmark's cells, in absolute seconds.

Every rate, period and deadline comes from the cell file; nothing here
reads a calibrated WCET or a timebase of the program, so the offered
load never follows the program's own speed. A schedule is a pure
function of the tenant's parameters, the horizon and ``--seed``.

Kinds (the ``arrival`` key of a cell's tenant):

- ``periodic``: one release every ``period_s`` from a phase that every
  periodic tenant of the cell shares (synchronized sensors, released
  together at each common multiple of their periods). The seed draws
  the phase uniformly in ``[0, shortest period)``; the count stays
  within one job and the relative phases never change.
- ``poisson``: ``round(rate_hz * horizon)`` releases at uniform
  positions, sorted: a Poisson process conditioned on its count, so
  every seed offers the same number of jobs in another order.
- ``closed``: no schedule; the harness submits the next job when the
  previous one completes (``outstanding`` jobs in flight).

Copied from the periodic and Poisson generators of
``repro.traffic.arrival``, with rates in absolute units and the
Poisson count fixed.
"""
from __future__ import annotations

import random

KINDS = ("periodic", "poisson", "closed")


def tenant_rng(seed: int, tenant: str) -> random.Random:
    """The tenant's own stream: independent of the other tenants and of
    the order they are listed in."""
    return random.Random(f"{seed}:{tenant}")


def periodic(period_s: float, horizon_s: float, phase: float) -> list[float]:
    if period_s <= 0:
        raise ValueError("period_s must be positive")
    t = phase
    out = []
    while t < horizon_s:
        out.append(t)
        t += period_s
    return out


def poisson(rate_hz: float, horizon_s: float, rng: random.Random) -> list[float]:
    if rate_hz <= 0:
        raise ValueError("rate_hz must be positive")
    n = round(rate_hz * horizon_s)
    return sorted(rng.uniform(0.0, horizon_s) for _ in range(n))


def schedules(tenants: dict, horizon_s: float, seed: int) -> dict[str, list[float]]:
    """Release times in ``[0, horizon_s)`` for every tenant of a cell
    (``tenants``: name -> spec; empty for a closed loop)."""
    periods = [
        s["period_s"] for s in tenants.values() if s["arrival"] == "periodic"
    ]
    phase = random.Random(f"{seed}:phase").uniform(0.0, min(periods, default=1.0))
    out = {}
    for name, spec in tenants.items():
        kind = spec["arrival"]
        if kind not in KINDS:
            raise ValueError(f"unknown arrival kind {kind!r}; have {KINDS}")
        if kind == "periodic":
            out[name] = periodic(spec["period_s"], horizon_s, phase)
        elif kind == "poisson":
            out[name] = poisson(spec["rate_hz"], horizon_s, tenant_rng(seed, name))
        else:
            out[name] = []
    return out
