"""The comparison that decides whether a served output is correct.

Each tenant's model module (``models/<name>.py``) computes what a job
must produce, plainly and without the program; this compares a served
output with it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def rel_err(out, ref):
    """``max |out - ref| / max |ref|``; inf where ``out`` is not finite
    or not of the reference's shape."""
    err = jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref))
    return jnp.where(jnp.all(jnp.isfinite(out)), err, jnp.inf)


def max_rel_err(outputs, ref) -> float:
    """Largest `rel_err` over ``outputs`` (inf for a wrong shape)."""
    worst = 0.0
    for out in outputs:
        if out is None or tuple(out.shape) != tuple(ref.shape):
            return float("inf")
        worst = max(worst, float(rel_err(out, ref)))
    return worst
