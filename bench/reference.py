"""The plain reference every cell's outputs are compared with.

A served job of a tenant is the GEMM chain ``x @ W1 @ ... @ Wn`` in
fp32, contracted at ``Precision.HIGHEST``: that is what the deployment
states. The reference computes it layer by layer in ``jax.numpy`` from
the weights and inputs the benchmark made from the seed; it imports
nothing of the program.

`chain_bf16x3` is the control: the same chain in the next precision
down, bf16 with three passes (what ``Precision.HIGH`` runs on the MXU),
written out so it computes the same on every backend. The split into
bf16 parts rounds with ``reduce_precision``, which XLA keeps even
where it may skip a round trip through a narrower type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def chain(x, weights):
    """``x @ W1 @ ... @ Wn`` at fp32 ``HIGHEST``."""
    for w in weights:
        x = jnp.dot(
            x, w,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    return x


@jax.jit
def _dot_bf16x3(a, b):
    def bf16(v):
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    def split(v):
        hi = bf16(v)
        return hi.astype(jnp.bfloat16), bf16(v - hi).astype(jnp.bfloat16)

    def dot(p, q):
        return jnp.dot(p, q, preferred_element_type=jnp.float32)

    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def chain_bf16x3(x, weights):
    """The control: the chain with each product in three bf16 passes."""
    for w in weights:
        x = _dot_bf16x3(x, w)
    return x


def chain_high(x, weights):
    """The chain at ``Precision.HIGH`` (three bf16 passes on a TPU; plain
    fp32 on a CPU), beside `chain_bf16x3` as a witness on the chip."""
    for w in weights:
        x = jnp.dot(
            x, w,
            precision=jax.lax.Precision.HIGH,
            preferred_element_type=jnp.float32,
        )
    return x


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
