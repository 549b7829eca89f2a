"""Chunked selective-SSM scan — Pallas TPU kernel (jamba's mamba mixer).

The diagonal recurrence ``h_t = a_t * h_{t-1} + b_t`` (per (d_inner,
d_state) channel) is blocked exactly like `repro.models.ssm`: the grid is
``(batch, d_inner blocks, n_chunks)`` with the chunk axis sequential; the
carried state ``h`` lives in VMEM scratch across chunk iterations, so HBM
sees each input element once and each output element once (the scan
itself is bandwidth-bound — its roofline term is the chunk streaming,
not FLOPs). Channels are independent, so d_inner is tiled into
``block_d``-wide lanes blocks that keep VMEM bounded at real widths.

In-chunk, the recurrence is a `fori_loop` over time steps operating on a
VMEM-resident state held transposed, ``(d_state, block_d)``, so d_inner
runs along the lanes: each step reads one row of dt/x/B/C straight from
the refs (``pl.ds``), turns the B/C row into a column with an identity
mask and a lane reduction, and writes one row of y. No (B, S, d_inner,
d_state) tensor is ever materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

#: d_inner lanes per grid step: keeps the (d_state, block) state, the
#: chunk's dt/x/y blocks and their double buffers well inside VMEM
BLOCK_D = 512


def _scan_kernel(
    dt_ref,  # (1, ch, bd) fp32
    b_ref,  # (1, ch, ns) fp32
    c_ref,  # (1, ch, ns) fp32
    x_ref,  # (1, ch, bd) fp32
    at_ref,  # (ns, bd) fp32 — A transposed
    h0_ref,  # (1, ns, bd) fp32 — initial state, transposed
    y_ref,  # (1, ch, bd) out
    hout_ref,  # (1, ns, bd) out
    h_scr,  # (ns, bd) scratch
    *,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    at = at_ref[...]  # (ns, bd)
    ns = at.shape[0]
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (ns, ns), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (ns, ns), 1)
    ).astype(jnp.float32)

    def column(row):  # (1, ns) -> (ns, 1)
        return jnp.sum(eye * row, axis=1, keepdims=True)

    def step(t, h):
        dt_t = dt_ref[0, pl.ds(t, 1), :]  # (1, bd)
        x_t = x_ref[0, pl.ds(t, 1), :]
        b_t = column(b_ref[0, pl.ds(t, 1), :])  # (ns, 1)
        c_t = column(c_ref[0, pl.ds(t, 1), :])
        h = jnp.exp(dt_t * at) * h + b_t * (dt_t * x_t)  # (ns, bd)
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(h * c_t, axis=0, keepdims=True)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h
    hout_ref[0] = h


def _pick_block_d(di: int) -> int:
    """Largest lane-aligned divisor of ``di`` that is <= `BLOCK_D`
    (the whole of ``di`` when it is not a multiple of 128)."""
    if di % 128:
        return di
    b = min(BLOCK_D, di)
    while di % b:
        b -= 128
    return b


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_scan_call(
    dt, B, C, x, A, h0, *, chunk: int, interpret: bool | None = None
):
    """dt/x: (Bb, S, di); B/C: (Bb, S, ns); A: (di, ns); h0: (Bb, di, ns).

    Returns (y (Bb, S, di), h_final (Bb, di, ns)), all fp32.
    """
    Bb, S, di = x.shape
    ns = A.shape[1]
    if S % chunk:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    bd = _pick_block_d(di)
    n_chunks = S // chunk

    grid = (Bb, di // bd, n_chunks)
    call = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, ns), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, ns), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((ns, bd), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, ns, bd), lambda b, d, c: (b, 0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, ns, bd), lambda b, d, c: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, S, di), jnp.float32),
            jax.ShapeDtypeStruct((Bb, ns, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ns, bd), jnp.float32)],
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )
    f32 = jnp.float32
    y, h_t = call(
        dt.astype(f32),
        B.astype(f32),
        C.astype(f32),
        x.astype(f32),
        A.astype(f32).T,
        jnp.swapaxes(h0.astype(f32), 1, 2),
    )
    return y, jnp.swapaxes(h_t, 1, 2)
