"""RWKV-6 chunked WKV scan — Pallas TPU kernel.

GLA-style blocking identical to `repro.models.rwkv._tmix_impl`: the
(batch*heads) axis is parallel, the chunk axis sequential with the
(hd, hd) state matrix in VMEM scratch. Within a chunk everything is
GEMM-shaped for the MXU:

- the cumulative log-decay is a lower-triangular-ones matmul (no cumsum
  primitive needed on the VPU),
- intra-chunk interaction is ``(r*W_prev) @ (k/W)^T`` masked strictly
  lower-triangular, then ``@ v``,
- the carry update is ``k_scaled^T @ v``.

Decay logits are clamped upstream (models/rwkv._DECAY_CLAMP) so the
``exp(-cumw)`` rescale stays in fp32 range for chunk <= 64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _wkv_kernel(
    r_ref,  # (1, ch, hd)
    k_ref,
    v_ref,
    w_ref,
    u_ref,  # (H, hd) — every head's bonus; row picked per grid step
    y_ref,  # (1, ch, hd) out
    sout_ref,  # (1, hd, hd) out
    s_scr,  # (hd, hd) scratch
    *,
    chunk: int,
    n_heads: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    rc = r_ref[0]  # (ch, hd) fp32
    kc = k_ref[0]
    vc = v_ref[0]
    wc = w_ref[0]
    u = u_ref[pl.ds(pl.program_id(0) % n_heads, 1), :]  # (1, hd)

    logw = jnp.log(wc)  # (ch, hd), negative
    tri_incl = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    cumw = jnp.dot(tri_incl, logw, preferred_element_type=jnp.float32)
    w_prev = jnp.exp(cumw - logw)  # prod_{s<=t-1} w_s
    rw = rc * w_prev
    kw = kc * jnp.exp(-cumw)  # k_j / prod_{s<=j} w_s

    S = s_scr[...]
    y_inter = jnp.dot(rw, S, preferred_element_type=jnp.float32)
    att = jnp.dot(rw, kw.T, preferred_element_type=jnp.float32)  # (ch, ch)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(rows > cols, att, 0.0)  # strict lower triangle
    y_intra = jnp.dot(att, vc, preferred_element_type=jnp.float32)
    diag = jnp.sum(rc * u * kc, axis=-1, keepdims=True)  # (ch, 1)
    y_ref[0] = y_inter + y_intra + diag * vc

    last = cumw[chunk - 1 : chunk, :]  # (1, hd)
    k_scale = kc * jnp.exp(last - cumw)  # prod_{s>j} w_s
    # the chunk's total decay scales the state's rows: turn the (1, hd)
    # row into a (hd, 1) column with an identity mask + lane reduction
    hd = last.shape[1]
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    ).astype(jnp.float32)
    w_tot = jnp.sum(eye * jnp.exp(last), axis=1, keepdims=True)  # (hd, 1)
    s_scr[...] = w_tot * S + jnp.dot(
        k_scale.T, vc, preferred_element_type=jnp.float32
    )
    sout_ref[0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("n_heads", "chunk", "interpret"))
def rwkv6_scan_call(
    r, k, v, w, u, *, n_heads: int, chunk: int, interpret: bool | None = None
):
    """r/k/v/w: (B*H, S, hd) fp32; u: (H, hd). Returns (y, S_final)."""
    BH, S, hd = r.shape
    if S % chunk:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    n_chunks = S // chunk

    grid = (BH, n_chunks)
    call = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, n_heads=n_heads),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((n_heads, hd), lambda bh, c: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, hd, hd), lambda bh, c: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )
    f32 = jnp.float32
    return call(r.astype(f32), k.astype(f32), v.astype(f32), w.astype(f32), u.astype(f32))
