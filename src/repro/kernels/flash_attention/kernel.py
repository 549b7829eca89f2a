"""Causal flash attention (GQA) — Pallas TPU kernel.

Blocking mirrors `repro.models.layers._attn_chunked`: queries are tiled
into ``block_q`` rows; keys/values stream in ``block_k`` tiles along the
minor grid axis with the online-softmax state (running max ``m``,
normalizer ``l``, unnormalized accumulator ``acc``) living in VMEM
scratch across the K sweep. Causal blocks strictly above the diagonal
are skipped with ``pl.when`` (no FLOPs, no VMEM traffic beyond the
prefetch pipeline).

GQA is handled in the index maps: query head ``h`` reads KV head
``h // (H // Hkv)`` — the KV tensor is never materialized per-q-head.

Scratch rows are replicated across 128 lanes (TPU fp32 tile is 8x128);
column 0 is authoritative.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

_LANES = 128
_NEG_INF = -1e30


def _fa_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    block_q: int,
    block_k: int,
    n_kblocks: int,
    scale: float,
    causal: bool,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # diagonal-or-below blocks only (first q row >= last k row iff any
    # element of the block is unmasked)
    run = (not causal) or (qi * block_q + block_q - 1 >= ki * block_k)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows >= cols, s, _NEG_INF)

        m_prev = m_scr[:, :1]  # (bq, 1)
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_kblocks - 1)
    def _finish():
        l = l_scr[:, :1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_q_heads",
        "n_kv_heads",
        "block_q",
        "block_k",
        "causal",
        "interpret",
    ),
)
def flash_attention_call(
    q,
    k,
    v,
    *,
    n_q_heads: int,
    n_kv_heads: int,
    block_q: int,
    block_k: int,
    causal: bool = True,
    interpret: bool | None = None,
):
    """q: (B*H, S, hd); k/v: (B*Hkv, S, hd). Returns (B*H, S, hd)."""
    BH, S, hd = q.shape
    if S % block_q or S % block_k:
        raise ValueError(f"S={S} not divisible by blocks ({block_q},{block_k})")
    group = n_q_heads // n_kv_heads
    n_qb, n_kb = S // block_q, S // block_k
    scale = hd**-0.5

    def kv_head(bh):
        b, h = bh // n_q_heads, bh % n_q_heads
        return b * n_kv_heads + h // group

    grid = (BH, n_qb, n_kb)
    call = pl.pallas_call(
        functools.partial(
            _fa_kernel,
            block_q=block_q,
            block_k=block_k,
            n_kblocks=n_kb,
            scale=scale,
            causal=causal,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki: (kv_head(bh), ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki: (kv_head(bh), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )
    return call(q, k, v)
