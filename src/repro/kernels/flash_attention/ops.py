"""Public flash-attention API over (B, S, H, hd) activations."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_call

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _shrink_to_divisor(block: int, extent: int) -> int:
    b = min(block, extent)
    while extent % b:
        b //= 2
    return max(b, 1)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
):
    """Causal GQA attention. q: (B, S, H, hd); k/v: (B, S, Hkv, hd).

    Returns (B, S, H, hd) in q's dtype. Softmax runs in fp32 in-kernel.
    """
    B, S, H, hd = q.shape
    _, _, Hkv, _ = k.shape
    if H % Hkv:
        raise ValueError("n_heads must be divisible by n_kv_heads")
    bq = _shrink_to_divisor(block_q, S)
    bk = _shrink_to_divisor(block_k, S)
    qf = jnp.swapaxes(q, 1, 2).reshape(B * H, S, hd)
    kf = jnp.swapaxes(k, 1, 2).reshape(B * Hkv, S, hd)
    vf = jnp.swapaxes(v, 1, 2).reshape(B * Hkv, S, hd)
    out = flash_attention_call(
        qf,
        kf,
        vf,
        n_q_heads=H,
        n_kv_heads=Hkv,
        block_q=bq,
        block_k=bk,
        causal=causal,
        interpret=interpret,
    )
    return jnp.swapaxes(out.reshape(B, H, S, hd), 1, 2)
