"""Public API for the RWKV-6 WKV scan kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.rwkv6_scan.kernel import rwkv6_scan_call

DEFAULT_CHUNK = 64


def _shrink_to_divisor(chunk: int, extent: int) -> int:
    c = min(chunk, extent)
    while extent % c:
        c //= 2
    return max(c, 1)


def rwkv6_scan(r, k, v, w, u, *, chunk: int = DEFAULT_CHUNK, interpret=None):
    """WKV-6 recurrence over (B, S, H, hd) tensors.

    ``S_t = diag(w_t) S_{t-1} + k_t v_t^T``;
    ``y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)``.
    Returns (y (B, S, H, hd) fp32, S_final (B, H, hd, hd) fp32).
    """
    B, S, H, hd = r.shape

    def flat(t):
        return jnp.swapaxes(t, 1, 2).reshape(B * H, S, hd)

    ch = _shrink_to_divisor(chunk, S)
    y, s_fin = rwkv6_scan_call(
        flat(r), flat(k), flat(v), flat(w), u, n_heads=H, chunk=ch,
        interpret=interpret,
    )
    y = jnp.swapaxes(y.reshape(B, H, S, hd), 1, 2)
    return y, s_fin.reshape(B, H, hd, hd)
