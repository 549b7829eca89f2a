"""Public API for the selective-scan kernel."""
from __future__ import annotations

from repro.kernels.mamba_scan.kernel import mamba_scan_call

DEFAULT_CHUNK = 64


def _shrink_to_divisor(chunk: int, extent: int) -> int:
    c = min(chunk, extent)
    while extent % c:
        c //= 2
    return max(c, 1)


def mamba_scan(
    dt, B, C, x, A, h0=None, *, chunk: int = DEFAULT_CHUNK, interpret=None
):
    """Selective scan ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``,
    ``y_t = <h_t, C_t>``. Shapes as in kernel.py; ``h0`` defaults to 0.
    Returns (y, h_final) fp32.
    """
    import jax.numpy as jnp

    Bb, S, di = x.shape
    ns = A.shape[1]
    if h0 is None:
        h0 = jnp.zeros((Bb, di, ns), jnp.float32)
    ch = _shrink_to_divisor(chunk, S)
    return mamba_scan_call(dt, B, C, x, A, h0, chunk=ch, interpret=interpret)
