"""Preemptible output-stationary matmul — the paper's §3.4 mechanism on TPU.

PHAROS preempts *inside* a layer at tile boundaries: the accelerator
finishes the in-flight tile, spills the partial output to DDR, records
loop iterators in the progress table, runs the high-priority job, then
reloads and resumes. An XLA dispatch is non-interruptible, so on TPU the
preemption quantum becomes a *grid window*: one `pallas_call` executes
output tiles ``[start, start + window)`` of the flattened (m, n) tile
grid and accumulates into an HBM-resident fp32 buffer (aliased in/out,
so untouched tiles persist). The host scheduler interleaves windows of
different jobs; the progress table entry is just ``next_tile``.

The overhead this structure pays is exactly Eq. 5's:

    e_tile  — the in-flight window must finish before the preemptor runs,
    e_store — the fp32 partial tiles are written back to HBM,
    e_load  — resume re-streams the A/B operand tiles (+ partial C).

Arithmetic is fp32 throughout (``Precision.HIGHEST``: the MXU runs its
multi-pass fp32 contraction), so the compiled kernel, the interpreter
and a plain ``jnp`` chain agree to fp32 rounding.

Grid: ``(window, k_steps)`` with k minor — each window position owns one
output tile, revisited across k so the accumulator stays in VMEM for the
whole K reduction; block index maps use a scalar-prefetch ``start`` so
the same compiled kernel serves every window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _window_kernel(start_ref, a_ref, b_ref, cin_ref, o_ref, *, k_steps: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = cin_ref[...]

    o_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        b_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block", "window", "n_tiles_n", "k_steps", "interpret"),
)
def matmul_window_call(
    start,
    a,
    b,
    c_acc,
    *,
    block: tuple[int, int, int],
    window: int,
    n_tiles_n: int,
    k_steps: int,
    interpret: bool | None = None,
):
    """Execute output tiles ``[start, start + window)``; returns new c_acc.

    ``a``: (M, K) any float dtype, ``b``: (K, N), ``c_acc``: (M, N) fp32.
    All dims must be multiples of the block. ``start`` is a traced int32
    scalar — one compiled kernel serves every window of a given geometry.
    """
    bm, bk, bn = block

    def im_a(w, k, s):
        return ((s[0] + w) // n_tiles_n, k)

    def im_b(w, k, s):
        return (k, (s[0] + w) % n_tiles_n)

    def im_c(w, k, s):
        return ((s[0] + w) // n_tiles_n, (s[0] + w) % n_tiles_n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(window, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), im_a),
            pl.BlockSpec((bk, bn), im_b),
            pl.BlockSpec((bm, bn), im_c),
        ],
        out_specs=pl.BlockSpec((bm, bn), im_c),
    )
    call = pl.pallas_call(
        functools.partial(_window_kernel, k_steps=k_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(c_acc.shape, jnp.float32),
        input_output_aliases={3: 0},  # c_acc (after the scalar operand)
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )
    start_vec = jnp.asarray([start], jnp.int32)
    return call(start_vec, a, b, c_acc)
