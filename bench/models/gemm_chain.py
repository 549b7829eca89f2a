"""The GEMM-chain tenant: the model of every tenant that names none.

A served job of such a tenant is the chain ``x @ W1 @ ... @ Wn`` in
fp32, contracted at ``Precision.HIGHEST``: that is what the deployment
states. Each layer is one ``(M, K) @ (K, N)`` GEMM at the widths of the
tenant's DSE workload (`chain_shapes`), served by the tile-window
kernel `KERNEL`. Every job of a tenant reads the same input, so the
answer of every ordinal is the same chain.

The reference computes the chain layer by layer in ``jax.numpy`` from
the weights and input made from the seed; it imports nothing of the
program. The control is the same chain in the next precision down,
bf16 with three passes (what ``Precision.HIGH`` runs on the MXU),
written out so it computes the same on every backend. The split into
bf16 parts rounds with ``reduce_precision``, which XLA keeps even where
it may skip a round trip through a narrower type. The witness is the
chain at ``Precision.HIGH`` (three bf16 passes on a TPU; plain fp32 on
a CPU), beside the control on the chip.

Operations and bytes: a layer is cut into ``(bm, bn)`` output tiles,
each taking the whole K reduction; a window is ``w`` consecutive tiles
of the row-major tile grid (``w`` the largest divisor of the tile count
not above the server's ``window_tiles``), so a layer's windows start at
multiples of ``w``. A window's bytes are `counts.window_bytes`.

Implements the model contract of `harness.load_model`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import counts

#: device operations of the window executor (the Pallas kernel's jit)
KERNEL = "matmul_window"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def chain_shapes(workload, rows: int, block, max_dim=None):
    """The served GEMM chain of one workload: each layer's N rounded up
    to the block, its K the previous layer's N (the first layer's own
    K), M the input rows; ``max_dim`` caps K and N (CPU tests only).
    Copied from `repro.pipeline.stage_split.design_to_segments`."""
    bm, bk, bn = block
    cap = None if max_dim is None else _round_up(max_dim, max(bk, bn))
    M = _round_up(rows, bm)
    k = _round_up(workload.layers[0].K, bk)
    out = []
    for layer in workload.layers:
        n = _round_up(layer.N, bn)
        if cap is not None:
            k, n = min(k, cap), min(n, cap)
        out.append((M, k, n))
        k = n
    return out


@partial(jax.jit, static_argnums=0)
def _normal_leaves(shapes, key):
    """One array per shape, standard normal over sqrt(rows) for a
    weight (``scale`` flag 1) or plain for an input."""
    keys = jax.random.split(key, len(shapes))
    return tuple(
        jax.random.normal(k, s, jnp.float32)
        / (jnp.sqrt(jnp.float32(s[0])) if scale else 1.0)
        for k, (*s, scale) in zip(keys, shapes)
    )


def build(specs, *, key, rows, block, stages, workloads, max_dim=None):
    """One `Tenant` per entry of ``specs``: every tenant's weights from
    ``fold_in(key, 0)`` in one jitted call, and the inputs from
    ``fold_in(key, 1)`` in another, fp32 normal over sqrt(K)."""
    shapes = [chain_shapes(w, rows, block, max_dim) for w in workloads]
    leaves = _normal_leaves(
        tuple((K, N, 1) for s in shapes for _, K, N in s),
        jax.random.fold_in(key, 0),
    )
    inputs = _normal_leaves(
        tuple((s[0][0], s[0][1], 0) for s in shapes),
        jax.random.fold_in(key, 1),
    )
    jax.block_until_ready((leaves, inputs))
    out, pos = [], 0
    for s, st, x in zip(shapes, stages, inputs):
        out.append(Tenant(s, block, tuple(leaves[pos:pos + len(s)]), x, st))
        pos += len(s)
    return out


class Tenant:
    """One GEMM-chain tenant: per layer ``(M, K, N)``, its weights, the
    input every job reads, and each layer's stage."""

    def __init__(self, shapes, block, weights, x, stage_of_layer):
        self.shapes = shapes
        self.block = block
        self.weights = weights
        self.x = x
        self.stage_of_layer = tuple(stage_of_layer)
        self.layers = len(shapes)
        self.output_bytes = shapes[-1][0] * shapes[-1][2] * counts.F32
        self._ref = None

    def task(self, name: str, period: float, deadline: float):
        from repro.pipeline.serve import ServeTask

        return ServeTask(
            name=name,
            weights=self.weights,
            stage_of_layer=self.stage_of_layer,
            period=period,
            deadline=deadline,
            input_rows=self.shapes[0][0],
        )

    def job_input(self, k: int):
        return self.x

    def output(self, job):
        return job.x

    def reference(self, k: int):
        if self._ref is None:
            self._ref = chain(self.x, self.weights)
        return self._ref

    def control(self, k: int):
        return chain_bf16x3(self.x, self.weights)

    def witness(self, k: int):
        return chain_high(self.x, self.weights)

    def flops_done(self, progress) -> int:
        return flops_done(self.shapes, self.block, progress)

    def windows_between(self, window_tiles: int, a, b) -> list[tuple]:
        return [
            (KERNEL, counts.window_flops(K, tiles, self.block),
             counts.window_bytes(M, K, N, self.block, start, tiles),
             (M, K, N, start, tiles))
            for M, K, N, start, tiles in windows_between(
                self.shapes, self.block, window_tiles, a, b)
        ]


# ---------------------------------------------------------------------------
# the plain reference and the control
# ---------------------------------------------------------------------------
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
    """The chain at ``Precision.HIGH``, the witness of `chain_bf16x3`."""
    for w in weights:
        x = jnp.dot(
            x, w,
            precision=jax.lax.Precision.HIGH,
            preferred_element_type=jnp.float32,
        )
    return x


# ---------------------------------------------------------------------------
# operations of the window executor, from shapes
# ---------------------------------------------------------------------------
def window_size(total_tiles: int, window_tiles: int) -> int:
    """Largest divisor of ``total_tiles`` not above ``window_tiles``."""
    w = max(1, min(window_tiles, total_tiles))
    while total_tiles % w:
        w -= 1
    return w


def layer_flops(M: int, K: int, N: int) -> int:
    return 2 * M * K * N


def windows_between(shapes, block, window_tiles, a, b):
    """Windows a job ran going from progress ``a`` to progress ``b``.

    ``shapes`` are the job's layers as ``(M, K, N)``; a progress is
    ``(layer, next_tile)`` with ``layer == len(shapes)`` once done.
    Yields ``(M, K, N, start, tiles)`` per window.
    """
    (la, ta), (lb, tb) = a, b
    for layer in range(la, min(lb, len(shapes) - 1) + 1):
        M, K, N = shapes[layer]
        total = counts.tile_grid(M, K, N, block)[2]
        w = window_size(total, window_tiles)
        lo = ta if layer == la else 0
        hi = tb if layer == lb else total
        for start in range(lo, hi, w):
            yield M, K, N, start, min(w, total - start)


def flops_done(shapes, block, progress) -> int:
    """FLOPs of the tiles a job has completed at ``progress``."""
    layer, nxt = progress
    bm, _, bn = block
    done = sum(layer_flops(*shapes[j]) for j in range(min(layer, len(shapes))))
    if layer < len(shapes):
        done += 2 * bm * bn * shapes[layer][1] * nxt
    return done
