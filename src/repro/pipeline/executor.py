"""SPMD pipeline executor: the PHAROS chained topology on a TPU mesh.

The paper's spatial architecture — M accelerators, each owning a
consecutive layer segment, jobs streaming through FIFO links — maps to
a ``stage`` mesh axis under `shard_map`:

- stage k holds repeats ``[k*R/M, (k+1)*R/M)`` of the block stack
  (parameters sharded on their leading repeats axis);
- activations advance stage->stage with ``lax.ppermute`` (the HLS
  stream of paper Fig. 2);
- microbatches play the role of jobs: after the M-1-tick fill phase,
  every stage computes a different microbatch each tick — the paper's
  pipelined execution model (one job per accelerator, §3.3).

GPipe-style schedule: ``n_ticks = n_micro + M - 1``; stage M-1's output
at tick t is microbatch ``t - (M-1)``. The executor covers the backbone
(B, S, d) -> (B, S, d); embed/head run outside (they belong to the
first/last stage in a deployment and are not part of the repeat stack).

Equal segments are required (`n_repeats % n_stages == 0`) — the
asymmetric-resource designs from the DSE run through the host runtime
(`pipeline.serve`) and the DES; see DESIGN.md §2.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import lm
from repro.models.lm import NO_POLICY


def make_stage_mesh(n_stages: int):
    """1-D ``stage`` mesh of ``n_stages`` devices. The axis
    is `AxisType.Auto`: the pipeline's own sharding lives inside
    `shard_map`, and the caller may index the stage-stacked result
    (``outs[-1]``) without naming a sharding for it."""
    return jax.make_mesh(
        (n_stages,), ("stage",), axis_types=(AxisType.Auto,)
    )


def _segment_apply(cfg: ArchConfig, params_seg, x, positions):
    """Run this stage's repeats (a scan over its slice of the stack)."""
    pattern = cfg.pattern()

    def body(x, rep):
        for j, kind in enumerate(pattern):
            x = lm._apply_block(
                kind, rep[j]["mixer"], rep[j]["ffn"], x, cfg, positions,
                NO_POLICY,
            )
        return x, None

    x, _ = jax.lax.scan(body, x, params_seg)
    return x


def pipeline_backbone(cfg: ArchConfig, mesh, n_stages: int):
    """Build ``fn(stacked_blocks, microbatches) -> outputs``.

    ``stacked_blocks``: block params with leading repeats axis R,
    sharded R over ``stage`` (R % n_stages == 0).
    ``microbatches``: (n_micro, B_mb, S, d) embedded inputs.
    Returns (n_micro, B_mb, S, d) — the backbone output per microbatch.
    """
    if cfg.n_repeats % n_stages:
        raise ValueError(
            f"n_repeats={cfg.n_repeats} not divisible by stages={n_stages}"
        )

    def staged(blocks_local, micro):
        # blocks_local: repeats slice (R/M, ...); micro: (n_micro, B, S, d)
        stage = jax.lax.axis_index("stage")
        n_micro, B, S, d = micro.shape
        n_ticks = n_micro + n_stages - 1
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S)
        )
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (or zeros past the stream)
            inject = micro[jnp.minimum(t, n_micro - 1)]
            x_in = jnp.where(stage == 0, inject, buf)
            y = _segment_apply(cfg, blocks_local, x_in, positions)
            # last stage records its finished microbatch
            out_idx = t - (n_stages - 1)
            record = (stage == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                record,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0
                ),
                lambda o: o,
                outs,
            )
            # forward activations down the chain (FIFO stream)
            buf_next = jax.lax.ppermute(y, "stage", perm)
            return (buf_next, outs), None

        buf0 = jnp.zeros((B, S, d), micro.dtype)
        outs0 = jnp.zeros((n_micro, B, S, d), micro.dtype)
        (buf, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_ticks)
        )
        return outs[None]  # leading stage axis for the out_spec

    fn = jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(P("stage"), P()),
        out_specs=P("stage"),
        check_vma=False,
    )

    def run(blocks_stacked, micro):
        outs = fn(blocks_stacked, micro)  # (n_stages, n_micro, B, S, d)
        return outs[-1]

    return run


def split_blocks_for_stages(params, n_stages: int):
    """Slice the (R, ...) block stack into the stage-sharded layout.

    Identity reshape — the repeats axis is already the pipeline order;
    with the mesh sharding R over ``stage`` each stage holds its
    consecutive slice, matching the paper's consecutive-layer mapping.
    """
    return params["blocks"]


def reference_backbone(cfg: ArchConfig, params, micro):
    """Non-pipelined oracle: same stacked params, scan over all repeats."""
    outs = []
    for i in range(micro.shape[0]):
        x = micro[i]
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None],
            (x.shape[0], x.shape[1]),
        )
        pattern = cfg.pattern()

        def body(x, rep):
            for j, kind in enumerate(pattern):
                x = lm._apply_block(
                    kind, rep[j]["mixer"], rep[j]["ffn"], x, cfg, positions,
                    NO_POLICY,
                )
            return x, None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        outs.append(x)
    return jnp.stack(outs)
