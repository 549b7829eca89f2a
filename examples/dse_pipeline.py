"""DSE -> SPMD pipeline: PHAROS partitioning for an assigned LM arch.

1. Extract minitron-4b's layer chain (the PHAROS task view of an LM),
2. run the SRT-guided DSE for a 2-task serving mix (prefill task +
   decode task with different periods) on a 16-chip slice via the
   unified `explore` driver (batched evaluator; the TG configuration
   is shown alongside for contrast),
3. show the chosen stage partition + per-stage utilizations,
4. provision a registry scenario straight from the DSE (`provision`:
   design -> shard plan -> per-shard Eq. 3 contracts + headroom),
5. run the *equal-stage* variant on the SPMD pipeline executor
   (4 fake CPU devices, ppermute streams) and validate it against the
   sequential backbone.

Run: ``PYTHONPATH=src python examples/dse_pipeline.py``
(sets XLA_FLAGS itself — run in a fresh interpreter)
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.dse import DSEConfig, explore, provision
from repro.core.dse.space import evaluate_design
from repro.core.perfmodel.hardware import paper_platform
from repro.core.rt.schedulability import stage_utilizations
from repro.core.rt.task import Task, TaskSet
from repro.launch.dryrun import load_config
from repro.models import lm
from repro.models.extract import arch_workload
from repro.pipeline.executor import (
    make_stage_mesh,
    pipeline_backbone,
    reference_backbone,
)
from repro.compile_cache import enable_compile_cache


def main():
    cfg = load_config("minitron_4b")
    platform = paper_platform(16)

    # -- PHAROS task view of the LM: prefill + decode tenants ---------
    wl_prefill = arch_workload(cfg, batch=1, seq=2048, mode="prefill")
    wl_decode = arch_workload(cfg, batch=32, seq=2048, mode="decode")
    print(f"{cfg.name}: prefill chain {wl_prefill.num_layers} layers, "
          f"decode chain {wl_decode.num_layers} layers")

    # periods: prefill every 60ms, decode step budget 15ms
    ts = TaskSet(tasks=(
        Task(workload=wl_prefill, period=0.060, name="prefill"),
        Task(workload=wl_decode, period=0.015, name="decode"),
    ))
    # two ~160-layer flattened chains: a layer-granular split grid has
    # ~26k slice pairs per chip budget, so coarsen the boundaries to
    # every 8 layers (the DSE still prices every layer exactly)
    res = explore([wl_prefill, wl_decode], ts, platform,
                  method="beam", max_m=4, beam_width=8, split_stride=8)
    if res.best is None:
        print("no feasible design at these periods; relax and retry")
        return
    best = res.best
    table = evaluate_design(best.accs, best.splits,
                            [wl_prefill, wl_decode], ts)
    print(f"best: {best.n_stages} stages chips={[a.chips for a in best.accs]} "
          f"max_util={best.max_util:.3f} "
          f"({res.stats.candidates_per_sec:,.0f} candidates/s batched)")
    print("stage utilizations:",
          [f"{u:.3f}" for u in stage_utilizations(table, ts, False)])
    print("layer split (prefill):",
          [best.splits[k][0] for k in range(best.n_stages)])
    tg = explore([wl_prefill, wl_decode], ts, platform, method="tg")
    print(f"TG baseline (same driver, throughput objective): "
          f"max_util={tg.tg.max_util:.3f} eq2_feasible={tg.tg_eq2_feasible}")

    # -- DSE -> serving: provision a registry scenario ----------------
    plan = provision("steady_city", platform,
                     cfg=DSEConfig(method="beam", max_m=3, beam_width=4),
                     shards=2, placement="least_loaded")
    gw = plan.sharded_gateway()
    gw.open()
    print(f"\nprovisioned steady_city across K={plan.n_shards} shards "
          f"({plan.placement}): assignment={plan.plan.assignment}, "
          f"admission verified={gw.verify()}")
    for hr in gw.headroom():
        print(f"  shard {hr.shard}: tenants={list(hr.tenants)} "
              f"slacks={[f'{s:.2f}' for s in hr.stage_slacks]}")

    # -- equal-stage SPMD executor ------------------------------------
    small = dataclasses.replace(
        cfg, name="minitron-pipe", n_layers=4, d_model=128, n_heads=4,
        n_kv_heads=4, head_dim=32, d_ff=256, vocab=1024,
    )
    params = lm.init_params(jax.random.PRNGKey(0), small)
    mesh = make_stage_mesh(4)
    micro = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 32, 128),
                              jnp.bfloat16)
    with jax.set_mesh(mesh):
        out = pipeline_backbone(small, mesh, 4)(params["blocks"], micro)
    ref = reference_backbone(small, params, micro)
    err = float(jnp.abs(out.astype(jnp.float32)
                        - ref.astype(jnp.float32)).max())
    print(f"\nSPMD pipeline (4 stages x 8 microbatches over ppermute): "
          f"max err vs sequential = {err:.2e}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
