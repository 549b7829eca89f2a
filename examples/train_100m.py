"""End-to-end training driver: a ~100M-param model for a few hundred
steps on CPU, with checkpoints, auto-resume, and fault tolerance.

The model is a scaled-down stablelm-family config (~100M params, the
largest that trains in reasonable CPU time); the data pipeline is the
deterministic synthetic corpus; checkpoints commit atomically every 50
steps so killing and relaunching this script resumes (try it!).

Run: ``PYTHONPATH=src python examples/train_100m.py [--steps 300]``
"""
import argparse
import dataclasses

from repro.launch.dryrun import load_config
from repro.launch.train import train_loop
from repro.models.module import param_count
from repro.models import lm
import jax
from repro.compile_cache import enable_compile_cache


def build_100m():
    base = load_config("stablelm_1_6b")
    return dataclasses.replace(
        base,
        name="stablelm-100m",
        n_layers=6,
        d_model=768,
        n_heads=12,
        n_kv_heads=12,
        head_dim=64,
        d_ff=2048,
        vocab=32768,
        max_seq=2048,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/pharos_train_100m")
    args = ap.parse_args()

    cfg = build_100m()
    n = param_count(lm.init_params(jax.random.PRNGKey(0), cfg))
    print(f"[train_100m] {cfg.name}: {n/1e6:.1f}M params, "
          f"{args.steps} steps, batch {args.batch} x seq {args.seq}")
    losses = train_loop(
        cfg,
        steps=args.steps,
        global_batch=args.batch,
        seq_len=args.seq,
        lr=6e-4,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=50,
        log_every=20,
        schedule_steps=args.steps,
    )
    k = max(1, len(losses) // 10)
    print(f"[train_100m] loss {sum(losses[:k])/k:.4f} -> "
          f"{sum(losses[-k:])/k:.4f} over {len(losses)} steps")


if __name__ == "__main__":
    enable_compile_cache()
    main()
