"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel package ships three files:

- ``kernel.py`` — the ``pl.pallas_call`` with explicit BlockSpec VMEM
  tiling, compiled on the TPU and interpreted on the CPU
  (`repro.kernels.backend.resolve_interpret`);
- ``ops.py``    — the jit'd public wrapper;
- ``ref.py``    — the pure-jnp oracle the tests sweep against.

Kernels:

- ``preemptible_matmul`` — the paper's §3.4 tile-granular preemption
  mechanism: grid-windowed output-stationary GEMM resumable from a flat
  tile index, partial fp32 accumulator persisted in HBM.
- ``flash_attention``    — causal GQA attention, online softmax.
- ``mamba_scan``         — chunked selective-SSM scan (jamba mixer).
- ``rwkv6_scan``         — chunked WKV-6 recurrence (GLA-style GEMMs).
"""
from repro.kernels.preemptible_matmul import (
    MatmulProgress,
    matmul,
    matmul_resumable,
    matmul_window,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.rwkv6_scan import rwkv6_scan

__all__ = [
    "MatmulProgress",
    "matmul",
    "matmul_resumable",
    "matmul_window",
    "flash_attention",
    "mamba_scan",
    "rwkv6_scan",
]
