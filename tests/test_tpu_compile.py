"""Compile-only checks of the Pallas kernels for a TPU v5e, at real widths.

Nothing runs: each kernel is lowered and compiled by the TPU compiler
for a described ``v5e:2x2`` chip, which refuses what interpret mode
accepts (unaligned blocks, primitives Mosaic cannot lower, VMEM
overuse). The topology is described inside a fixture — never while a
module is imported — and every test here skips where it cannot be.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.jamba_v0_1_52b import CONFIG as JAMBA
from repro.configs.rwkv6_7b import CONFIG as RWKV6
from repro.configs.stablelm_1_6b import CONFIG as STABLELM
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan.kernel import mamba_scan_call
from repro.kernels.preemptible_matmul.kernel import matmul_window_call
from repro.kernels.rwkv6_scan.kernel import rwkv6_scan_call
from repro.pipeline.serve import DEFAULT_BLOCK, _jnp_row_strip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes
    ]
    return jax.jit(fn).lower(*args).compile()


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32
D, FF, V = STABLELM.d_model, STABLELM.d_ff, STABLELM.vocab


@pytest.mark.parametrize(
    "M,K,N,dtype",
    [
        (128, D, 3 * D, F32),  # stablelm fused qkv
        (128, 11264, D, F32),  # stablelm swiglu down (2 x d_ff, rounded)
        (128, FF, D, F32),
        (128, D, V, BF16),  # stablelm head
        (128, 256, 640, F32),  # DeiT-T 192 x 576, block-rounded
    ],
)
def test_matmul_window_compiles(one_chip, M, K, N, dtype):
    bm, bk, bn = DEFAULT_BLOCK
    compiled = _compile(
        lambda s, a, b, c: matmul_window_call(
            s, a, b, c, block=DEFAULT_BLOCK, window=4, n_tiles_n=N // bn,
            k_steps=K // bk, interpret=False,
        ),
        ((), I32), ((M, K), dtype), ((K, N), dtype), ((M, N), F32),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_jnp_row_strip_compiles(one_chip):
    compiled = _compile(
        lambda a, b, c, r: _jnp_row_strip(a, b, c, r, bm=DEFAULT_BLOCK[0]),
        ((128, D), F32), ((D, 3 * D), F32), ((128, 3 * D), F32), ((), I32),
        sharding=one_chip,
    )
    assert compiled.memory_analysis() is not None


def test_flash_attention_compiles(one_chip):
    hd = D // STABLELM.n_heads
    qkv = ((1, 2048, STABLELM.n_heads, hd), BF16)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        qkv, qkv, qkv, sharding=one_chip,
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_mamba_scan_compiles(one_chip):
    di, ns, ch = JAMBA.d_inner, JAMBA.mamba_d_state, JAMBA.mamba_chunk
    S = 2 * ch
    compiled = _compile(
        lambda *a: mamba_scan_call(*a, chunk=ch, interpret=False),
        ((1, S, di), F32), ((1, S, ns), F32), ((1, S, ns), F32),
        ((1, S, di), F32), ((di, ns), F32), ((1, di, ns), F32),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_rwkv6_scan_compiles(one_chip):
    hd = RWKV6.rwkv_head_size
    H = RWKV6.d_model // hd
    seq = ((H, 512, hd), F32)
    compiled = _compile(
        lambda *a: rwkv6_scan_call(*a, n_heads=H, chunk=64, interpret=False),
        seq, seq, seq, seq, ((H, hd), F32), sharding=one_chip,
    )
    assert "tpu_custom_call" in compiled.as_text()
