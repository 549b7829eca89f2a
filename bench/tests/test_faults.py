"""The check that decides ``correct`` fails a broken timed path.

Each run goes through the harness as on the chip (no look for a chip),
on the CPU at a small width, with the window executor of the served
path broken underneath, or replaced by the control (the window's
products one precision down). The CPU interprets the Pallas kernel far slower
than the chip runs it, so the cell's rates are slowed 5x here, enough
for every tenant to finish jobs. The cells run on one chip, so the
fault of an exchange between chips does not exist here.
"""
import copy
import time

import jax
import jax.numpy as jnp
import pytest

import harness

SEED = 2**31 + 999
CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]
#: wide enough that layers span several windows, so EDF preempts jobs
#: in the middle of a layer
MAX_DIM = 640


def _unchanged(real):
    def run(a, b, c_acc, start, *, block, window, backend):
        _, n = real(a, b, c_acc, start, block=block, window=window, backend=backend)
        return c_acc, n
    return run


def _half_batch(real):
    def run(a, b, c_acc, start, *, block, window, backend):
        c, n = real(a, b, c_acc, start, block=block, window=window, backend=backend)
        h = c.shape[0] // 2
        return c.at[h:].set(c_acc[h:]), n
    return run


def _altered(real):
    def run(a, b, c_acc, start, *, block, window, backend):
        c, n = real(a, b, c_acc, start, block=block, window=window, backend=backend)
        return c.at[0, 0].add(1e-3 * jnp.max(jnp.abs(c))), n
    return run


def _bf16x3(real):
    """The control in the program's place: each window's products in
    three bf16 passes (``reference.chain_bf16x3``), not fp32 at HIGHEST.
    A window adds its tiles' products to ``c_acc``, linearly in ``a``
    and ``b``, so the three passes are three windows over the bf16
    parts; bf16 parts multiply exactly in fp32."""
    def parts(v):
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(v - hi, exponent_bits=8, mantissa_bits=7)
        return hi, lo

    def run(a, b, c_acc, start, *, block, window, backend):
        (a_hi, a_lo), (b_hi, b_lo) = parts(a), parts(b)
        kw = dict(block=block, window=window, backend=backend)
        c, n = real(a_hi, b_hi, c_acc, start, **kw)
        zero = jnp.zeros_like(c_acc)
        c2, _ = real(a_hi, b_lo, zero, start, **kw)
        c3, _ = real(a_lo, b_hi, zero, start, **kw)
        return c + (c2 + c3), n
    return run


def _slowed(cell: str, factor: float = 5.0) -> dict:
    spec = copy.deepcopy(harness.load_spec("workloads", cell))
    for t in spec["tenants"].values():
        for k in ("period_s", "deadline_s", "contract_period_s"):
            if k in t:
                t[k] *= factor
        if "rate_hz" in t:
            t["rate_hz"] /= factor
    return spec


def _run(cell):
    return harness.run_cell(
        cell, SEED, 4.0, False, t_start=time.perf_counter(),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        max_dim=MAX_DIM, log=lambda m: None, cell=_slowed(cell),
    )


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_passes_the_comparison(cell):
    """Every tenant's outputs agree with the reference. (Whether EDF cut
    a job in the middle of a layer depends on timing the CPU does not
    reproduce; the chip runs check that too.)"""
    res = _run(cell)
    checks = res["checks"]
    assert all(
        c["ok"] for k, c in checks.items() if not k.startswith("midlayer")
    ), checks


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered, _bf16x3])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_window_executor_is_not_correct(cell, fault, monkeypatch):
    import repro.pipeline.serve as serve

    monkeypatch.setattr(serve, "_run_window", fault(serve._run_window))
    res = _run(cell)
    assert res["correct"] is False
    assert any(
        not c["ok"] for k, c in res["checks"].items() if k.startswith("rel_err.")
    ), res["checks"]
