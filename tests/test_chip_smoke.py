"""`chip_smoke.py` on the CPU: its serve and correctness phases at a
small width with interpret-mode windows, its four-chip pipeline phase on
four host devices, its refusal to report without a TPU, and the
compile-cache rule every entry point shares."""
import importlib.util
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.compile_cache import (
    CACHE_ENV,
    REPO_CACHE_DIR,
    compile_cache_dir,
    enable_compile_cache,
)

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_serve_and_correctness_phases_on_cpu(chip_smoke):
    built, serve_model = chip_smoke.build_tenants(max_dim=256)
    assert [len(t.weights) for t in serve_model] == [10, 121]
    lines = []
    server, served = chip_smoke.serve_phase(
        built, serve_model, wall_limit_s=120.0, log=lines.append
    )
    assert served["ok"], "\n".join(lines)
    for name, quota in chip_smoke.QUOTAS.items():
        assert served["tenants"][name]["jobs"] >= quota
    # the served tasks share the calibration probe's weight arrays
    assert all(
        a is b
        for t, m in zip(server.tasks, serve_model)
        for a, b in zip(t.weights, m.weights)
    )
    checked = chip_smoke.correctness_phase(server, log=lines.append)
    assert checked["ok"], "\n".join(lines)
    assert max(checked["rel_err"].values()) < chip_smoke.REL_ERR_BOUND


def test_smoke_correctness_phase_fails_a_bf16_contraction(
    chip_smoke, monkeypatch
):
    """The bound catches windows that contract in bf16 (operands rounded
    to bf16, fp32 accumulation: one MXU pass) on both tenants."""
    import jax.numpy as jnp

    from repro.pipeline import serve

    built, serve_model = chip_smoke.build_tenants(max_dim=256)
    server = serve.PharosServer(
        serve_model, built.design.n_stages,
        policy=built.scenario.policy, backend="pallas",
    )
    window = serve._run_window
    monkeypatch.setattr(
        serve, "_run_window",
        lambda a, b, *args, **kw: window(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), *args, **kw
        ),
    )
    lines = []
    checked = chip_smoke.correctness_phase(server, log=lines.append)
    assert not checked["ok"]
    assert min(checked["rel_err"].values()) > chip_smoke.REL_ERR_BOUND, (
        "\n".join(lines)
    )


_PIPELINE_SCRIPT = textwrap.dedent(
    """
    import importlib.util, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    from repro.configs.base import ArchConfig
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    cfg = ArchConfig(name="t", family="dense", n_layers=8, d_model=64,
                     n_heads=4, n_kv_heads=4, d_ff=128, vocab=128)
    res = cs.pipeline_phase(cfg, seq=32, n_micro=4, batch=2)
    assert res["ok"], res
    assert len(set(res["held_bytes"])) == 1 and res["held_bytes"][0] > 0
    print("PIPELINE_OK")
    """
)


def test_smoke_pipeline_phase_on_four_host_devices():
    """The ``--chips 4`` phase: each stage's weights on its own device,
    and the pipeline equal to the one-device reference."""
    proc = subprocess.run(
        [sys.executable, "-c", _PIPELINE_SCRIPT, str(ROOT / "chip_smoke.py")],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert "PIPELINE_OK" in proc.stdout, proc.stderr[-2000:]


def test_smoke_main_refuses_a_cpu(chip_smoke, capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir_follows_env(monkeypatch, tmp_path, env_set):
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv(CACHE_ENV, str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv(CACHE_ENV, raising=False)
        want = str(ROOT / ".jax_cache")
    assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    assert compile_cache_dir() == want == compile_cache_dir()
    try:
        assert enable_compile_cache() == want
        # with the variable set JAX reads it itself: no other cache is set
        assert jax.config.jax_compilation_cache_dir == (
            before if env_set else want
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
