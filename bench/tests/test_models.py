"""The model seam: a configuration's tenant names the module that
builds, counts and checks it (``"model": "<name>"``), found by name
like a metric reader, so that a model comes in as new files.

The test model ``data/models/rolled_chain.py`` serves job ``k`` the
seed's input rolled by ``k`` rows. In the test configuration it serves
two tenants of ``av_stack``: the camera monitor, which the gateway
releases, and infotainment, here a closed loop that the client submits
to, so that EDF preempts it in the middle of a layer whenever a HI job
comes. The open-loop rates are slowed 5x for the CPU's interpreted
kernel (as in ``test_faults.py``).
"""
import copy
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import harness

DATA = Path(__file__).parent / "data"
SEED = 2**31 + 1515
#: the tenants the test model serves
ROLLED = ("camera_monitor", "infotainment")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _rolled_config() -> dict:
    config = copy.deepcopy(harness.load_spec("configs", "av_stack"))
    config["name"] = "rolled_av"
    for t in config["tenants"]:
        if t["name"] in ROLLED:
            t["model"] = "rolled_chain"
    return config


def _rolled_cell(factor: float = 5.0) -> dict:
    cell = copy.deepcopy(harness.load_spec("workloads", "av_stack.edf"))
    cell["config"] = "rolled_av"
    for t in cell["tenants"].values():
        for k in ("period_s", "deadline_s", "contract_period_s"):
            if k in t:
                t[k] *= factor
        if "rate_hz" in t:
            t["rate_hz"] /= factor
    cell["tenants"]["infotainment"] = {
        "arrival": "closed", "outstanding": 1,
        "contract_period_s": cell["tenants"]["infotainment"]["contract_period_s"],
    }
    return cell


@pytest.fixture
def rolled(monkeypatch):
    """The test model on the search path, the test configuration found
    by name; yields the model module."""
    monkeypatch.setattr(harness, "MODEL_DIRS", [*harness.MODEL_DIRS, DATA / "models"])
    real, config = harness.load_spec, _rolled_config()
    monkeypatch.setattr(
        harness, "load_spec",
        lambda kind, name: config if (kind, name) == ("configs", "rolled_av")
        else real(kind, name),
    )
    return harness.load_model("rolled_chain")


def _run(max_dim=640):
    return harness.run_cell(
        "rolled_av.edf", SEED, 4.0, False, t_start=time.perf_counter(),
        peaks=PEAKS, max_dim=max_dim, log=lambda m: None, cell=_rolled_cell(),
    )


def test_default_model_is_the_gemm_chain():
    config = harness.load_spec("configs", "copilot_decode")
    dep = harness.build_deployment(config, SEED, max_dim=128)
    gemm = harness.load_model(harness.DEFAULT_MODEL)
    assert all(type(t) is gemm.Tenant for t in dep.tenants)
    for t, spec in zip(dep.tenants, config["tenants"]):
        assert "model" not in spec
        assert t.job_input(0) is t.job_input(7)


def test_rolled_chain_reads_its_ordinal(rolled):
    dep = harness.build_deployment(_rolled_config(), SEED, max_dim=128)
    kinds = {n: type(t) for n, t in zip(dep.names, dep.tenants)}
    assert kinds == {"lidar_perception": rolled.gemm.Tenant,
                     "camera_monitor": rolled.Tenant, "infotainment": rolled.Tenant}
    t = dep.tenants[dep.names.index("camera_monitor")]
    a, b = t.reference(0), t.reference(1)
    assert float(abs(a - b).max()) > 0
    assert float(abs(b - rolled.jnp.roll(a, 1, axis=0)).max()) <= 1e-5 * float(abs(a).max())


def test_rolled_chain_cell_is_correct(rolled):
    res = _run()
    checks = res["checks"]
    assert res["correct"] is True, checks
    for name in ROLLED:
        assert checks[f"jobs_compared.{name}"]["value"] >= 2


@pytest.mark.parametrize("fault", ["SHIFT", "CONTROL_AS_REFERENCE"])
def test_shifted_ordinal_or_control_is_not_correct(rolled, fault, monkeypatch):
    monkeypatch.setattr(rolled, fault, 1 if fault == "SHIFT" else True)
    res = _run(max_dim=128)
    checks = res["checks"]
    assert res["correct"] is False
    for name in ROLLED:
        assert not checks[f"rel_err.{name}"]["ok"], checks
    assert checks["rel_err.lidar_perception"]["ok"], checks


def test_model_config_and_cell_added_as_new_files_run_by_name(tmp_path):
    """What a later PR adds to bring a model: ``models/<name>.py``,
    ``configs/<config>.json``, ``workloads/<cell>.json`` and a
    ``workloads`` entry of ``BENCHMARK.json``; no other file changes.
    The cell runs by name in a copy of the benchmark."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    shutil.copy(DATA / "models" / "rolled_chain.py", bench / "models")
    (bench / "configs" / "rolled_av.json").write_text(json.dumps(_rolled_config()))
    (bench / "workloads" / "rolled_av.edf.json").write_text(json.dumps(_rolled_cell()))
    spec = harness.load_benchmark()
    spec["workloads"].append({
        "name": "rolled_av.edf", "config": "rolled_av", "traffic": "edf",
        "chips": 1, "why": "a test cell",
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    assert all(p.read_bytes() == b for p, b in before.items())
    script = (
        "import json, sys, time\n"
        "import harness\n"
        f"res = harness.run_cell('rolled_av.edf', {SEED}, 2.0, False,"
        f" t_start=time.perf_counter(), peaks={PEAKS!r}, max_dim=128,"
        " log=lambda m: None)\n"
        "res['model_file'] = harness.load_model('rolled_chain').__file__\n"
        "print(json.dumps(res))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(bench), str(harness.ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", script], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert Path(res["model_file"]).parent == bench / "models"
    for name in ROLLED:
        assert res["checks"][f"rel_err.{name}"]["ok"], res["checks"]
        assert res["checks"][f"jobs_compared.{name}"]["value"] >= 2
