"""Each cell through the harness on the CPU at a tiny width (Pallas in
interpret mode), and the command line without a chip."""
import copy
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]
SEED = 2**31 + 4242
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_through_the_harness(cell):
    res = harness.run_cell(
        cell, SEED, 1.0, False, t_start=time.perf_counter(),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        max_dim=128, log=lambda m: None,
    )
    assert list(res) == KEYS
    bench = harness.load_benchmark()
    want = [m["name"] for m in harness.cell_metrics(bench, cell, "end_to_end")]
    assert list(res["metrics"]) == want
    assert all(set(v) == {"value", "unit"} for v in res["metrics"].values())
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["attempted"] > 0
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(res)


def test_mode_switch_runs_through_the_harness():
    """A cell file may arm the gateway's mode controller (``modes``): no
    committed cell does, since the controller lets queued LO jobs delay
    HI ones under overdrive (PERF.md), but a later cell adds it as data."""
    cell = copy.deepcopy(harness.load_spec("workloads", "av_stack.edf"))
    cell["modes"] = {"action": "drop"}
    cell["tenants"]["infotainment"]["rate_hz"] *= 5
    res = harness.run_cell(
        "av_stack.edf", SEED, 1.0, False, t_start=time.perf_counter(),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        max_dim=128, log=lambda m: None, cell=cell,
    )
    assert list(res) == KEYS and res["attempted"] > 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_cli_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _cli(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_cli_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
