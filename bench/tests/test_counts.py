"""Operation and byte counts of the window executor, and the peak
table."""
import json
import types

import pytest

import counts
import harness
import run

gemm = harness.load_model("gemm_chain")

BLOCK = (128, 128, 128)


@pytest.mark.parametrize("M,K,N,wt", [
    (128, 2048, 6144, 4),
    (128, 2048, 100352, 4),
    (256, 384, 640, 4),
    (384, 128, 384, 8),
    (128, 11264, 2048, 3),
])
def test_window_flops_sum_to_the_layer(M, K, N, wt):
    shapes = [(M, K, N)]
    wins = list(gemm.windows_between(shapes, BLOCK, wt, (0, 0), (1, 0)))
    assert sum(counts.window_flops(k, t, BLOCK) for _, k, _, _, t in wins) \
        == 2 * M * K * N
    assert sum(t for *_, t in wins) == counts.tile_grid(M, K, N, BLOCK)[2]
    assert gemm.flops_done(shapes, BLOCK, (1, 0)) == 2 * M * K * N


def test_windows_between_follow_a_job_across_layers():
    shapes = [(128, 256, 640), (128, 640, 256), (128, 256, 512)]
    a, b = (0, 3), (2, 0)
    wins = list(gemm.windows_between(shapes, BLOCK, 4, a, b))
    # layer 0: 5 tiles, window 1, from tile 3; layer 1: 2 tiles, one window
    assert [(w[0:3], w[3], w[4]) for w in wins] == [
        ((128, 256, 640), 3, 1), ((128, 256, 640), 4, 1),
        ((128, 640, 256), 0, 2),
    ]
    done = gemm.flops_done(shapes, BLOCK, b) - gemm.flops_done(shapes, BLOCK, a)
    assert done == sum(counts.window_flops(w[1], w[4], BLOCK) for w in wins)


def test_window_bytes_count_each_operand_once():
    # one tile row, 4 tiles: A strip once, 4 B column blocks, C in and out
    b = counts.window_bytes(128, 2048, 6144, BLOCK, 0, 4)
    assert b == 4 * (128 * 2048 + 2048 * 512 + 2 * 4 * 128 * 128)


def test_peak_table_is_keyed_by_device_kind():
    peaks = json.loads((run.BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def _fake_devices(platform, kind, n=1):
    return [types.SimpleNamespace(platform=platform, device_kind=kind)] * n


def test_unknown_device_kind_is_an_error(monkeypatch, capsys):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: _fake_devices("tpu", "TPU v99"))
    assert run.chip_or_none(1) is None
    assert "not in peaks.json" in capsys.readouterr().err
    monkeypatch.setattr(jax, "devices", lambda: _fake_devices("tpu", "TPU v5 lite"))
    kind, peaks = run.chip_or_none(1)
    assert kind == "TPU v5 lite" and peaks["flops_per_s"] == 197e12
    assert run.chip_or_none(4) is None
    monkeypatch.setattr(jax, "devices", lambda: _fake_devices("cpu", "cpu"))
    assert run.chip_or_none(1) is None
