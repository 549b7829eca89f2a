"""The control: the reference in the program's place, one precision
down (bf16 in three passes instead of fp32 at HIGHEST), has to fail
the cell's check. At full width on the CPU for the small tenants; the
stablelm decode chain is capped at 512 wide here, and its full-width
readings on the chip are in PERF.md."""
import jax
import pytest

import harness
import reference

SEEDS = (3, 2**31 + 5, 2**32 + 7)


def _tenant(config, name, max_dim, seed):
    from repro.traffic.scenarios import TenantSpec, resolve_workload

    t = next(t for t in config["tenants"] if t["name"] == name)
    w = resolve_workload(TenantSpec(
        workload=t["workload"], ratio=1.0, batch=t.get("batch", 1),
        seq=t.get("seq", 2048),
    ))
    shapes = harness.chain_shapes(w, config["rows"], (128, 128, 128), max_dim)
    key = harness.seed_key(seed)
    ws = harness._normal_leaves(tuple((K, N, 1) for _, K, N in shapes),
                                jax.random.fold_in(key, 0))
    (x,) = harness._normal_leaves(((shapes[0][0], shapes[0][1], 0),),
                                  jax.random.fold_in(key, 1))
    return x, ws


#: tenants held at a width the CPU can hold (None: full width)
CAPS = {"stablelm_decode": 512}


@pytest.mark.parametrize("cell", [
    w["name"] for w in harness.load_benchmark()["workloads"]
])
@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_cell(cell, seed):
    spec = harness.load_spec("workloads", cell)
    config = harness.load_spec("configs", spec["config"])
    failed = []
    for t in config["tenants"]:
        x, ws = _tenant(config, t["name"], CAPS.get(t["name"]), seed)
        ref = reference.chain(x, ws)
        assert reference.max_rel_err([ref], ref) == 0.0
        err = reference.max_rel_err([reference.chain_bf16x3(x, ws)], ref)
        if err > spec["limits"]["rel_err"][t["name"]]:
            failed.append(t["name"])
    assert failed
