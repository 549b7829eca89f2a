"""The control: the reference in the program's place, one precision
down (each tenant model's ``control``: for the GEMM chain bf16 in three
passes instead of fp32 at HIGHEST), has to fail
the cell's check. At full width on the CPU for the small tenants; the
stablelm decode chain is capped at 512 wide here, and its full-width
readings on the chip are in PERF.md."""
import pytest

import harness
import reference

SEEDS = (3, 2**31 + 5, 2**32 + 7)


def _tenant(config, name, max_dim, seed):
    """The tenant ``name`` alone through its model, as a run builds it."""
    from repro.traffic.scenarios import TenantSpec, resolve_workload

    t = next(t for t in config["tenants"] if t["name"] == name)
    w = resolve_workload(TenantSpec(
        workload=t["workload"], ratio=1.0, batch=t.get("batch", 1),
        seq=t.get("seq", 2048),
    ))
    model = harness.load_model(t.get("model", harness.DEFAULT_MODEL))
    (tenant,) = model.build(
        [t], key=harness.seed_key(seed), rows=config["rows"],
        block=(128, 128, 128), stages=[(0,) * len(w.layers)], workloads=[w],
        max_dim=max_dim,
    )
    return tenant


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
        tenant = _tenant(config, t["name"], CAPS.get(t["name"]), seed)
        ref = tenant.reference(0)
        assert reference.max_rel_err([ref], ref) == 0.0
        err = reference.max_rel_err([tenant.control(0)], ref)
        if err > spec["limits"]["rel_err"][t["name"]]:
            failed.append(t["name"])
    assert failed
