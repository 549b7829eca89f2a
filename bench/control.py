#!/usr/bin/env python3
"""The control of a cell's check, at the cell's own size on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it builds the cell's deployment as a run does (each
tenant through its model, from the seed, at full width) and, for each
tenant and each of the ordinals `ORDINALS`, computes the model's
``reference(k)`` and ``control(k)`` (the reference one precision
down). It prints the control's ``rel_err`` per tenant and ordinal
beside the cell's limit, one JSON line per seed, and the model's
``witness(k)`` beside it where the model has one (the GEMM chain's:
the chain at ``Precision.HIGH``, a witness of the emulated control).
The limits were set between the sound runs' largest reading and the
control's smallest (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
#: the job ordinals whose control each tenant reads
ORDINALS = (0, 1, 2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    import harness
    import reference

    print(f"control on {jax.devices()[0].device_kind}", file=sys.stderr)
    cell = harness.load_spec("workloads", args.workload)
    config = harness.load_spec("configs", cell["config"])
    for seed in (int(x) for x in args.seeds.split(",")):
        dep = harness.build_deployment(config, seed)
        row = {"seed": seed}
        for name, t in zip(dep.names, dep.tenants):
            got = {"control": [], "limit": cell["limits"]["rel_err"][name]}
            for k in ORDINALS:
                ref = t.reference(k)
                got["control"].append(reference.max_rel_err([t.control(k)], ref))
                if hasattr(t, "witness"):
                    got.setdefault("witness", []).append(
                        reference.max_rel_err([t.witness(k)], ref))
            row[name] = got
        print(json.dumps(row), flush=True)
        del dep
    return 0


if __name__ == "__main__":
    sys.exit(main())
