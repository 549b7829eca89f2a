#!/usr/bin/env python3
"""The control of a cell's check, at the cell's own size on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it builds the cell's deployment as a run does (weights
and inputs from the seed, at full width) and computes each tenant's
chain twice: with the reference (fp32 at HIGHEST) and with the control
(the reference one precision down: bf16 in three passes). It prints the
control's ``rel_err`` per tenant beside the cell's limit, one JSON line
per seed, with the chain at ``Precision.HIGH`` beside it as a witness
of the emulated control. Every served job of a tenant reads the same input, so one
chain per tenant stands for every job a run compares. The limits were
set between the sound runs' largest reading and the control's smallest
(PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


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
        for name, x, ws in zip(dep.names, dep.inputs, dep.weights):
            ref = reference.chain(x, ws)
            err = reference.max_rel_err([reference.chain_bf16x3(x, ws)], ref)
            high = reference.max_rel_err([reference.chain_high(x, ws)], ref)
            row[name] = {
                "control": err, "precision_high": high,
                "limit": cell["limits"]["rel_err"][name],
            }
        print(json.dumps(row), flush=True)
        del dep
    return 0


if __name__ == "__main__":
    sys.exit(main())
