#!/usr/bin/env python3
"""Knee sweep: the highest HI rate of a cell at which every HI job
meets its deadline.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --periods 0.04,0.05,0.06

Serves the cell once per candidate in one process, with the cell's
whole open-loop mix (every period, deadline and contract period, and
every Poisson rate) scaled together so that the first HI tenant's
period is the candidate; the tenants keep their ratios, and
closed-loop tenants keep theirs. Prints one JSON line per candidate.
The knee is the shortest period whose run has every HI job on time;
the cell file then states 5/4 of it, a number. Run once when a cell is
made.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def scaled(cell: dict, hi: list[str], period: float) -> dict:
    out = copy.deepcopy(cell)
    f = period / cell["tenants"][hi[0]]["period_s"]
    for t in out["tenants"].values():
        for k in ("period_s", "deadline_s", "contract_period_s"):
            if k in t and t["arrival"] != "closed":
                t[k] *= f
        if "rate_hz" in t:
            t["rate_hz"] /= f
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--periods", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    import harness
    from repro.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_spec("workloads", args.workload)
    config = harness.load_spec("configs", cell["config"])
    dep = harness.build_deployment(config, args.seed)
    hi = [t["name"] for t in config["tenants"] if t["criticality"] == "HI"]
    for p in (float(x) for x in args.periods.split(",")):
        c = scaled(cell, hi, p)
        s = harness.prepare(dep, c, args.seed, args.seconds)
        w = harness.serve(s, args.seconds)
        out = harness.outcomes(s, w)
        hi_ms = [r * 1e3 for r in out["hi_resp_s"]]
        print(json.dumps({
            "period_s": {
                n: t.get("period_s", 1.0 / t["rate_hz"] if "rate_hz" in t else None)
                for n, t in c["tenants"].items()
            },
            "hi_due": out["hi_due"],
            "hi_on_time": out["hi_on_time"],
            "p50_ms": harness.percentile(hi_ms, 50),
            "p95_ms": harness.percentile(hi_ms, 95),
            "max_ms": max(hi_ms, default=None),
            "lo_gflop_per_s": w.lo_flops / out["window_s"] / 1e9,
            "failed": out["failed"],
            "late": out["late"],
            "attempted": out["attempted"],
            "mode_switches": len(w.report.mode_switches),
            "preemptions": w.report.server_report.preemptions,
        }), flush=True)
        s.server = s.gateway = s.tracker.server = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
