"""Benchmark orchestrator — one entry per paper table/figure.

Prints ``name,seconds,derived`` CSV lines; per-benchmark CSV detail
lands in ``experiments/benchmarks/``. ``--quick`` shrinks grids for CI.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import enable_compile_cache


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="smaller grids")
    ap.add_argument("--only", default=None, help="run a single benchmark")
    args = ap.parse_args()

    from benchmarks import (
        fig1_schedulability,
        fig6_sg_vs_tg,
        fig7_utilization,
        fig8_response_time,
        fig9_beam_quality,
        kernel_micro,
        roofline_report,
    )

    benches = {
        "fig1_schedulability": lambda: fig1_schedulability.run(
            5 if args.quick else 7
        ),
        "fig6_sg_vs_tg": lambda: fig6_sg_vs_tg.run(3 if args.quick else 5),
        "fig7_utilization": lambda: fig7_utilization.run(3 if args.quick else 4),
        "fig8_response_time": lambda: fig8_response_time.run(
            3 if args.quick else 4
        ),
        "fig9_beam_quality": lambda: fig9_beam_quality.run(
            6 if args.quick else 8
        ),
        "kernel_micro": kernel_micro.run,
        "roofline_16x16": lambda: roofline_report.run("16x16"),
        "roofline_2x16x16": lambda: roofline_report.run("2x16x16"),
    }
    if args.only:
        benches = {k: v for k, v in benches.items() if args.only in k}

    print("name,seconds,derived")
    failures = 0
    for name, fn in benches.items():
        t0 = time.perf_counter()
        try:
            derived = fn()
        except Exception as e:  # pragma: no cover
            derived = f"ERROR {type(e).__name__}: {e}"
            failures += 1
        dt = time.perf_counter() - t0
        print(f"{name},{dt:.2f},{derived}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
