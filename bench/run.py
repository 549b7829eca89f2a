#!/usr/bin/env python3
"""PHAROS chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs in this one process, which holds the chip. The last line of
standard output is the result as one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and ``checks`` last: each number compared with the
reference beside its limit); the last lines of standard error repeat
the checks. With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics.

Exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, or a ``device_kind`` that ``peaks.json`` does
not list. The cells, configurations and metric readers are found by
name under this directory; JAX's compilation cache is kept inside the
checkout (``repro.compile_cache``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chip_or_none(chips: int):
    """``(device_kind, peaks)`` of the TPU JAX found, or None (with the
    reason on standard error)."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if devs[0].platform != "tpu":
        reason = f"needs a TPU, JAX found {devs[0].platform!r}"
    elif len(devs) < chips:
        reason = f"the cell asks for {chips} chips, JAX found {len(devs)}"
    elif kind not in peaks:
        reason = f"device_kind {kind!r} is not in peaks.json"
    else:
        return kind, peaks[kind]
    print(f"bench: {reason}", file=sys.stderr)
    return None


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0 or args.seconds <= 0:
        print("bench: --seed must be >= 0 and --seconds > 0", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import harness

    cell = harness.load_spec("workloads", args.workload)
    chip = chip_or_none(int(cell["chips"]))
    if chip is None:
        return 1
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # cache every program, however quick to compile, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, peaks=chip[1],
    )
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} {c['op']} {c['limit']!r} "
              + ("ok" if c["ok"] else "FAIL"), file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
