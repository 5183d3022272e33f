#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line of stdout.

    python3 bench/run.py --workload bound-solve-open --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic mix, reference and metrics are
found by name from ``BENCHMARK.json`` at the checkout's root.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window.  The numbers that decide ``correct`` are printed, each beside
its limit, as the last lines of stderr and under ``checked`` at the end
of the result.  Exits 2, printing no result, when JAX finds no TPU or
another number of chips than the cell asks for, or when the checkout
holds no program to run.
"""
import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    sys.path.insert(0, str(BENCH))
    from harness.cell import NoChip, run_cell
    from harness.spec import SpecError
    from harness.system import ProgramMissing

    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_PROCESS)
    except (NoChip, SpecError, ProgramMissing) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
