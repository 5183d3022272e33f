#!/usr/bin/env python3
"""Read the numbers ``correct`` is decided by, for the program and for the
control, over many seeds in one process (set-up is paid once).

    python3 bench/tools/readings.py --workload bound-solve-open \
        --seeds 1,2,3 --control-seeds 4,5,6 --seconds 5

For each program seed, one run of the cell as the check makes it; for
each control seed, the same run with the reference's lower-precision
plans put in the program's place.  The limits in the configuration are
set between the largest program reading and the smallest control one.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))


def main(argv=None) -> int:
    from harness.cell import run_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default="bench_out/readings.jsonl")
    args = ap.parse_args(argv)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    worst = {}
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in [int(s) for s in seeds.split(",") if s]:
            r = run_cell(ROOT, args.workload, seed, args.seconds, False,
                         time.perf_counter(),
                         substitute="control" if kind == "control" else None)
            row = {"kind": kind, "seed": seed, "correct": r["correct"],
                   "attempted": r["attempted"], "failed": r["failed"],
                   "checked": r["checked"], "metrics": r["metrics"]}
            print("[readings]", json.dumps(row), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
            for name, v in r["checked"].items():
                key = (kind, name)
                agg = max if kind == "program" else min
                worst[key] = agg(worst.get(key, v["value"]), v["value"])
    for (kind, name), v in sorted(worst.items()):
        print(f"[readings] {kind} {'max' if kind == 'program' else 'min'} "
              f"{name} = {v!r}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
