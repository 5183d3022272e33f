#!/usr/bin/env python3
"""Run one cell several times, each run its own process as the check runs
it, and summarise the spread of every metric.

    python3 bench/tools/repeat.py --workload bound-solve-open \
        --seeds 11,12,13 --seconds 10 --trace 0 --out bench_out/b.jsonl

Each line of ``--out`` is one run: seed, exit code, wall seconds and the
run's result line.  The summary gives each metric's median and its
spread (interquartile range over the median, ``statistics.quantiles``)
where there are three runs or more.  This process never touches JAX, so
each run holds the chips alone.
"""
import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
from harness.stats import relative_spread  # noqa: E402  (no JAX)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        cmd = [sys.executable, str(ROOT / "bench" / "run.py"),
               "--workload", args.workload, "--seed", str(seed),
               "--seconds", str(args.seconds), "--trace", str(args.trace)]
        t = time.perf_counter()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t
        lines = p.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except ValueError:
            result = None
        row = {"seed": seed, "rc": p.returncode, "wall_s": wall,
               "result": result, "stdout": "\n".join(lines[:-1])[-4000:],
               "stderr_tail": p.stderr[-3000:]}
        rows.append(row)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")
        print(f"seed {seed} rc {p.returncode} wall {wall:.1f}s "
              f"correct {result and result['correct']} "
              f"metrics {result and result['metrics']} "
              f"checked {result and result['checked']}", flush=True)
        if p.returncode != 0 or result is None:
            print(p.stdout[-3000:], p.stderr[-3000:], flush=True)
    names = sorted({n for r in rows if r["result"]
                    for n in r["result"]["metrics"]})
    for n in names:
        vals = [r["result"]["metrics"][n]["value"] for r in rows
                if r["result"] and n in r["result"]["metrics"]]
        line = f"{n}: median {statistics.median(vals)!r} over {len(vals)}"
        if len(vals) >= 3:
            line += f", spread {relative_spread(vals)!r}"
        print(line, "values", vals, flush=True)
    return 0 if all(r["rc"] == 0 and r["result"] and r["result"]["correct"]
                    for r in rows) else 1


if __name__ == "__main__":
    raise SystemExit(main())
