#!/usr/bin/env python3
"""Set an open-loop cell's offered rate to a share of the knee a sweep
found (``bench/tools/sweep.py``), in the cell's traffic file.

    python3 bench/tools/set_rate.py --workload bound-solve-open \
        --sweep bench_out/sweep.jsonl --share 0.8

The knee is the highest swept rate that was held, with every rate
below it, by every seed swept: no failed request, plans completed inside
the window at 97% of the offered rate or more, a backlog drift (mean
latency of the window's last fifth minus its first fifth) below the
median latency, and a 95th percentile under twice the median.  The last
catches the service's second, slower state: where a micro-batch group
outgrows the smallest bucket, its chunks double, the cycle lengthens and
the groups stay large, with no growing backlog but about twice the tail.
The rate is rounded down to a multiple of 50 plans/s.
"""
import argparse
import json
import math
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def held(r) -> bool:
    return (r["failed"] == 0
            and r["completed_per_s"] >= 0.97 * r["rate_per_s"]
            and r["drift_ms"] < r["p50_ms"]
            and r["p95_ms"] < 2 * r["p50_ms"])


def knee(rows):
    best = None
    for rate in sorted({r["rate_per_s"] for r in rows}):
        if not all(held(r) for r in rows if r["rate_per_s"] == rate):
            break
        best = rate
    if best is None:
        raise SystemExit("no swept rate was held")
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sweep", required=True)
    ap.add_argument("--share", type=float, default=0.8)
    args = ap.parse_args(argv)
    rows = [json.loads(line) for line in Path(args.sweep).read_text()
            .splitlines() if line.strip()]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}[args.workload]
    path = ROOT / "bench" / "traffic" / f"{work['traffic']}.json"
    mix = json.loads(path.read_text())
    k = knee(rows)
    rate = 50 * math.floor(args.share * k / 50)
    mix["arrivals"]["rate_per_s"] = rate
    path.write_text(json.dumps(mix, indent=2) + "\n")
    print(f"knee {k} plans/s; {args.workload} offered {rate} plans/s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
