#!/usr/bin/env python3
"""Find an open-loop cell's knee: run its window at several offered rates
in one process and report what each rate was served at.

    python3 bench/tools/sweep.py --workload bound-solve-open \
        --rates 2000,4000,6000 --seconds 5 --seeds 1,2

Per rate and seed: requests offered and failed, plans completed per
second inside the window, p50 and p95 latency from each request's
scheduled send, the drift of the backlog (the mean latency of the
window's last fifth of requests minus that of its first fifth; a growing
backlog shows as a drift that grows with the window), and the chunks the
worker solved: how many a second, of which bucket, and their mean
host-clock time from chunk start to its last future resolved.  The knee
is the highest rate with no growing backlog and no jump in the tail.
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
    from harness.readers import chunks
    from harness.stats import percentile

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--out", default="bench_out/sweep.jsonl")
    args = ap.parse_args(argv)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        for rate in [float(r) for r in args.rates.split(",")]:
            keep = []
            result = run_cell(ROOT, args.workload, seed, args.seconds,
                              False, time.perf_counter(), keep=keep,
                              overrides={"traffic": {"arrivals": {
                                  "rate_per_s": rate}}})
            ctx = keep[0]
            lat = ctx.latency_s
            fifth = max(1, len(lat) // 5)
            solved = list(chunks(ctx).values())
            buckets = {}
            for s in solved:
                buckets[str(s.bucket)] = buckets.get(str(s.bucket), 0) + 1
            row = {"rate_per_s": rate, "seed": seed,
                   "attempted": result["attempted"],
                   "failed": result["failed"],
                   "completed_per_s": ctx.completed_in_window / ctx.seconds,
                   "p50_ms": 1e3 * percentile(lat, 50),
                   "p95_ms": 1e3 * percentile(lat, 95),
                   "drift_ms": 1e3 * (sum(lat[-fifth:]) - sum(lat[:fifth]))
                   / fifth,
                   "chunks_per_s": len(solved) / ctx.seconds,
                   "chunks_by_bucket": buckets,
                   "chunk_ms": 1e3 * sum(
                       s.pad_s + s.cache_lookup_s + s.solve_s + s.resolve_s
                       for s in solved) / max(1, len(solved)),
                   "solve_ms": 1e3 * sum(s.solve_s for s in solved)
                   / max(1, len(solved)),
                   "correct": result["correct"]}
            print("[sweep]", json.dumps(row), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
