#!/usr/bin/env python3
"""Run one cell in this process and account for the host's time: the
program's leaf spans per chunk, every program span that overlaps each
of the device's longest idle gaps (read from the raw trace), and the
garbage collector's rate, pauses and hook cost.

    python3 bench/tools/host_phases.py --workload bound-solve-open \
        --seed 7 --seconds 50 --trace 1 --out bench_out/host_phases.json

Prints the run's result line, then one JSON object with the account,
which ``--out`` also holds.  Collections are counted by a callback of
this tool's own; the window is taken as the span of the window's
request enqueue times.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

#: the program's own span names in a trace
PROGRAM = ("serve.", "planner.", "gc.gen")


class Collections:
    """Every collection's start, generation and pause, in preallocated
    arrays (the log itself must not feed the collector)."""

    def __init__(self, size: int = 1 << 21):
        self.t = np.zeros(size)
        self.gen = np.zeros(size, np.int8)
        self.dur = np.zeros(size)
        self.n = 0
        self._t0 = 0.0

    def __call__(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        elif self.n < self.t.shape[0]:
            self.t[self.n] = self._t0
            self.gen[self.n] = info["generation"]
            self.dur[self.n] = now - self._t0
            self.n += 1

    def within(self, lo: float, hi: float):
        sel = slice(0, self.n)
        t, gen, dur = self.t[sel], self.gen[sel], self.dur[sel]
        inside = (t >= lo) & (t < hi)
        out = {}
        for g in (0, 1, 2):
            m = inside & (gen == g)
            out[f"gen{g}"] = {
                "per_s": float(m.sum() / (hi - lo)),
                "pause_ms_per_s": float(1e3 * dur[m].sum() / (hi - lo)),
                "pause_ms_max": float(1e3 * dur[m].max()) if m.any()
                else 0.0}
        return out


def hook_cost_us(runs: int = 20000):
    """Microseconds the program's collection hook adds to one collection
    of generation 0 and of generation 1 (annotated), by difference."""
    from repro.obs import runtime

    def per_collection(gen):
        t = time.perf_counter()
        for _ in range(runs):
            gc.collect(gen)
        return (time.perf_counter() - t) / runs

    out = {}
    for gen in (0, 1):
        bare = per_collection(gen)
        runtime.install_gc_hook()
        try:
            hooked = per_collection(gen)
        finally:
            runtime.remove_gc_hook()
        out[f"gen{gen}"] = {"bare_us": 1e6 * bare,
                            "hook_us": 1e6 * (hooked - bare)}
    return out


def gap_overlaps(directory, window="bench.window", longest=10):
    """The device's longest idle gaps in the window, each with every
    program span that overlaps it (ms of overlap) and the name the
    benchmark's reducer gives it."""
    import glob
    from harness import trace as tr

    path = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    trace = tr.read_xplane(path)
    lo, hi = tr.window_of(trace, window)
    host = trace.host_events()
    devices = trace.device_ops()
    if not devices:
        return []
    ops = next(iter(devices.values()))
    gaps = sorted(tr.gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:longest]
    out = []
    for a, b in gaps:
        spans = {}
        for e in host:
            if e.name.startswith(PROGRAM):
                o = min(e.end_ns, b) - max(e.start_ns, a)
                if o > 0:
                    spans[e.name] = spans.get(e.name, 0.0) + o / 1e6
        out.append({"ms": (b - a) / 1e6, "at_s": (a - lo) / 1e9,
                    "named": tr._name_gap(host, a, b, window),
                    "spans_ms": dict(sorted(spans.items(),
                                            key=lambda kv: -kv[1]))})
    return out


def leaf_account(spans, seconds):
    """Per-chunk means of every leaf and counter, and the share of the
    window the worker's leaves cover."""
    from repro.obs import COUNTERS, LEAVES

    seen = {}
    for s in spans:
        if getattr(s, "chunk_id", -1) >= 0:
            seen.setdefault(s.chunk_id, s)
    chunks = list(seen.values())
    if not chunks:
        return {}
    n = len(chunks)
    leaves = {name: 1e3 * sum(c.leaves()[name] for c in chunks) / n
              for name in LEAVES}
    return {"chunks": n, "requests": len(spans),
            "leaf_ms_per_chunk": leaves,
            "counters_per_chunk": {k: sum(getattr(c, k) for c in chunks) / n
                                   for k in COUNTERS},
            "solve_ms_per_chunk": 1e3 * sum(c.solve_s for c in chunks) / n,
            "gc_ms_per_chunk": 1e3 * sum(c.gc_s for c in chunks) / n,
            "leaves_share_of_window": sum(leaves.values()) * n / 1e3
            / seconds}


def main(argv=None) -> int:
    from harness import trace as trace_mod
    from harness.cell import run_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default="bench_out/host_phases.json")
    args = ap.parse_args(argv)

    account = {}
    reduce_dir = trace_mod.reduce_dir

    def reduce_and_overlap(directory, window="bench.window"):
        account["gaps"] = gap_overlaps(directory, window)
        return reduce_dir(directory, window)

    trace_mod.reduce_dir = reduce_and_overlap
    log = Collections()
    gc.callbacks.append(log)
    keep = []
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), time.perf_counter(), keep=keep)
    finally:
        gc.callbacks.remove(log)
        trace_mod.reduce_dir = reduce_dir
    print(json.dumps(result), flush=True)
    spans = keep[0].spans
    if spans:
        lo = min(s.enqueue_t for s in spans)
        hi = max(s.enqueue_t for s in spans)
        account["gc_in_window"] = log.within(lo, hi)
    account["leaves"] = leaf_account(spans, args.seconds)
    account["gc_hook_cost"] = hook_cost_us()
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(account, indent=1))
    print(json.dumps(account), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
