"""Tiny sizes at which each cell runs end to end on the CPU, and a runner
that skips the harness's look for a chip."""
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

OVERRIDES = {
    "bound-solve-open": {
        "config": {"service": {"grid_size": 32, "batch_buckets": [8, 16]}},
        "traffic": {"arrivals": {"rate_per_s": 1000}, "preroll_s": 0.3,
                    "check": {"sample": 64}},
    },
}


def run(cell, seed=2**31 + 5, seconds=1.5, trace=False, substitute=None,
        keep=None):
    from harness.cell import run_cell
    return run_cell(ROOT, cell, seed, seconds, trace, time.perf_counter(),
                    require_chip=False, overrides=OVERRIDES[cell],
                    substitute=substitute, keep=keep)
