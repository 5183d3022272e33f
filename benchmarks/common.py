"""Shared helpers for the benchmark harness."""
from __future__ import annotations

import json
import os
import subprocess
import time
from datetime import datetime, timezone

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts")

#: BENCH_*.json schema: 2 adds the bench_stamp() provenance fields
#: (schema_version, generated_utc, git_commit) so runs from different
#: commits can be told apart.
SCHEMA_VERSION = 2


def bench_stamp() -> dict:
    """Provenance stamp merged into every BENCH_*.json payload:
    schema version, UTC generation time, and the git commit (``None``
    outside a git checkout — artifacts must still be writable there)."""
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        commit = None
    return {
        "schema_version": SCHEMA_VERSION,
        "generated_utc": datetime.now(timezone.utc).isoformat(
            timespec="seconds"),
        "git_commit": commit,
    }


def emit(name: str, us_per_call: float, derived: str = ""):
    """The run.py CSV contract: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.1f},{derived}")


def save_artifact(name: str, payload: dict):
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(os.path.join(ARTIFACTS, name + ".json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)


def timed(fn, *args, repeat: int = 3, **kw):
    fn(*args, **kw)  # compile / warm
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt * 1e6  # microseconds
