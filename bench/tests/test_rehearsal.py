"""Each cell end to end at a tiny size on the CPU: the same harness path
as on the chip, minus the look for a chip."""
import os
import subprocess
import sys

import pytest

import tiny


@pytest.mark.parametrize("trace", [False, True])
def test_bound_cell(trace):
    keep = []
    r = tiny.run("bound-solve-open", trace=trace, keep=keep)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 1500
    names = set(r["metrics"])
    if trace:
        assert {"serve.batch_wait_ms", "planner.solve_ms.bound"} <= names
        assert r["device"]["window_s"] > 0
    else:
        assert names == {"latency_p95_ms", "setup_s"}
    assert list(r)[-1] == "checked"
    assert set(r["checked"]) == {"value_rel_gap", "regret_rel"}


def test_no_chip_exits_nonzero_and_prints_no_result():
    p = subprocess.run([sys.executable, str(tiny.ROOT / "bench" / "run.py"),
                        "--workload", "bound-solve-open", "--seed", "1",
                        "--seconds", "1"], cwd=tiny.ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
