"""The readers of the chunk spans: one chunk per ``chunk_id``, each
metric's arithmetic, and nothing from a program whose spans name no
chunk."""
from dataclasses import dataclass
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import chunk_spans, spec

ROOT = Path(__file__).resolve().parents[2]
NAMES = ("planner.dispatch_ms.bound", "planner.device_wait_ms.bound",
         "planner.fetch_ms.bound", "planner.host_ms.bound",
         "planner.useful_lane_pct.bound", "serve.resolve_ms",
         "process.gc_ms_per_s")


@dataclass
class OldSpan:
    """A request span as a program without chunk identifiers writes it."""
    objective: str = "corollary1"
    bucket: int = 64
    solve_s: float = 0.01


def _span(cid, objective="corollary1", bucket=64, scale=1.0):
    return SimpleNamespace(
        chunk_id=cid, objective=objective, bucket=bucket,
        planner_dispatch_s=0.003 * scale, planner_device_wait_s=0.001,
        planner_fetch_s=0.002, planner_build_s=0.001,
        planner_refine_host_s=0.0005, planner_records_s=0.0005,
        serve_resolve_s=0.0004, lanes_unique=16, gc_s=0.002)


def _read(name, spans, seconds=2.0):
    ctx = SimpleNamespace(spans=spans, seconds=seconds)
    return spec.metric_reader(ROOT, name).read(ctx)


def test_chunks_are_told_apart_by_chunk_id():
    spans = [_span(0), _span(0), _span(1, scale=3.0), _span(2, bucket=0),
             _span(3, objective="montecarlo"), _span(-1)]
    assert [s.chunk_id for s in chunk_spans.chunks(SimpleNamespace(
        spans=spans))] == [0, 1, 3]
    bound = chunk_spans.chunks(SimpleNamespace(spans=spans),
                               chunk_spans.BOUND)
    assert [s.chunk_id for s in bound] == [0, 1]
    assert chunk_spans.mean_ms(bound, "planner_dispatch_s") == \
        pytest.approx(6.0)
    assert chunk_spans.mean_ms([], "planner_dispatch_s") is None


def test_readers_compute_their_metrics():
    spans = [_span(0), _span(0), _span(1, scale=3.0)]
    got = {name: _read(name, spans) for name in NAMES}
    assert got["planner.dispatch_ms.bound"] == pytest.approx(6.0)
    assert got["planner.device_wait_ms.bound"] == pytest.approx(1.0)
    assert got["planner.fetch_ms.bound"] == pytest.approx(2.0)
    assert got["planner.host_ms.bound"] == pytest.approx(2.0)
    assert got["planner.useful_lane_pct.bound"] == pytest.approx(25.0)
    assert got["serve.resolve_ms"] == pytest.approx(0.4)
    # the first chunk reaches back before the window: left out
    assert got["process.gc_ms_per_s"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", NAMES)
def test_readers_find_nothing_in_spans_without_chunks(name):
    assert _read(name, [OldSpan(), OldSpan()]) is None
    assert _read(name, []) is None
