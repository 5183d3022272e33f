"""The ``mc.run_slot_pct`` reader of the four-chip Monte-Carlo cell, on
synthetic spans: the share of the padded lane-slots the kernel stepped
through, and nothing where no kernel pass was counted."""
import pytest

from test_mc_x4 import _ctx, _read, _span


def _with_run(span, run):
    span.mc_run_slots = run
    return span


def test_reader_computes_the_run_share():
    spans = [_with_run(_span(0), 2 * 8 * 60 * 300),
             _with_run(_span(0), 2 * 8 * 60 * 300),
             _with_run(_span(1, live=2 * 8 * 60 * 512), 2 * 8 * 60 * 700),
             _with_run(_span(2, objective="corollary1"), 0)]
    # chunks 0 and 1 once each: (300 + 700) of 2 x 1,024 slots a lane
    assert _read("mc.run_slot_pct", _ctx(spans)) == pytest.approx(
        100.0 * 1000 / (2 * 1024))
    assert _read("mc.live_slot_pct", _ctx(spans)) <= _read(
        "mc.run_slot_pct", _ctx(spans))


@pytest.mark.parametrize("spans", [
    [_span(0)],                                  # no run-slot counter
    [_with_run(_span(0), 0)],                    # scan engine: no kernel
    [_with_run(_span(0, objective="corollary1"), 5)],
    [],
], ids=["parent", "scan_engine", "bound_only", "empty"])
def test_reader_finds_nothing_without_kernel_passes(spans):
    assert _read("mc.run_slot_pct", _ctx(spans)) is None
