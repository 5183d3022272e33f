"""The one-chip Monte-Carlo cell over the paper's data set, at a tiny size
on the CPU: about 1,000 rows and N, through ``overrides``.  The check
passes sound runs and fails the control and each fault; the
``mc_ridge.block_slot_ns`` reader is checked on synthetic spans and
traces."""
import time

import pytest

import tiny
from harness import spec
from test_faults import answer_altered, half_batch_left_out
from test_mc_x4 import _span, _trace

CELL = "mc-paper-open"
ROWS = 1000
OVERRIDES = {
    "config": {"service": {"grid_size": 16, "batch_buckets": [8, 16],
                           "n_max": ROWS + 1, "mc_dataset_rows": ROWS},
               "objective": {"dataset": {"rows": ROWS}}},
    "traffic": {"arrivals": {"rate_per_s": 12}, "preroll_s": 0.3,
                "requests": {"N": [ROWS, ROWS + 1]}, "check": {"sample": 12}},
}


def run(substitute=None):
    from harness.cell import run_cell
    return run_cell(tiny.ROOT, CELL, 2**31 + 11, 1.5, False,
                    time.perf_counter(), require_chip=False,
                    overrides=OVERRIDES, substitute=substitute)


def test_sound_run_is_correct():
    r = run()
    assert r["correct"] is True and r["failed"] == 0, r["checked"]
    assert set(r["checked"]) == {"value_rel_gap", "regret_rel"}
    assert set(r["metrics"]) == {"latency_p95_ms", "setup_s"}


def test_control_is_not_correct():
    r = run(substitute="control")
    assert r["correct"] is False, r["checked"]


@pytest.mark.parametrize("fault", [half_batch_left_out, answer_altered],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = run()
    assert r["correct"] is False, r["checked"]


def _ctx(spans, trace):
    from types import SimpleNamespace
    cell = spec.load_cell(tiny.ROOT, CELL)
    return SimpleNamespace(spans=spans, seconds=2.0, trace=trace,
                           config=cell.config, traffic=cell.traffic,
                           device={"kind": "TPU v5 lite"})


def _paper_span(cid, run_slots, rows=18576, objective="montecarlo"):
    span = _span(cid, objective=objective)
    span.mc_run_slots = run_slots
    span.mc_rows = rows
    return span


def _read(ctx):
    return spec.metric_reader(tiny.ROOT, "mc_ridge.block_slot_ns").read(ctx)


def test_block_slot_ns_computes_by_hand():
    # chunks 0 and 1 once each (chunk 0's second span is the same chunk),
    # 128 x 3,000 lane-slots stepped; the kernel ran 0.1 s on each of two
    # chips: 0.2 s over 3,000 block-slots
    spans = [_paper_span(0, 128 * 1000), _paper_span(0, 128 * 1000),
             _paper_span(1, 128 * 2000),
             _paper_span(2, 128 * 5000, objective="corollary1")]
    got = _read(_ctx(spans, _trace(kernel_ns=0.1e9)))
    assert got == pytest.approx(1e9 * 0.2 / 3000)


@pytest.mark.parametrize("case", ["no_trace", "no_counters", "no_run_slots",
                                  "other_rows", "no_kernel"])
def test_block_slot_ns_gives_nothing_without_its_inputs(case):
    spans = [_paper_span(0, 128 * 1000)]
    trace = _trace(kernel_ns=0.1e9)
    if case == "no_trace":
        trace = None
    elif case == "no_counters":
        # a program that counts neither run slots nor rows
        spans = [_span(0)]
    elif case == "no_run_slots":
        spans = [_paper_span(0, 0)]
    elif case == "other_rows":
        # the program served its default 256 rows, not the paper's set
        spans = [_paper_span(0, 128 * 1000, rows=256)]
    else:
        trace = _trace(kernel_ns=0.0)
    assert _read(_ctx(spans, trace)) is None
