"""The four-chip Monte-Carlo cell at a tiny size on the CPU: the check
passes sound runs and fails the control and each fault; the ``mc_ridge``
cost is counted by hand; the cell's own readers are checked on synthetic
spans and traces."""
import time
from types import SimpleNamespace

import pytest

import tiny
from harness import mc_ridge_cost, spec
from harness.trace import Event, Reduced
from test_faults import answer_altered, half_batch_left_out

CELL = "mc-x4-open"
OVERRIDES = {
    "config": {"service": {"grid_size": 16, "batch_buckets": [8, 16],
                           "n_max": 320}},
    "traffic": {"arrivals": {"rate_per_s": 20}, "preroll_s": 0.3,
                "requests": {"N": [256, 320]}, "check": {"sample": 24}},
}
NAMES = ("planner.solve_ms.mc", "planner.device_wait_ms.mc",
         "mc.live_slot_pct", "device.idle_pct.mc", "mc_ridge.roofline_pct",
         "planner.dispatch_ms.mc", "planner.fetch_ms.mc",
         "planner.host_ms.mc", "planner.sharded_pct.mc")
#: the readers a program without the Monte-Carlo counters still feeds
LEAF_NAMES = ("planner.solve_ms.mc", "planner.device_wait_ms.mc",
              "planner.dispatch_ms.mc", "planner.fetch_ms.mc",
              "planner.host_ms.mc")


def run(trace=False, substitute=None):
    from harness.cell import run_cell
    return run_cell(tiny.ROOT, CELL, 2**31 + 9, 1.5, trace,
                    time.perf_counter(), require_chip=False,
                    overrides=OVERRIDES, substitute=substitute)


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(trace):
    r = run(trace=trace)
    assert r["correct"] is True and r["failed"] == 0, r["checked"]
    assert set(r["checked"]) == {"value_rel_gap", "regret_rel"}
    if trace:
        # the CPU trace has no TPU planes: the device readers give nothing
        assert set(r["metrics"]) == set(LEAF_NAMES) | {
            "mc.live_slot_pct", "planner.sharded_pct.mc",
            "serve.batch_wait_ms", "serve.resolve_ms", "process.gc_ms_per_s"}
        assert 0 < r["metrics"]["mc.live_slot_pct"]["value"] <= 100
        assert 0 <= r["metrics"]["planner.sharded_pct.mc"]["value"] <= 100
    else:
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


def test_cost_counts_by_hand():
    # 2 runs x 4 lanes x 512 slots, 1,000 of them live, d = 8, 256 rows:
    # 41 operations a live slot; 2 runs x 2 slabs = 4 calls, each moving
    # 4 lanes x 8 weights x 4 bytes in and out, and 9 x 256 x 4 bytes of
    # rows and targets
    flops, nbytes = mc_ridge_cost.cost(lanes=4, slots=512, runs=2, d=8,
                                       rows=256, live=1000)
    assert flops == 41 * 1000
    assert nbytes == 4 * (2 * 4 * 8 * 4 + 9 * 256 * 4)


def test_peaks_of_an_unknown_chip_are_an_error():
    assert mc_ridge_cost.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        mc_ridge_cost.peaks("cpu")


def _span(cid, objective="montecarlo", bucket=8, solve=0.2, wait=0.15,
          lane_slots=2 * 8 * 60 * 1024, live=2 * 8 * 60 * 256,
          sharded=1):
    return SimpleNamespace(
        chunk_id=cid, objective=objective, bucket=bucket, solve_s=solve,
        planner_device_wait_s=wait, planner_dispatch_s=0.01,
        planner_fetch_s=0.004, planner_build_s=0.002,
        planner_refine_host_s=0.001, planner_records_s=0.003,
        dispatches=1, mc_lane_slots=lane_slots, mc_live_slots=live,
        mc_sharded_dispatches=sharded)


def _ctx(spans, trace=None, kind="TPU v5 lite"):
    cell = spec.load_cell(tiny.ROOT, CELL)
    return SimpleNamespace(spans=spans, seconds=2.0, trace=trace,
                           config=cell.config, traffic=cell.traffic,
                           device={"kind": kind})


def _trace(kernel_ns):
    """Two chips, each running the kernel for ``kernel_ns`` and a fusion
    that reads its output, busy 0.5 s of a 2-s window."""
    events = {}
    for chip in (0, 1):
        events[f"/device:TPU:{chip}"] = [
            Event("%mc_ridge_slab.1 = f32[8,3840]{1,0} custom-call(%a, %b), "
                  'custom_call_target="tpu_custom_call"', 0.0, kernel_ns),
            Event("%fusion.2 = f32[3840,8]{0,1} fusion(%mc_ridge_slab.1), "
                  "kind=kLoop", kernel_ns, 1e6),
        ]
    return Reduced(window_s=2.0, busy_s_by_device={"a": 0.5, "b": 0.5},
                   op_seconds={}, idle_gaps=[], kernel_events=events)


def _read(name, ctx):
    return spec.metric_reader(tiny.ROOT, name).read(ctx)


def test_readers_compute_their_metrics():
    spans = [_span(0), _span(0), _span(1, solve=0.4, wait=0.25,
                                        live=2 * 8 * 60 * 512),
             _span(2, objective="corollary1", solve=9.0)]
    ctx = _ctx(spans, trace=_trace(kernel_ns=0.1e9))
    assert _read("planner.solve_ms.mc", ctx) == pytest.approx(300.0)
    assert _read("planner.device_wait_ms.mc", ctx) == pytest.approx(200.0)
    assert _read("mc.live_slot_pct", ctx) == pytest.approx(37.5)
    assert _read("device.idle_pct.mc", ctx) == pytest.approx(75.0)
    assert _read("planner.dispatch_ms.mc", ctx) == pytest.approx(10.0)
    assert _read("planner.fetch_ms.mc", ctx) == pytest.approx(4.0)
    assert _read("planner.host_ms.mc", ctx) == pytest.approx(6.0)
    assert _read("planner.sharded_pct.mc", ctx) == pytest.approx(100.0)
    # work of the two chunks: 480 lanes a run, 1,024 slots, 2 runs
    flops = bytes_ = 0
    for live in (2 * 8 * 60 * 256, 2 * 8 * 60 * 512):
        f, b = mc_ridge_cost.cost(480, 1024, 2, 8, 256, live)
        flops, bytes_ = flops + f, bytes_ + b
    least = max(flops / 197e12, bytes_ / 819e9)
    # the kernel ran 0.1 s on each of two chips; its consumer is not it
    assert _read("mc_ridge.roofline_pct", ctx) == pytest.approx(
        100.0 * least / 0.2)


def test_roofline_of_an_unknown_chip_is_an_error():
    ctx = _ctx([_span(0)], trace=_trace(kernel_ns=0.1e9), kind="TPU v9")
    with pytest.raises(KeyError):
        _read("mc_ridge.roofline_pct", ctx)


@pytest.mark.parametrize("name", NAMES)
def test_readers_find_nothing_without_counters_or_trace(name):
    # a program with the leaves and ``dispatches`` but no Monte-Carlo
    # counters, as the commit before them
    old = [SimpleNamespace(
        chunk_id=0, objective="montecarlo", bucket=8, solve_s=0.2,
        planner_device_wait_s=0.1, planner_dispatch_s=0.01,
        planner_fetch_s=0.004, planner_build_s=0.002,
        planner_refine_host_s=0.001, planner_records_s=0.003,
        dispatches=1)]
    bound_only = [_span(0, objective="corollary1")]
    for spans in (old, bound_only, []):
        got = _read(name, _ctx(spans))
        if name in LEAF_NAMES and spans is old:
            assert got is not None
        else:
            assert got is None, (name, spans)
