from pathlib import Path

import pytest

from harness import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def _synthetic():
    E = tr.Event
    ops = [E("fusion.1", 100, 50), E("fusion.2", 120, 60),   # overlap
           E("mc_ridge", 300, 100, "f32[8,3840]"), E("copy", 950, 100)]
    host = [E("bench.window", 0, 1000), E("PjitFunction(solve)", 180, 100)]
    return tr.Trace(planes={"/device:TPU:0": {"XLA Ops": ops},
                            "/device:TPU:1": {"XLA Ops": [E("x", 0, 500)]},
                            "/host:CPU": {"python": host}})


def test_busy_is_the_union_of_op_intervals_in_the_window():
    red = tr.reduce(_synthetic())
    assert red.window_s == pytest.approx(1e-6)
    # chip 0: [100, 180) + [300, 400) + [950, 1000) = 230 ns
    assert red.busy_s_by_device["/device:TPU:0"] == pytest.approx(230e-9)
    assert red.busy_s_by_device["/device:TPU:1"] == pytest.approx(500e-9)
    assert red.busy_s == pytest.approx(365e-9)


def test_gaps_are_named_by_the_host_events_over_them():
    red = tr.reduce(_synthetic())
    # chip 0's gaps: [0,100) [180,300) [400,950); longest first
    assert [round(s * 1e9) for _, s in red.idle_gaps] == [550, 120, 100]
    names = dict((round(s * 1e9), n) for n, s in red.idle_gaps)
    assert names[120] == "PjitFunction(solve)"
    assert names[550] == "untraced host work"


def test_op_seconds_and_kernel_events():
    red = tr.reduce(_synthetic())
    assert red.op_seconds["copy"] == pytest.approx(50e-9)  # clipped
    assert [e.name for e in red.events_named("mc_ridge")] == ["mc_ridge"]
    b = red.breakdown()
    assert b["device_ops"][0][0] == "x"
    assert len(b["idle_gaps"]) == 3


def test_merge_and_gaps():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    evs = [tr.Event("a", 2, 2), tr.Event("b", 6, 10)]
    assert tr.gaps(evs, 0, 10) == [(0, 2), (4, 6)]
    assert tr.busy_ns(evs, 0, 10) == 6


def test_recorded_cpu_trace():
    """A trace the profiler wrote on the CPU (``bench.window`` around a
    jitted matmul, a 20 ms sleep and an interpreted ``mc_ridge`` call):
    the reader finds the host planes and the window, and no chip."""
    trace = tr.read_xplane(DATA / "cpu_small.xplane.pb")
    assert "/host:CPU" in trace.planes
    lo, hi = tr.window_of(trace, "bench.window")
    assert 0.02 < (hi - lo) / 1e9 < 1.0
    red = tr.reduce(trace)
    assert red.window_s == pytest.approx((hi - lo) / 1e9)
    assert red.busy_s_by_device == {} and red.busy_s == 0.0
    assert red.breakdown() == {"device_ops": [], "idle_gaps": []}


def test_recorded_tpu_trace():
    """A trace the profiler wrote on one TPU v5e
    (``bench/tools/record_test_trace.py``): inside ``bench.window``, a
    jitted matmul, a 20 ms sleep and a jitted float64 reduction.  The
    matmul's ops sit just before the window on the common clock (the
    device's events are about 1.1 ms early), so busy is the reduction's."""
    trace = tr.read_xplane(DATA / "tpu_small.xplane.pb")
    assert "/device:TPU:0" in trace.device_ops()
    red = tr.reduce(trace)
    assert 0.02 < red.window_s < 0.03
    busy = red.busy_s_by_device["/device:TPU:0"]
    assert 0.0 < busy < 0.1 * red.window_s
    assert red.busy_s == busy
    # the sleep is the longest gap, and no host event covers it
    name, seconds = red.idle_gaps[0]
    assert name == "untraced host work" and 0.019 < seconds < 0.023
    b = red.breakdown()
    assert b["device_ops"] and all(" = " not in n for n, _ in b["device_ops"])
    assert b["device_ops"][0][0] == "reduce-window.1"
    assert sum(s for _, s in b["device_ops"]) <= busy + 1e-9


def test_lines_that_share_a_name_are_joined():
    """On a TPU's host two threads are both named ``python3``; the one
    that holds ``bench.window`` must not be lost to the other."""
    from types import SimpleNamespace as NS

    def ev(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur, stats=[])

    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[ev("bench.window", 0, 1000)]),
        NS(name="python3", events=[ev("DevicePut", 10, 5)])])
    chip = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("%fusion.1 = f32[] fusion()", 100, 50)])])
    trace = tr.from_planes([host, chip])
    assert [e.name for e in trace.planes["/host:CPU"]["python3"]] == [
        "bench.window", "DevicePut"]
    red = tr.reduce(trace)
    assert red.window_s == pytest.approx(1e-6)
    assert red.op_seconds == {"fusion.1": pytest.approx(50e-9)}


def test_a_trace_without_the_window_gives_no_reading():
    trace = _synthetic()
    del trace.planes["/host:CPU"]
    with pytest.raises(tr.TraceError):
        tr.reduce(trace)
