"""Observability layer: log-spaced mergeable histograms, request spans,
the event journal, Prometheus render/parse round-trips, trace delta
brackets, and the StatsRecorder throughput-baseline fix."""
import json
import math
import threading

import numpy as np
import pytest

from repro.fleet.tracing import record_trace, trace_delta
from repro.obs import (EventJournal, LogHistogram, Metric, MetricsRegistry,
                       RequestSpan, SpanRecorder, parse_exposition,
                       percentiles, read_jsonl, render_prometheus)
from repro.serve.stats import StatsRecorder


# ---------------------------------------------------------------------------
# LogHistogram
# ---------------------------------------------------------------------------

def test_histogram_percentile_accuracy_vs_exact():
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=-5.0, sigma=1.5, size=4000)
    h = LogHistogram(lo=1e-6, hi=1e3, per_decade=100)
    for s in samples:
        h.record(float(s))
    # bucket-interpolated percentiles within one bucket width (10^(1/100)
    # ~ 2.3%) of the exact sample percentiles
    width = 10.0 ** (1.0 / 100)
    for q in (10.0, 50.0, 90.0, 99.0):
        exact = float(np.percentile(samples, q))
        approx = h.percentile(q)
        assert exact / width <= approx <= exact * width, (q, exact, approx)
    assert h.percentile(100.0) == pytest.approx(float(samples.max()))
    assert h.count == 4000
    assert h.sum == pytest.approx(float(samples.sum()))


def test_histogram_empty_and_input_validation():
    h = LogHistogram()
    assert h.percentile(50.0) == 0.0
    with pytest.raises(ValueError):
        h.record(-1.0)
    with pytest.raises(ValueError):
        h.record(float("nan"))
    with pytest.raises(ValueError):
        h.percentile(101.0)
    with pytest.raises(ValueError):
        LogHistogram(lo=1.0, hi=0.5)


def test_histogram_under_and_overflow_buckets():
    h = LogHistogram(lo=1e-3, hi=1e0, per_decade=5)
    h.record(1e-6)          # underflow
    h.record(50.0)          # overflow
    assert h.counts[0] == 1 and h.counts[-1] == 1
    assert h.percentile(0.0) <= h.lo
    assert h.percentile(100.0) == 50.0
    cum = h.cumulative()
    assert math.isinf(cum[-1][0]) and cum[-1][1] == h.count == 2
    counts = [n for _, n in cum]
    assert counts == sorted(counts)  # cumulative is monotone


def test_histogram_merge_is_associative_and_matches_union():
    rng = np.random.default_rng(11)
    chunks = [rng.lognormal(-4.0, 1.0, size=200) for _ in range(3)]
    hists = []
    for chunk in chunks:
        h = LogHistogram(per_decade=20)
        for s in chunk:
            h.record(float(s))
        hists.append(h)
    a, b, c = hists
    left = a.copy().merge(b).merge(c)
    right = a.copy().merge(b.copy().merge(c))
    assert left.counts == right.counts
    assert left.count == right.count == 600
    assert left.sum == pytest.approx(right.sum)
    assert left.max == right.max
    # merge result is identical to recording the union into one histogram
    union = LogHistogram(per_decade=20)
    for s in np.concatenate(chunks):
        union.record(float(s))
    assert union.counts == left.counts
    assert LogHistogram.merged(hists).counts == left.counts
    assert LogHistogram.merged([]).count == 0


def test_histogram_merge_rejects_layout_mismatch():
    with pytest.raises(ValueError, match="different layouts"):
        LogHistogram(per_decade=10).merge(LogHistogram(per_decade=20))
    with pytest.raises(ValueError, match="different layouts"):
        LogHistogram(lo=1e-6).merge(LogHistogram(lo=1e-5))


def test_histogram_dict_round_trip():
    h = LogHistogram(lo=1e-5, hi=1e2, per_decade=30)
    for s in (1e-6, 3e-4, 0.02, 0.02, 7.0, 500.0):
        h.record(s)
    d = json.loads(json.dumps(h.to_dict()))   # must be JSON-serialisable
    back = LogHistogram.from_dict(d)
    assert back.counts == h.counts
    assert back.count == h.count
    assert back.sum == pytest.approx(h.sum)
    assert back.max == h.max
    assert back.percentile(99.0) == h.percentile(99.0)


# ---------------------------------------------------------------------------
# percentiles
# ---------------------------------------------------------------------------

def test_reservoir_and_percentiles_edge_cases():
    assert percentiles([]) == (0.0, 0.0)
    assert percentiles([2.0], qs=(50.0,)) == (2.0,)


# ---------------------------------------------------------------------------
# SpanRecorder
# ---------------------------------------------------------------------------

def _span(i=0, batch_wait=0.004, solve=0.002, device=0.0015):
    return RequestSpan(objective="corollary1", grid_mode="dense", bucket=8,
                       enqueue_t=float(i), admit_s=1e-5,
                       batch_wait_s=batch_wait, pad_s=0.001,
                       cache_lookup_s=0.0005, solve_s=solve,
                       solve_device_s=device, resolve_s=0.0005,
                       latency_s=batch_wait + 0.001 + 0.0005 + solve + 0.0005)


def test_span_phases_partition_latency():
    s = _span()
    assert s.phase_sum == pytest.approx(s.latency_s)
    assert set(s.phases()) == {"batch_wait", "pad", "cache_lookup",
                               "solve", "resolve"}
    assert sum(s.phases().values()) == pytest.approx(s.latency_s)


def test_span_recorder_ring_evicts_but_totals_survive():
    rec = SpanRecorder(capacity=4)
    for i in range(10):
        rec.record(_span(i))
    assert len(rec) == 4
    assert rec.recorded == 10
    window = rec.snapshot()
    assert [s.enqueue_t for s in window] == [6.0, 7.0, 8.0, 9.0]
    totals = rec.totals()
    assert totals["count"] == 10                       # lifetime, not window
    assert totals["solve"] == pytest.approx(10 * 0.002)
    assert totals["solve_device"] == pytest.approx(10 * 0.0015)
    assert totals["latency"] == pytest.approx(10 * _span().latency_s)
    assert rec.solve_fraction == pytest.approx(
        totals["solve"] / totals["latency"])
    means = rec.phase_means_ms()
    assert means["solve"] == pytest.approx(2.0)        # 0.002 s -> 2 ms
    assert means["latency"] == pytest.approx(_span().latency_s * 1e3)


def test_span_recorder_empty_and_validation():
    rec = SpanRecorder(capacity=8)
    assert rec.solve_fraction == 0.0
    assert rec.phase_means_ms()["latency"] == 0.0
    assert rec.snapshot() == []
    with pytest.raises(ValueError):
        SpanRecorder(capacity=0)


# ---------------------------------------------------------------------------
# EventJournal + JSONL
# ---------------------------------------------------------------------------

def test_event_journal_ring_counts_and_file_sink(tmp_path):
    path = tmp_path / "events.jsonl"
    with EventJournal(capacity=3, path=str(path)) as journal:
        for i in range(5):
            journal.emit("drift_detected", session="dev-0", ewma=0.1 * i)
        journal.emit("warmup", traces=4)
    assert journal.emitted == 6
    assert journal.counts() == {"drift_detected": 5, "warmup": 1}
    tail = journal.tail(2)
    assert [e["kind"] for e in tail] == ["drift_detected", "warmup"]
    assert tail[-1]["traces"] == 4
    # the file keeps EVERY event (the ring only bounds memory), stamped
    # with a wall-clock ts
    events = read_jsonl(str(path))
    assert len(events) == 6
    assert all(e["ts"] > 0 for e in events)
    assert events[0]["ewma"] == 0.0
    # close() detached the sink; in-memory emission still works
    journal.emit("session_close", session="dev-0")
    assert journal.emitted == 7
    assert len(read_jsonl(str(path))) == 6


def test_read_jsonl_is_strict(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"kind": "ok"}\nnot json\n')
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        read_jsonl(str(path))


def test_event_journal_serialises_non_json_fields(tmp_path):
    path = tmp_path / "e.jsonl"
    journal = EventJournal(path=str(path))
    journal.emit("session_open", key=("corollary1", "dense", 8))
    journal.close()
    (event,) = read_jsonl(str(path))
    assert event["kind"] == "session_open"   # default=str made it through


def test_event_journal_rotates_by_size_and_reads_back_in_order(tmp_path):
    import os
    path = tmp_path / "rot.jsonl"
    # each event line is ~60 bytes: 2-3 events per rotated file
    with EventJournal(path=str(path), max_bytes=150, keep=2) as journal:
        for i in range(20):
            journal.emit("tick", i=i)
    assert journal.rotations > 1
    files = sorted(p.name for p in tmp_path.iterdir())
    assert files == ["rot.jsonl", "rot.jsonl.1", "rot.jsonl.2"]
    # keep=2 bounded the disk: older rotations were DROPPED...
    events = read_jsonl(str(path))
    assert len(events) < 20
    # ... and the survivors read back as one contiguous, ordered stream
    idx = [e["i"] for e in events]
    assert idx == list(range(idx[0], 20))
    assert os.path.getsize(path) < 150 + 80   # live file stays bounded


def test_event_journal_fsync_and_validation(tmp_path):
    path = tmp_path / "durable.jsonl"
    with EventJournal(path=str(path), fsync=True) as journal:
        journal.emit("decision", what="replan")
        # durable before emit returns: visible without close()/flush()
        assert read_jsonl(str(path)) == journal.tail(1)
    with pytest.raises(ValueError, match="max_bytes"):
        EventJournal(max_bytes=-1)
    with pytest.raises(ValueError, match="keep"):
        EventJournal(keep=0)


# ---------------------------------------------------------------------------
# Prometheus render / parse
# ---------------------------------------------------------------------------

def _families():
    hist = LogHistogram(lo=1e-3, hi=1e0, per_decade=3)
    for s in (0.002, 0.02, 0.02, 0.4, 9.0):
        hist.record(s)
    return [
        Metric("test_requests_total", "counter", "requests served")
        .add(12, objective="corollary1", grid_mode="dense")
        .add(30, objective="markov_arq", grid_mode="refine"),
        Metric("test_queue_depth", "gauge").add(3.5),
        Metric("test_latency_seconds", "histogram", "e2e latency").add(hist),
        Metric("test_weird_label_total", "counter")
        .add(1, note='quote " backslash \\ newline \n done'),
    ]


def test_prometheus_round_trip_preserves_every_sample():
    text = render_prometheus(_families())
    snap = parse_exposition(text)
    key = (("grid_mode", "dense"), ("objective", "corollary1"))
    assert snap["test_requests_total"][key] == 12
    assert snap["test_queue_depth"][()] == 3.5
    assert snap["test_latency_seconds_count"][()] == 5
    assert snap["test_latency_seconds_sum"][()] == pytest.approx(9.442)
    assert snap["test_latency_seconds_bucket"][(("le", "+Inf"),)] == 5
    # label escaping survives the round trip
    (labels,) = snap["test_weird_label_total"]
    assert dict(labels)["note"] == 'quote " backslash \\ newline \n done'
    # rendering is deterministic (textfile dumps must diff cleanly)
    assert text == render_prometheus(_families())


def test_parse_exposition_rejects_malformed_input():
    with pytest.raises(ValueError, match="malformed sample"):
        parse_exposition("no value here\n")
    with pytest.raises(ValueError, match="bad sample value"):
        parse_exposition("ok_metric twelve\n")
    with pytest.raises(ValueError, match="unknown metric type"):
        parse_exposition("# TYPE m summary\nm 1\n")
    with pytest.raises(ValueError, match="malformed labels"):
        parse_exposition('m{a="1", b=} 1\n')
    with pytest.raises(ValueError, match="no _bucket"):
        parse_exposition("# TYPE h histogram\nh_sum 1\nh_count 1\n")
    with pytest.raises(ValueError, match="missing _sum"):
        parse_exposition('# TYPE h histogram\nh_bucket{le="+Inf"} 1\n')
    with pytest.raises(ValueError, match="non-monotone"):
        parse_exposition('# TYPE h histogram\n'
                         'h_bucket{le="0.1"} 5\nh_bucket{le="+Inf"} 3\n'
                         'h_sum 1\nh_count 3\n')
    with pytest.raises(ValueError, match=r"lacks a \+Inf"):
        parse_exposition('# TYPE h histogram\nh_bucket{le="0.1"} 1\n'
                         'h_sum 1\nh_count 1\n')


def test_prometheus_client_cross_check():
    """When prometheus_client happens to be installed, its parser must
    agree with ours on our own output (we are not inventing a dialect)."""
    prom = pytest.importorskip("prometheus_client")
    from prometheus_client.parser import text_string_to_metric_families
    text = render_prometheus(_families())
    theirs = {}
    for fam in text_string_to_metric_families(text):
        for sample in fam.samples:
            labels = tuple(sorted(sample.labels.items()))
            theirs[(sample.name, labels)] = sample.value
    ours = parse_exposition(text)
    for name, series in ours.items():
        for labels, value in series.items():
            assert theirs[(name, labels)] == pytest.approx(value), name
    del prom


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_registry_merges_sources_and_snapshots():
    reg = MetricsRegistry()
    reg.register_source("a", lambda: [
        Metric("test_reg_total", "counter").add(2, src="a")])
    reg.register_source("b", lambda: [
        Metric("test_reg_total", "counter").add(3, src="b"),
        Metric("test_reg_gauge", "gauge").add(1.25)])
    assert reg.sources() == ["a", "b"]
    snap = reg.snapshot()
    assert snap["test_reg_total"][(("src", "a"),)] == 2
    assert snap["test_reg_total"][(("src", "b"),)] == 3
    assert reg.value("test_reg_total", src="b") == 3
    assert reg.value("test_reg_gauge") == 1.25
    assert reg.value("test_reg_missing", default=-1.0) == -1.0
    reg.unregister_source("a")
    assert (("src", "a"),) not in reg.snapshot().get("test_reg_total", {})
    with pytest.raises(KeyError):
        reg.unregister_source("a")
    with pytest.raises(ValueError, match="already registered"):
        reg.register_source("b", list)


def test_registry_rejects_kind_conflicts():
    reg = MetricsRegistry()
    reg.register_source("a", lambda: [Metric("test_x", "counter").add(1)])
    reg.register_source("b", lambda: [Metric("test_x", "gauge").add(2)])
    with pytest.raises(ValueError, match="both"):
        reg.collect()


def test_registry_write_textfile_is_parseable(tmp_path):
    reg = MetricsRegistry()
    reg.register_source("s", lambda: [
        Metric("test_file_total", "counter").add(7)])
    path = tmp_path / "metrics.prom"
    text = reg.write_textfile(str(path))
    assert path.read_text() == text
    assert parse_exposition(path.read_text())["test_file_total"][()] == 7
    assert not list(tmp_path.glob("*.tmp.*"))   # atomic rename cleaned up


# ---------------------------------------------------------------------------
# trace_delta brackets
# ---------------------------------------------------------------------------

def test_trace_delta_counts_only_inner_traces():
    record_trace(("test_obs_outer", 1))
    with trace_delta() as d:
        record_trace(("test_obs_inner", 8))
        record_trace(("test_obs_inner", 8))
        record_trace(("test_obs_other", 16))
    assert d.total == 3
    assert bool(d) is True
    assert d.by_tag == {("test_obs_inner", 8): 2, ("test_obs_other", 16): 1}
    with trace_delta() as empty:
        pass
    assert empty.total == 0 and not empty.by_tag and bool(empty) is False


# ---------------------------------------------------------------------------
# StatsRecorder: histogram percentiles, restart baseline, thread-safety
# ---------------------------------------------------------------------------

def test_stats_recorder_restart_clock_resets_throughput_baseline():
    rec = StatsRecorder()
    for _ in range(5):
        rec.count("planned")
        rec.record_latency(0.01)
    assert rec.snapshot().plans_per_sec > 0.0
    # the satellite fix: restarting the clock must also re-baseline the
    # planned counter, else 5 pre-restart plans divided by a microsecond
    # of post-restart uptime reports absurd throughput
    rec.restart_clock()
    snap = rec.snapshot()
    assert snap.plans_per_sec == 0.0
    assert snap.n_planned == 5            # lifetime counter is untouched
    rec.count("planned", 3)
    assert rec.snapshot().plans_per_sec > 0.0


def test_stats_recorder_per_key_histograms_roll_up():
    rec = StatsRecorder()
    k1, k2 = ("corollary1", "dense", 8), ("markov_arq", "refine", 16)
    for i in range(10):
        rec.record_latency(0.001 * (i + 1), key=k1 if i % 2 else k2)
    hists = rec.latency_histograms()
    assert set(hists) == {None, k1, k2}
    merged = hists[k1].copy().merge(hists[k2])
    assert merged.counts == hists[None].counts   # per-key sums to global
    snap = rec.snapshot()
    assert set(snap.histograms) == {"corollary1/dense/8",
                                    "markov_arq/refine/16"}
    back = LogHistogram.from_dict(snap.latency_hist)
    assert back.count == 10
    assert snap.latency_p99_ms >= snap.latency_p50_ms > 0.0
    assert snap.latency_max_ms == pytest.approx(10.0)


def test_stats_recorder_concurrent_record_and_snapshot():
    rec = StatsRecorder()
    stop = threading.Event()
    errors = []

    def writer(tid):
        try:
            for i in range(2000):
                rec.record_latency(1e-4 * (i % 50 + 1),
                                   key=("corollary1", "dense", 4))
                rec.count("planned")
        except Exception as e:            # pragma: no cover
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                snap = rec.snapshot()
                assert snap.latency_p99_ms >= 0.0
                rec.latency_histograms()
        except Exception as e:            # pragma: no cover
            errors.append(e)

    writers = [threading.Thread(target=writer, args=(t,)) for t in range(4)]
    r = threading.Thread(target=reader)
    r.start()
    for w in writers:
        w.start()
    for w in writers:
        w.join()
    stop.set()
    r.join()
    assert not errors
    snap = rec.snapshot()
    assert snap.n_planned == 8000
    hist = LogHistogram.from_dict(snap.latency_hist)
    assert hist.count == 8000             # no lost updates


def test_profile_capture_raises_when_the_profiler_fails(tmp_path,
                                                        monkeypatch):
    """A trace that was asked for and not written must not pass silently;
    no directory means no profiler at all."""
    import jax
    from repro.obs.runtime import profile_capture

    def refuse(_dir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with profile_capture(None):
        pass
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with profile_capture(str(tmp_path)):
            pass
