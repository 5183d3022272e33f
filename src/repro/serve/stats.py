"""Serving statistics: latency histograms, throughput, counters.

The service's counter sink.  :class:`StatsRecorder` is the mutable,
lock-protected accumulator the worker threads write into;
:meth:`StatsRecorder.snapshot` freezes it into a :class:`ServiceStats`
for reporting.  Latencies are ENQUEUE-TO-PLAN: the clock starts when a
request enters the ingestion queue and stops when its plan record is
resolved, so queueing delay, micro-batch formation wait, cache lookup
and the jitted solve are all inside the measured number — the figure an
SLO is actually stated against, not the solve time alone.

Latency distributions live in log-spaced MERGEABLE histograms
(:class:`repro.obs.hist.LogHistogram`) rather than a raw-sample
reservoir: one global histogram plus one per ``(objective, grid_mode,
bucket)`` key, so the per-key distributions roll up into the global one
by addition and the Prometheus export can ship both.  Percentiles are
bucket-interpolated (relative error bounded by the bucket width, ~2.3%
at the 100/decade resolution used here); ``latency_max_ms`` stays exact.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from repro.obs.hist import LogHistogram

BucketKey = Tuple[str, str, int]

#: Histogram layout for enqueue-to-plan latencies: 10 µs .. 100 s at 100
#: buckets/decade — ±2.3% relative percentile error, 702 counters.
_LAT_LO, _LAT_HI, _LAT_PER_DECADE = 1e-5, 1e2, 100


def _new_hist() -> LogHistogram:
    return LogHistogram(_LAT_LO, _LAT_HI, _LAT_PER_DECADE)


@dataclass(frozen=True)
class ServiceStats:
    """Immutable snapshot of a running (or drained) planning service."""

    n_requests: int            # accepted into the queue
    n_planned: int             # futures resolved with a plan
    n_batches: int             # micro-batches flushed
    queue_depth: int           # requests waiting at snapshot time
    uptime_s: float            # since the recorder (re)started its clock
    plans_per_sec: float       # plans resolved since the clock (re)start
    latency_p50_ms: float      # enqueue-to-plan percentiles
    latency_p99_ms: float
    latency_max_ms: float
    #: per-(objective_id, grid_mode, bucket) request/batch/compile counts
    buckets: Dict[BucketKey, Dict[str, int]] = field(default_factory=dict)
    counters: Dict[str, int] = field(default_factory=dict)
    cache: Dict[str, object] = field(default_factory=dict)
    #: lifetime per-phase durations (seconds) from the span recorder:
    #: batch_wait / pad / cache_lookup / solve / resolve (+ admit,
    #: solve_device, latency, count); empty when spans are off
    phases: Dict[str, float] = field(default_factory=dict)
    #: lifetime solve share of enqueue-to-plan latency (0.0 with no spans)
    solve_fraction: float = 0.0
    #: serialised global latency histogram (LogHistogram.to_dict())
    latency_hist: Dict[str, object] = field(default_factory=dict)
    #: serialised per-(objective, grid_mode, bucket) latency histograms,
    #: keyed "objective/grid_mode/bucket" (JSON-friendly)
    histograms: Dict[str, Dict[str, object]] = field(default_factory=dict)
    #: resilience snapshot (ResilienceManager.snapshot()): fallback
    #: counts by level, retry/backoff totals, breaker states, sheds,
    #: injected-fault counts, health; empty for recorders outside a
    #: service
    resilience: Dict[str, object] = field(default_factory=dict)


class StatsRecorder:
    """Thread-safe accumulator behind :class:`ServiceStats`.

    Keeps one global latency histogram plus one per ``(objective,
    grid_mode, bucket)`` key — bounded memory however long the service
    runs, and the per-key histograms merge into the global by addition
    (asserted by the histogram property tests).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._hist_all = _new_hist()
        self._hist_by_key: Dict[BucketKey, LogHistogram] = {}
        self._counters: Dict[str, int] = {}
        self._buckets: Dict[BucketKey, Dict[str, int]] = {}
        self._t0 = time.perf_counter()
        # counter values at the last clock restart: throughput reports
        # work done SINCE the restart, not lifetime work over a short
        # post-restart window
        self._baseline: Dict[str, int] = {}

    def restart_clock(self) -> None:
        """Reset the throughput clock (called after warmup so reported
        plans/sec describes steady-state serving, not compilation).
        Snapshots the counters as the new baseline: plans_per_sec divides
        post-restart plans by post-restart uptime — previously the
        counter kept its pre-restart value against the fresh clock,
        inflating throughput right after warmup."""
        with self._lock:
            self._t0 = time.perf_counter()
            self._baseline = dict(self._counters)

    def count(self, name: str, k: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + k

    def record_latency(self, seconds: float,
                       key: Optional[BucketKey] = None) -> None:
        """Record one enqueue-to-plan latency into the global histogram
        and, when ``key`` is given, the per-(objective, grid_mode,
        bucket) histogram."""
        with self._lock:
            self._hist_all.record(seconds)
            if key is not None:
                h = self._hist_by_key.get(key)
                if h is None:
                    h = self._hist_by_key[key] = _new_hist()
                h.record(seconds)

    def record_bucket(self, objective_id: str, grid_mode: str, bucket: int,
                      *, requests: int = 0, batches: int = 0,
                      compiles: int = 0) -> None:
        """Accumulate per-(objective, mode, bucket) serving counters."""
        key = (objective_id, grid_mode, int(bucket))
        with self._lock:
            slot = self._buckets.setdefault(
                key, {"requests": 0, "batches": 0, "compiles": 0})
            slot["requests"] += requests
            slot["batches"] += batches
            slot["compiles"] += compiles

    def latency_histograms(self) -> Dict[Optional[BucketKey], LogHistogram]:
        """Copies of the live histograms: ``None`` maps to the global,
        tuple keys to the per-(objective, grid_mode, bucket) ones."""
        with self._lock:
            out: Dict[Optional[BucketKey], LogHistogram] = {
                None: self._hist_all.copy()}
            for k, h in self._hist_by_key.items():
                out[k] = h.copy()
            return out

    def snapshot(self, *, queue_depth: int = 0,
                 cache_stats=None) -> ServiceStats:
        with self._lock:
            uptime = max(time.perf_counter() - self._t0, 1e-9)
            p50 = self._hist_all.percentile(50.0)
            p99 = self._hist_all.percentile(99.0)
            lat_max = self._hist_all.max
            counters = dict(self._counters)
            baseline = dict(self._baseline)
            buckets = {k: dict(v) for k, v in self._buckets.items()}
            lat_hist = self._hist_all.to_dict()
            hists = {"/".join(map(str, k)): h.to_dict()
                     for k, h in self._hist_by_key.items()}
        n_planned = counters.get("planned", 0)
        since_restart = n_planned - baseline.get("planned", 0)
        return ServiceStats(
            n_requests=counters.get("requests", 0),
            n_planned=n_planned,
            n_batches=counters.get("batches", 0),
            queue_depth=queue_depth, uptime_s=uptime,
            plans_per_sec=since_restart / uptime,
            latency_p50_ms=p50 * 1e3, latency_p99_ms=p99 * 1e3,
            latency_max_ms=lat_max * 1e3,
            buckets=buckets, counters=counters,
            cache=dict(cache_stats) if cache_stats else {},
            latency_hist=lat_hist, histograms=hists)


class FederatedRecorder:
    """Thread-safe accumulator for the federated round path.

    Rounds are synchronous population-level requests (one
    ``submit_round`` call = one round), so they get their own counters
    and histograms instead of riding the per-scenario request stats:
    lifetime round / participant / infeasible-round counts, the
    submit-to-record planning latency, and the PLANNED straggler-bounded
    round time — both as mergeable log histograms so the Prometheus
    export ships full distributions (``repro_federated_*`` families, see
    :mod:`repro.serve.export`).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.rounds = 0
        self.participants = 0
        self.infeasible_rounds = 0
        self._latency = _new_hist()
        self._round_time = LogHistogram(1e-2, 1e9, 10)

    def observe(self, record, latency_s: float) -> None:
        """Account one planned round (a :class:`~repro.federated.round.
        RoundRecord`) and its submit-to-record latency."""
        with self._lock:
            self.rounds += 1
            self.participants += int(record.n_participants)
            if not record.feasible:
                self.infeasible_rounds += 1
            else:
                self._round_time.record(float(record.round_time))
            self._latency.record(float(latency_s))

    def snapshot(self) -> Dict[str, object]:
        """Consistent copy of every counter + serialised histograms."""
        with self._lock:
            return {
                "rounds": self.rounds,
                "participants": self.participants,
                "infeasible_rounds": self.infeasible_rounds,
                "latency_hist": self._latency.to_dict(),
                "round_time_hist": self._round_time.to_dict(),
            }
