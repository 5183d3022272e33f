"""Metric-source adapters: serving state -> ``repro.obs`` metric families.

``repro.obs`` is deliberately standalone (no serve/fleet imports, so the
kernels can use it without cycles); this module is the glue in the other
direction — it knows the serving layer's snapshot shapes
(:class:`~repro.serve.stats.ServiceStats`, ``PlanCache.stats()``,
``SpanRecorder.totals()``, ``EventJournal.counts()``, the fleet trace
events) and renders each as :class:`~repro.obs.metrics.Metric` families
under a stable naming scheme:

================================================  =========  ==========
metric                                            kind       labels
================================================  =========  ==========
``repro_serve_<counter>_total``                   counter    —
``repro_serve_sessions_open``                     gauge      —
``repro_serve_queue_depth``                       gauge      —
``repro_serve_uptime_seconds``                    gauge      —
``repro_serve_plans_per_sec``                     gauge      —
``repro_serve_bucket_{requests,batches,           counter    objective,
compiles}_total``                                            grid_mode,
                                                             bucket
``repro_serve_latency_seconds``                   histogram  —
``repro_serve_bucket_latency_seconds``            histogram  objective,
                                                             grid_mode,
                                                             bucket
``repro_serve_cache_{hits,misses,evictions,       counter    —
invalidations}_total``
``repro_serve_cache_{hits,misses}                 counter    objective
_by_objective_total``
``repro_serve_cache_{entries,maxsize,hit_rate}``  gauge      —
``repro_serve_phase_seconds_total``               counter    phase
``repro_serve_solve_device_seconds_total``        counter    —
``repro_serve_spans_recorded_total``              counter    —
``repro_serve_solve_fraction``                    gauge      —
``repro_serve_events_total``                      counter    kind
``repro_fleet_kernel_traces_total``               counter    kind, shape
``repro_fleet_traces_total``                      counter    —
``repro_federated_rounds_total``                  counter    —
``repro_federated_participants_total``            counter    —
``repro_federated_infeasible_rounds_total``       counter    —
``repro_federated_round_latency_seconds``         histogram  —
``repro_federated_round_time_seconds``            histogram  —
``repro_process_gc_collections_total``            counter    generation
``repro_process_gc_pause_seconds_total``          counter    generation
================================================  =========  ==========

:func:`register_service_sources` wires a live
:class:`~repro.serve.service.PlanningService` into its registry;
:func:`write_textfile` dumps any registry for the node-exporter
textfile collector.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Dict, List, Tuple

from repro.fleet.tracing import trace_events
from repro.obs import (LEAVES, PHASES, EventJournal, LogHistogram, Metric,
                       MetricsRegistry, SpanRecorder, gc_totals)
from repro.serve.stats import ServiceStats

if TYPE_CHECKING:  # pragma: no cover - annotation-only import
    from repro.serve.service import PlanningService

#: ``ServiceStats.counters`` entries that are levels, not monotone
#: counts — exported as gauges without the ``_total`` suffix.
GAUGE_COUNTERS = ("sessions_open",)


def service_metrics(stats: ServiceStats) -> List[Metric]:
    """The :class:`ServiceStats` snapshot as metric families: every
    ``counters`` entry, the per-bucket counters, the cache counters and
    the latency histograms.  Phase/span families come from
    :func:`span_metrics` (the span recorder is the source of truth for
    those; the copies on ``stats`` exist for JSON reporting)."""
    out: List[Metric] = []
    for name in sorted(stats.counters):
        v = stats.counters[name]
        if name in GAUGE_COUNTERS:
            out.append(Metric(f"repro_serve_{name}", "gauge",
                              f"service level {name}").add(float(v)))
        else:
            out.append(Metric(f"repro_serve_{name}_total", "counter",
                              f"service counter {name}").add(float(v)))
    out.append(Metric("repro_serve_queue_depth", "gauge",
                      "requests waiting in the ingestion queue")
               .add(float(stats.queue_depth)))
    out.append(Metric("repro_serve_uptime_seconds", "gauge",
                      "seconds since the stats clock (re)started")
               .add(stats.uptime_s))
    out.append(Metric("repro_serve_plans_per_sec", "gauge",
                      "plans resolved per second since the clock restart")
               .add(stats.plans_per_sec))

    for field_name in ("requests", "batches", "compiles"):
        m = Metric(f"repro_serve_bucket_{field_name}_total", "counter",
                   f"per-(objective, grid_mode, bucket) {field_name}")
        for (oid, mode, bucket), slot in sorted(stats.buckets.items()):
            m.add(float(slot[field_name]), objective=oid, grid_mode=mode,
                  bucket=str(bucket))
        if m.samples:
            out.append(m)

    if stats.latency_hist:
        out.append(Metric("repro_serve_latency_seconds", "histogram",
                          "enqueue-to-plan latency")
                   .add(LogHistogram.from_dict(stats.latency_hist)))
    if stats.histograms:
        m = Metric("repro_serve_bucket_latency_seconds", "histogram",
                   "enqueue-to-plan latency per (objective, grid_mode, "
                   "bucket)")
        for key, hd in sorted(stats.histograms.items()):
            oid, mode, bucket = key.rsplit("/", 2)
            m.add(LogHistogram.from_dict(hd), objective=oid,
                  grid_mode=mode, bucket=bucket)
        out.append(m)

    out.extend(cache_metrics(stats.cache))
    return out


def cache_metrics(cache_stats: Dict[str, object]) -> List[Metric]:
    """``PlanCache.stats()`` (or ``ServiceStats.cache``) as families."""
    if not cache_stats:
        return []
    out: List[Metric] = []
    for name in ("hits", "misses", "evictions", "invalidations",
                 "corruptions"):
        if name in cache_stats:
            out.append(Metric(f"repro_serve_cache_{name}_total", "counter",
                              f"plan cache {name}")
                       .add(float(cache_stats[name])))  # type: ignore[arg-type]
    for name in ("hits", "misses"):
        per = cache_stats.get(f"{name}_by_objective") or {}
        if per:
            m = Metric(f"repro_serve_cache_{name}_by_objective_total",
                       "counter", f"plan cache {name} per objective")
            for oid, v in sorted(per.items()):  # type: ignore[union-attr]
                m.add(float(v), objective=str(oid))
            out.append(m)
    gauges = (("size", "entries", "live cache entries"),
              ("maxsize", "maxsize", "cache capacity"),
              ("hit_rate", "hit_rate", "lifetime cache hit rate"))
    for src, dst, help_text in gauges:
        if src in cache_stats:
            out.append(Metric(f"repro_serve_cache_{dst}", "gauge",
                              help_text)
                       .add(float(cache_stats[src])))  # type: ignore[arg-type]
    return out


def tracing_metrics(events: Dict[Tuple, int] = None) -> List[Metric]:
    """The fleet kernel trace counters (``None`` snapshots the live
    process-global events) — the audit trail behind the
    zero-traces-after-warmup SLO."""
    if events is None:
        events = trace_events()
    per_tag = Metric("repro_fleet_kernel_traces_total", "counter",
                     "jit traces per kernel (kind, shape)")
    total = 0
    for tag, n in sorted(events.items(), key=lambda kv: str(kv[0])):
        kind = str(tag[0]) if tag else "unknown"
        shape = ",".join(str(t) for t in tag[1:])
        per_tag.add(float(n), kind=kind, shape=shape)
        total += n
    out = [Metric("repro_fleet_traces_total", "counter",
                  "total jit traces across all fleet kernels")
           .add(float(total))]
    if per_tag.samples:
        out.append(per_tag)
    return out


def span_metrics(spans: SpanRecorder) -> List[Metric]:
    """Lifetime phase totals from the span recorder: the exact
    decomposition of cumulative enqueue-to-plan latency, and the
    worker's host leaves."""
    totals = spans.totals()
    phase = Metric("repro_serve_phase_seconds_total", "counter",
                   "lifecycle phases (batch_wait..resolve, admit: request "
                   "seconds summed over requests; admit is pre-enqueue, "
                   "outside the latency SLO) and host leaves (serve.*, "
                   "planner.*: worker seconds summed over chunks)")
    for name in sorted((*PHASES, "admit", *LEAVES)):
        phase.add(totals[name], phase=name)
    return [
        phase,
        Metric("repro_serve_solve_device_seconds_total", "counter",
               "host seconds waiting on the device after launch "
               "(planner.device_wait), summed over requests")
        .add(totals["solve_device"]),
        Metric("repro_serve_span_latency_seconds_total", "counter",
               "cumulative enqueue-to-plan latency over all spans")
        .add(totals["latency"]),
        Metric("repro_serve_spans_recorded_total", "counter",
               "request spans recorded (lifetime, ring may hold fewer)")
        .add(float(totals["count"])),
        Metric("repro_serve_solve_fraction", "gauge",
               "lifetime solve share of enqueue-to-plan latency")
        .add(spans.solve_fraction),
    ]


def gc_metrics() -> List[Metric]:
    """The process's garbage collections and pauses per generation,
    counted while a running service holds the collection hook."""
    totals = gc_totals()
    collections = Metric("repro_process_gc_collections_total", "counter",
                         "garbage collections per generation")
    pauses = Metric("repro_process_gc_pause_seconds_total", "counter",
                    "garbage-collection pause seconds per generation")
    for gen, (n, s) in enumerate(zip(totals["collections"],
                                     totals["pause_s"])):
        collections.add(float(n), generation=str(gen))
        pauses.add(s, generation=str(gen))
    return [collections, pauses]


def federated_metrics(recorder) -> List[Metric]:
    """The federated round path's counters and distributions (a
    :class:`~repro.serve.stats.FederatedRecorder` snapshot) as
    ``repro_federated_*`` families."""
    snap = recorder.snapshot()
    out = [
        Metric("repro_federated_rounds_total", "counter",
               "federated rounds planned").add(float(snap["rounds"])),
        Metric("repro_federated_participants_total", "counter",
               "participants selected across all rounds")
        .add(float(snap["participants"])),
        Metric("repro_federated_infeasible_rounds_total", "counter",
               "rounds with no deadline-feasible participant")
        .add(float(snap["infeasible_rounds"])),
    ]
    if snap["latency_hist"]:
        out.append(Metric("repro_federated_round_latency_seconds",
                          "histogram", "submit_round latency")
                   .add(LogHistogram.from_dict(snap["latency_hist"])))
    if snap["round_time_hist"]:
        out.append(Metric("repro_federated_round_time_seconds",
                          "histogram",
                          "planned straggler-bounded round time")
                   .add(LogHistogram.from_dict(snap["round_time_hist"])))
    return out


def resilience_metrics(service: "PlanningService") -> List[Metric]:
    """The resilience layer as ``repro_resilience_*`` families:

    ================================================  =========  ========
    metric                                            kind       labels
    ================================================  =========  ========
    ``repro_resilience_fallbacks_total``              counter    level
    ``repro_resilience_degrade_reasons_total``        counter    reason
    ``repro_resilience_retries_total``                counter    —
    ``repro_resilience_backoff_seconds_total``        counter    —
    ``repro_resilience_shed_total``                   counter    reason
    ``repro_resilience_budget_exceeded_total``        counter    —
    ``repro_resilience_exhausted_total``              counter    —
    ``repro_resilience_breaker_state``                gauge      objective,
                                                                 grid_mode
    ``repro_resilience_breaker_{trips,probes,         counter    objective,
    recoveries}_total``                                          grid_mode
    ``repro_resilience_faults_injected_total``        counter    point
    ``repro_resilience_health_state``                 gauge      —
    ``repro_resilience_health``                       gauge      state
    ================================================  =========  ========

    Breaker state gauges encode closed=0 / open=1 / half_open=2;
    ``health_state`` encodes STARTING=0 / READY=1 / DEGRADED=2 /
    SHEDDING=3 (plus the one-hot ``health{state=...}`` for dashboards
    that match on labels).  ``health()`` is evaluated at collect time,
    so a scrape always sees current readiness.
    """
    from repro.serve.resilience import (BREAKER_STATES, FALLBACK_LEVELS,
                                        HEALTH_STATES)

    snap = service.resilience.snapshot()
    health = service.health()
    out: List[Metric] = []

    # every ladder level is pre-declared at 0 (a dashboard's rate()
    # needs the zero sample BEFORE the first degrade, not after)
    m = Metric("repro_resilience_fallbacks_total", "counter",
               "degraded responses per fallback level")
    for level in FALLBACK_LEVELS[1:]:
        m.add(float(snap["fallbacks"].get(level, 0)), level=level)
    for level, n in sorted(snap["fallbacks"].items()):
        if level not in FALLBACK_LEVELS[1:]:
            m.add(float(n), level=str(level))
    out.append(m)

    m = Metric("repro_resilience_degrade_reasons_total", "counter",
               "ladder entries per degrade reason")
    for reason, n in sorted(snap["degrade_reasons"].items()):
        m.add(float(n), reason=str(reason))
    if m.samples:
        out.append(m)

    out.append(Metric("repro_resilience_retries_total", "counter",
                      "transient solve retries")
               .add(float(snap["retries"])))
    out.append(Metric("repro_resilience_backoff_seconds_total", "counter",
                      "cumulative retry backoff sleep")
               .add(float(snap["backoff_seconds"])))
    out.append(Metric("repro_resilience_budget_exceeded_total", "counter",
                      "requests degraded for deadline-budget pressure")
               .add(float(snap["budget_exceeded"])))
    out.append(Metric("repro_resilience_exhausted_total", "counter",
                      "requests that exhausted every ladder rung")
               .add(float(snap["exhausted"])))

    m = Metric("repro_resilience_shed_total", "counter",
               "requests shed at admission, per reason")
    for reason, n in sorted(snap["sheds"].items()):
        m.add(float(n), reason=str(reason))
    if m.samples:
        out.append(m)

    if snap["breakers"]:
        state_codes = {s: i for i, s in enumerate(BREAKER_STATES)}
        gauge = Metric("repro_resilience_breaker_state", "gauge",
                       "circuit breaker state "
                       "(0=closed, 1=open, 2=half_open)")
        per = {name: Metric(f"repro_resilience_breaker_{name}_total",
                            "counter", f"breaker {name}")
               for name in ("trips", "probes", "recoveries")}
        for (oid, mode), b in sorted(snap["breakers"].items()):
            labels = dict(objective=str(oid), grid_mode=str(mode))
            gauge.add(float(state_codes[b["state"]]), **labels)
            for name in ("trips", "probes", "recoveries"):
                per[name].add(float(b[name]), **labels)
        out.append(gauge)
        out.extend(per.values())

    m = Metric("repro_resilience_faults_injected_total", "counter",
               "chaos faults fired per injection point")
    enabled = tuple(service.faults.rules) if service.faults is not None \
        else ()
    for point in sorted(set(enabled) | set(snap["faults_injected"])):
        m.add(float(snap["faults_injected"].get(point, 0)),
              point=str(point))
    if m.samples:
        out.append(m)

    out.append(Metric("repro_resilience_health_state", "gauge",
                      "service readiness (0=STARTING, 1=READY, "
                      "2=DEGRADED, 3=SHEDDING)")
               .add(float(health.code)))
    one_hot = Metric("repro_resilience_health", "gauge",
                     "service readiness, one-hot by state label")
    for state in HEALTH_STATES:
        one_hot.add(1.0 if state == health.state else 0.0, state=state)
    out.append(one_hot)
    return out


def journal_metrics(journal: EventJournal) -> List[Metric]:
    """Lifetime per-kind event counts from the audit journal."""
    m = Metric("repro_serve_events_total", "counter",
               "journal events per kind")
    for kind, n in sorted(journal.counts().items()):
        m.add(float(n), kind=kind)
    out = [Metric("repro_serve_events_emitted_total", "counter",
                  "journal events emitted (lifetime)")
           .add(float(journal.emitted))]
    if m.samples:
        out.append(m)
    return out


def register_service_sources(registry: MetricsRegistry,
                             service: "PlanningService") -> None:
    """Wire a live service's four counter surfaces into its registry.
    Sources pull at collect time, so every export is a fresh snapshot."""
    registry.register_source(
        "service", lambda: service_metrics(service.stats()))
    registry.register_source("tracing", tracing_metrics)
    registry.register_source(
        "spans", lambda: span_metrics(service.spans))
    registry.register_source("gc", gc_metrics)
    registry.register_source(
        "events", lambda: journal_metrics(service.journal))
    registry.register_source(
        "federated", lambda: federated_metrics(service.federated))
    registry.register_source(
        "resilience", lambda: resilience_metrics(service))


def write_textfile(registry: MetricsRegistry, path: str) -> str:
    """Dump ``registry`` as a Prometheus textfile (atomic rename); the
    parsed-on-read contract lives in ``MetricsRegistry.snapshot``."""
    return registry.write_textfile(path)
