"""Observability subsystem: spans, histograms, metrics, event journal.

The serving stack's measurement layer, deliberately free of any
``repro.serve`` / ``repro.fleet`` imports so every layer (kernels,
planner, service, CLIs, benches) can flow through it without cycles:

  * :mod:`repro.obs.hist` — log-spaced MERGEABLE histograms (the
    bounded-memory latency representation a fleet of service instances
    can aggregate by addition);
  * :mod:`repro.obs.spans` — the per-request lifecycle trace (enqueue ->
    admit -> batch-wait -> bucket/pad -> cache lookup -> solve ->
    resolve) with each chunk's host leaves and counters, in a ring of
    numpy columns, decomposing the enqueue-to-plan latency EXACTLY into
    phases;
  * :mod:`repro.obs.metrics` — :class:`MetricsRegistry` unifying every
    counter source behind one snapshot, with Prometheus text exposition
    (:func:`render_prometheus`) and a strict parser
    (:func:`parse_exposition`) so exports are validated, not assumed;
  * :mod:`repro.obs.journal` — the JSONL event journal (audit log for
    drift / re-plan / session lifecycle events);
  * :mod:`repro.obs.runtime` — :class:`span`, the one timing helper of
    the host path (a per-thread chunk record plus a profiler
    annotation of the same name), the process-wide garbage-collection
    hook, and the optional ``jax.profiler`` capture hook.
"""
from repro.obs.hist import LogHistogram, percentiles
from repro.obs.journal import EventJournal, read_jsonl
from repro.obs.metrics import (Metric, MetricsRegistry, parse_exposition,
                               render_prometheus)
from repro.obs.runtime import gc_totals, profile_capture, span
from repro.obs.spans import (COUNTERS, LEAVES, PHASES, RequestSpan,
                             SpanRecorder)

__all__ = [
    "COUNTERS", "EventJournal", "LEAVES", "LogHistogram", "Metric",
    "MetricsRegistry", "PHASES", "RequestSpan", "SpanRecorder",
    "gc_totals", "parse_exposition", "percentiles", "profile_capture",
    "read_jsonl", "render_prometheus", "span",
]
