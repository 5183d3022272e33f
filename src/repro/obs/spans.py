"""Per-request lifecycle spans: the enqueue-to-plan latency, decomposed.

A service that reports one opaque enqueue-to-plan number cannot be
steered: 318 ms might be queue backlog (add workers), flush-deadline
wait (shrink the interval), padding waste (re-bucket), or a slow solve
(optimise the kernel) — four different fixes.  :class:`RequestSpan`
attaches the decomposition to every request:

    enqueue --(batch_wait)--> chunk start --(pad)--> plan_many
            --(cache_lookup)--> --(solve)--> --(resolve)-->
            future resolved

The phases are CONTIGUOUS intervals cut from the same monotonic clock,
so ``batch_wait + pad + cache_lookup + solve + resolve == latency``
exactly (``resolve`` is defined as the remainder after the measured
sub-intervals, absorbing per-chunk bookkeeping; the serving tests assert
the sum).  ``admit_s`` — admission-policy routing BEFORE the request
enters the queue — is recorded but sits outside the enqueue-to-plan
window, matching how the SLO is stated.

Every request also names its chunk (``chunk_id``) and the batcher flush
that formed it (``flush_id``), and carries its chunk's record of host
leaves (:data:`LEAVES`, timed by :class:`repro.obs.runtime.span`: the
leaves the worker closed since it wrote the previous chunk), the
chunk's counters (:data:`COUNTERS`) and ``gc_s``, the process's
garbage-collection pauses over the same stretch.  ``solve_device_s`` is
the chunk's ``planner.device_wait`` leaf: the time the host waited on
the device after launch, never more than ``solve_s``.

:class:`SpanRecorder` keeps spans in a fixed-capacity ring of
preallocated numpy columns — one vectorised row-set per chunk, no
Python object per request (a window of a hundred thousand live span
objects made full collections stall the service) — and builds
:class:`RequestSpan` objects only when :meth:`SpanRecorder.snapshot`
asks.  Running TOTALS survive ring eviction: they are what the
solve-fraction SLO and the Prometheus export read, so they cover the
whole lifetime, not the window.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, fields
from typing import Dict, List, Mapping, Optional

import numpy as np

#: Phase names, in lifecycle order.  Their durations partition the
#: enqueue-to-plan latency exactly.
PHASES = ("batch_wait", "pad", "cache_lookup", "solve", "resolve")

#: Leaf spans of the worker's host path, in timeline order: waiting for
#: a flush, taking and grouping it, chunk formation, the planner's cache
#: probe, batch and kernel-array building, the jitted call until it
#: returns (argument conversion, host-to-device copies, launch), the
#: wait on the device, the copies back, host work between the refine
#: passes, plan records and cache puts, future resolution (with the
#: callbacks clients attach) and the chunk's bookkeeping.
LEAVES = ("serve.wait", "serve.take", "serve.pad", "planner.cache_lookup",
          "planner.build", "planner.dispatch", "planner.device_wait",
          "planner.fetch", "planner.refine_host", "planner.records",
          "serve.resolve", "serve.record")

#: Counters of a chunk's record: jitted calls, arrays passed in and
#: arrays converted back, live (unpadded) lanes and unique lanes
#: (cache misses after in-batch dedup), and of its Monte-Carlo passes:
#: simulated lane-slots dispatched (every run's lanes times the padded
#: timeline), the lane-slots before each lane's deadline, the lane-slots
#: the ``mc_ridge`` kernel stepped through (each lane to its block's
#: longest deadline), and the passes that ran across every local device.
COUNTERS = ("dispatches", "h2d_arrays", "d2h_arrays", "lanes_live",
            "lanes_unique", "mc_lane_slots", "mc_live_slots",
            "mc_run_slots", "mc_sharded_dispatches")


def leaf_field(name: str) -> str:
    """The :class:`RequestSpan` field of leaf ``name``
    (``planner.fetch`` -> ``planner_fetch_s``)."""
    return name.replace(".", "_") + "_s"


@dataclass(frozen=True)
class RequestSpan:
    """One completed request trace.  Durations are seconds; chunk-level
    phases (pad/cache/solve/resolve), identifiers, leaves and counters
    are shared by every request solved in the same micro-batch chunk,
    ``batch_wait`` is per-request."""

    objective: str
    grid_mode: str
    bucket: int
    enqueue_t: float        # perf_counter at enqueue (clock origin)
    admit_s: float          # pre-enqueue admission routing (outside SLO)
    batch_wait_s: float     # enqueue -> chunk taken by the worker
    pad_s: float            # chunk formation + bucket selection
    cache_lookup_s: float   # quantised-key cache probe inside plan_many
    solve_s: float          # plan_batch wall clock (host view)
    solve_device_s: float   # host wait on the device after launch
    resolve_s: float        # record fan-out + future resolution remainder
    latency_s: float        # enqueue -> future resolved (the SLO number)
    chunk_id: int = -1      # the chunk that answered (per recorder)
    flush_id: int = -1      # the batcher flush that formed the chunk
    gc_s: float = 0.0       # process GC pauses over the chunk's record
    serve_wait_s: float = 0.0
    serve_take_s: float = 0.0
    serve_pad_s: float = 0.0
    planner_cache_lookup_s: float = 0.0
    planner_build_s: float = 0.0
    planner_dispatch_s: float = 0.0
    planner_device_wait_s: float = 0.0
    planner_fetch_s: float = 0.0
    planner_refine_host_s: float = 0.0
    planner_records_s: float = 0.0
    serve_resolve_s: float = 0.0
    serve_record_s: float = 0.0
    dispatches: int = 0
    h2d_arrays: int = 0
    d2h_arrays: int = 0
    lanes_live: int = 0
    lanes_unique: int = 0
    mc_lane_slots: int = 0
    mc_live_slots: int = 0
    mc_run_slots: int = 0
    mc_sharded_dispatches: int = 0

    @property
    def phase_sum(self) -> float:
        return (self.batch_wait_s + self.pad_s + self.cache_lookup_s
                + self.solve_s + self.resolve_s)

    def phases(self) -> Dict[str, float]:
        return {"batch_wait": self.batch_wait_s, "pad": self.pad_s,
                "cache_lookup": self.cache_lookup_s, "solve": self.solve_s,
                "resolve": self.resolve_s}

    def leaves(self) -> Dict[str, float]:
        """The chunk's leaf seconds by leaf name."""
        return {name: getattr(self, leaf_field(name)) for name in LEAVES}


_FIELDS = tuple(f.name for f in fields(RequestSpan))
assert _FIELDS[-len(COUNTERS):] == COUNTERS
assert _FIELDS[-len(COUNTERS) - len(LEAVES):-len(COUNTERS)] == \
    tuple(leaf_field(n) for n in LEAVES)

# ring columns: per request, then per chunk (floats and integers)
_REQ = ("enqueue_t", "admit_s", "batch_wait_s", "resolve_s", "latency_s")
_CHUNK_F = ("pad_s", "cache_lookup_s", "solve_s", "solve_device_s",
            "gc_s") + tuple(leaf_field(n) for n in LEAVES)
_CHUNK_I = ("chunk_id", "flush_id", "objective", "grid_mode",
            "bucket") + COUNTERS


class SpanRecorder:
    """Thread-safe fixed-capacity span ring plus lifetime totals.

    Requests and chunks live in preallocated numpy columns; the chunk
    table has as many rows as the request table, and every chunk holds
    at least one request, so no request in the ring ever points to an
    evicted chunk.  One lock acquisition and one vectorised write per
    chunk."""

    def __init__(self, capacity: int = 131072):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._req = np.zeros((capacity, len(_REQ)))
        self._req_chunk = np.zeros(capacity, np.int64)
        self._chunk_f = np.zeros((capacity, len(_CHUNK_F)))
        self._chunk_i = np.zeros((capacity, len(_CHUNK_I)), np.int64)
        self._labels: List[str] = []
        self._codes: Dict[str, int] = {}
        self._totals = {name: 0.0 for name in (*PHASES, "admit",
                                               "solve_device", "latency",
                                               *LEAVES)}
        self._count = 0
        self._chunks = 0

    def _code(self, label: str) -> int:
        code = self._codes.get(label)
        if code is None:
            code = self._codes[label] = len(self._labels)
            self._labels.append(label)
        return code

    def record_chunk(self, *, objective: str, grid_mode: str, bucket: int,
                     enqueue_t, admit_s, t_start: float, t_end, pad_s=0.0,
                     cache_lookup_s=0.0, solve_s=0.0, resolve_s=None,
                     flush_id: int = -1,
                     phases: Optional[Mapping[str, float]] = None,
                     counts: Optional[Mapping[str, int]] = None,
                     gc_s: float = 0.0) -> int:
        """Record one chunk's requests; returns its ``chunk_id`` (-1 for
        an empty chunk, which is not recorded).

        ``enqueue_t`` / ``admit_s`` are per request; the request's
        batch wait runs from its enqueue to ``t_start`` and its latency
        to ``t_end`` (a scalar, or one per request).  ``resolve_s``
        defaults to the remainder, so the five phases sum exactly to
        each latency.  ``phases`` and ``counts`` are the chunk's leaf
        record (names outside :data:`LEAVES` / :data:`COUNTERS` are
        ignored)."""
        enq = np.asarray(enqueue_t, np.float64).reshape(-1)
        n = enq.shape[0]
        if n == 0:
            return -1
        phases = phases or {}
        counts = counts or {}
        wait = t_start - enq
        latency = np.asarray(t_end, np.float64) - enq
        if resolve_s is None:
            resolve = latency - wait - (pad_s + cache_lookup_s + solve_s)
        else:
            resolve = np.broadcast_to(np.asarray(resolve_s, np.float64),
                                      (n,))
        admit = np.broadcast_to(np.asarray(admit_s, np.float64), (n,))
        device = min(float(phases.get("planner.device_wait", 0.0)),
                     float(solve_s))
        leaves = [float(phases.get(name, 0.0)) for name in LEAVES]
        frow = [pad_s, cache_lookup_s, solve_s, device, gc_s] + leaves
        rows = np.stack([enq, admit, wait, resolve, latency], axis=1)
        keep = min(n, self.capacity)
        with self._lock:
            cid = self._chunks
            self._chunks += 1
            c = cid % self.capacity
            self._chunk_f[c] = frow
            self._chunk_i[c] = [cid, flush_id, self._code(objective),
                                self._code(grid_mode), bucket] + [
                int(counts.get(k, 0)) for k in COUNTERS]
            idx = (self._count + n - keep + np.arange(keep)) % self.capacity
            self._req[idx] = rows[n - keep:]
            self._req_chunk[idx] = cid
            self._count += n
            t = self._totals
            t["batch_wait"] += float(wait.sum())
            t["pad"] += n * pad_s
            t["cache_lookup"] += n * cache_lookup_s
            t["solve"] += n * solve_s
            t["resolve"] += float(resolve.sum())
            t["admit"] += float(admit.sum())
            t["solve_device"] += n * device
            t["latency"] += float(latency.sum())
            for name, v in zip(LEAVES, leaves):
                t[name] += v
        return cid

    def record(self, span: RequestSpan) -> int:
        """Record one request as a chunk of its own (its identifiers are
        assigned anew); returns the chunk's id."""
        return self.record_chunk(
            objective=span.objective, grid_mode=span.grid_mode,
            bucket=span.bucket, enqueue_t=[span.enqueue_t],
            admit_s=span.admit_s,
            t_start=span.enqueue_t + span.batch_wait_s,
            t_end=span.enqueue_t + span.latency_s, pad_s=span.pad_s,
            cache_lookup_s=span.cache_lookup_s, solve_s=span.solve_s,
            resolve_s=span.resolve_s, flush_id=span.flush_id,
            phases={**span.leaves(),
                    "planner.device_wait": span.solve_device_s},
            counts={k: getattr(span, k) for k in COUNTERS},
            gc_s=span.gc_s)

    def __len__(self) -> int:
        with self._lock:
            return min(self._count, self.capacity)

    @property
    def recorded(self) -> int:
        """Lifetime span count (>= ring length once the ring wraps)."""
        with self._lock:
            return self._count

    def snapshot(self) -> List[RequestSpan]:
        """The ring's current window as :class:`RequestSpan` objects,
        oldest first (built on demand)."""
        with self._lock:
            n = min(self._count, self.capacity)
            order = (self._count - n + np.arange(n)) % self.capacity
            req = self._req[order]
            chunk_ids = self._req_chunk[order]
            rows = np.unique(chunk_ids % self.capacity)
            cf = self._chunk_f[rows]
            ci = self._chunk_i[rows]
            labels = list(self._labels)
        # the chunk-shared tail of each span, built once per chunk
        shared = {}
        for f, i in zip(cf.tolist(), ci.tolist()):
            cid, flush, obj, mode, bucket = i[:5]
            pad, cache, solve, device, gc_s = f[:5]
            shared[cid] = ((labels[obj], labels[mode], bucket),
                           (pad, cache, solve, device),
                           (cid, flush, gc_s, *f[5:], *i[5:]))
        out = []
        for (enq, admit, wait, resolve, latency), cid in zip(
                req.tolist(), chunk_ids.tolist()):
            head, mid, tail = shared[cid]
            out.append(RequestSpan(*head, enq, admit, wait, *mid, resolve,
                                   latency, *tail))
        return out

    def totals(self) -> Dict[str, float]:
        """Lifetime totals (seconds) plus ``count`` (requests) and
        ``chunks``: the request phases, ``admit``, ``solve_device`` and
        ``latency`` summed over requests; each leaf of :data:`LEAVES`
        summed over chunks."""
        with self._lock:
            out = dict(self._totals)
            out["count"] = self._count
            out["chunks"] = self._chunks
            return out

    @property
    def solve_fraction(self) -> float:
        """Lifetime solve share of enqueue-to-plan latency — the number
        that says whether the service is compute-bound (optimise the
        kernel) or wait-bound (tune batching); 0.0 before any span."""
        with self._lock:
            lat = self._totals["latency"]
            return self._totals["solve"] / lat if lat > 0.0 else 0.0

    def phase_means_ms(self) -> Dict[str, float]:
        """Mean per-request phase durations in milliseconds (the
        human-readable breakdown the CLI and bench print)."""
        with self._lock:
            if self._count == 0:
                return {name: 0.0 for name in (*PHASES, "latency")}
            return {name: self._totals[name] / self._count * 1e3
                    for name in (*PHASES, "latency")}
