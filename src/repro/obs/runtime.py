"""Leaf spans of the host path, the chunk records they fill, garbage-
collection accounting, and the profiler capture hook.

A served chunk costs milliseconds of host work around well under a
millisecond of device time, and one wall-clock number cannot say where
it goes.  :class:`span` is the one timing helper of that path: each
leaf (``serve.wait``, ``planner.dispatch``, ``planner.fetch``, ...) is
timed once on ``perf_counter`` and

  * added to the OPEN RECORD of the current thread, if one is open — a
    service worker opens one (:func:`open_record`) and takes it at each
    chunk it writes (:func:`take_record`), so a chunk's record holds the
    leaves the worker closed since it wrote the previous chunk;
  * entered as a ``jax.profiler.TraceAnnotation`` of the same name, so
    the leaf sits on the device trace's clock and names the device's
    idle gaps.  With no profiler running an annotation costs about a
    microsecond.

Leaves do not nest on one thread: a trace reader names a device gap by
the host event that covers most of it, and an enclosing span would
take the name of every gap inside it.  Records are per thread, so
several services (or a caller planning on its own thread) never mix
their leaves.  :func:`count` adds to the open record's counters
(jitted calls, arrays copied each way, live and unique lanes), and
:func:`note` sets one that is a property of the chunk.

The garbage-collection hook (:func:`install_gc_hook`, reference-counted
so each running service holds it once) counts collections and pause
seconds per generation for the whole process, annotates generations 1
and 2 as ``gc.gen<N>``, and gives each taken record ``gc_s``: the
process's pause seconds since the record was opened.

:func:`profile_capture` is the opt-in ``jax.profiler`` hook
(``--profile-dir`` on the serve CLI): a no-op unless a directory is
given.
"""
from __future__ import annotations

import gc
import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

_TLS = threading.local()
_perf = time.perf_counter

#: garbage-collector generations counted by the hook
GC_GENERATIONS = (0, 1, 2)


class _Record:
    """What one thread's leaves and counters added since it opened."""

    __slots__ = ("phases", "counts", "gc0")

    def __init__(self):
        self.phases: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.gc0 = _GC.pause_total


def open_record() -> None:
    """Start accumulating this thread's leaves (replacing any open
    record)."""
    _TLS.record = _Record()


def close_record() -> None:
    """Stop accumulating this thread's leaves."""
    _TLS.record = None


def take_record() -> Optional[Tuple[Dict[str, float], Dict[str, int], float]]:
    """``(phases, counts, gc_s)`` of this thread's open record, which is
    replaced by a fresh one; ``None`` when no record is open."""
    rec = getattr(_TLS, "record", None)
    if rec is None:
        return None
    fresh = _TLS.record = _Record()
    return rec.phases, rec.counts, max(0.0, fresh.gc0 - rec.gc0)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of this thread's open record."""
    rec = getattr(_TLS, "record", None)
    if rec is not None:
        rec.counts[name] = rec.counts.get(name, 0) + int(n)


def note(name: str, n: int) -> None:
    """Set counter ``name`` of this thread's open record to ``n``, or keep
    the larger value noted before: a property of the record's passes
    (such as the rows they simulated over), not a tally."""
    rec = getattr(_TLS, "record", None)
    if rec is not None:
        rec.counts[name] = max(rec.counts.get(name, 0), int(n))


class span:
    """``with span("planner.fetch"): ...`` — time a leaf of the host
    path into this thread's open record and annotate it for the
    profiler (see the module docstring)."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = _perf()
        return self

    def __exit__(self, *exc) -> None:
        dt = _perf() - self._t0
        self._ann.__exit__(None, None, None)
        rec = getattr(_TLS, "record", None)
        if rec is not None:
            rec.phases[self.name] = rec.phases.get(self.name, 0.0) + dt


# ---------------------------------------------------------------------------
# garbage collection
# ---------------------------------------------------------------------------


class _GcState:
    """Process-wide collection counts and pauses.  The interpreter runs
    one collection at a time, so the start/stop pair never interleaves."""

    def __init__(self):
        self.lock = threading.Lock()
        self.users = 0
        self.collections = [0] * len(GC_GENERATIONS)
        self.pause_s = [0.0] * len(GC_GENERATIONS)
        self.pause_total = 0.0
        self.t0 = 0.0
        self.ann: Optional[TraceAnnotation] = None


_GC = _GcState()


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        gen = info["generation"]
        if gen >= 1:
            _GC.ann = TraceAnnotation(f"gc.gen{gen}")
            _GC.ann.__enter__()
        _GC.t0 = _perf()
        return
    dt = _perf() - _GC.t0
    gen = info["generation"]
    _GC.collections[gen] += 1
    _GC.pause_s[gen] += dt
    _GC.pause_total += dt
    if _GC.ann is not None:
        _GC.ann.__exit__(None, None, None)
        _GC.ann = None


def install_gc_hook() -> None:
    """Hold the process's collection hook (installed by the first
    holder)."""
    with _GC.lock:
        _GC.users += 1
        if _GC.users == 1:
            gc.callbacks.append(_on_gc)


def remove_gc_hook() -> None:
    """Release the hook (removed with its last holder)."""
    with _GC.lock:
        if _GC.users == 0:
            return
        _GC.users -= 1
        if _GC.users == 0 and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)


def gc_totals() -> Dict[str, List[float]]:
    """Lifetime ``collections`` and ``pause_s`` per generation, counted
    while the hook was held."""
    return {"collections": list(_GC.collections),
            "pause_s": list(_GC.pause_s)}


@contextmanager
def profile_capture(profile_dir: Optional[str]) -> Iterator[None]:
    """Wrap a block in a ``jax.profiler`` trace written to
    ``profile_dir`` (view with TensorBoard / Perfetto).  Falsy dir ->
    no-op.  A profiler that fails to start or stop raises: a trace that
    was asked for and silently not written would be read as one that
    saw nothing."""
    if not profile_dir:
        yield
        return
    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
