"""Device-vs-host solve attribution and the profiler capture hook.

``plan_batch`` wall clock conflates two very different costs: device
compute (the jitted solve itself) and host work (padding, transfer,
``np.asarray`` materialisation).  The kernel wrappers in
``repro.fleet.objective_kernels`` fence the jitted call with
``jax.block_until_ready`` and report both portions here via
:func:`record_solve`; the serving layer brackets each micro-batch chunk
with :func:`solve_delta` to read back exactly the solve time that chunk
incurred.

Accumulators are kept BOTH process-global (:func:`solve_totals`, for
whole-run reporting) and per-thread (what :func:`solve_delta` reads) —
the test suite runs several services concurrently, and a per-thread
delta cannot be contaminated by another service's worker solving at the
same moment.

:func:`profile_capture` is the opt-in ``jax.profiler`` hook
(``--profile-dir`` on the serve CLI): a no-op unless a directory is
given.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, Iterator, Optional

import jax

_LOCK = threading.Lock()
_GLOBAL = {"device_s": 0.0, "host_s": 0.0, "calls": 0}
_TLS = threading.local()


def _tls_totals() -> Dict[str, float]:
    t = getattr(_TLS, "totals", None)
    if t is None:
        t = _TLS.totals = {"device_s": 0.0, "host_s": 0.0, "calls": 0}
    return t


def record_solve(device_s: float, host_s: float = 0.0) -> None:
    """Called by the kernel solve wrappers after every fenced solve.
    ``device_s`` is the ``block_until_ready``-fenced jitted-call
    duration; ``host_s`` the host-side materialisation that follows."""
    device_s = max(0.0, float(device_s))
    host_s = max(0.0, float(host_s))
    with _LOCK:
        _GLOBAL["device_s"] += device_s
        _GLOBAL["host_s"] += host_s
        _GLOBAL["calls"] += 1
    t = _tls_totals()
    t["device_s"] += device_s
    t["host_s"] += host_s
    t["calls"] += 1


def solve_totals() -> Dict[str, float]:
    """Process-lifetime solve attribution across all threads."""
    with _LOCK:
        return dict(_GLOBAL)


@dataclass
class SolveDelta:
    """Solve time accrued on THIS thread inside a :func:`solve_delta`
    block.  Live while the block runs, frozen at exit."""

    device_s: float = 0.0
    host_s: float = 0.0
    calls: int = 0

    @property
    def total_s(self) -> float:
        return self.device_s + self.host_s


@contextmanager
def solve_delta() -> Iterator[SolveDelta]:
    """Measure solve time recorded by the current thread within the
    block.  Per-thread on purpose: a service worker bracketing its own
    ``plan_many`` call must not absorb another worker's solves."""
    t = _tls_totals()
    before = dict(t)
    delta = SolveDelta()
    try:
        yield delta
    finally:
        delta.device_s = t["device_s"] - before["device_s"]
        delta.host_s = t["host_s"] - before["host_s"]
        delta.calls = int(t["calls"] - before["calls"])


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
