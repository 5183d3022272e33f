"""Load from one thread of the benchmark's process: an open loop that
sends on a fixed schedule.

Each request's outcome is kept in a :class:`Tally`: when it was due (the
scheduled send time), when it was sent, when its future resolved, and
what came back, as ``keep`` makes it.  A ``keep`` that turns an answer
into a dict of plain numbers and strings leaves nothing for Python's
garbage collector to scan: a window's hundred thousand answers held as
objects made its full collections stall the service for over 100 ms.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


@dataclass
class Tally:
    due: List[float] = field(default_factory=list)
    sent: List[float] = field(default_factory=list)
    done: List[Optional[float]] = field(default_factory=list)
    outcome: List[object] = field(default_factory=list)
    requests: List[Dict] = field(default_factory=list)
    keep: Callable[[object], object] = lambda answer: answer
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def add(self, req: Dict, due: float) -> int:
        with self._lock:
            self.due.append(due)
            self.sent.append(float("nan"))
            self.done.append(None)
            self.outcome.append(None)
            self.requests.append(req)
            return len(self.due) - 1

    def resolve(self, k: int, fut) -> None:
        t = time.perf_counter()
        exc = fut.exception() if not fut.cancelled() else None
        self.outcome[k] = exc if exc is not None else (
            None if fut.cancelled() else self.keep(fut.result()))
        self.done[k] = t


def _submit(service, tally: Tally, k: int, scenario) -> None:
    req = tally.requests[k]
    tally.sent[k] = time.perf_counter()
    try:
        fut = service.submit(scenario, objective=req["objective"],
                             grid_mode=req["grid_mode"])
    except Exception as e:  # noqa: BLE001 — a shed or refused request
        tally.outcome[k] = e
        tally.done[k] = time.perf_counter()
        return
    fut.add_done_callback(lambda f, k=k: tally.resolve(k, f))


def open_loop(service, tally: Tally, reqs: Sequence[Dict],
              scenarios: Sequence, times: np.ndarray, t0: float) -> None:
    """Send request ``k`` at ``t0 + times[k]``, late or not, without
    waiting for any reply."""
    idx = [tally.add(r, t0 + float(t)) for r, t in zip(reqs, times)]
    for k, sc in zip(idx, scenarios):
        wait = tally.due[k] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        _submit(service, tally, k, sc)


def wait_all(tally: Tally, indices: Sequence[int], deadline: float) -> None:
    """Wait until each request has an outcome or ``deadline`` passes."""
    for k in indices:
        while tally.done[k] is None and time.perf_counter() < deadline:
            time.sleep(0.005)
