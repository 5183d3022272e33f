"""Thread-safe ingestion queue + continuous size-or-deadline micro-batcher.

The service's front end.  Producers call :meth:`MicroBatcher.submit`
from any thread and get a ``concurrent.futures.Future`` back; a
background worker drains the queue into micro-batches and hands each
(objective, grid-mode)-homogeneous group to the plan function.

Flush policy — CONTINUOUS batching, not fixed windows: the worker
sleeps only while the queue is empty.  Once a request arrives it
collects arrivals until either ``max_batch`` requests are pending
(flush on size) or the OLDEST pending request has waited
``flush_interval`` seconds (flush on deadline), whichever comes first —
so a full queue streams back-to-back batches with no artificial delay,
while a trickle pays at most one flush interval of latency.  A deadline
that fires on an empty queue (the wake raced a consumer) is a no-op
tick, not an error.

Groups preserve per-request order: within one flush, requests are
grouped by ``group_key`` in first-seen order and each group keeps its
arrival order, so results (delivered through per-request futures) can
never cross between interleaved objective streams.

``stop(drain=True)`` — clean shutdown — flushes everything still queued
(in ``max_batch``-sized batches, deadline waived) before the worker
exits; ``drain=False`` cancels the remaining futures instead.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Hashable, List, Optional

from repro.core.scenario import Scenario
from repro.obs import runtime


#: longest single wait of an idle worker: a leaf open when a profiler
#: starts is not in its trace, so an idle wait is cut into pieces this
#: long, and a trace begun mid-wait sees the rest of it
IDLE_WAIT_S = 0.02


class QueueFull(RuntimeError):
    """submit() refused: the ingestion queue is at ``max_pending``.
    Raised to the PRODUCER immediately (shed, don't block) — the
    service maps it to its admission-level RequestShed outcome."""


@dataclass
class PlanRequest:
    """One in-flight planning request.

    ``objective`` is an objective INSTANCE (or ``None`` for the
    planner's default) — identity groups micro-batches and keys the
    jitted Monte-Carlo kernel cache, exactly as in ``plan_many``.
    """

    scenario: Scenario
    objective: Any = None
    grid_mode: str = "dense"
    session_id: Optional[str] = None
    enqueue_t: float = field(default_factory=time.perf_counter)
    future: "Future" = field(default_factory=Future)
    #: admission-policy routing time spent BEFORE enqueue (seconds);
    #: reported on the request's span, outside the enqueue-to-plan SLO
    admit_s: float = 0.0
    #: enqueue-to-plan latency budget in seconds (``None`` = no budget).
    #: When the estimated solve time exceeds what remains of the budget,
    #: the resilience layer degrades the request instead of solving it
    #: late — see ``repro.serve.resilience``.
    budget_s: Optional[float] = None
    #: the batcher flush that took the request (-1 until taken)
    flush_id: int = -1

    def remaining_budget(self, now: Optional[float] = None) \
            -> Optional[float]:
        """Seconds of budget left (negative = already blown), or
        ``None`` for unbudgeted requests."""
        if self.budget_s is None:
            return None
        if now is None:
            now = time.perf_counter()
        return self.budget_s - (now - self.enqueue_t)

    def group_key(self) -> Hashable:
        """Micro-batch grouping key: one jitted solve serves one
        (objective identity, grid mode) pair."""
        return (id(self.objective), self.grid_mode)


def group_requests(items: List, key: Callable[[Any], Hashable]) -> List[List]:
    """Group ``items`` by ``key`` in first-seen order, preserving each
    group's internal order — the micro-batcher's grouping of one flush."""
    groups: "OrderedDict[Hashable, List]" = OrderedDict()
    for it in items:
        groups.setdefault(key(it), []).append(it)
    return list(groups.values())


class MicroBatcher:
    """Size-or-deadline continuous micro-batcher over a FIFO queue.

    ``plan_group(requests)`` is called on the worker thread with a
    non-empty, (objective, grid-mode)-homogeneous, arrival-ordered list;
    it must resolve every request's future (the batcher resolves them
    with the exception instead if it raises).  Each taken request carries
    its flush's ``flush_id``.

    The worker holds an open :mod:`repro.obs.runtime` record for its
    whole life and times two leaves into it: ``serve.wait`` (each
    ``cv.wait``, for requests or for the flush deadline) and
    ``serve.take`` (the flush popped and grouped).  ``plan_group`` takes
    the record at each chunk it writes.
    """

    def __init__(self, plan_group: Callable[[List[PlanRequest]], None], *,
                 max_batch: int = 256, flush_interval: float = 0.01,
                 max_pending: int = 0, faults=None,
                 name: str = "plan-batcher"):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if flush_interval < 0:
            raise ValueError(
                f"flush_interval must be >= 0, got {flush_interval}")
        if max_pending < 0:
            raise ValueError(
                f"max_pending must be >= 0, got {max_pending}")
        self._plan_group = plan_group
        self.max_batch = max_batch
        self.flush_interval = flush_interval
        #: ingestion-queue bound; 0 = unbounded.  A full queue REJECTS
        #: (QueueFull from submit, immediately) rather than blocking the
        #: producer or growing memory without limit.
        self.max_pending = max_pending
        #: optional repro.chaos.FaultPlan; the worker draws the
        #: "queue.stall" point before planning each taken batch
        self.faults = faults
        self.rejections = 0       # submits refused by the queue bound
        self._name = name
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._queue: Deque[PlanRequest] = deque()
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._drain = True
        self.flushes = 0          # micro-batches handed to plan_group
        self.taken = 0            # flushes taken (the next flush_id)
        self.idle_ticks = 0       # deadline wakes that found nothing to do
        #: per-cause flush counts: "size" (max_batch pending), "deadline"
        #: (oldest request waited out flush_interval), "drain" (shutdown
        #: flush) — the signal separating a saturated service (size) from
        #: a trickle paying the deadline on every batch
        self.flush_causes = {"size": 0, "deadline": 0, "drain": 0}

    # -- producer side ------------------------------------------------------

    def submit(self, request: PlanRequest) -> "Future":
        """Enqueue; returns the request's future.  Raises once stopped —
        a draining queue must not grow behind the worker's back."""
        with self._cv:
            if self._stopping or self._thread is None:
                raise RuntimeError(
                    f"{self._name} is not running; start() it first")
            if (self.max_pending > 0
                    and len(self._queue) >= self.max_pending):
                self.rejections += 1
                raise QueueFull(
                    f"{self._name}: queue at capacity "
                    f"({len(self._queue)}/{self.max_pending})")
            self._queue.append(request)
            self._cv.notify()
        return request.future

    @property
    def depth(self) -> int:
        """Requests currently waiting (the service's load signal)."""
        with self._lock:
            return len(self._queue)

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "MicroBatcher":
        with self._cv:
            if self._thread is not None:
                raise RuntimeError(f"{self._name} already started")
            self._stopping = False
            self._thread = threading.Thread(
                target=self._run, name=self._name, daemon=True)
            self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout: Optional[float] = None) \
            -> None:
        """Stop the worker.  ``drain=True`` plans everything still queued
        first; ``drain=False`` cancels the queued futures."""
        with self._cv:
            if self._thread is None:
                return
            self._stopping = True
            self._drain = drain
            self._cv.notify_all()
            thread = self._thread
        thread.join(timeout)
        with self._cv:
            self._thread = None

    # -- worker -------------------------------------------------------------

    def _await_flush(self) -> Optional[int]:
        """Block until a flush is due; return how many requests it takes
        from the head of the queue, or ``None`` when stopped and
        (post-drain) empty.  Only the worker pops the queue."""
        with self._cv:
            while True:
                cause = "drain"
                while not self._queue and not self._stopping:
                    self._wait(IDLE_WAIT_S)
                if not self._queue:
                    return None  # stopping on an empty queue
                if self._stopping:
                    if not self._drain:
                        while self._queue:
                            self._queue.popleft().future.cancel()
                        return None
                else:
                    # deadline of the OLDEST pending request; new arrivals
                    # notify, size max_batch flushes immediately
                    deadline = self._queue[0].enqueue_t + self.flush_interval
                    while (len(self._queue) < self.max_batch
                           and not self._stopping):
                        remaining = deadline - time.perf_counter()
                        if remaining <= 0:
                            break
                        self._wait(remaining)
                    if not self._queue:
                        # the deadline wake found nothing to flush (e.g.
                        # a cancel drained the queue mid-wait): count the
                        # no-op tick and go back to sleep
                        self.idle_ticks += 1
                        continue
                    cause = ("size" if len(self._queue) >= self.max_batch
                             else "deadline")
                self.flush_causes[cause] += 1
                return min(self.max_batch, len(self._queue))

    def _wait(self, timeout: float) -> None:
        """One ``cv.wait`` (lock held) as a ``serve.wait`` leaf."""
        with runtime.span("serve.wait"):
            self._cv.wait(timeout)

    def _run(self) -> None:
        runtime.open_record()
        try:
            while self._flush_once():
                pass
        finally:
            runtime.close_record()

    def _flush_once(self) -> bool:
        """Take one flush and plan its groups; ``False`` once stopped."""
        n = self._await_flush()
        if n is None:
            return False
        with runtime.span("serve.take"):
            with self._cv:
                batch = [self._queue.popleft() for _ in range(n)]
                flush_id = self.taken
                self.taken += 1
            for req in batch:
                req.flush_id = flush_id
            groups = group_requests(batch, key=PlanRequest.group_key)
        if self.faults is not None:
            action = self.faults.draw("queue.stall")
            if action is not None:
                time.sleep(action.duration_s)
        for group in groups:
            self.flushes += 1
            try:
                self._plan_group(group)
            except BaseException as e:  # noqa: BLE001 — futures carry it
                for req in group:
                    if not req.future.done():
                        req.future.set_exception(e)
        return True
