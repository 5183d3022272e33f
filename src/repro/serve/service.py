"""The always-on planning service: warmup, continuous batching, drift.

:class:`PlanningService` is the long-lived front end over the fleet
planning engine — the piece that turns "a fast batched solver"
(:class:`~repro.fleet.planner.FleetPlanner`) into "a service an edge
population talks to":

  1. **Ingestion + continuous batching** — :meth:`submit` enqueues from
     any thread and returns a future; the
     :class:`~repro.serve.batcher.MicroBatcher` worker flushes
     size-or-deadline micro-batches grouped by (objective, grid mode)
     and pads each group to a configured power-of-two BUCKET, so the
     whole request stream exercises a small, fixed set of kernel shapes.
  2. **Bucketed AOT warmup** — :meth:`warmup` sweeps
     ``FleetPlanner.warm`` over every configured (objective, grid mode,
     bucket), compiling the dense solve, the coarse pass and every
     reachable pow2 fine-pass width up front.  After warmup NO request
     pays a ``jax.jit`` trace — audited end to end by the
     :mod:`repro.fleet.tracing` counters, surfaced per bucket in
     :meth:`stats`, and asserted by the serving tests and CI smoke.
  3. **Admission policy** — requests that don't name an objective/mode
     are routed by a pluggable policy (:mod:`repro.serve.policy`), e.g.
     exact burst-aware ``markov_arq`` for sticky Gilbert-Elliott links
     and refined ``corollary1`` under backpressure.
  4. **Drift-triggered re-planning** — devices open sessions and stream
     observed per-attempt loss outcomes in (:meth:`observe`); when a
     session's loss EWMA drifts past the threshold, the service
     re-estimates the link (:func:`repro.serve.sessions.reestimate_link`),
     INVALIDATES the prefix-keyed cache entry the stale plan lives at,
     and re-enqueues the corrected scenario through the same batcher.
  5. **Observability** — every request leaves a
     :class:`~repro.obs.spans.RequestSpan` decomposing its
     enqueue-to-plan latency exactly into batch-wait / pad / cache-lookup
     / solve / resolve phases, plus its chunk's identifiers, host leaves
     (``serve.*`` / ``planner.*``, each also a profiler annotation) and
     counters; latencies aggregate into mergeable
     log-histograms per (objective, grid mode, bucket); drift and
     session lifecycle events land in a JSONL-exportable audit journal;
     and ``service.metrics`` — a :class:`~repro.obs.metrics\
     .MetricsRegistry` over the stats recorder, the plan cache, the
     kernel trace counters, the span totals and the journal — renders
     the whole picture as Prometheus text exposition in one call.

Plans are bitwise-identical to direct ``FleetPlanner.plan_batch`` calls:
the service adds routing, batching and caching around the solver, never
arithmetic.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from repro.chaos import FaultPlan, parse_chaos_spec
from repro.core.bounds import BoundConstants
from repro.core.scenario import Scenario
from repro.federated.round import (FEDERATED_TOKEN, RoundPlanner,
                                   RoundRecord, population_key)
from repro.fleet import GRID_MODES, MC_IMPLS, FleetPlanner, PlanCache
from repro.fleet.objective_kernels import pow2ceil
from repro.fleet.tracing import trace_delta
from repro.obs import (EventJournal, MetricsRegistry, RequestSpan,
                       SpanRecorder, runtime)
from repro.serve import export
from repro.serve.batcher import MicroBatcher, PlanRequest, QueueFull
from repro.serve.catalogue import (ALL_MODELS, FEDERATED_KIND,
                                   default_consts, mc_update_floor,
                                   resolve_objectives, synth_population,
                                   synth_requests)
from repro.serve.policy import policy_spec
from repro.serve.resilience import (DegradationExhausted, HealthReport,
                                    RequestShed, ResilienceManager,
                                    RetryPolicy)
from repro.serve.sessions import Session, SessionTracker, reestimate_link
from repro.serve.stats import FederatedRecorder, ServiceStats, StatsRecorder


@dataclass(frozen=True)
class ServiceConfig:
    """Static configuration of a :class:`PlanningService`.

    ``batch_buckets`` are the micro-batch pad shapes (ascending powers
    of two; the largest is also the flush size ``max_batch``) — the
    complete set of batch lengths the service will ever compile.
    ``objective_ids`` name the served objectives (``montecarlo`` is
    opt-in: its simulated scan makes warmup cost scale with ``n_max``).
    ``n_max`` bounds the dataset sizes the service expects — it sizes
    the Monte-Carlo scan-length floor so MC streams compile ONE scan
    shape — and ``grid_modes`` restricts which solve strategies the
    admission layer may hand out.

    The ``mc_*`` knobs configure the served Monte-Carlo objective and
    engine: ``mc_impl`` selects the simulation engine (``"auto"`` /
    ``"scan"`` / ``"pallas"``; "auto" resolves by backend), ``mc_crn``
    turns on the common-random-numbers estimator, ``mc_seed_stream``
    picks the per-run RNG derivation, and ``mc_coarse_seeds`` /
    ``mc_refine_rates`` / ``mc_coarse_strides`` / ``mc_fine_radius`` /
    ``mc_coarse_updates`` install the refine-mode seed/rate/stride/
    window/horizon schedules.  All of them flow into the objective's
    cache token (and the engine into the planner's cache-context
    prefix), so differently-configured services never alias entries.
    """

    grid_size: int = 64
    batch_buckets: Tuple[int, ...] = (64, 256)
    flush_interval: float = 0.01
    objective_ids: Tuple[str, ...] = ("corollary1", "markov_arq")
    grid_modes: Tuple[str, ...] = GRID_MODES
    mc_impl: str = "auto"
    mc_crn: bool = False
    mc_seed_stream: str = "fold_in"
    mc_coarse_seeds: Optional[int] = None
    mc_refine_rates: Optional[int] = None
    mc_coarse_strides: Optional[Tuple[int, ...]] = None
    mc_fine_radius: Optional[int] = None
    mc_coarse_updates: Optional[int] = None
    policy_id: str = "link_aware"
    cache_size: int = 8192
    sig_digits: int = 3
    n_max: int = 32768
    drift_threshold: float = 0.1
    ewma_alpha: float = 0.05
    min_observations: int = 20
    shard: bool = True
    warm_models: Tuple[str, ...] = ALL_MODELS
    #: federated-round population pad shapes (ascending powers of two).
    #: Empty (the default) leaves the round path cold: ``submit_round``
    #: still works, but the first round at each population shape pays a
    #: trace.  Non-empty buckets are AOT-warmed like batch buckets, so
    #: round requests inside the largest bucket hit compiled code only.
    population_buckets: Tuple[int, ...] = ()
    #: span ring capacity (lifetime phase TOTALS are kept regardless;
    #: the ring holds the most recent complete traces): a minute at
    #: 2,200 plans/s, in numpy columns, with no object per request
    span_capacity: int = 131072
    #: event-journal ring capacity (per-kind counts are lifetime)
    journal_capacity: int = 4096
    #: when set, every journal event is also appended to this JSONL file
    journal_path: Optional[str] = None
    #: journal file rotation: rotate at ``journal_max_bytes`` (0 = never),
    #: keeping ``journal_keep`` rotated files; ``journal_fsync`` makes
    #: every appended event durable (fsync per flush) — the crash-journal
    #: posture, off by default because it serialises on disk latency
    journal_max_bytes: int = 0
    journal_keep: int = 3
    journal_fsync: bool = False
    #: ingestion-queue bound (0 = unbounded): a full queue SHEDS new
    #: submits (RequestShed) instead of growing without limit
    max_pending: int = 0
    #: default enqueue-to-plan budget applied to submits that don't
    #: carry one (None = unbudgeted); the degradation ladder fires when
    #: the estimated solve would overrun what remains of the budget
    default_budget_s: Optional[float] = None
    #: transient-solve retry: total attempts per chunk, then the
    #: decorrelated-jitter backoff's base/cap (seconds)
    retry_attempts: int = 3
    retry_base_s: float = 0.02
    retry_cap_s: float = 0.5
    #: per-(objective, grid_mode) circuit breaker: consecutive failures
    #: to trip, and the open->half-open probe cooldown (seconds)
    breaker_threshold: int = 5
    breaker_cooldown_s: float = 1.0
    #: solve-time estimate used against budgets: histogram quantile and
    #: a safety multiplier on top of it
    budget_quantile: float = 90.0
    budget_safety: float = 1.0
    #: sessions with a pending drift re-plan before health reports
    #: DEGRADED
    health_drift_backlog: int = 8
    #: deterministic fault injection (repro.chaos.parse_chaos_spec
    #: grammar); None/empty = chaos-free
    chaos_spec: Optional[str] = None

    def __post_init__(self):
        if not self.batch_buckets:
            raise ValueError("batch_buckets must name >= 1 bucket")
        for b in self.batch_buckets:
            if b < 1 or pow2ceil(int(b)) != int(b):
                raise ValueError(
                    f"batch_buckets must be powers of two, got "
                    f"{self.batch_buckets}")
        if tuple(sorted(self.batch_buckets)) != tuple(self.batch_buckets):
            raise ValueError(
                f"batch_buckets must ascend, got {self.batch_buckets}")
        for b in self.population_buckets:
            if b < 1 or pow2ceil(int(b)) != int(b):
                raise ValueError(
                    f"population_buckets must be powers of two, got "
                    f"{self.population_buckets}")
        if tuple(sorted(self.population_buckets)) != \
                tuple(self.population_buckets):
            raise ValueError(
                f"population_buckets must ascend, got "
                f"{self.population_buckets}")
        unknown = [m for m in self.grid_modes if m not in GRID_MODES]
        if unknown:
            raise ValueError(
                f"unknown grid mode(s) {unknown}; valid: {list(GRID_MODES)}")
        if not self.grid_modes:
            raise ValueError("grid_modes must name >= 1 mode")
        if self.mc_impl not in MC_IMPLS:
            raise ValueError(
                f"unknown mc_impl {self.mc_impl!r}; valid: {MC_IMPLS}")
        if self.max_pending < 0:
            raise ValueError(
                f"max_pending must be >= 0, got {self.max_pending}")
        if self.default_budget_s is not None and self.default_budget_s < 0:
            raise ValueError(
                f"default_budget_s must be >= 0, got "
                f"{self.default_budget_s}")
        if self.retry_attempts < 1:
            raise ValueError(
                f"retry_attempts must be >= 1, got {self.retry_attempts}")
        if self.breaker_threshold < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1, got "
                f"{self.breaker_threshold}")
        if self.journal_max_bytes < 0 or self.journal_keep < 1:
            raise ValueError(
                f"need journal_max_bytes >= 0 and journal_keep >= 1, got "
                f"{self.journal_max_bytes}/{self.journal_keep}")

    @property
    def max_batch(self) -> int:
        return int(self.batch_buckets[-1])


class PlanningService:
    """Long-lived planning service over the fleet engine (see module
    docstring).  Lifecycle: ``warmup()`` (optional but what the
    zero-trace SLO needs) -> ``start()`` -> ``submit``/``open_session``/
    ``observe`` from any thread -> ``stop()`` (drains by default).  Also
    a context manager: ``with PlanningService() as svc: ...`` starts and
    drains it."""

    def __init__(self, config: Optional[ServiceConfig] = None,
                 consts: Optional[BoundConstants] = None, *,
                 objectives: Optional[Dict[str, Any]] = None,
                 policy: Any = None, faults: Optional[FaultPlan] = None):
        self.config = config if config is not None else ServiceConfig()
        self.consts = consts if consts is not None else default_consts()
        self.consts.validate()
        cfg = self.config
        if faults is None and cfg.chaos_spec:
            faults = parse_chaos_spec(cfg.chaos_spec)
        self.faults = faults
        # pow2 refine widths: the width set becomes enumerable, which is
        # what lets warmup() cover EVERY shape the stream can reach
        self.planner = FleetPlanner(grid_size=cfg.grid_size,
                                    shard=cfg.shard,
                                    pow2_refine_widths=True,
                                    mc_impl=cfg.mc_impl)
        corruptor = None
        if faults is not None and faults.enabled("cache.corrupt"):
            corruptor = (
                lambda: faults.draw("cache.corrupt") is not None)
        self.cache = PlanCache(maxsize=cfg.cache_size,
                               sig_digits=cfg.sig_digits,
                               checksums=faults is not None,
                               corruptor=corruptor)
        if objectives is not None:
            self.objectives = dict(objectives)
        else:
            self.objectives = resolve_objectives(
                cfg.objective_ids,
                mc_min_updates=(mc_update_floor(cfg.n_max)
                                if "montecarlo" in cfg.objective_ids
                                else 0),
                mc_options=dict(crn=cfg.mc_crn,
                                seed_stream=cfg.mc_seed_stream,
                                coarse_seeds=cfg.mc_coarse_seeds,
                                refine_rates=cfg.mc_refine_rates,
                                coarse_strides=cfg.mc_coarse_strides,
                                fine_radius=cfg.mc_fine_radius,
                                coarse_updates=cfg.mc_coarse_updates))
        self.policy = policy if policy is not None \
            else policy_spec(cfg.policy_id).cls()
        self.round_planner = RoundPlanner(grid_size=cfg.grid_size,
                                          shard=cfg.shard)
        self.federated = FederatedRecorder()
        self.sessions = SessionTracker(
            drift_threshold=cfg.drift_threshold,
            ewma_alpha=cfg.ewma_alpha,
            min_observations=cfg.min_observations)
        self.recorder = StatsRecorder()
        self.spans = SpanRecorder(capacity=cfg.span_capacity)
        self.journal = EventJournal(capacity=cfg.journal_capacity,
                                    path=cfg.journal_path,
                                    max_bytes=cfg.journal_max_bytes,
                                    keep=cfg.journal_keep,
                                    fsync=cfg.journal_fsync)
        self.batcher = MicroBatcher(self._plan_group,
                                    max_batch=cfg.max_batch,
                                    flush_interval=cfg.flush_interval,
                                    max_pending=cfg.max_pending,
                                    faults=faults)
        self.resilience = ResilienceManager(
            retry=RetryPolicy(attempts=cfg.retry_attempts,
                              base_s=cfg.retry_base_s,
                              cap_s=cfg.retry_cap_s,
                              seed=faults.seed if faults else 0),
            breaker_threshold=cfg.breaker_threshold,
            breaker_cooldown_s=cfg.breaker_cooldown_s,
            budget_quantile=cfg.budget_quantile,
            budget_safety=cfg.budget_safety,
            journal=self.journal, faults=faults)
        # the degradation ladder's "bound" rung: the cheap dense
        # Corollary-1 solve.  Reuse the SERVED corollary1 instance when
        # there is one — objective identity keys the jitted executables,
        # so reuse is what keeps the fallback inside the warmed shapes.
        self._fallback_objective = self.objectives.get("corollary1")
        if self._fallback_objective is None:
            self._fallback_objective = \
                resolve_objectives(("corollary1",))["corollary1"]
        self.metrics = MetricsRegistry()
        export.register_service_sources(self.metrics, self)
        self._lock = threading.Lock()
        self.warmed = False
        self.warmup_traces = 0
        self.warmup_seconds = 0.0
        self._gc_hooked = False

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "PlanningService":
        """Start the worker; a running service holds the process's
        garbage-collection hook (``repro.obs.runtime``)."""
        self.batcher.start()
        if not self._gc_hooked:
            runtime.install_gc_hook()
            self._gc_hooked = True
        return self

    def stop(self, drain: bool = True) -> None:
        self.batcher.stop(drain=drain)
        if self._gc_hooked:
            runtime.remove_gc_hook()
            self._gc_hooked = False

    def __enter__(self) -> "PlanningService":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop(drain=exc_type is None)

    def warmup(self, scenarios: Optional[Sequence[Scenario]] = None) -> int:
        """AOT-compile every (objective, grid mode, bucket) executable
        the configuration admits; returns the total trace count it cost.

        ``scenarios`` fixes the warm batch signature (rate width, update
        counts); the default draws a small synthetic mix over
        ``config.warm_models``.  Restarts the stats clock afterwards so
        reported throughput is steady-state serving, not compilation.
        """
        cfg = self.config
        if scenarios is None:
            scenarios = synth_requests(
                min(8, cfg.batch_buckets[0]), seed=0, dup_frac=0.0,
                models=cfg.warm_models, n_max=cfg.n_max)
        scenarios = list(scenarios)
        t0 = time.perf_counter()
        total = 0
        for oid, objective in self.objectives.items():
            for mode in cfg.grid_modes:
                for bucket in cfg.batch_buckets:
                    traces = self.planner.warm(
                        scenarios[:bucket], self.consts,
                        objective=objective, grid_mode=mode,
                        pad_to=bucket)
                    total += traces
                    self.recorder.record_bucket(oid, mode, bucket,
                                                compiles=traces)
        # the degradation ladder's "bound" rung solves (corollary1,
        # dense) at the same chunk shapes — warm it when the configured
        # sweep above didn't already cover that exact objective instance,
        # so a degraded request never pays a post-warmup trace
        fallback_covered = (
            self._fallback_objective is self.objectives.get("corollary1")
            and "dense" in cfg.grid_modes)
        if not fallback_covered:
            for bucket in cfg.batch_buckets:
                traces = self.planner.warm(
                    scenarios[:bucket], self.consts,
                    objective=self._fallback_objective, grid_mode="dense",
                    pad_to=bucket)
                total += traces
                self.recorder.record_bucket("corollary1", "dense", bucket,
                                            compiles=traces)
        if cfg.population_buckets:
            # federated rounds use the catalogue rate set too, but draw
            # through synth_population so the warm batch carries the
            # round-request signature (shared deadline, D = 1)
            pop, _ = synth_population(cfg.population_buckets[0], seed=0,
                                      models=cfg.warm_models,
                                      n_max=min(cfg.n_max, 4096))
            for bucket in cfg.population_buckets:
                traces = self.round_planner.warm(
                    pop[:bucket], self.consts, pad_to=bucket)
                total += traces
                self.recorder.record_bucket(FEDERATED_KIND, "dense",
                                            bucket, compiles=traces)
        self.warmup_seconds = time.perf_counter() - t0
        self.warmup_traces = total
        self.warmed = True
        self.journal.emit("warmup", traces=total,
                          seconds=round(self.warmup_seconds, 6),
                          objectives=sorted(self.objectives),
                          grid_modes=list(cfg.grid_modes),
                          buckets=list(cfg.batch_buckets),
                          population_buckets=list(cfg.population_buckets))
        self.recorder.restart_clock()
        return total

    # -- request path -------------------------------------------------------

    def _resolve_objective(self, objective) -> Tuple[str, Any]:
        """(objective_id, instance) for an instance, a registry id, or
        ``None`` (caller routes through the admission policy first)."""
        if isinstance(objective, str):
            inst = self.objectives.get(objective)
            if inst is None:
                raise KeyError(
                    f"objective {objective!r} is not served; configured: "
                    f"{sorted(self.objectives)}")
            return objective, inst
        oid = getattr(objective, "objective_id", None)
        if oid is None:
            raise TypeError(
                f"{type(objective).__name__} is not a registered planning "
                "objective (no objective_id)")
        return str(oid), objective

    def _admit(self, scenario: Scenario, objective, grid_mode):
        """Fill whichever of (objective, grid_mode) the caller left to
        the admission policy, and validate the result.  The fourth
        element is the admission ACTION ("accept"/"shed") — policies
        only decide it for requests they actually routed."""
        cfg = self.config
        action = "accept"
        if objective is None or grid_mode is None:
            load = self.batcher.depth / cfg.max_batch
            decision = self.policy.admit(scenario, load=load)
            action = getattr(decision, "action", "accept")
            if objective is None:
                objective = decision.objective_id
            if grid_mode is None:
                grid_mode = decision.grid_mode
        oid, inst = self._resolve_objective(objective)
        if grid_mode not in cfg.grid_modes:
            raise ValueError(
                f"grid mode {grid_mode!r} is not served; configured: "
                f"{list(cfg.grid_modes)}")
        return oid, inst, grid_mode, action

    def submit(self, scenario: Scenario, *, objective: Any = None,
               grid_mode: Optional[str] = None,
               session_id: Optional[str] = None,
               budget_s: Optional[float] = None) -> "Future":
        """Enqueue one planning request; returns a future resolving to
        its :class:`~repro.fleet.planner.PlanRecord`.  ``objective`` may
        be a served instance, a registry id, or ``None``/``grid_mode``
        ``None`` to let the admission policy decide.  ``budget_s`` caps
        the enqueue-to-plan latency (default from the config): requests
        the service can't solve inside the budget degrade along the
        fallback ladder instead of arriving late.

        Raises :class:`~repro.serve.resilience.RequestShed` when the
        admission policy sheds the request or the bounded ingestion
        queue is full — explicit rejection, never silent queuing past
        capacity."""
        t_admit = time.perf_counter()
        _, inst, mode, action = self._admit(scenario, objective, grid_mode)
        if action == "shed":
            self.recorder.count("shed")
            self.resilience.note_shed("policy")
            raise RequestShed("admission policy shed the request "
                              f"(queue depth {self.batcher.depth})")
        admit_s = time.perf_counter() - t_admit
        if budget_s is None:
            budget_s = self.config.default_budget_s
        request = PlanRequest(scenario=scenario, objective=inst,
                              grid_mode=mode, session_id=session_id,
                              admit_s=admit_s, budget_s=budget_s)
        try:
            self.batcher.submit(request)
        except QueueFull as exc:
            self.recorder.count("shed")
            self.resilience.note_shed("queue_full")
            raise RequestShed(str(exc)) from None
        self.recorder.count("requests")
        return request.future

    def _population_bucket(self, n: int) -> int:
        """The pad shape for an ``n``-device round: the smallest
        configured population bucket that fits, else (an unwarmed
        population size) the next power of two."""
        for b in self.config.population_buckets:
            if n <= b:
                return int(b)
        return pow2ceil(n)

    def submit_round(self, population: Sequence[Scenario], *,
                     deadline: Optional[float] = None) -> RoundRecord:
        """Plan one federated round over a candidate population —
        synchronous (a round is a population-level decision, not a
        per-device stream; there is nothing to micro-batch it with).

        The population is padded to the smallest configured
        ``population_buckets`` entry that fits (so warmed services pay
        zero traces), solved by the shared :class:`~repro.federated.
        round.RoundPlanner`, and cached under ``(round context,
        FEDERATED_TOKEN, population_key)`` in the same
        :class:`~repro.fleet.PlanCache` as per-device plans — the key
        shapes guarantee a round entry can never alias one (see
        ``PlanCache.get_by_key``).  Returns the round's
        :class:`~repro.federated.round.RoundRecord`.
        """
        t_start = time.perf_counter()
        population = list(population)
        if not population:
            raise ValueError("population must be non-empty")
        if deadline is None:
            deadline = self.round_planner.resolve_deadline(population)
        bucket = self._population_bucket(len(population))
        key = (self.round_planner.cache_context(self.consts),
               FEDERATED_TOKEN,
               population_key(population, deadline,
                              self.config.sig_digits))
        self.recorder.count("round_requests")
        record = self.cache.get_by_key(key, label=FEDERATED_KIND)
        if record is None:
            with trace_delta() as traces:
                plan = self.round_planner.plan_round(
                    population, self.consts, deadline=deadline,
                    pad_to=bucket)
            record = plan.record()
            self.cache.put_by_key(key, record)
            self.recorder.record_bucket(FEDERATED_KIND, "dense", bucket,
                                        requests=1, batches=1,
                                        compiles=traces.total)
            if traces.total and self.warmed:
                self.recorder.count("post_warmup_traces", traces.total)
        else:
            self.recorder.record_bucket(FEDERATED_KIND, "dense", bucket,
                                        requests=1)
        latency = time.perf_counter() - t_start
        self.recorder.count("planned")
        self.recorder.record_latency(latency,
                                     key=(FEDERATED_KIND, "dense", bucket))
        self.federated.observe(record, latency)
        self.journal.emit("federated_round", devices=len(population),
                          bucket=bucket, k=record.n_participants,
                          eligible=record.n_eligible,
                          feasible=record.feasible,
                          deadline=round(float(deadline), 6))
        return record

    def _chunk_buckets(self, n: int):
        """Greedy bucket cover of ``n`` requests: repeatedly the largest
        configured bucket that fits, then one padded smallest bucket for
        the remainder — so a 100-request group costs 64+64 solve lanes,
        not a single 256-lane solve (wasted pad lanes are bounded by the
        smallest bucket, and every chunk shape is a warmed executable)."""
        buckets = self.config.batch_buckets
        out = []
        while n > 0:
            b = next((b for b in reversed(buckets) if b <= n), buckets[0])
            out.append(int(b))
            n -= min(int(b), n)
        return out

    def _plan_group(self, requests) -> None:
        """Worker-side: solve one (objective, grid mode)-homogeneous
        micro-batch through the cache, resolve its futures, and record
        one :class:`RequestSpan` per request.

        Phase attribution: every phase is a contiguous interval cut from
        the same ``perf_counter`` timeline — ``batch_wait`` (enqueue ->
        chunk start, per request), then the chunk-shared ``pad`` /
        ``cache_lookup`` / ``solve`` (``plan_many`` reports the latter
        two; ``pad`` is its remaining interior: batch formation and pad
        lanes) and ``resolve`` (everything after ``plan_many`` returns:
        session delivery and future resolution, defined as the remainder
        so the five phases sum EXACTLY to the enqueue-to-plan latency).
        """
        objective = requests[0].objective
        mode = requests[0].grid_mode
        oid, _ = self._resolve_objective(objective)
        res = self.resilience

        # Resilience triage: budget-exhausted requests degrade instead
        # of solving late, and an open breaker routes the whole group to
        # the ladder (allow() is also what promotes open -> half-open
        # after the cooldown, making this solve the probe).  With no
        # budgets, no faults, and a closed breaker this adds nothing to
        # the path: same plan_many, bitwise-identical records.
        with runtime.span("serve.pad"):
            degraded = []  # (request, reason) pairs for the ladder
            solve_reqs, over_budget = res.split_over_budget(requests, oid,
                                                            mode)
            degraded.extend((r, "budget") for r in over_budget)
            if solve_reqs and not res.breaker(oid, mode).allow():
                degraded.extend((r, "breaker_open") for r in solve_reqs)
                solve_reqs = []
            buckets = (self._chunk_buckets(len(solve_reqs))
                       if solve_reqs else ())

        lo = 0
        for bucket in buckets:
            chunk = solve_reqs[lo:lo + bucket]
            lo += len(chunk)
            try:
                self._solve_chunk(oid, mode, bucket, chunk, objective)
            except Exception:  # noqa: BLE001 — retries exhausted: degrade
                degraded.extend((r, "solve_failed") for r in chunk)
        if degraded:
            self._degrade_requests(oid, mode, objective, degraded)

    def _solve_chunk(self, oid: str, mode: str, bucket: int, chunk,
                     objective) -> None:
        """Solve one padded chunk (under retry/fault injection), resolve
        its futures, and record its spans.  Raises once retries are
        exhausted — the caller sends the chunk down the ladder.

        The chunk's record (``repro.obs.runtime``) holds the leaves the
        worker closed since it wrote the previous chunk, this chunk's
        ``serve.resolve`` last; its ``serve.record`` (the bookkeeping
        below, the span write included) lands in the next chunk's."""
        res = self.resilience
        t_chunk = time.perf_counter()
        timings: Dict[str, float] = {}

        def _attempt():
            timings.clear()
            return self.planner.plan_many(
                [r.scenario for r in chunk], self.consts,
                cache=self.cache, pad_to=bucket, objective=objective,
                grid_mode=mode, timings=timings)

        with trace_delta() as traces:
            records = res.run_attempts(oid, mode, _attempt)
        t_planned = time.perf_counter()
        with runtime.span("serve.resolve"):
            for request, record in zip(chunk, records):
                if request.session_id is not None:
                    self._deliver_to_session(request.session_id, record)
                request.future.set_result(record)
        t_end = time.perf_counter()

        with runtime.span("serve.record"):
            taken = runtime.take_record() or ({}, {}, 0.0)
            self.recorder.record_bucket(oid, mode, bucket,
                                        requests=len(chunk), batches=1,
                                        compiles=traces.total)
            self.recorder.count("batches")
            self.recorder.count("planned", len(chunk))
            if traces.total and self.warmed:
                self.recorder.count("post_warmup_traces", traces.total)
            cache_s = timings.get("cache_lookup_s", 0.0)
            solve_s = timings.get("solve_s", 0.0)
            res.estimator.observe(oid, mode, solve_s)
            if records:
                res.note_last_good(oid, mode, records[-1])
            pad_s = max(0.0, (t_planned - t_chunk) - cache_s - solve_s)
            resolve_s = max(0.0, (t_end - t_chunk)
                            - (pad_s + cache_s + solve_s))
            enqueue_t = np.fromiter((r.enqueue_t for r in chunk),
                                    np.float64, len(chunk))
            key = (oid, mode, bucket)
            for latency in (t_end - enqueue_t).tolist():
                self.recorder.record_latency(latency, key=key)
            phases, counts, gc_s = taken
            self.spans.record_chunk(
                objective=oid, grid_mode=mode, bucket=bucket,
                enqueue_t=enqueue_t,
                admit_s=np.fromiter((r.admit_s for r in chunk),
                                    np.float64, len(chunk)),
                t_start=t_chunk, t_end=t_end, pad_s=pad_s,
                cache_lookup_s=cache_s, solve_s=solve_s,
                resolve_s=resolve_s, flush_id=chunk[0].flush_id,
                phases=phases, counts=counts, gc_s=gc_s)

    def _finish_degraded(self, request, record, oid: str, mode: str,
                         t_start: float) -> None:
        """Resolve one degraded request: deliver, count, span (bucket 0
        marks ladder-served requests; phases still sum to latency)."""
        if request.session_id is not None:
            self._deliver_to_session(request.session_id, record)
        request.future.set_result(record)
        t_end = time.perf_counter()
        latency = t_end - request.enqueue_t
        self.recorder.count("planned")
        self.recorder.count("degraded")
        self.recorder.record_latency(latency, key=(oid, mode, 0))
        batch_wait = max(0.0, t_start - request.enqueue_t)
        self.spans.record(RequestSpan(
            objective=oid, grid_mode=mode, bucket=0,
            enqueue_t=request.enqueue_t, admit_s=request.admit_s,
            batch_wait_s=batch_wait, pad_s=0.0, cache_lookup_s=0.0,
            solve_s=0.0, solve_device_s=0.0,
            resolve_s=max(0.0, latency - batch_wait),
            latency_s=latency, flush_id=request.flush_id))

    def _degrade_requests(self, oid: str, mode: str, objective,
                          pairs) -> None:
        """Walk the fallback ladder for requests that can't take (or
        survived retries of) the real solve: cached -> bound ->
        last_good, stamping and counting the level that answered.  A
        request only errors (DegradationExhausted) when every rung comes
        up empty — the 100%-completion guarantee under chaos."""
        res = self.resilience
        t_start = time.perf_counter()
        context = self.planner.cache_context(self.consts, mode)
        remaining = []
        for request, reason in pairs:
            cached = self.cache.peek(request.scenario, context=context,
                                     objective=objective)
            if cached is not None:
                res.count_fallback("cached", reason)
                self._finish_degraded(
                    request,
                    dataclasses.replace(cached, fallback="cached"),
                    oid, mode, t_start)
            else:
                remaining.append((request, reason))
        if not remaining:
            return
        # bound rung: batched dense Corollary-1 at warmed chunk shapes
        try:
            lo = 0
            for bucket in self._chunk_buckets(len(remaining)):
                chunk = remaining[lo:lo + bucket]
                lo += len(chunk)
                with trace_delta() as traces:
                    records = self.planner.plan_many(
                        [r.scenario for r, _ in chunk], self.consts,
                        cache=self.cache, pad_to=bucket,
                        objective=self._fallback_objective,
                        grid_mode="dense")
                self.recorder.record_bucket(
                    "corollary1", "dense", bucket,
                    requests=len(chunk), batches=1, compiles=traces.total)
                if traces.total and self.warmed:
                    self.recorder.count("post_warmup_traces", traces.total)
                for (request, reason), record in zip(chunk, records):
                    res.count_fallback("bound", reason)
                    self._finish_degraded(
                        request,
                        dataclasses.replace(record, fallback="bound"),
                        oid, mode, t_start)
            return
        except Exception:  # noqa: BLE001 — bound rung failed: last rung
            pass
        last = res.last_good(oid, mode)
        for request, reason in remaining:
            if request.future.done():
                continue
            if last is not None:
                res.count_fallback("last_good", reason)
                self._finish_degraded(
                    request,
                    dataclasses.replace(last, fallback="last_good"),
                    oid, mode, t_start)
            else:
                res.note_exhausted()
                request.future.set_exception(DegradationExhausted(
                    f"no fallback available for ({oid}, {mode}): "
                    f"reason={reason}"))

    # -- sessions and drift -------------------------------------------------

    def open_session(self, session_id: str, scenario: Scenario, *,
                     objective: Any = None,
                     grid_mode: Optional[str] = None) -> "Future":
        """Register a live session and enqueue its first plan.  The
        returned future resolves to the initial plan; the session keeps
        tracking the latest one (``service.session(id).plan``)."""
        _, inst, mode, _ = self._admit(scenario, objective, grid_mode)
        session = Session(session_id=session_id, scenario=scenario,
                          objective=inst, grid_mode=mode)
        self.sessions.open(session)
        session.replan_pending = True
        self.journal.emit("session_open", session_id=session_id,
                          objective=getattr(inst, "objective_id", None),
                          grid_mode=mode)
        return self.submit(scenario, objective=inst, grid_mode=mode,
                           session_id=session_id)

    def session(self, session_id: str) -> Session:
        return self.sessions.get(session_id)

    def close_session(self, session_id: str) -> Optional[Session]:
        session = self.sessions.close(session_id)
        if session is not None:
            self.journal.emit("session_close", session_id=session_id,
                              generation=session.generation,
                              replans=session.replans,
                              observations=session.n_observations)
        return session

    def _deliver_to_session(self, session_id: str, record) -> None:
        try:
            session = self.sessions.get(session_id)
        except KeyError:
            return  # closed while its plan was in flight
        with self._lock:
            session.plan = record
            session.generation += 1
            session.replan_pending = False

    def observe(self, session_id: str, losses) -> Optional["Future"]:
        """Stream a session's observed per-attempt loss outcomes
        (iterable of bools, e.g. sampled from
        ``link.make_loss_process``).  When the observed EWMA drifts past
        the threshold, re-estimates the link, invalidates the stale
        prefix-keyed cache entry and re-enqueues the corrected scenario
        — returning the re-plan future (else ``None``)."""
        session = self.sessions.get(session_id)
        session.observe(losses)
        if not self.sessions.drifted(session):
            return None
        self.recorder.count("drift_detected")
        self.journal.emit("drift_detected", session_id=session_id,
                          ewma=round(session.ewma, 6),
                          planned_p_err=round(session.plan.p_err, 6))
        new_link = reestimate_link(session.scenario.link,
                                   session.plan.rate, session.ewma)
        if new_link is None:
            self.recorder.count("drift_unactionable")
            self.journal.emit("drift_unactionable", session_id=session_id,
                              ewma=round(session.ewma, 6))
            return None
        with self._lock:
            if session.replan_pending:
                return None  # a racing observe already re-enqueued
            session.replan_pending = True
            session.replans += 1
            stale = session.scenario
            session.scenario = dataclasses.replace(stale, link=new_link)
        # drop the stale plan for EVERY session collapsing onto this
        # quantised key — the whole device class drifted, not one radio
        context = self.planner.cache_context(self.consts, session.grid_mode)
        self.cache.invalidate(stale, context=context,
                              objective=session.objective)
        self.recorder.count("drift_replans")
        self.journal.emit("drift_replan", session_id=session_id,
                          replans=session.replans,
                          ewma=round(session.ewma, 6))
        return self.submit(session.scenario, objective=session.objective,
                           grid_mode=session.grid_mode,
                           session_id=session_id)

    # -- observability ------------------------------------------------------

    def health(self) -> HealthReport:
        """STARTING/READY/DEGRADED/SHEDDING readiness, derived from
        warmup state, queue depth vs the bound, breaker states, and the
        drift re-plan backlog.  State changes land in the journal."""
        return self.resilience.health(
            warmed=self.warmed,
            queue_depth=self.batcher.depth,
            max_pending=self.config.max_pending,
            drift_backlog=self.sessions.pending_replans(),
            drift_backlog_limit=self.config.health_drift_backlog)

    def stats(self) -> ServiceStats:
        self.recorder.count("sessions_open", 0)  # ensure key exists
        snapshot = self.recorder.snapshot(queue_depth=self.batcher.depth,
                                          cache_stats=self.cache.stats())
        snapshot.counters["sessions_open"] = len(self.sessions)
        snapshot.counters["idle_ticks"] = self.batcher.idle_ticks
        snapshot.counters.setdefault("post_warmup_traces", 0)
        snapshot.counters.setdefault("shed", 0)
        snapshot.counters.setdefault("degraded", 0)
        snapshot.counters["warmup_traces"] = self.warmup_traces
        for cause, n in self.batcher.flush_causes.items():
            snapshot.counters[f"flushes_{cause}"] = n
        return dataclasses.replace(
            snapshot, phases=self.spans.totals(),
            solve_fraction=self.spans.solve_fraction,
            resilience=self.resilience.snapshot())

    def prometheus_text(self) -> str:
        """The full Prometheus text exposition across every source."""
        return self.metrics.prometheus_text()

    def metrics_snapshot(self) -> Dict[str, Dict[tuple, float]]:
        """Every exported series as ``{name: {label_tuple: value}}`` —
        the render/parse round-trip, so reading it also validates the
        export (see :meth:`MetricsRegistry.snapshot`)."""
        return self.metrics.snapshot()
