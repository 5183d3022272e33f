"""Resilience layer: deterministic fault injection, retry/backoff +
circuit breaker, deadline-budget degradation ladder, bounded-queue
shedding, health states, and the chaos gate (faults on -> 100%
completion with stamped fallbacks; faults off -> bitwise parity)."""
import threading
import time
from concurrent.futures import Future

import pytest

from repro.chaos import (INJECTION_POINTS, FaultPlan, FaultRule,
                         InjectedFault, parse_chaos_spec)
from repro.fleet import FleetPlanner, PlanCache
from repro.serve import (BREAKER_STATES, FALLBACK_LEVELS, HEALTH_STATES,
                         CircuitBreaker, LoadSheddingPolicy, MicroBatcher,
                         PlanRequest, PlanningService, QueueFull,
                         RequestShed, ResilienceManager, RetryPolicy,
                         ServiceConfig, SolveTimeEstimator, policy_spec,
                         synth_requests)
from repro.serve.resilience import CLOSED, HALF_OPEN, OPEN

# same small warm population the serving tests use (keep grids tiny:
# CI runs on one CPU core)
SMALL = dict(grid_size=16, batch_buckets=(4, 8), flush_interval=0.01,
             objective_ids=("corollary1", "markov_arq"), n_max=512,
             min_observations=4)

# every injection point enabled, transient rates: most solves succeed,
# some chunks exhaust their retries and walk the ladder
CHAOS_SPEC = ("seed=7,solve_error=0.4,solve_latency=0.2:2ms,"
              "cache_corrupt=0.3,queue_stall=0.2:1ms")


# ---------------------------------------------------------------------------
# FaultPlan: determinism, spec grammar, counters
# ---------------------------------------------------------------------------

def test_fault_plan_schedule_is_deterministic_and_pure():
    a = parse_chaos_spec(CHAOS_SPEC)
    b = parse_chaos_spec(CHAOS_SPEC)
    for point in INJECTION_POINTS:
        assert a.schedule(point, 64) == b.schedule(point, 64)
    # schedule() is pure: it never advances the draw counters
    assert a.draws == {}
    # draw() follows the published schedule exactly
    want = a.schedule("solve.error", 32)
    got = [a.draw("solve.error") is not None for _ in range(32)]
    assert got == want
    assert a.fires.get("solve.error", 0) == sum(want)
    assert a.draws["solve.error"] == 32
    # reset() rewinds to a byte-identical replay
    a.reset()
    assert [a.draw("solve.error") is not None for _ in range(32)] == want


def test_fault_plan_points_are_independent():
    plan = FaultPlan(seed=3, rules=(FaultRule("solve.error", 0.5),
                                    FaultRule("cache.corrupt", 0.5)))
    want = plan.schedule("cache.corrupt", 16)
    # interleave draws at another point: cache.corrupt's schedule must
    # not shift (no shared RNG stream)
    got = []
    for _ in range(16):
        plan.draw("solve.error")
        got.append(plan.draw("cache.corrupt") is not None)
    assert got == want


def test_parse_chaos_spec_grammar_and_round_trip():
    plan = parse_chaos_spec(CHAOS_SPEC)
    assert plan.seed == 7
    assert plan.rules["solve.latency"].duration_s == pytest.approx(2e-3)
    assert not plan.rules["cache.corrupt"].duration_s
    # spec() round-trips through the parser to the same schedule
    again = parse_chaos_spec(plan.spec())
    for point in INJECTION_POINTS:
        assert again.schedule(point, 32) == plan.schedule(point, 32)
    assert parse_chaos_spec("").rules == {}      # empty = clean control
    with pytest.raises(ValueError, match="unknown injection point"):
        parse_chaos_spec("solve_eror=0.5")
    with pytest.raises(ValueError, match="bad rate"):
        parse_chaos_spec("solve_error=lots")
    with pytest.raises(ValueError, match="bare rate"):
        parse_chaos_spec("solve_error=0.5:10ms")
    with pytest.raises(ValueError, match="rate must be in"):
        parse_chaos_spec("solve_error=1.5")
    with pytest.raises(ValueError, match="unknown injection point"):
        FaultPlan(rules=(FaultRule("bogus.point", 0.5),))


# ---------------------------------------------------------------------------
# CircuitBreaker: transitions, probes, recovery
# ---------------------------------------------------------------------------

def _clocked_breaker(**kw):
    t = [0.0]
    transitions = []
    brk = CircuitBreaker(clock=lambda: t[0],
                         on_transition=lambda a, b: transitions.append(
                             (a, b)), **kw)
    return brk, t, transitions


def test_breaker_full_cycle_and_counters():
    brk, t, transitions = _clocked_breaker(threshold=2, cooldown_s=1.0)
    assert brk.state == CLOSED and brk.allow()
    brk.record_failure()
    assert brk.state == CLOSED          # below threshold
    brk.record_failure()
    assert brk.state == OPEN and brk.trips == 1
    assert not brk.allow()              # cooldown not elapsed
    t[0] += 1.0
    assert brk.allow()                  # promotes + admits the probe
    assert brk.state == HALF_OPEN and brk.probes == 1
    brk.record_failure()                # probe failed: re-open
    assert brk.state == OPEN and brk.trips == 1   # re-open is not a trip
    t[0] += 1.0
    assert brk.allow() and brk.state == HALF_OPEN
    brk.record_success()                # probe succeeded: recover
    assert brk.state == CLOSED and brk.recoveries == 1
    assert brk.failures == 0
    # transitions never skip a state
    legal = {(CLOSED, OPEN), (OPEN, HALF_OPEN),
             (HALF_OPEN, CLOSED), (HALF_OPEN, OPEN)}
    assert set(transitions) <= legal
    assert transitions[0] == (CLOSED, OPEN)


def test_breaker_success_resets_consecutive_failures():
    brk, _, _ = _clocked_breaker(threshold=3, cooldown_s=1.0)
    brk.record_failure()
    brk.record_failure()
    brk.record_success()                # streak broken
    brk.record_failure()
    brk.record_failure()
    assert brk.state == CLOSED          # 2 < threshold again
    with pytest.raises(ValueError, match="threshold"):
        CircuitBreaker(threshold=0)


# ---------------------------------------------------------------------------
# hypothesis: breaker state machine + chaos determinism property
# ---------------------------------------------------------------------------

def test_breaker_state_machine_never_skips_states():
    pytest.importorskip("hypothesis")
    from hypothesis import settings
    from hypothesis.stateful import (RuleBasedStateMachine, rule,
                                     invariant, run_state_machine_as_test)

    class BreakerMachine(RuleBasedStateMachine):
        def __init__(self):
            super().__init__()
            self.t = 0.0
            self.transitions = []
            self.brk = CircuitBreaker(
                threshold=2, cooldown_s=1.0, clock=lambda: self.t,
                on_transition=lambda a, b: self.transitions.append((a, b)))

        @rule()
        def allow(self):
            before = self.brk.state
            probes = self.brk.probes
            admitted = self.brk.allow()
            if before == CLOSED:
                assert admitted
            # a probe is only ever admitted from (or into) half-open
            if self.brk.probes > probes:
                assert self.brk.state == HALF_OPEN

        @rule()
        def succeed(self):
            self.brk.record_success()
            assert self.brk.state in (CLOSED, OPEN)

        @rule()
        def fail(self):
            self.brk.record_failure()

        @rule()
        def tick(self):
            self.t += 0.6

        @invariant()
        def state_is_valid_and_transitions_are_adjacent(self):
            assert self.brk.state in BREAKER_STATES
            legal = {(CLOSED, OPEN), (OPEN, HALF_OPEN),
                     (HALF_OPEN, CLOSED), (HALF_OPEN, OPEN)}
            assert set(self.transitions) <= legal
            for (_, into), (frm, _) in zip(self.transitions,
                                           self.transitions[1:]):
                assert frm == into      # the chain has no gaps

    run_state_machine_as_test(
        BreakerMachine, settings=settings(max_examples=30,
                                          stateful_step_count=40,
                                          deadline=None))


def test_chaos_schedule_property_same_seed_same_faults():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(seed=st.integers(0, 2**32 - 1),
           rate=st.floats(0.0, 1.0, allow_nan=False))
    def check(seed, rate):
        mk = lambda: FaultPlan(seed=seed, rules=(  # noqa: E731
            FaultRule("solve.error", rate),))
        a, b = mk(), mk()
        sched = a.schedule("solve.error", 40)
        assert sched == b.schedule("solve.error", 40)
        assert [b.draw("solve.error") is not None
                for _ in range(40)] == sched
        # rate bounds the empirical fire fraction only degenerately
        if rate == 0.0:
            assert not any(sched)
        if rate == 1.0:
            assert all(sched)

    check()


# ---------------------------------------------------------------------------
# RetryPolicy, estimator, manager-level retry/breaker loop
# ---------------------------------------------------------------------------

def test_retry_policy_backoff_is_seeded_and_capped():
    policy = RetryPolicy(attempts=5, base_s=0.01, cap_s=0.05, seed=11)
    a = [policy.delays().next_delay() for _ in range(1)]
    d1, d2 = policy.delays(), policy.delays()
    seq1 = [d1.next_delay() for _ in range(6)]
    seq2 = [d2.next_delay() for _ in range(6)]
    assert seq1 == seq2                    # same seed, same sequence
    assert all(0.01 <= d <= 0.05 for d in seq1 + a)
    with pytest.raises(ValueError, match="attempts"):
        RetryPolicy(attempts=0)
    with pytest.raises(ValueError, match="base_s"):
        RetryPolicy(base_s=0.5, cap_s=0.1)


def test_solve_time_estimator_quantile_and_empty():
    est = SolveTimeEstimator(quantile=90.0)
    assert est.estimate("corollary1", "dense") == 0.0   # optimistic
    for ms in (1, 1, 1, 1, 1, 1, 1, 1, 50, 50):
        est.observe("corollary1", "dense", ms * 1e-3)
    q = est.estimate("corollary1", "dense")
    assert q > 5e-3                        # the p90 sees the slow tail
    assert est.estimate("corollary1", "refine") == 0.0  # keys separate


def test_run_attempts_retries_then_raises_and_trips_breaker():
    mgr = ResilienceManager(retry=RetryPolicy(attempts=3, base_s=1e-4,
                                              cap_s=1e-3),
                            breaker_threshold=3, breaker_cooldown_s=9.0)
    calls = []
    naps = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"
    assert mgr.run_attempts("o", "dense", flaky,
                            sleep=naps.append) == "ok"
    assert len(calls) == 3 and mgr.retries == 2 and len(naps) == 2
    assert mgr.breaker("o", "dense").state == CLOSED  # success reset it

    def always():
        raise RuntimeError("hard")
    with pytest.raises(RuntimeError, match="hard"):
        mgr.run_attempts("o", "dense", always, sleep=naps.append)
    assert mgr.breaker("o", "dense").state == OPEN
    snap = mgr.snapshot()
    assert snap["breakers"][("o", "dense")]["trips"] == 1
    assert snap["retries"] == mgr.retries
    assert snap["backoff_seconds"] == pytest.approx(sum(naps))


def test_run_attempts_breaker_recovery_via_half_open_probe():
    t = [0.0]
    mgr = ResilienceManager(retry=RetryPolicy(attempts=1),
                            breaker_threshold=2, breaker_cooldown_s=1.0,
                            clock=lambda: t[0])

    def boom():
        raise RuntimeError("down")
    for _ in range(2):
        with pytest.raises(RuntimeError):
            mgr.run_attempts("o", "dense", boom, sleep=lambda s: None)
    brk = mgr.breaker("o", "dense")
    assert brk.state == OPEN and not brk.allow()
    t[0] += 1.0
    assert brk.allow()                    # the half-open probe admission
    assert brk.state == HALF_OPEN and brk.probes == 1
    mgr.run_attempts("o", "dense", lambda: "ok", sleep=lambda s: None)
    assert brk.state == CLOSED and brk.recoveries == 1


def test_manager_health_derivation():
    mgr = ResilienceManager()
    assert mgr.health(warmed=False, queue_depth=0,
                      max_pending=0).state == "STARTING"
    assert mgr.health(warmed=True, queue_depth=0,
                      max_pending=0).state == "READY"
    report = mgr.health(warmed=True, queue_depth=4, max_pending=4)
    assert report.state == "SHEDDING" and not report.ready
    brk = mgr.breaker("o", "dense")
    brk.record_failure()
    for _ in range(mgr.breaker_threshold):
        brk.record_failure()
    report = mgr.health(warmed=True, queue_depth=0, max_pending=4)
    assert report.state == "DEGRADED" and report.ready
    assert any("breaker" in r for r in report.reasons)
    drift = mgr.health(warmed=True, queue_depth=0, max_pending=0,
                       drift_backlog=8, drift_backlog_limit=8)
    assert drift.state == "DEGRADED"
    assert [s for s in HEALTH_STATES] == \
        ["STARTING", "READY", "DEGRADED", "SHEDDING"]


# ---------------------------------------------------------------------------
# Bounded ingestion queue + load-shedding policy + corrupting cache
# ---------------------------------------------------------------------------

def test_bounded_queue_rejects_instead_of_blocking():
    release = threading.Event()

    def plan_group(reqs):
        release.wait(timeout=10.0)
        for r in reqs:
            r.future.set_result(r.scenario)
    b = MicroBatcher(plan_group, max_batch=1, flush_interval=0.005,
                     max_pending=2)
    b.start()
    try:
        first = b.submit(PlanRequest(scenario=0))
        # the worker is stuck in plan_group holding request 0, so these
        # two fill the bounded queue...
        deadline = time.monotonic() + 5.0
        queued = []
        while len(queued) < 2 and time.monotonic() < deadline:
            try:
                queued.append(b.submit(PlanRequest(scenario=1)))
            except QueueFull:
                time.sleep(0.001)
        assert len(queued) == 2
        # ... and the next submit is REJECTED immediately, not blocked
        t0 = time.monotonic()
        with pytest.raises(QueueFull, match="capacity"):
            b.submit(PlanRequest(scenario=2))
        assert time.monotonic() - t0 < 1.0
        assert b.rejections >= 1
        release.set()
        assert first.result(timeout=5.0) == 0
        for f in queued:
            assert f.result(timeout=5.0) == 1
    finally:
        release.set()
        b.stop()
    with pytest.raises(ValueError, match="max_pending"):
        MicroBatcher(plan_group, max_pending=-1)


def test_load_shedding_policy_sheds_at_threshold():
    spec = policy_spec("load_shedding")
    policy = spec.cls()
    assert isinstance(policy, LoadSheddingPolicy)
    sc = synth_requests(1, seed=0, models=("erasure",), n_max=512)[0]
    ok = policy.admit(sc, load=0.0)
    assert ok.action == "accept" and ok.accepted
    shed = policy.admit(sc, load=policy.shed_load)
    assert shed.action == "shed" and not shed.accepted
    # the shed decision still carries the inner policy's routing
    assert shed.objective_id == policy.admit(sc, load=0.0).objective_id


def test_cache_checksums_detect_injected_corruption():
    sc = synth_requests(1, seed=1, models=("erasure",), n_max=512)[0]
    hits = [True, False]     # corrupt the first read only
    cache = PlanCache(maxsize=8, corruptor=lambda: hits.pop(0)
                      if hits else False)
    cache.put(sc, "record")
    assert cache.get(sc) is None         # corrupted -> dropped, a miss
    assert cache.corruptions == 1 and cache.misses == 1
    cache.put(sc, "record")
    assert cache.get(sc) == "record"     # clean read round-trips
    assert cache.stats()["corruptions"] == 1
    # peek never draws corruption and never counts
    always = PlanCache(maxsize=8, corruptor=lambda: True)
    always.put(sc, "record")
    assert always.peek(sc) == "record"
    assert always.corruptions == 0 and always.hits + always.misses == 0


# ---------------------------------------------------------------------------
# Service-level: chaos gate, deterministic degrade, budgets, recovery
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chaos_service():
    """Warm service under transient chaos at every injection point."""
    cfg = ServiceConfig(retry_attempts=2, breaker_threshold=3,
                        breaker_cooldown_s=0.05, chaos_spec=CHAOS_SPEC,
                        **SMALL)
    service = PlanningService(cfg)
    service.warmup()
    service.start()
    yield service
    service.stop()


@pytest.fixture(scope="module")
def blackout_service():
    """Warm service whose every requested-objective solve fails: the
    degradation ladder is the only way a request completes."""
    cfg = ServiceConfig(retry_attempts=2, breaker_threshold=3,
                        breaker_cooldown_s=30.0,
                        chaos_spec="seed=1,solve_error=1.0", **SMALL)
    service = PlanningService(cfg)
    service.warmup()
    service.start()
    yield service
    service.stop()


def test_chaos_gate_completion_stamps_and_parity(chaos_service):
    service = chaos_service
    # dup_frac=0: all-distinct scenarios, so no record is served off the
    # quantised cache (a hit replays the KEY's first plan, which is only
    # grid-resolution-close for a near-duplicate — not bitwise equal)
    requests = synth_requests(48, seed=9, dup_frac=0.0, n_classes=48,
                              models=("erasure", "gilbert_elliott"),
                              n_max=512)
    instances = list(service.objectives.values())
    modes = service.config.grid_modes
    futures, assigned = [], []
    for i, sc in enumerate(requests):
        obj = instances[i % len(instances)]
        mode = modes[i % len(modes)]
        futures.append(service.submit(sc, objective=obj, grid_mode=mode))
        assigned.append((obj, mode))
    # 100% completion: chaos may degrade answers, never lose them
    records = [f.result(timeout=120) for f in futures]
    assert all(r is not None for r in records)

    stats = service.stats()
    snap = stats.resilience
    # faults actually fired (the run was a chaos run, not a control)
    assert sum(snap["faults_injected"].values()) > 0
    assert snap["faults_injected"].get("solve.error", 0) > 0
    # every non-full record is stamped with a ladder level AND counted
    degraded = [r for r in records if r.fallback != "full"]
    for rec in degraded:
        assert rec.fallback in FALLBACK_LEVELS[1:]
    assert sum(snap["fallbacks"].values()) >= len(degraded)
    if degraded:
        assert stats.counters["degraded"] >= len(degraded)
        assert sum(snap["degrade_reasons"].values()) >= len(degraded)
    # the fallback ladder never traces post-warmup
    assert stats.counters.get("post_warmup_traces", 0) == 0, stats.buckets
    # faults-off parity: a record the chaos run served at level "full"
    # is bitwise what a direct chaos-free solve produces
    direct = FleetPlanner(grid_size=SMALL["grid_size"],
                          pow2_refine_widths=True)
    checked = 0
    for sc, rec, (obj, mode) in zip(requests, records, assigned):
        if rec.fallback != "full" or checked >= 8:
            continue
        want = direct.plan_many([sc], service.consts, objective=obj,
                                grid_mode=mode)[0]
        assert want == rec
        checked += 1
    assert checked > 0


def test_blackout_degrades_every_request_deterministically(
        blackout_service):
    service = blackout_service
    requests = synth_requests(12, seed=4, dup_frac=0.0, n_classes=12,
                              models=("erasure",), n_max=512)
    obj = service.objectives["markov_arq"]
    futures = [service.submit(sc, objective=obj, grid_mode="dense")
               for sc in requests]
    records = [f.result(timeout=120) for f in futures]
    # every solve failed, so every answer came off the ladder
    assert all(r.fallback in ("cached", "bound", "last_good")
               for r in records)
    # the bound rung serves the dense Corollary-1 objective
    bound = [r for r in records if r.fallback == "bound"]
    assert bound and all(r.objective == "corollary1" for r in bound)
    snap = service.stats().resilience
    assert snap["fallbacks"].get("bound", 0) >= len(bound)
    assert snap["degrade_reasons"].get("solve_failed", 0) > 0
    # enough consecutive failures tripped the group's breaker
    brk = service.resilience.breaker("markov_arq", "dense")
    assert brk.state in (OPEN, HALF_OPEN) and brk.trips >= 1
    assert service.health().state == "DEGRADED"
    assert service.stats().counters.get("post_warmup_traces", 0) == 0


def test_blackout_budget_triage_degrades_before_solving(
        blackout_service):
    service = blackout_service
    sc = synth_requests(1, seed=6, models=("erasure",), n_max=512)[0]
    before = service.stats().resilience["budget_exceeded"]
    fut = service.submit(sc, objective="corollary1", grid_mode="dense",
                         budget_s=1e-9)
    rec = fut.result(timeout=120)
    # the budget was blown before the solve could run: degraded, and
    # counted as a budget degrade (not a solve failure)
    assert rec.fallback in ("cached", "bound", "last_good")
    assert service.stats().resilience["budget_exceeded"] > before


def test_blackout_breaker_recovers_once_faults_clear(blackout_service):
    service = blackout_service
    # the markov_arq/dense breaker is open (tripped by the test above;
    # trip it here too so this test stands alone), then the fault rule
    # is cleared — the outage "ends"
    brk = service.resilience.breaker("markov_arq", "dense")
    while brk.state == CLOSED:
        brk.record_failure()
    service.faults.rules["solve.error"] = FaultRule("solve.error", 0.0)
    brk.cooldown_s = 0.0                  # cooldown elapses immediately
    sc = synth_requests(1, seed=8, models=("erasure",), n_max=512)[0]
    fut = service.submit(sc, objective="markov_arq", grid_mode="dense")
    rec = fut.result(timeout=120)
    # ... and the half-open probe solve recovers the breaker
    assert rec.fallback == "full"
    assert brk.state == CLOSED and brk.recoveries >= 1 \
        and brk.probes >= 1
    assert service.health().state == "READY"


def test_resilience_metrics_exported(chaos_service):
    # ensure the per-breaker families have at least one series
    chaos_service.resilience.breaker("corollary1", "dense")
    snap = chaos_service.metrics_snapshot()
    for family in ("repro_resilience_fallbacks_total",
                   "repro_resilience_retries_total",
                   "repro_resilience_faults_injected_total",
                   "repro_resilience_breaker_state",
                   "repro_resilience_breaker_trips_total",
                   "repro_resilience_health_state"):
        assert family in snap, sorted(snap)
    states = snap["repro_resilience_health_state"]
    assert list(states.values())[0] in range(len(HEALTH_STATES))
    onehot = snap["repro_resilience_health"]
    assert sum(onehot.values()) == 1.0    # exactly one state is current
    # ladder levels and ENABLED injection points are pre-declared at 0,
    # so dashboards can rate() them before the first incident
    levels = {dict(lbls)["level"] for lbls in
              snap["repro_resilience_fallbacks_total"]}
    assert set(FALLBACK_LEVELS[1:]) <= levels
    points = {dict(lbls)["point"] for lbls in
              snap["repro_resilience_faults_injected_total"]}
    assert {"solve.error", "solve.latency", "queue.stall",
            "cache.corrupt"} <= points


def test_service_sheds_when_queue_is_full():
    release = threading.Event()
    cfg = ServiceConfig(max_pending=1, flush_interval=30.0,
                        batch_buckets=(4,), grid_size=8,
                        objective_ids=("corollary1",), n_max=512)
    service = PlanningService(cfg)
    # stall the worker without jax: replace the group planner with a gate
    service.batcher._plan_group = lambda reqs: (
        release.wait(timeout=10.0),
        [r.future.set_result(None) for r in reqs])
    service.warmup = lambda *a, **k: 0
    service.start()
    try:
        sc = synth_requests(1, seed=2, models=("erasure",), n_max=512)[0]
        service.submit(sc, objective="corollary1", grid_mode="dense")
        deadline = time.monotonic() + 5.0
        shed = None
        while shed is None and time.monotonic() < deadline:
            try:
                service.submit(sc, objective="corollary1",
                               grid_mode="dense")
            except RequestShed as exc:
                shed = exc
            time.sleep(0.001)
        assert shed is not None
        snap = service.stats()
        assert snap.counters["shed"] >= 1
        assert snap.resilience["sheds"].get("queue_full", 0) >= 1
    finally:
        release.set()
        service.stop()


def test_chaos_run_replays_identically_on_a_fresh_service():
    """Two fresh services with the same chaos spec serve the same stream
    to identical records, fallback stamps and fault counts."""
    reqs = synth_requests(16, seed=5, dup_frac=0.0, n_classes=16,
                          models=("erasure",), n_max=512)

    def run():
        # the flush deadline is never reached, so every flush takes the
        # next four requests and the fault draws fall on the same solves;
        # a breaker that trips stays open for the whole run
        cfg = ServiceConfig(grid_size=8, batch_buckets=(4,),
                            flush_interval=60.0,
                            objective_ids=("corollary1", "markov_arq"),
                            grid_modes=("dense",), n_max=512,
                            retry_attempts=2, retry_base_s=0.001,
                            retry_cap_s=0.002, breaker_cooldown_s=3600.0,
                            chaos_spec="seed=13,solve_error=0.5")
        service = PlanningService(cfg)
        oids = list(service.objectives)
        with service:
            futures = [service.submit(sc, objective=oids[i % 2],
                                      grid_mode="dense")
                       for i, sc in enumerate(reqs)]
            records = [f.result(timeout=120) for f in futures]
        return records, service.stats().resilience

    (a, res_a), (b, res_b) = run(), run()
    assert a == b                        # fallback stamps included
    assert res_a["faults_injected"] == res_b["faults_injected"]
    assert res_a["fallbacks"] == res_b["fallbacks"]
    assert res_a["faults_injected"].get("solve.error", 0) > 0
    assert sum(r.fallback != "full" for r in a) > 0   # the chaos bit


def test_future_type_contract():
    # PlanRequest futures are concurrent.futures.Future: the shed path
    # must reject BEFORE a future exists, never resolve one with an error
    req = PlanRequest(scenario=None)
    assert isinstance(req.future, Future)
    assert req.remaining_budget() is None          # no budget -> None
    req2 = PlanRequest(scenario=None, budget_s=60.0)
    remaining = req2.remaining_budget()
    assert remaining is not None and 59.0 < remaining <= 60.0
