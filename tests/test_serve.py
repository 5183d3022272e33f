"""Always-on planning service: micro-batcher edge cases, bucketed AOT
warmup (zero post-warmup traces), admission-policy registry, PlanCache
invalidation/stats, session drift -> re-plan, bitwise parity of served
plans against direct ``FleetPlanner.plan_many`` calls, mixed streams
against scalar solves, cache replays, and the serve CLI."""
import threading
import time

import numpy as np
import pytest

from repro.core import (BoundConstants, ErasureLink, FadingLink,
                        GilbertElliottLink, IdealLink, ObjectivePlanner,
                        Scenario)
from repro.core.links import link_spec_for
from repro.core.planner import fleet_grid
from repro.fleet import FleetPlanner, PlanCache
from repro.obs import LEAVES, PHASES
from repro.serve import (ALL_MODELS, AdmissionDecision, MicroBatcher,
                         PlanRequest, PlanningService, ServiceConfig,
                         group_requests, policy_spec, register_policy,
                         registered_policies, reestimate_link,
                         synth_requests, unregister_policy)

CONSTS = BoundConstants(L=1.908, c=0.061, M=1.0, M_G=1.0, D=1.0, alpha=1e-4)
# the catalogue's 5-wide rate set: custom links in service tests must
# match it, or a batch of one would present a NEW padded rate width to
# the jitted kernel and trip the zero-post-warmup-traces assertions
RATES = (1.0, 1.25, 1.5, 2.0, 3.0)

# one small warm population shared by the service tests (keep grids tiny:
# CI runs on one CPU core)
SMALL = dict(grid_size=16, batch_buckets=(4, 8), flush_interval=0.01,
             objective_ids=("corollary1", "markov_arq"), n_max=512,
             min_observations=4)


def _scenario(seed=0, n=1024, link=None):
    rng = np.random.default_rng(seed)
    return Scenario(N=n, T=float(rng.uniform(1.2, 2.0)) * n,
                    n_o=float(rng.uniform(5.0, 500.0)),
                    link=link if link is not None
                    else ErasureLink(beta=0.4, p_base=0.1, rates=RATES))


# ---------------------------------------------------------------------------
# MicroBatcher edge cases (no jax involved: plan_group is a stub)
# ---------------------------------------------------------------------------

def _collecting_batcher(**kw):
    batches = []

    def plan_group(reqs):
        batches.append(list(reqs))
        for r in reqs:
            r.future.set_result(r.scenario)
    return MicroBatcher(plan_group, **kw), batches


def test_batcher_flush_on_size():
    b, batches = _collecting_batcher(max_batch=4, flush_interval=30.0)
    b.start()
    try:
        futs = [b.submit(PlanRequest(scenario=i)) for i in range(4)]
        for f in futs:       # a full batch must flush without the deadline
            assert f.result(timeout=5.0) is not None or True
    finally:
        b.stop()
    assert sum(len(g) for g in batches) == 4


def test_batcher_deadline_flushes_partial_batch():
    b, batches = _collecting_batcher(max_batch=1000, flush_interval=0.02)
    b.start()
    try:
        futs = [b.submit(PlanRequest(scenario=i)) for i in range(3)]
        out = [f.result(timeout=5.0) for f in futs]
        assert out == [0, 1, 2]   # deadline flushed a far-from-full batch
    finally:
        b.stop()
    assert sum(len(g) for g in batches) == 3


def test_batcher_clean_shutdown_drains_queue():
    release = threading.Event()
    done = []

    def slow_plan(reqs):
        release.wait(5.0)
        for r in reqs:
            done.append(r.scenario)
            r.future.set_result(r.scenario)

    b = MicroBatcher(slow_plan, max_batch=2, flush_interval=0.001)
    b.start()
    futs = [b.submit(PlanRequest(scenario=i)) for i in range(7)]
    release.set()
    b.stop(drain=True)            # must plan everything still queued
    assert sorted(done) == list(range(7))
    assert [f.result(timeout=0) for f in futs] == list(range(7))
    with pytest.raises(RuntimeError):
        b.submit(PlanRequest(scenario=99))   # stopped: submissions refused


def test_batcher_stop_without_drain_cancels():
    hold = threading.Event()

    def stall(reqs):
        hold.wait(5.0)
        for r in reqs:
            r.future.set_result(r.scenario)

    b = MicroBatcher(stall, max_batch=1, flush_interval=0.001)
    b.start()
    futs = [b.submit(PlanRequest(scenario=i)) for i in range(5)]
    time.sleep(0.05)              # let the worker take (and stall on) one
    hold.set()
    b.stop(drain=False)
    states = [f.cancelled() for f in futs]
    assert any(states), "queued futures must be cancelled on drain=False"
    for f, cancelled in zip(futs, states):
        if not cancelled:
            f.result(timeout=5.0)  # the in-flight batch still completes


def test_batcher_exception_propagates_to_futures():
    def broken(reqs):
        raise RuntimeError("kernel exploded")

    b = MicroBatcher(broken, max_batch=2, flush_interval=0.001)
    b.start()
    fut = b.submit(PlanRequest(scenario=0))
    with pytest.raises(RuntimeError, match="kernel exploded"):
        fut.result(timeout=5.0)
    b.stop()


def test_group_requests_preserves_interleaved_order():
    obj_a, obj_b = object(), object()
    reqs = [PlanRequest(scenario=i, objective=obj_a if i % 3 else obj_b,
                        grid_mode="dense" if i % 2 else "refine")
            for i in range(12)]
    groups = group_requests(reqs, key=PlanRequest.group_key)
    # every (objective, mode) pair present, first-seen order, and each
    # group preserves arrival order
    assert sum(len(g) for g in groups) == 12
    seen = set()
    for g in groups:
        key = g[0].group_key()
        assert key not in seen
        seen.add(key)
        assert all(r.group_key() == key for r in g)
        assert [r.scenario for r in g] == sorted(r.scenario for r in g)
    assert len(seen) == 4


# ---------------------------------------------------------------------------
# PlanCache invalidation + observable stats (satellite)
# ---------------------------------------------------------------------------

def test_plan_cache_stats_and_invalidate():
    cache = PlanCache(maxsize=2)
    planner = FleetPlanner(grid_size=8)
    scenarios = [_scenario(seed=s, n=512 + 64 * s) for s in range(3)]
    ctx = planner.cache_context(CONSTS)

    planner.plan_many(scenarios[:1], CONSTS, cache=cache)
    planner.plan_many(scenarios[:1], CONSTS, cache=cache)   # hit
    stats = cache.stats()
    assert stats["hits"] == 1 and stats["misses"] == 1
    assert stats["hits_by_objective"] == {"corollary1": 1}
    assert stats["misses_by_objective"] == {"corollary1": 1}

    # invalidate: the exact entry disappears, the next lookup re-solves
    # (entries live under the RESOLVED objective's token, so the caller
    # names the objective — a value-equal instance produces the same key)
    obj = planner._resolve_objective(None)
    assert cache.invalidate(scenarios[0], context=ctx, objective=obj) is True
    assert cache.invalidate(scenarios[0], context=ctx, objective=obj) \
        is False  # idempotent
    stats = cache.stats()
    assert stats["invalidations"] == 1 and stats["size"] == 0
    planner.plan_many(scenarios[:1], CONSTS, cache=cache)
    assert cache.stats()["misses"] == 2

    # LRU eviction is counted
    planner.plan_many(scenarios, CONSTS, cache=cache)
    stats = cache.stats()
    assert stats["size"] == 2
    assert stats["evictions"] >= 1
    assert 0.0 <= stats["hit_rate"] <= 1.0


# ---------------------------------------------------------------------------
# Admission-policy registry (pluggable, mirrors links/objectives)
# ---------------------------------------------------------------------------

def test_policy_registry_builtins_and_plugin():
    ids = {spec.policy_id for spec in registered_policies()}
    assert {"static", "link_aware"} <= ids
    with pytest.raises(KeyError, match="unregistered admission policy"):
        policy_spec("nope")

    @register_policy
    class EverythingMarkov:
        policy_id = "test_all_markov"

        def admit(self, scenario, *, load):
            return AdmissionDecision("markov_arq", "dense")

    try:
        assert policy_spec("test_all_markov").cls is EverythingMarkov
        decision = EverythingMarkov().admit(_scenario(), load=0.0)
        assert decision == AdmissionDecision("markov_arq", "dense")
    finally:
        unregister_policy("test_all_markov")
    with pytest.raises(KeyError):
        policy_spec("test_all_markov")


def test_register_policy_validates_interface():
    with pytest.raises(TypeError, match="policy_id"):
        register_policy(type("NoId", (), {}))
    with pytest.raises(TypeError, match="admit"):
        register_policy(type("NoAdmit", (), {"policy_id": "x_no_admit"}))


def test_link_aware_policy_routes_sticky_ge_to_markov():
    policy = policy_spec("link_aware").cls()
    sticky = GilbertElliottLink(p_gb=0.05, p_bg=0.2, p_good=0.01,
                                p_bad=0.6, rates=RATES)
    fast = GilbertElliottLink(p_gb=0.5, p_bg=0.5, p_good=0.01,
                              p_bad=0.6, rates=RATES)
    assert policy.admit(_scenario(link=sticky), load=0.0).objective_id \
        == "markov_arq"
    assert policy.admit(_scenario(link=fast), load=0.0).objective_id \
        == "corollary1"
    assert policy.admit(_scenario(), load=0.0).grid_mode == "dense"
    assert policy.admit(_scenario(), load=2.0).grid_mode == "refine"


# ---------------------------------------------------------------------------
# PlanningService: warmup, zero traces, parity, stats
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def warm_service():
    service = PlanningService(ServiceConfig(**SMALL))
    service.warmup()
    service.start()
    yield service
    service.stop()


def test_service_zero_post_warmup_traces_and_parity(warm_service):
    service = warm_service
    requests = synth_requests(24, seed=5, dup_frac=0.0, n_classes=24,
                              models=("ideal", "erasure", "fading",
                                      "gilbert_elliott"), n_max=512)
    instances = list(service.objectives.values())
    modes = service.config.grid_modes
    futures, assigned = [], []
    for i, sc in enumerate(requests):
        if i % 3 == 0:
            futures.append(service.submit(sc))       # admission policy
            assigned.append((None, None))
        else:
            obj = instances[i % len(instances)]
            mode = modes[i % len(modes)]
            futures.append(service.submit(sc, objective=obj, grid_mode=mode))
            assigned.append((obj, mode))
    records = [f.result(timeout=60) for f in futures]

    stats = service.stats()
    assert stats.counters.get("post_warmup_traces", 0) == 0, stats.buckets
    assert stats.n_planned >= 24
    assert stats.latency_p99_ms >= stats.latency_p50_ms >= 0.0
    assert stats.plans_per_sec > 0

    # bitwise parity: the service adds batching/caching, never arithmetic
    direct = FleetPlanner(grid_size=SMALL["grid_size"],
                          pow2_refine_widths=True)
    for sc, rec, (obj, mode) in zip(requests, records, assigned):
        if obj is None:
            continue  # policy-routed: mode pick is load-dependent
        want = direct.plan_many([sc], service.consts, objective=obj,
                                grid_mode=mode)[0]
        assert want == rec


def test_service_refined_stream_zero_traces_and_parity(monkeypatch):
    """The service with the two-pass refine solve ENGAGED: zero traces
    after warmup and bitwise parity with ``plan_many`` under each
    request's own grid mode.  The shared SMALL service cannot show this:
    at G=16 every refine batch falls back to the dense pass, as it does
    at G=64 and G=128 for these N (the pow2-padded fine windows cover the
    grid), so this one runs at G=96 with N up to 2^20."""
    config = ServiceConfig(grid_size=96, batch_buckets=(4, 8),
                           flush_interval=0.01,
                           objective_ids=("corollary1", "markov_arq"),
                           grid_modes=("dense", "refine"), n_max=1 << 20)
    service = PlanningService(config)
    service.warmup()
    engaged = []
    refine_solve = FleetPlanner._refine_solve

    def counting(self, *args, **kw):
        out = refine_solve(self, *args, **kw)
        engaged.append(out[0] is not None)
        return out

    monkeypatch.setattr(FleetPlanner, "_refine_solve", counting)
    requests = synth_requests(16, seed=3, dup_frac=0.0, n_classes=16,
                              models=ALL_MODELS, n_max=1 << 20)
    assigned = [(config.objective_ids[i % 2], config.grid_modes[i // 2 % 2])
                for i in range(len(requests))]
    with service:
        futures = [service.submit(sc, objective=oid, grid_mode=mode)
                   for sc, (oid, mode) in zip(requests, assigned)]
        records = [f.result(timeout=60) for f in futures]
    assert any(engaged), "no refine batch left the dense fallback"
    assert service.stats().counters["post_warmup_traces"] == 0
    direct = FleetPlanner(grid_size=config.grid_size,
                          pow2_refine_widths=True)
    for sc, rec, (oid, mode) in zip(requests, records, assigned):
        assert rec.fallback == "full"
        want = direct.plan_many([sc], service.consts,
                                objective=service.objectives[oid],
                                grid_mode=mode)[0]
        assert want == rec


def test_service_objective_and_mode_validation(warm_service):
    sc = _scenario()
    with pytest.raises(KeyError, match="not served"):
        warm_service.submit(sc, objective="montecarlo")
    with pytest.raises(ValueError, match="not served"):
        warm_service.submit(sc, objective="corollary1", grid_mode="bogus")


def test_service_config_validation():
    with pytest.raises(ValueError, match="powers of two"):
        ServiceConfig(batch_buckets=(3,))
    with pytest.raises(ValueError, match="ascend"):
        ServiceConfig(batch_buckets=(8, 4))
    with pytest.raises(ValueError, match="grid mode"):
        ServiceConfig(grid_modes=("sparse",))


# ---------------------------------------------------------------------------
# Drift-triggered re-planning
# ---------------------------------------------------------------------------

def test_reestimate_link_gilbert_elliott_and_erasure():
    ge = GilbertElliottLink(p_gb=0.05, p_bg=0.45, p_good=0.01, p_bad=0.8,
                            rates=RATES)
    worse = reestimate_link(ge, rate=1.0, observed_loss=0.6)
    assert isinstance(worse, GilbertElliottLink)
    # mixing speed preserved, occupancy re-fit upward
    assert worse.p_gb + worse.p_bg == pytest.approx(ge.p_gb + ge.p_bg)
    pi_old = ge.p_gb / (ge.p_gb + ge.p_bg)
    pi_new = worse.p_gb / (worse.p_gb + worse.p_bg)
    assert pi_new > pi_old
    assert worse.p_err(1.0) == pytest.approx(0.6, abs=1e-9)

    er = ErasureLink(beta=0.4, p_base=0.05, rates=RATES)
    worse_er = reestimate_link(er, rate=1.5, observed_loss=0.5)
    assert worse_er.p_err(1.5) == pytest.approx(0.5, abs=1e-9)

    degenerate = GilbertElliottLink(p_gb=0.1, p_bg=0.4, p_good=0.3,
                                    p_bad=0.3, rates=RATES)
    assert reestimate_link(degenerate, 1.0, 0.6) is None


def test_session_drift_triggers_replan_with_changed_argmin(warm_service):
    service = warm_service
    # a GE link planned while mostly-good; the chain then degrades hard
    link = GilbertElliottLink(p_gb=0.02, p_bg=0.5, p_good=0.005, p_bad=0.9,
                              beta=0.3, rates=RATES)
    scenario = _scenario(seed=11, n=2048, link=link)
    fut = service.open_session("dev-0", scenario, objective="markov_arq",
                               grid_mode="dense")
    first = fut.result(timeout=60)
    session = service.session("dev-0")
    assert session.plan == first and session.generation == 1

    # stream heavy observed loss: EWMA -> ~0.9 while the plan priced the
    # near-stationary chain (pi_bad ~ 0.04)
    replan_future = None
    for _ in range(50):
        replan_future = service.observe("dev-0", [True] * 4)
        if replan_future is not None:
            break
    assert replan_future is not None, "drift never fired"
    second = replan_future.result(timeout=60)
    assert session.replans == 1
    assert session.generation == 2
    assert session.scenario.link != link         # link was re-estimated
    # the degraded channel must change the chosen operating point
    assert (second.n_c, second.rate, second.p_err) \
        != (first.n_c, first.rate, first.p_err)
    # and the re-planned answer must equal a direct solve of the
    # re-estimated scenario (drift path reuses the ordinary plan path)
    # at the service's padded batch shape: XLA:CPU contracts float64
    # multiply-adds into FMAs differently for other batch lengths, so a
    # lane's n_o_eff can move by an ulp with the batch around it
    direct = FleetPlanner(grid_size=SMALL["grid_size"],
                          pow2_refine_widths=True)
    want = direct.plan_many([session.scenario], service.consts,
                            pad_to=SMALL["batch_buckets"][0],
                            objective=service.objectives["markov_arq"],
                            grid_mode="dense")[0]
    assert want == second
    stats = service.stats()
    assert stats.counters.get("drift_replans", 0) >= 1
    assert stats.cache.get("invalidations", 0) >= 1
    assert stats.counters.get("post_warmup_traces", 0) == 0
    service.close_session("dev-0")
    with pytest.raises(KeyError):
        service.session("dev-0")


def test_session_open_rejects_duplicate_and_tracks_count(warm_service):
    service = warm_service
    sc = _scenario(seed=21, n=768)
    service.open_session("dup-1", sc, objective="corollary1",
                         grid_mode="dense").result(timeout=60)
    try:
        with pytest.raises(ValueError, match="already open"):
            service.open_session("dup-1", sc, objective="corollary1",
                                 grid_mode="dense")
        assert service.stats().counters["sessions_open"] >= 1
    finally:
        service.close_session("dup-1")


# ---------------------------------------------------------------------------
# Launch driver wiring
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flag,value", [
    ("--objective", "bogus"), ("--policy", "bogus"),
    ("--grid-mode", "bogus"), ("--buckets", "3"),
    ("--chaos-spec", "bogus_point=0.5"),
], ids=["objective", "policy", "grid_mode", "buckets", "chaos_spec"])
def test_serve_cli_rejects_unknown_names(flag, value):
    from repro.launch.serve import main
    assert main([flag, value, "--requests", "1"]) == 2


def test_serve_cli_serves_both_grid_modes(capsys):
    from repro.launch.serve import main
    assert main(["--requests", "8", "--buckets", "4", "--grid", "8",
                 "--n-max", "512", "--models", "erasure",
                 "--objective", "corollary1", "--grid-mode", "all",
                 "--policy-frac", "0"]) == 0
    out = capsys.readouterr().out
    assert "bucket corollary1/dense/4: 4 requests" in out
    assert "bucket corollary1/refine/4: 4 requests" in out


# ---------------------------------------------------------------------------
# One serving loop: mixed streams, stats, cache replay
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("axis", ["link_model", "objective", "grid_mode"])
def test_service_mixed_stream_matches_scalar_solves(warm_service, axis):
    """A stream mixing link models, objectives or grid modes goes through
    the one micro-batch loop, and every record is the scalar solve under
    its own objective and grid mode (at G=16 refine falls back to the
    dense solve)."""
    service = warm_service
    models = ALL_MODELS if axis == "link_model" else ("erasure",)
    oids = service.config.objective_ids if axis == "objective" \
        else ("corollary1",)
    modes = service.config.grid_modes if axis == "grid_mode" \
        else ("dense",)
    requests = synth_requests(16, seed=70, dup_frac=0.0, n_classes=16,
                              models=models, n_max=512)
    assigned = [(oids[i % len(oids)], modes[i % len(modes)])
                for i in range(len(requests))]
    futures = [service.submit(sc, objective=oid, grid_mode=mode)
               for sc, (oid, mode) in zip(requests, assigned)]
    records = [f.result(timeout=60) for f in futures]
    if axis == "link_model":
        assert {link_spec_for(sc.link).model_id for sc in requests} == {
            IdealLink.model_id, ErasureLink.model_id, FadingLink.model_id,
            GilbertElliottLink.model_id}
    for sc, rec, (oid, _) in zip(requests, records, assigned):
        assert rec.objective == oid and rec.fallback == "full"
        sp = ObjectivePlanner(
            objective=service.objectives[oid],
            grid=fleet_grid(sc.N, SMALL["grid_size"])).plan(sc,
                                                            service.consts)
        # equal picks, or a tie at float64 resolution
        assert (rec.n_c, rec.rate) == (sp.n_c, sp.rate) \
            or abs(rec.bound_value - sp.bound_value) \
            <= 1e-9 * abs(sp.bound_value)
    assert service.stats().counters["post_warmup_traces"] == 0


@pytest.mark.parametrize("case", ["empty", "replay"])
def test_service_stats_stay_finite_and_replays_hit_the_cache(warm_service,
                                                             case):
    if case == "empty":
        stats = PlanningService(ServiceConfig(**SMALL)).stats()
        assert stats.n_requests == stats.n_planned == stats.n_batches == 0
        for value in (stats.plans_per_sec, stats.latency_p50_ms,
                      stats.latency_p99_ms, stats.latency_max_ms,
                      stats.solve_fraction, stats.cache["hit_rate"]):
            assert np.isfinite(value)
        return
    service = warm_service
    requests = synth_requests(24, seed=71, dup_frac=0.5, n_max=512)

    def serve_stream():
        futures = [service.submit(sc, objective="corollary1",
                                  grid_mode="dense") for sc in requests]
        return [f.result(timeout=60) for f in futures]

    first = serve_stream()
    hits, misses = service.cache.hits, service.cache.misses
    replay = serve_stream()
    assert service.cache.hits - hits == len(requests)
    assert service.cache.misses == misses
    assert replay == first
    for rec in first:
        assert rec.n_c >= 1 and np.isfinite(rec.bound_value)
        assert rec.rate in RATES
    stats = service.stats()
    assert stats.latency_p99_ms >= stats.latency_p50_ms > 0.0


# ---------------------------------------------------------------------------
# Observability: spans, metrics export, flush causes, CLI wiring
# ---------------------------------------------------------------------------

def test_batcher_counts_flush_causes():
    b, batches = _collecting_batcher(max_batch=2, flush_interval=0.02)
    b.start()
    try:
        futs = [b.submit(PlanRequest(scenario=i)) for i in range(4)]
        for f in futs:
            f.result(timeout=5.0)       # two full batches -> size flushes
        last = b.submit(PlanRequest(scenario=9))
        last.result(timeout=5.0)        # partial batch -> deadline flush
    finally:
        b.stop()
    assert b.flush_causes["size"] >= 1
    assert b.flush_causes["deadline"] >= 1
    assert sum(b.flush_causes.values()) == len(batches)


def test_service_spans_sum_to_latency(warm_service):
    service = warm_service
    requests = synth_requests(12, seed=40, dup_frac=0.0, n_classes=12,
                              models=("erasure", "fading"), n_max=512)
    futures = [service.submit(sc) for sc in requests]
    for f in futures:
        f.result(timeout=60)
    spans = service.spans.snapshot()
    assert spans and service.spans.recorded >= 12
    for span in spans:
        # the phases partition the enqueue-to-plan latency exactly:
        # contiguous intervals cut from one monotonic clock
        assert abs(span.phase_sum - span.latency_s) <= 1e-6, span
        assert span.solve_device_s <= span.solve_s + 1e-9
        assert all(v >= 0.0 for v in span.phases().values())
        assert span.bucket in warm_service.config.batch_buckets
        assert span.objective in SMALL["objective_ids"]
    totals = service.spans.totals()
    assert totals["batch_wait"] > 0.0       # the spans measure queueing
    # and attribute real compute to the solve
    assert 1e-3 <= service.spans.solve_fraction <= 1.0
    phase_sum = sum(totals[p] for p in ("batch_wait", "pad", "cache_lookup",
                                        "solve", "resolve"))
    assert phase_sum == pytest.approx(totals["latency"], rel=1e-9)


def test_service_metrics_round_trip_all_counters(warm_service):
    from repro.serve.export import GAUGE_COUNTERS
    service = warm_service
    requests = synth_requests(8, seed=41, dup_frac=0.0, n_classes=8,
                              models=("erasure",), n_max=512)
    for f in [service.submit(sc) for sc in requests]:
        f.result(timeout=60)
    stats = service.stats()
    snap = service.metrics_snapshot()   # parses the rendered exposition

    # EVERY ServiceStats counter is reachable through the export
    for name, v in stats.counters.items():
        if name in GAUGE_COUNTERS:
            assert snap[f"repro_serve_{name}"][()] == v, name
        else:
            assert snap[f"repro_serve_{name}_total"][()] == v, name
    assert {"flushes_size", "flushes_deadline", "flushes_drain"} \
        <= set(stats.counters)

    # per-bucket counters carry their (objective, grid_mode, bucket) labels
    for (oid, mode, bucket), slot in stats.buckets.items():
        labels = (("bucket", str(bucket)), ("grid_mode", mode),
                  ("objective", oid))
        assert snap["repro_serve_bucket_requests_total"][labels] \
            == slot["requests"]

    # one span and one histogram sample per planned request, and the
    # exported phase totals re-partition the exported latency total
    assert snap["repro_serve_spans_recorded_total"][()] \
        == snap["repro_serve_latency_seconds_count"][()]
    phases = {dict(labels)["phase"]: v for labels, v
              in snap["repro_serve_phase_seconds_total"].items()}
    phase_total = sum(phases[p] for p in PHASES)
    assert phase_total == pytest.approx(
        snap["repro_serve_span_latency_seconds_total"][()], rel=1e-6)
    assert snap["repro_serve_solve_device_seconds_total"][()] > 0.0
    # the worker's host leaves are labels of the same family, and the
    # collection counters come per generation
    assert set(LEAVES) <= set(phases)
    for leaf in ("serve.wait", "planner.dispatch", "planner.device_wait",
                 "planner.fetch", "serve.resolve"):
        assert phases[leaf] > 0.0, leaf
    for family in ("repro_process_gc_collections_total",
                   "repro_process_gc_pause_seconds_total"):
        assert {dict(labels)["generation"] for labels in snap[family]} \
            == {"0", "1", "2"}
    assert 0.0 < snap["repro_serve_solve_fraction"][()] <= 1.0
    # the zero-trace SLO series a scrape would alert on
    assert snap["repro_serve_post_warmup_traces_total"][()] == 0
    assert snap["repro_fleet_traces_total"][()] > 0
    assert service.metrics.value("repro_serve_planned_total") \
        == stats.n_planned


def test_service_journal_records_session_lifecycle(warm_service):
    service = warm_service
    before = service.journal.counts()
    sc = _scenario(seed=51, n=640)
    service.open_session("obs-1", sc, objective="corollary1",
                         grid_mode="dense").result(timeout=60)
    service.close_session("obs-1")
    counts = service.journal.counts()
    assert counts.get("session_open", 0) == before.get("session_open", 0) + 1
    assert counts.get("session_close", 0) \
        == before.get("session_close", 0) + 1
    kinds = [e["kind"] for e in service.journal.tail(50)]
    assert "session_open" in kinds and "session_close" in kinds
    closes = [e for e in service.journal.tail(50)
              if e["kind"] == "session_close"
              and e["session_id"] == "obs-1"]
    assert closes and closes[-1]["generation"] == 1


def test_serve_cli_writes_metrics_textfile_and_journal(tmp_path):
    from repro.launch.serve import main
    from repro.obs import parse_exposition, read_jsonl
    metrics_path = tmp_path / "metrics.prom"
    journal_path = tmp_path / "events.jsonl"
    # --policy-frac 0: the link_aware policy may route to "refine",
    # which this one-mode config does not serve
    rc = main(["--requests", "6", "--buckets", "4", "--grid", "8",
               "--n-max", "512", "--models", "erasure",
               "--objective", "corollary1", "--grid-mode", "dense",
               "--policy-frac", "0",
               "--metrics-textfile", str(metrics_path),
               "--journal", str(journal_path)])
    assert rc == 0
    snap = parse_exposition(metrics_path.read_text())
    assert snap["repro_serve_planned_total"][()] == 6
    assert snap["repro_serve_post_warmup_traces_total"][()] == 0
    assert snap["repro_serve_latency_seconds_count"][()] == 6
    events = read_jsonl(str(journal_path))
    assert any(e["kind"] == "warmup" for e in events)
