"""Always-on planning service: micro-batcher edge cases, bucketed AOT
warmup (zero post-warmup traces), admission-policy registry, PlanCache
invalidation/stats, session drift -> re-plan, and bitwise parity of
served plans against direct ``FleetPlanner.plan_many`` calls."""
import threading
import time

import numpy as np
import pytest

from repro.core import (BoundConstants, ErasureLink, GilbertElliottLink,
                        Scenario)
from repro.fleet import FleetPlanner, PlanCache
from repro.serve import (AdmissionDecision, MicroBatcher, PlanRequest,
                         PlanningService, ServiceConfig, group_requests,
                         policy_spec, register_policy, registered_policies,
                         reestimate_link, synth_requests, unregister_policy)

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

def test_serve_cli_rejects_unknown_names():
    from repro.launch.serve import main
    assert main(["--objective", "bogus", "--requests", "1"]) == 2
    assert main(["--policy", "bogus", "--requests", "1"]) == 2
    assert main(["--grid-mode", "bogus", "--requests", "1"]) == 2
    assert main(["--buckets", "3", "--requests", "1"]) == 2


def test_plan_server_reports_batch_latency_percentiles():
    from repro.launch.plan_server import serve
    planner = FleetPlanner(grid_size=8)
    reqs = synth_requests(12, seed=3, dup_frac=0.0, n_classes=12,
                          models=("erasure",), n_max=512)
    stats = serve(reqs, planner=planner, consts=CONSTS,
                  cache=PlanCache(maxsize=64), batch_size=4)
    assert stats.batch_p99_ms >= stats.batch_p50_ms > 0.0
    assert stats.batch_max_ms >= stats.batch_p99_ms


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
    assert 0.0 < service.spans.solve_fraction <= 1.0
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
    phase_total = sum(
        v for labels, v in snap["repro_serve_phase_seconds_total"].items()
        if dict(labels)["phase"] != "admit")
    assert phase_total == pytest.approx(
        snap["repro_serve_span_latency_seconds_total"][()], rel=1e-6)
    assert snap["repro_serve_solve_device_seconds_total"][()] > 0.0
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
