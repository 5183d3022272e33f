"""Federated round planner: kernel/reference/bruteforce parity, pad
invariance, the deadline-gated simulator, serving integration (zero
post-warmup traces + metrics + cache isolation), the ``synth_population``
catalogue entry, and the PR's multi-device validation regressions."""
import dataclasses

import numpy as np
import pytest

from repro.core import (ErasureLink, GilbertElliottLink, IdealLink,
                        Scenario)
from repro.core.bounds import corollary1_bound
from repro.core.multidevice import (MultiDeviceSchedule, plan_multi_device,
                                    split_samples)
from repro.core.scenario import RidgeTask
from repro.data.synthetic import make_regression_dataset
from repro.federated import (FEDERATED_TOKEN, FederatedSimulator,
                             RoundPlanner, RoundRecord,
                             plan_round_bruteforce, plan_round_reference,
                             population_key)
from repro.fleet import PlanCache
from repro.fleet.tracing import trace_delta
from repro.serve import (FEDERATED_KIND, PlanningService, ServiceConfig,
                         default_consts, synth_population)

CONSTS = default_consts()
# the catalogue rate set: one padded rate width -> one kernel shape
RATES = (1.0, 1.25, 1.5, 2.0, 3.0)
GRID = 8


def _population(seed=0, size=6):
    """Small mixed-link population with a shared feasible-ish deadline."""
    rng = np.random.default_rng(seed)
    deadline = None
    pop = []
    for i in range(size):
        n = int(rng.integers(64, 2048))
        link = [
            IdealLink(rates=RATES),
            ErasureLink(beta=float(rng.uniform(0.0, 1.0)),
                        p_base=float(rng.uniform(0.0, 0.5)), rates=RATES),
            GilbertElliottLink(p_gb=float(rng.uniform(0.05, 0.8)),
                               p_bg=float(rng.uniform(0.05, 0.8)),
                               p_good=float(rng.uniform(0.0, 0.3)),
                               p_bad=float(rng.uniform(0.2, 0.9)),
                               beta=float(rng.uniform(0.0, 1.0)),
                               rates=RATES),
        ][i % 3]
        pop.append(Scenario(N=n, T=float(rng.uniform(0.8, 2.5)) * n,
                            n_o=float(rng.uniform(1.0, 800.0)),
                            tau_p=float(rng.choice([0.5, 1.0, 2.0])),
                            link=link))
    deadline = 1.4 * float(np.median([sc.N for sc in pop]))
    return pop, deadline


# ---------------------------------------------------------------------------
# planner == numpy reference == brute force
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_plan_round_matches_reference_and_bruteforce(seed):
    pop, deadline = _population(seed)
    planner = RoundPlanner(grid_size=GRID)
    plan = planner.plan_round(pop, CONSTS, deadline=deadline, pad_to=8)
    ref = plan_round_reference(pop, CONSTS, deadline=deadline,
                               grid_size=GRID)

    assert np.array_equal(plan.participants, ref.participants)
    assert plan.k_best == ref.k_best
    assert plan.n_eligible == ref.n_eligible
    assert np.array_equal(plan.eligible, ref.eligible)
    assert np.array_equal(plan.n_c, ref.n_c)
    assert np.array_equal(plan.rate, ref.rate)

    if plan.feasible:
        bf = plan_round_bruteforce(pop, CONSTS, deadline=deadline,
                                   grid_size=GRID)
        rec = plan.record()
        assert rec.participants == bf.participants
        assert rec.n_c == bf.n_c
        assert rec.rate == bf.rate
        assert np.isclose(rec.objective_value, bf.objective_value,
                          rtol=1e-12)
        assert np.isclose(rec.round_time, bf.round_time, rtol=1e-12)


def test_plan_round_pad_invariance():
    """Pad lanes (valid=False) must not change the chosen round."""
    pop, deadline = _population(7, size=5)
    planner = RoundPlanner(grid_size=GRID)
    base = planner.plan_round(pop, CONSTS, deadline=deadline)   # pow2 -> 8
    padded = planner.plan_round(pop, CONSTS, deadline=deadline, pad_to=16)
    assert np.array_equal(base.participants, padded.participants)
    assert base.k_best == padded.k_best
    assert base.n_eligible == padded.n_eligible
    assert np.array_equal(base.n_c, padded.n_c)
    assert np.array_equal(base.rate, padded.rate)
    assert len(base) == len(padded) == 5


def test_plan_round_infeasible_population():
    pop, _ = _population(9, size=4)
    planner = RoundPlanner(grid_size=GRID)
    plan = planner.plan_round(pop, CONSTS, deadline=1e-3, pad_to=8)
    assert not plan.feasible
    assert plan.k_best == 0 and plan.n_eligible == 0
    assert plan.participants.size == 0
    assert plan.objective_value == np.inf
    assert plan.round_time == np.inf
    rec = plan.record()
    assert rec.participants == () and not rec.feasible
    assert rec.n_c == () and rec.rate == ()


def test_plan_round_validation():
    pop, deadline = _population(0, size=3)
    planner = RoundPlanner(grid_size=GRID)
    with pytest.raises(ValueError, match="non-empty"):
        planner.plan_round([], CONSTS)
    with pytest.raises(ValueError, match="deadline"):
        planner.plan_round(pop, CONSTS, deadline=0.0)
    from repro.fleet.batch import ScenarioBatch
    batch = ScenarioBatch.from_scenarios(pop)
    with pytest.raises(ValueError, match="n_real"):
        planner.plan_round_batch(batch, CONSTS, deadline=deadline,
                                 n_real=4)
    with pytest.raises(ValueError, match="grid"):
        planner.plan_round_batch(batch, CONSTS, deadline=deadline,
                                 grid=np.ones((5, GRID), np.int64))


def test_warm_then_plan_pays_zero_traces():
    pop, deadline = _population(3)
    planner = RoundPlanner(grid_size=GRID)
    planner.warm(pop, CONSTS, pad_to=8)
    with trace_delta() as traces:
        planner.plan_round(pop, CONSTS, deadline=deadline, pad_to=8)
        planner.plan_round(pop[:4], CONSTS, deadline=deadline, pad_to=8)
    assert traces.total == 0


# hypothesis sweep: randomly drawn mixed-link populations ------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def _h_scenario(draw):
        kind = draw(st.sampled_from(["ideal", "erasure", "ge"]))
        if kind == "erasure":
            link = ErasureLink(beta=draw(st.floats(0.0, 1.5)),
                               p_base=draw(st.floats(0.0, 0.8)),
                               rates=RATES)
        elif kind == "ge":
            link = GilbertElliottLink(
                p_gb=draw(st.floats(0.01, 1.0)),
                p_bg=draw(st.floats(0.01, 1.0)),
                p_good=draw(st.floats(0.0, 0.8)),
                p_bad=draw(st.floats(0.0, 0.9)),
                beta=draw(st.floats(0.0, 1.5)), rates=RATES)
        else:
            link = IdealLink(rates=RATES)
        N = draw(st.integers(32, 4096))
        return Scenario(N=N, T=draw(st.floats(0.4, 3.0)) * N,
                        n_o=draw(st.floats(0.0, 1500.0)),
                        tau_p=draw(st.sampled_from([0.5, 1.0, 2.0])),
                        link=link)

    @settings(max_examples=15, deadline=None)
    @given(pop=st.lists(_h_scenario(), min_size=2, max_size=8),
           frac=st.floats(0.2, 2.5))
    def test_plan_round_property_matches_references(pop, frac):
        """Participant set + per-participant (rate, n_c) argmin-identical
        to the numpy reference AND the exponential brute force on
        randomly drawn mixed-link populations (a pick may differ only at
        a float64 near-tie, see :func:`_assert_same_or_near_tie`)."""
        deadline = frac * float(np.median([sc.N for sc in pop]))
        _assert_round_matches_references(pop, deadline)


#: Largest gap, in ulps, between numpy's bounds at two picks that may
#: both stand as a device's optimum.
TIE_ULPS = 2


def _assert_same_or_near_tie(sc, deadline, got, want):
    """A device's pick ``got = (n_c, rate)`` is ``want``, or numpy's bound
    scores the two within ``TIE_ULPS`` of each other.

    Near-ties are real: at rate 3 with no overhead, a block shorter than
    one update (``n_c < 3 tau_p``) runs no update before the last block
    lands, and scores what ``n_c = N`` scores up to the rounding of
    ``ceil(B_d) / B_d``.  Below an ulp or two the order of such points
    is the arithmetic's, not the bound's: the jitted bound takes
    ``exp``/``log`` where numpy takes ``**``, and numpy's vectorised and
    scalar calls may round differently too.  In 3,000 random populations
    the kernel picked another point than the reference for 59 devices:
    at 41 numpy's scalar bound ties exactly, at 18 it is 1 ulp apart."""
    if got == want:
        return
    at = [float(corollary1_bound(
        np.asarray([float(n_c)]), N=sc.N, T=deadline,
        n_o=float(sc.effective_overhead(n_c, rate)), tau_p=sc.tau_p,
        consts=CONSTS)[0]) for n_c, rate in (got, want)]
    assert abs(at[0] - at[1]) <= TIE_ULPS * np.spacing(max(at)), \
        (got, want, at)


def _assert_round_matches_references(pop, deadline):
    """The kernel against the numpy reference and the exponential brute
    force: participants, ``K`` and eligibility exactly, and each device's
    pick exactly except at a near-tie."""
    plan = RoundPlanner(grid_size=GRID).plan_round(
        pop, CONSTS, deadline=deadline, pad_to=8)   # one compiled shape
    ref = plan_round_reference(pop, CONSTS, deadline=deadline,
                               grid_size=GRID)
    assert np.array_equal(plan.participants, ref.participants)
    assert plan.k_best == ref.k_best
    assert np.array_equal(plan.eligible, ref.eligible)
    for i, sc in enumerate(pop):
        if ref.eligible[i]:
            _assert_same_or_near_tie(
                sc, deadline, (int(plan.n_c[i]), float(plan.rate[i])),
                (int(ref.n_c[i]), float(ref.rate[i])))
    bf = plan_round_bruteforce(pop, CONSTS, deadline=deadline,
                               grid_size=GRID)
    for rec in (plan.record(), ref.record()):
        assert rec.participants == bf.participants
        for j, i in enumerate(rec.participants):
            _assert_same_or_near_tie(pop[i], deadline,
                                     (rec.n_c[j], rec.rate[j]),
                                     (bf.n_c[j], bf.rate[j]))


#: A random population at a near-tie: at rate 3, numpy's vectorised bound
#: puts ``n_c = 117`` 1 ulp below ``n_c = 1``; the kernel and numpy's
#: scalar bound (the brute force) put them the other way round.
_TIE_POPULATION = [Scenario(N=117, T=117.0, n_o=0.0, tau_p=2.0,
                            link=IdealLink(rates=RATES))] * 2
_TIE_DEADLINE = 1601.341506031503


def test_plan_round_float64_tie_matches_references():
    plan = RoundPlanner(grid_size=GRID).plan_round(
        _TIE_POPULATION, CONSTS, deadline=_TIE_DEADLINE, pad_to=8)
    assert list(plan.n_c) == [1, 1]     # the case this test exists for
    _assert_round_matches_references(_TIE_POPULATION, _TIE_DEADLINE)


# ---------------------------------------------------------------------------
# FederatedSimulator: sharded local SGD + deadline-gated averaging
# ---------------------------------------------------------------------------


def _feasible_plan(seed=1):
    for s in range(seed, seed + 20):
        pop, deadline = _population(s)
        plan = RoundPlanner(grid_size=GRID).plan_round(
            pop, CONSTS, deadline=deadline, pad_to=8)
        if plan.feasible:
            return pop, plan
    raise RuntimeError("no feasible population found")  # pragma: no cover


def test_simulator_runs_planned_round():
    pop, plan = _feasible_plan()
    X, y, _ = make_regression_dataset(n=256, d=6, seed=0)
    report = FederatedSimulator().run_round(pop, plan, RidgeTask(X=X, y=y))
    assert len(report.participants) == plan.k_best
    devs = sorted(r.device for r in report.participants)
    assert devs == list(plan.participants)
    # shards partition the task dataset remainder-exactly
    assert sum(r.shard_size for r in report.participants) == 256
    assert report.n_completed >= 1
    assert np.isfinite(report.aggregated_loss)
    assert report.w_round is not None and report.w_round.shape == (6,)
    assert 0.0 < report.completion_rate <= 1.0


def test_simulator_deadline_gates_stragglers():
    """Crushing the deadline after planning drops every participant."""
    pop, plan = _feasible_plan()
    starved = dataclasses.replace(plan, deadline=1e-6)
    X, y, _ = make_regression_dataset(n=128, d=4, seed=1)
    report = FederatedSimulator().run_round(pop, starved,
                                            RidgeTask(X=X, y=y))
    assert report.n_completed == 0
    assert report.aggregated_loss == np.inf
    assert report.w_round is None
    assert all(not r.completed for r in report.participants)


def test_simulator_infeasible_plan_and_length_mismatch():
    pop, _ = _population(9, size=4)
    plan = RoundPlanner(grid_size=GRID).plan_round(pop, CONSTS,
                                                   deadline=1e-3, pad_to=8)
    X, y, _ = make_regression_dataset(n=64, d=4, seed=2)
    report = FederatedSimulator().run_round(pop, plan, RidgeTask(X=X, y=y))
    assert report.participants == () and report.aggregated_loss == np.inf
    with pytest.raises(ValueError, match="population"):
        FederatedSimulator().run_round(pop[:2], plan, RidgeTask(X=X, y=y))


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def round_service():
    service = PlanningService(ServiceConfig(
        grid_size=GRID, batch_buckets=(4,), grid_modes=("dense",),
        objective_ids=("corollary1",), population_buckets=(8,),
        n_max=512, shard=False))
    service.warmup()
    yield service


def test_submit_round_zero_traces_metrics_and_cache(round_service):
    service = round_service
    pop, deadline = synth_population(6, seed=4, n_max=512)
    with trace_delta() as traces:
        record = service.submit_round(pop, deadline=deadline)
        repeat = service.submit_round(pop, deadline=deadline)
    assert traces.total == 0
    assert repeat == record                       # cache hit, same object
    assert isinstance(record, RoundRecord)
    stats = service.cache.stats()
    assert stats["hits_by_objective"].get(FEDERATED_KIND, 0) >= 1

    metrics = service.metrics_snapshot()
    assert int(metrics["repro_serve_post_warmup_traces_total"][()]) == 0
    assert int(metrics["repro_federated_rounds_total"][()]) >= 2
    if record.feasible:
        assert int(metrics["repro_federated_participants_total"][()]) >= \
            2 * record.n_participants
    # the plan agrees with a direct planner call at the serving pad shape
    direct = service.round_planner.plan_round(
        pop, service.consts, deadline=deadline, pad_to=8).record()
    assert direct == record


def test_federated_cache_key_isolated_from_scenario_plans(round_service):
    """Satellite: a federated entry can never alias a per-scenario plan
    even when the round is a single-device population."""
    service = round_service
    pop, deadline = synth_population(1, seed=6, n_max=512)
    cache = PlanCache(maxsize=32)
    key = (service.round_planner.cache_context(service.consts),
           FEDERATED_TOKEN, population_key(pop, deadline))
    cache.put_by_key(key, "round-entry")
    # the same scenario stored through the scenario path
    cache.put(pop[0], "scenario-entry",
              context=("federated", service.consts,
                       service.round_planner.grid_size))
    assert len(cache) == 2                        # no aliasing
    assert cache.get_by_key(key, label=FEDERATED_KIND) == "round-entry"
    assert cache.get(pop[0],
                     context=("federated", service.consts,
                              service.round_planner.grid_size)) == \
        "scenario-entry"
    stats = cache.stats()
    assert stats["hits_by_objective"][FEDERATED_KIND] == 1
    # population keys quantise the deadline like scenario keys do
    assert population_key(pop, deadline) == \
        population_key(pop, deadline * (1 + 1e-9))
    assert population_key(pop, deadline) != \
        population_key(pop, deadline * 2)


def test_population_buckets_config_validation():
    with pytest.raises(ValueError, match="powers of two"):
        ServiceConfig(population_buckets=(3,))
    with pytest.raises(ValueError, match="ascend"):
        ServiceConfig(population_buckets=(16, 8))


def test_synth_population_deterministic_and_validated():
    a, da = synth_population(5, seed=3, n_max=512)
    b, db = synth_population(5, seed=3, n_max=512)
    assert da == db and a == b
    c, _ = synth_population(5, seed=4, n_max=512)
    assert c != a
    assert all(sc.T == da for sc in a)            # shared round deadline
    with pytest.raises(ValueError, match="unknown link model"):
        synth_population(2, models=("nope",))
    with pytest.raises(ValueError):
        synth_population(0)


def test_federated_cli_verify_and_errors(tmp_path):
    from repro.launch.federated import main
    metrics = tmp_path / "fed.prom"
    assert main(["--devices", "5", "--rounds", "1", "--pop-buckets", "8",
                 "--grid", str(GRID), "--n-max", "512", "--verify",
                 "--metrics-textfile", str(metrics)]) == 0
    text = metrics.read_text()
    assert "repro_federated_rounds_total" in text
    assert main(["--models", "nope"]) == 2
    assert main(["--pop-buckets", "x"]) == 2


# ---------------------------------------------------------------------------
# multi-device validation + remainder-exact sharding (satellite)
# ---------------------------------------------------------------------------


def test_split_samples_remainder_exact():
    assert split_samples(1003, 4) == (251, 251, 251, 250)
    assert split_samples(8, 3) == (3, 3, 2)
    assert split_samples(5, 5) == (1, 1, 1, 1, 1)
    with pytest.raises(ValueError):
        split_samples(4, 0)
    with pytest.raises(ValueError):
        split_samples(2, 3)                       # device with no samples


def test_multi_device_schedule_validates_inputs():
    ok = dict(n_devices=2, samples_per_device=4, n_c=2, n_o=1.0,
              T=100.0, tau_p=1.0)
    MultiDeviceSchedule(**ok)                     # sanity: valid baseline
    for bad in [dict(ok, n_devices=0), dict(ok, samples_per_device=0),
                dict(ok, n_c=0), dict(ok, n_o=-1.0), dict(ok, T=0.0),
                dict(ok, tau_p=0.0)]:
        with pytest.raises(ValueError):
            MultiDeviceSchedule(**bad)
    with pytest.raises(ValueError, match="shard sizes"):
        MultiDeviceSchedule(**ok, shard_sizes=(4,))        # wrong length
    with pytest.raises(ValueError, match="at least one sample"):
        MultiDeviceSchedule(**ok, shard_sizes=(4, 0))      # empty shard
    with pytest.raises(ValueError, match="samples_per_device"):
        MultiDeviceSchedule(**ok, shard_sizes=(3, 3))      # max != spd


def test_multi_device_uneven_shards_available_at():
    sched = MultiDeviceSchedule(n_devices=3, samples_per_device=3, n_c=2,
                                n_o=1.0, T=100.0, tau_p=1.0,
                                shard_sizes=(3, 3, 2))
    assert sched.N_total == 8
    # one TDMA cycle (3 slots of n_c + n_o = 3): every device shipped one
    # block of min(n_c, shard) samples
    assert sched.available_at(9.0) == 2 + 2 + 2
    # by the deadline the short shard contributes only its own 2 samples
    assert sched.available_at(sched.T) == 8


def test_plan_multi_device_total_N_path():
    res = plan_multi_device(n_devices=4, N=1003, T=4000.0, n_o=8.0,
                            tau_p=1.0, consts=CONSTS)
    assert res["shard_sizes"] == (251, 251, 251, 250)
    assert sum(res["shard_sizes"]) == 1003
    assert res["schedule"].N_total == 1003
    legacy = plan_multi_device(n_devices=4, samples_per_device=251,
                               T=4000.0, n_o=8.0, tau_p=1.0, consts=CONSTS)
    assert legacy["shard_sizes"] == (251, 251, 251, 251)
    with pytest.raises(ValueError, match="exactly one"):
        plan_multi_device(n_devices=4, T=4000.0, n_o=8.0, tau_p=1.0,
                          consts=CONSTS)
    with pytest.raises(ValueError, match="exactly one"):
        plan_multi_device(n_devices=4, samples_per_device=8, N=32,
                          T=4000.0, n_o=8.0, tau_p=1.0, consts=CONSTS)
