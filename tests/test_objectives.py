"""Pluggable planner-objective registry: registration contracts, the
BoundObjective extraction (bitwise-identical plans), the exact burst-aware
MarkovARQObjective (reduction + strictly-better sticky plans, scalar and
fleet), the batched MonteCarloObjective (seed-for-seed equal to the scalar
planner, fixed cases + hypothesis property), objective-scoped PlanCache
keys, and a custom-objective plugin going end-to-end."""
from dataclasses import dataclass
from typing import ClassVar

import numpy as np
import pytest

from repro.core import (BoundConstants, BoundObjective, BoundPlanner,
                        ErasureLink, FadingLink, GilbertElliottLink,
                        IdealLink, MarkovARQObjective, MonteCarloObjective,
                        MonteCarloPlanner, MultiDevice, ObjectivePlanner,
                        Scenario, SingleDevice, objective_spec,
                        objective_spec_for, register_objective,
                        registered_objectives, unregister_objective)
from repro.core.planner import fleet_grid
from repro.fleet import (FleetPlanner, PlanCache, ScenarioBatch,
                         grid_objective_builder, objective_token,
                         register_objective_kernel,
                         unregister_objective_kernel)
from repro.serve import resolve_objectives

CONSTS = BoundConstants(L=1.908, c=0.061, M=1.0, M_G=1.0, D=1.0, alpha=1e-4)
RATES5 = (1.0, 1.25, 1.5, 2.0, 3.0)

#: Sticky Gilbert-Elliott chain: long bursts (p_gb + p_bg << 1) and a much
#: lossier bad state, where the stationary-loss approximation materially
#: underestimates the ARQ cost.
STICKY_LINK = GilbertElliottLink(p_gb=0.05, p_bg=0.05, p_good=0.0,
                                 p_bad=0.85, beta=0.7, rates=RATES5)
STICKY_SC = Scenario(N=8192, T=1.8 * 8192, n_o=800.0, link=STICKY_LINK)


def _ridge_data(n=128, d=6, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = X @ rng.normal(size=d) + 0.1 * rng.normal(size=n)
    return X, y


def _mixed_scenarios():
    return [
        Scenario(N=2048, T=1.5 * 2048, n_o=100.0),
        Scenario(N=18576, T=1.2 * 18576, n_o=500.0,
                 link=ErasureLink(beta=0.4, rates=RATES5)),
        Scenario(N=4096, T=1.4 * 4096, n_o=200.0,
                 link=FadingLink(snr=8.0, rates=RATES5)),
        Scenario(N=8192, T=1.3 * 8192, n_o=300.0,
                 link=GilbertElliottLink(p_gb=0.1, p_bg=0.6, p_good=0.05,
                                         p_bad=0.6, beta=0.3, rates=RATES5),
                 topology=MultiDevice(2)),
        STICKY_SC,
    ]


# ---------------------------------------------------------------------------
# registry contracts
# ---------------------------------------------------------------------------


def test_builtin_objectives_registered():
    ids = [s.objective_id for s in registered_objectives()]
    assert ids == sorted(ids)
    assert {"corollary1", "markov_arq", "montecarlo"} <= set(ids)
    assert objective_spec("corollary1").cls is BoundObjective
    assert objective_spec_for(MarkovARQObjective()).objective_id \
        == "markov_arq"


def test_register_objective_validation():
    with pytest.raises(KeyError, match="known ids"):
        objective_spec("definitely_not_registered")

    class NoId:
        pass

    with pytest.raises(ValueError, match="objective_id"):
        register_objective(NoId)

    class MissingMethods:
        objective_id = "missing_methods"

    with pytest.raises(TypeError, match="missing Objective methods"):
        register_objective(MissingMethods)

    class Duplicate:
        objective_id = "corollary1"

        def evaluate(self, *a): ...
        def effective_overhead(self, *a): ...
        def cache_token(self): ...

    with pytest.raises(ValueError, match="already registered"):
        register_objective(Duplicate)
    with pytest.raises(KeyError, match="not a registered objective"):
        objective_spec_for(Duplicate)
    # unregister is a tolerant no-op on absent ids
    unregister_objective("never_registered")


def test_objective_cache_tokens_distinct():
    X, y = _ridge_data()
    tokens = {objective_token(BoundObjective()),
              objective_token(MarkovARQObjective()),
              objective_token(MonteCarloObjective(X=X, y=y)),
              objective_token(None)}
    assert len(tokens) == 4
    # MC hyperparameters and DATA are part of the token
    assert objective_token(MonteCarloObjective(X=X, y=y, n_runs=3)) != \
        objective_token(MonteCarloObjective(X=X, y=y, n_runs=5))
    X2 = X.copy()
    X2[0, 0] += 1.0
    assert objective_token(MonteCarloObjective(X=X, y=y)) != \
        objective_token(MonteCarloObjective(X=X2, y=y))

    class NoToken:
        objective_id = "no_token"

    with pytest.raises(TypeError, match="cache_token"):
        objective_token(NoToken())


def test_montecarlo_objective_validates_inputs():
    X, y = _ridge_data()
    with pytest.raises(ValueError, match="data"):
        MonteCarloObjective()
    with pytest.raises(ValueError, match="n_runs"):
        MonteCarloObjective(X=X, y=y, n_runs=0)


# ---------------------------------------------------------------------------
# BoundObjective: the extraction is bitwise-identical to the old planner
# ---------------------------------------------------------------------------


def test_objective_planner_matches_bound_planner_bitwise():
    for sc in _mixed_scenarios():
        a = BoundPlanner().plan(sc, CONSTS)
        b = ObjectivePlanner().plan(sc, CONSTS)  # default BoundObjective
        assert (a.n_c, a.rate, a.bound_value) == (b.n_c, b.rate,
                                                  b.bound_value)
        assert a.schedule == b.schedule and a.boundary == b.boundary
        np.testing.assert_array_equal(a.bound_grid, b.bound_grid)
        assert b.objective == "corollary1"


def test_fleet_default_objective_unchanged():
    batch = ScenarioBatch.from_scenarios(_mixed_scenarios())
    fp = FleetPlanner(grid_size=40).plan_batch(batch, CONSTS)
    fb = FleetPlanner(grid_size=40).plan_batch(batch, CONSTS,
                                               objective=BoundObjective())
    assert fp.objective == fb.objective == "corollary1"
    for field in ("n_c", "rate", "bound_value", "p_err", "n_o_eff"):
        np.testing.assert_array_equal(getattr(fp, field),
                                      getattr(fb, field))


# ---------------------------------------------------------------------------
# MarkovARQObjective: exact burst-aware ARQ planning
# ---------------------------------------------------------------------------


def test_markov_arq_inflation_exact_vs_stationary():
    link = STICKY_LINK
    rates = np.asarray(RATES5)
    exact = link.exact_arq_inflation(rates)
    stationary = 1.0 / (1.0 - link.p_err(rates))
    # failures cluster on a sticky chain: the exact expected attempts per
    # block strictly exceed the memoryless stationary approximation
    assert np.all(exact > stationary)
    # degenerate chain: bitwise reduction whatever the transition probs
    deg = GilbertElliottLink(p_gb=0.05, p_bg=0.05, p_good=0.3, p_bad=0.3,
                             beta=0.7, rates=RATES5)
    np.testing.assert_array_equal(deg.exact_arq_inflation(rates),
                                  1.0 / (1.0 - deg.p_err(rates)))
    np.testing.assert_array_equal(
        deg.exact_expected_block_time(100.0, 10.0, rates),
        deg.expected_block_time(100.0, 10.0, rates))


def test_markov_arq_equals_bound_for_memoryless_links():
    for sc in _mixed_scenarios()[:3]:  # ideal / erasure / fading
        a = BoundPlanner().plan(sc, CONSTS)
        m = ObjectivePlanner(objective=MarkovARQObjective()).plan(sc, CONSTS)
        assert (a.n_c, a.rate, a.bound_value) == (m.n_c, m.rate,
                                                  m.bound_value)
        np.testing.assert_array_equal(a.bound_grid, m.bound_grid)
        assert m.objective == "markov_arq"


def test_markov_arq_sticky_chain_plans_strictly_better():
    """ISSUE acceptance: on a sticky Gilbert-Elliott chain the exact
    burst-aware objective picks a different plan whose EXACT expected
    block time is strictly lower than the stationary-approximation
    plan's."""
    sc, link = STICKY_SC, STICKY_LINK
    stat = BoundPlanner().plan(sc, CONSTS)
    markov = ObjectivePlanner(objective=MarkovARQObjective()).plan(sc, CONSTS)
    assert (markov.n_c, markov.rate) != (stat.n_c, stat.rate)

    def exact_ebt(n_c, rate):
        return float(link.exact_expected_block_time(
            n_c, sc.union_overhead, rate))

    assert exact_ebt(markov.n_c, markov.rate) \
        < exact_ebt(stat.n_c, stat.rate)
    # the reported schedule carries the objective's OWN (exact) overhead
    assert markov.schedule.n_o == pytest.approx(
        exact_ebt(markov.n_c, markov.rate) - markov.n_c, rel=1e-12)
    assert markov.schedule.n_o > float(
        sc.effective_overhead(markov.n_c, markov.rate))


def test_markov_arq_fleet_matches_scalar():
    scs = _mixed_scenarios()
    G = 40
    fm = FleetPlanner(grid_size=G).plan_batch(
        ScenarioBatch.from_scenarios(scs), CONSTS,
        objective=MarkovARQObjective())
    assert fm.objective == "markov_arq"
    for i, sc in enumerate(scs):
        sp = ObjectivePlanner(objective=MarkovARQObjective(),
                              grid=fleet_grid(sc.N, G)).plan(sc, CONSTS)
        assert int(fm.n_c[i]) == sp.n_c and float(fm.rate[i]) == sp.rate
        assert np.isclose(float(fm.bound_value[i]), sp.bound_value,
                          rtol=1e-12)
        assert np.isclose(float(fm.n_o_eff[i]), sp.schedule.n_o,
                          rtol=1e-12)


# ---------------------------------------------------------------------------
# MonteCarloObjective: batched == scalar, seed-for-seed
# ---------------------------------------------------------------------------


def _assert_mc_plans_match(scs, objective, grid, tol=1e-5):
    fleet = FleetPlanner(grid_size=len(grid)).plan_batch(
        ScenarioBatch.from_scenarios(scs), CONSTS, grid=np.asarray(grid),
        objective=objective)
    assert fleet.objective == "montecarlo"
    for i, sc in enumerate(scs):
        scalar = MonteCarloPlanner(
            X=objective.X, y=objective.y, lam=objective.lam,
            alpha=objective.alpha, n_runs=objective.n_runs,
            seed=objective.seed, grid=grid).plan(sc, CONSTS)
        assert int(fleet.n_c[i]) == scalar.n_c, (i, sc)
        assert float(fleet.rate[i]) == scalar.rate, (i, sc)
        assert np.isclose(float(fleet.bound_value[i]), scalar.bound_value,
                          rtol=tol)
        np.testing.assert_allclose(np.asarray(fleet.bound_grid[i]),
                                   scalar.bound_grid, rtol=tol)


@pytest.mark.slow
def test_montecarlo_fleet_matches_scalar_planner_fixed_cases():
    """ISSUE acceptance: batched MC planning matches the scalar MC path
    seed-for-seed across links, topologies, and per-scenario deadlines."""
    X, y = _ridge_data()
    scs = [
        Scenario(N=128, T=200.0, n_o=8.0,
                 link=ErasureLink(beta=0.5, p_base=0.1, rates=(1.0, 2.0))),
        Scenario(N=128, T=150.0, n_o=4.0, tau_p=0.5),
        Scenario(N=128, T=180.0, n_o=12.0,
                 link=GilbertElliottLink(p_gb=0.1, p_bg=0.4, p_good=0.05,
                                         p_bad=0.5, beta=0.4,
                                         rates=(1.0, 1.5, 3.0)),
                 topology=MultiDevice(2)),
    ]
    objective = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=7)
    _assert_mc_plans_match(scs, objective, grid=[1, 4, 16, 64])


@pytest.mark.slow
def test_montecarlo_fleet_default_grid_capped():
    """With grid=None the fleet planner honours the MC objective's coarse
    default width (every grid point is a simulated training run) instead
    of the bound-sized ``grid_size`` default."""
    X, y = _ridge_data(n=64, d=4)
    obj = MonteCarloObjective(X=X, y=y, n_runs=2, grid_points=4)
    scs = [Scenario(N=64, T=100.0, n_o=4.0)]
    fp = FleetPlanner(grid_size=128).plan_batch(
        ScenarioBatch.from_scenarios(scs), CONSTS, objective=obj)
    assert fp.grid.shape == (1, 4)
    # an explicit grid and a smaller planner grid_size still win
    fp2 = FleetPlanner(grid_size=2).plan_batch(
        ScenarioBatch.from_scenarios(scs), CONSTS, objective=obj)
    assert fp2.grid.shape == (1, 2)


@pytest.mark.slow
def test_montecarlo_default_grid_and_planner_facade():
    X, y = _ridge_data(n=64, d=4)
    obj = MonteCarloObjective(X=X, y=y, n_runs=2, grid_points=4)
    grid = obj.default_grid(64)
    assert grid[0] == 1 and grid[-1] == 64 and len(grid) <= 4
    sc = Scenario(N=64, T=100.0, n_o=4.0)
    a = ObjectivePlanner(objective=obj).plan(sc)      # no consts needed
    b = MonteCarloPlanner(X=X, y=y, n_runs=2, grid_points=4).plan(sc)
    assert (a.n_c, a.rate) == (b.n_c, b.rate)
    assert a.objective == b.objective == "montecarlo"


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def _mc_scenario(draw):
        N = draw(st.sampled_from([64, 128]))
        T = draw(st.sampled_from([0.8, 1.3, 1.9])) * N
        n_o = draw(st.sampled_from([2.0, 8.0, 24.0]))
        tau_p = draw(st.sampled_from([0.5, 1.0]))
        link = draw(st.sampled_from([
            IdealLink(rates=(1.0, 2.0)),
            ErasureLink(beta=0.6, p_base=0.2, rates=(1.0, 2.0)),
            GilbertElliottLink(p_gb=0.08, p_bg=0.3, p_good=0.02, p_bad=0.7,
                               beta=0.5, rates=(1.0, 2.0)),
        ]))
        D = draw(st.sampled_from([1, 2]))
        topology = MultiDevice(D) if D > 1 else SingleDevice()
        return Scenario(N=N, T=T, n_o=n_o, tau_p=tau_p, link=link,
                        topology=topology)

    _MC_X, _MC_Y = _ridge_data(n=128, d=5, seed=11)
    _MC_OBJECTIVE = MonteCarloObjective(X=_MC_X, y=_MC_Y, n_runs=2,
                                        alpha=1e-3, seed=3)

    @pytest.mark.slow
    @settings(max_examples=5, deadline=None)
    @given(scs=st.lists(_mc_scenario(), min_size=1, max_size=2))
    def test_montecarlo_batched_property_matches_scalar(scs):
        """ISSUE satellite: batched MonteCarloObjective plan == scalar
        MonteCarloPlanner plan for shared seeds on random scenarios."""
        _assert_mc_plans_match(scs, _MC_OBJECTIVE, grid=[2, 32])

    _ge_probs = st.floats(0.0, 0.9)

    @pytest.mark.slow
    @settings(max_examples=25, deadline=None)
    @given(p=_ge_probs, p_gb=st.floats(0.01, 1.0), p_bg=st.floats(0.01, 1.0),
           beta=st.floats(0.0, 2.0), n_o=st.floats(0.0, 1500.0),
           tn=st.floats(0.5, 2.5))
    def test_markov_arq_property_reduces_to_stationary(p, p_gb, p_bg, beta,
                                                       n_o, tn):
        """ISSUE satellite: MarkovARQObjective == stationary-loss plan
        whenever p_good == p_bad, whatever the transition probabilities."""
        link = GilbertElliottLink(p_gb=p_gb, p_bg=p_bg, p_good=p, p_bad=p,
                                  beta=beta, rates=RATES5)
        sc = Scenario(N=4096, T=tn * 4096, n_o=n_o, link=link)
        a = BoundPlanner().plan(sc, CONSTS)
        m = ObjectivePlanner(objective=MarkovARQObjective()).plan(sc, CONSTS)
        assert (a.n_c, a.rate, a.bound_value) == (m.n_c, m.rate,
                                                  m.bound_value)
        # and the fleet kernel agrees bitwise with the bound kernel
        G = 24
        fa = FleetPlanner(grid_size=G).plan_batch([sc], CONSTS)
        fm = FleetPlanner(grid_size=G).plan_batch(
            [sc], CONSTS, objective=MarkovARQObjective())
        assert int(fa.n_c[0]) == int(fm.n_c[0])
        assert float(fa.bound_value[0]) == float(fm.bound_value[0])


# ---------------------------------------------------------------------------
# PlanCache: objectives can never alias one entry
# ---------------------------------------------------------------------------


def test_cache_never_shared_across_objectives():
    """ISSUE satellite: two objectives on the same scenario never share a
    cache entry (the objective token is part of the quantised key)."""
    cache = PlanCache(maxsize=64)
    planner = FleetPlanner(grid_size=24)
    X, y = _ridge_data(n=64, d=4)
    mc = MonteCarloObjective(X=X, y=y, n_runs=2)
    sc = Scenario(N=64, T=100.0, n_o=4.0,
                  link=ErasureLink(beta=0.5, rates=(1.0, 2.0)))
    objectives = [BoundObjective(), MarkovARQObjective(), mc]
    keys = {cache.key(sc, context=("ctx",), objective=o)
            for o in objectives}
    assert len(keys) == 3
    recs = [planner.plan_many([sc], CONSTS, cache=cache, objective=o)[0]
            for o in objectives]
    assert len(cache) == 3
    assert {r.objective for r in recs} == {"corollary1", "markov_arq",
                                           "montecarlo"}
    # replays hit their own entry and only their own
    for o, rec in zip(objectives, recs):
        assert planner.plan_many([sc], CONSTS, cache=cache,
                                 objective=o)[0] == rec
    assert len(cache) == 3
    # MC hyperparams scope entries too: a different seed count, and a
    # different grid_points (it sets the DEFAULT search grid, so the
    # cached record's n_c can differ)
    mc5 = MonteCarloObjective(X=X, y=y, n_runs=3)
    planner.plan_many([sc], CONSTS, cache=cache, objective=mc5)
    assert len(cache) == 4
    mc_coarse = MonteCarloObjective(X=X, y=y, n_runs=2, grid_points=4)
    assert objective_token(mc) != objective_token(mc_coarse)
    planner.plan_many([sc], CONSTS, cache=cache, objective=mc_coarse)
    assert len(cache) == 5


def test_cache_objective_scoping_on_sticky_chain_records_differ():
    cache = PlanCache(maxsize=16)
    planner = FleetPlanner(grid_size=64)
    a = planner.plan_many([STICKY_SC], CONSTS, cache=cache,
                          objective=BoundObjective())[0]
    b = planner.plan_many([STICKY_SC], CONSTS, cache=cache,
                          objective=MarkovARQObjective())[0]
    assert (a.n_c, a.rate) != (b.n_c, b.rate)
    assert a.objective == "corollary1" and b.objective == "markov_arq"


# ---------------------------------------------------------------------------
# objective names
# ---------------------------------------------------------------------------


def test_resolve_objectives_unknown_and_empty():
    with pytest.raises(ValueError, match="unregistered planning objective"):
        resolve_objectives("nope")
    with pytest.raises(ValueError, match="no planning objective"):
        resolve_objectives(())
    assert set(resolve_objectives("all")) == {"corollary1", "markov_arq",
                                              "montecarlo"}


# ---------------------------------------------------------------------------
# custom objective plugin: scalar + fleet, end to end
# ---------------------------------------------------------------------------


def test_custom_objective_plugs_into_scalar_and_fleet_paths():
    """ISSUE tentpole: registering (numpy reference + grid value function)
    is ALL a new objective needs — the scalar planner minimises it, the
    shared grid kernel solves it batched next to the built-ins, and the
    cache keys it."""
    import jax.numpy as jnp  # noqa: F401  (grid kernel runs under jax)

    @dataclass(frozen=True)
    class ThroughputObjective:
        """Expected delivery time per sample — README's worked example."""

        objective_id: ClassVar[str] = "throughput"

        def evaluate(self, scenario, consts, grid, rates):
            grid = np.asarray(grid, np.float64)
            n_o_eff = self.effective_overhead(
                scenario, grid[None, :],
                np.asarray(rates, np.float64)[:, None])
            return (grid[None, :] + n_o_eff) / grid[None, :]

        def effective_overhead(self, scenario, n_c, rate):
            return scenario.effective_overhead(n_c, rate)

        def cache_token(self):
            return (self.objective_id,)

    register_objective(ThroughputObjective)
    register_objective_kernel(
        "throughput",
        grid_objective_builder(
            lambda g, N, T, n_o_eff, tau_p, sigma, e0, contraction:
                (g + n_o_eff) / g))
    try:
        obj = ThroughputObjective()
        scs = _mixed_scenarios()
        G = 24
        fp = FleetPlanner(grid_size=G).plan_batch(
            ScenarioBatch.from_scenarios(scs), CONSTS, objective=obj)
        assert fp.objective == "throughput"
        for i, sc in enumerate(scs):
            sp = ObjectivePlanner(objective=obj,
                                  grid=fleet_grid(sc.N, G)).plan(sc, CONSTS)
            assert int(fp.n_c[i]) == sp.n_c
            assert float(fp.rate[i]) == sp.rate
            assert np.isclose(float(fp.bound_value[i]), sp.bound_value,
                              rtol=1e-12)
        # throughput ignores the bound: it prefers the largest blocks
        assert int(fp.n_c[0]) == scs[0].N
        cache = PlanCache(maxsize=8)
        assert cache.key(scs[0], objective=obj) \
            != cache.key(scs[0], objective=BoundObjective())
    finally:
        unregister_objective_kernel("throughput")
        unregister_objective("throughput")
