"""Coarse->fine refinement solver: window construction (refine_grid /
coarse_indices / refine_window_bounds), fixed-case argmin parity of the
refined vs dense solves for all three shipped objectives, the hypothesis
refinement-parity property (subset invariants + tail-guard exactness +
rate-major tie-breaking) over mixed link-model batches, dense fallbacks,
and the grid-mode plumbing (validation, cache scoping)."""
import numpy as np
import pytest

from repro.core import BoundConstants
from repro.core.objectives import (BoundObjective, MarkovARQObjective,
                                   MonteCarloObjective, RefineHints,
                                   refine_hints_for)
from repro.core.planner import (coarse_indices, fleet_grid, refine_grid,
                                refine_window_bounds)
from repro.core.scenario import (ErasureLink, FadingLink, GilbertElliottLink,
                                 IdealLink, MultiDevice, Scenario,
                                 SingleDevice)
from repro.fleet import GRID_MODES, FleetPlanner, PlanCache, ScenarioBatch
from repro.serve import resolve_grid_modes

CONSTS = BoundConstants(L=1.908, c=0.061, M=1.0, M_G=1.0, D=1.0, alpha=1e-4)
RATES5 = (1.0, 1.25, 1.5, 2.0, 3.0)


def _fleet_scenarios(n, seed):
    """Fleet-scale tight-deadline population (the paper's regime) mixing
    every registered channel family — the fixed refinement-parity cases."""
    rng = np.random.default_rng(seed)
    links = [
        lambda: IdealLink(rates=RATES5),
        lambda: ErasureLink(beta=float(rng.uniform(0.05, 1.5)),
                            p_base=float(rng.uniform(0.0, 0.4)),
                            rates=RATES5),
        lambda: FadingLink(snr=float(rng.uniform(2.0, 50.0)), rates=RATES5),
        lambda: GilbertElliottLink(p_gb=float(rng.uniform(0.01, 0.3)),
                                   p_bg=float(rng.uniform(0.2, 0.9)),
                                   p_good=float(rng.uniform(0.0, 0.2)),
                                   p_bad=float(rng.uniform(0.2, 0.9)),
                                   beta=float(rng.uniform(0.05, 1.0)),
                                   rates=RATES5),
    ]
    out = []
    for _ in range(n):
        N = int(rng.integers(1 << 17, 1 << 20))
        D = int(rng.choice([1, 2, 4, 8]))
        out.append(Scenario(
            N=N, T=float(rng.uniform(1.05, 1.4)) * N,
            n_o=float(rng.uniform(10.0, 5000.0)),
            tau_p=float(rng.choice([0.5, 1.0, 2.0])),
            link=links[int(rng.integers(4))](),
            topology=MultiDevice(D) if D > 1 else SingleDevice()))
    return out


# ---------------------------------------------------------------------------
# window construction
# ---------------------------------------------------------------------------


def test_coarse_indices_anchor_last():
    np.testing.assert_array_equal(coarse_indices(10, 3), [0, 3, 6, 9])
    np.testing.assert_array_equal(coarse_indices(11, 3), [0, 3, 6, 9, 10])
    np.testing.assert_array_equal(coarse_indices(4, 8), [0, 3])
    with pytest.raises(ValueError):
        coarse_indices(8, 0)


def test_refine_grid_bracket_windows():
    grid = np.arange(100, 120, dtype=np.int64)[None, :]    # G = 20
    centers = np.array([[5, 0, 19]])                       # interior + edges
    win_idx, win_grid, count = refine_grid(grid, centers, 3)
    assert win_idx.shape == (1, 3, 7)
    np.testing.assert_array_equal(win_idx[0, 0], [2, 3, 4, 5, 6, 7, 8])
    # edge clamping: the bracket clips, padding repeats the LAST real index
    np.testing.assert_array_equal(win_idx[0, 1], [0, 1, 2, 3, 3, 3, 3])
    np.testing.assert_array_equal(win_idx[0, 2], [16, 17, 18, 19, 19, 19, 19])
    np.testing.assert_array_equal(count[0], [7, 4, 4])
    np.testing.assert_array_equal(win_grid, grid[0][win_idx])


def test_refine_grid_tail_merge_and_padding():
    grid = np.arange(20, dtype=np.int64)[None, :] + 1
    centers = np.array([[4, 16]])
    # disjoint bracket + tail for the first rate; overlapping for the
    # second (bracket [14,18] touches tail [15, 20) -> single interval)
    win_idx, win_grid, count = refine_grid(grid, centers, 2, tail_start=[15])
    np.testing.assert_array_equal(count[0], [10, 6])
    np.testing.assert_array_equal(win_idx[0, 0],
                                  [2, 3, 4, 5, 6, 15, 16, 17, 18, 19])
    np.testing.assert_array_equal(win_idx[0, 1],
                                  [14, 15, 16, 17, 18, 19, 19, 19, 19, 19])
    # windows enumerate ascending dense indices (tie-breaking invariant)
    assert (np.diff(win_idx, axis=2) >= 0).all()
    # pad_multiple rounds the padded width up
    w8 = refine_grid(grid, centers, 2, tail_start=[15], pad_multiple=8)[0]
    assert w8.shape[2] == 16
    with pytest.raises(ValueError):
        refine_grid(grid, centers, 2, tail_start=[15], width=4)


def test_refine_window_bounds_matches_refine_grid():
    rng = np.random.default_rng(5)
    G = 64
    grid = np.cumsum(rng.integers(1, 5, (3, G)), axis=1)
    centers = rng.integers(0, G, (3, 4))
    tail = rng.integers(0, G + 1, 3)
    lo, hi2, t2, len1, count = refine_window_bounds(centers, 5, G, tail)
    win_idx, _, count2 = refine_grid(grid, centers, 5, tail_start=tail)
    np.testing.assert_array_equal(count, count2)
    for s in range(3):
        for r in range(4):
            want = sorted(set(range(lo[s, r], hi2[s, r] + 1))
                          | set(range(t2[s, r], G)))
            got = list(dict.fromkeys(win_idx[s, r].tolist()))
            assert got == want, (s, r)


# ---------------------------------------------------------------------------
# refined == dense: fixed cases, all three shipped objectives
# ---------------------------------------------------------------------------


def _assert_plans_identical(dense, refined):
    np.testing.assert_array_equal(dense.n_c, refined.n_c)
    np.testing.assert_array_equal(dense.rate, refined.rate)
    # same argmin point, same kernel ops -> bitwise-equal objective values
    np.testing.assert_array_equal(dense.bound_value, refined.bound_value)
    np.testing.assert_array_equal(dense.p_err, refined.p_err)
    np.testing.assert_array_equal(dense.full_transfer, refined.full_transfer)
    np.testing.assert_array_equal(dense.n_c_per_device,
                                  refined.n_c_per_device)


@pytest.mark.parametrize("objective", [BoundObjective(), MarkovARQObjective()],
                         ids=["corollary1", "markov_arq"])
def test_refined_matches_dense_bound_objectives_fixed(objective):
    """ISSUE acceptance: refined and dense solves produce argmin-identical
    plans on the fleet-scale tight-deadline population (the guarded
    sawtooth tail plus the coarse bracket covers every optimum here)."""
    batch = ScenarioBatch.from_scenarios(_fleet_scenarios(96, seed=23))
    G = 384
    grids = fleet_grid(batch.N, G)
    dense = FleetPlanner(grid_size=G).plan_batch(
        batch, CONSTS, grid=grids, objective=objective)
    refined = FleetPlanner(grid_size=G, grid_mode="refine").plan_batch(
        batch, CONSTS, grid=grids, objective=objective)
    _assert_plans_identical(dense, refined)
    # the refined pass really did evaluate fewer points
    assert refined.grid.shape[1] < G
    assert refined.bound_grid.shape == refined.grid.shape


@pytest.mark.slow
def test_refined_matches_dense_montecarlo_fixed():
    """Monte-Carlo refined == dense on bracket-resolved fixed cases (the
    empirical landscape is seed-noise ragged, so unlike the guarded bound
    objectives exactness holds on resolved basins, not universally —
    these cases are verified resolved)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(96, 5))
    y = X @ rng.normal(size=5) + 0.1 * rng.normal(size=96)
    mc = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0)
    rates = (1.0, 1.5, 3.0)
    scs = [
        Scenario(N=1657, T=2100.0, n_o=99.0, tau_p=1.0,
                 link=ErasureLink(beta=0.3, p_base=0.1, rates=rates)),
        Scenario(N=699, T=899.0, n_o=238.4, tau_p=1.0,
                 link=ErasureLink(beta=0.6, p_base=0.05, rates=rates)),
        Scenario(N=545, T=635.0, n_o=111.2, tau_p=1.0,
                 link=IdealLink(rates=rates)),
        Scenario(N=1479, T=2350.0, n_o=213.6, tau_p=1.0,
                 link=ErasureLink(beta=0.2, p_base=0.15, rates=rates)),
    ]
    batch = ScenarioBatch.from_scenarios(scs)
    G = 32
    grids = fleet_grid(batch.N, G)
    dense = FleetPlanner(grid_size=G).plan_batch(batch, CONSTS, grid=grids,
                                                objective=mc)
    refined = FleetPlanner(grid_size=G, grid_mode="refine").plan_batch(
        batch, CONSTS, grid=grids, objective=mc)
    _assert_plans_identical(dense, refined)
    assert refined.grid.shape[1] < G


def test_refine_falls_back_to_dense_on_narrow_grids():
    """Below the objective's min_grid hint (brackets would clip at the
    grid edges) refine mode IS the dense solve, bitwise."""
    batch = ScenarioBatch.from_scenarios(_fleet_scenarios(8, seed=3))
    for G in (8, 24):
        dense = FleetPlanner(grid_size=G).plan_batch(batch, CONSTS)
        refined = FleetPlanner(grid_size=G, grid_mode="refine").plan_batch(
            batch, CONSTS)
        _assert_plans_identical(dense, refined)
        np.testing.assert_array_equal(dense.grid, refined.grid)
        np.testing.assert_array_equal(dense.bound_grid, refined.bound_grid)


def test_refine_hints_registry():
    assert refine_hints_for(BoundObjective()).tail_blocks == 32
    assert refine_hints_for(MarkovARQObjective()).stride == 16
    mc_hints = refine_hints_for(
        MonteCarloObjective(X=np.eye(4), y=np.ones(4)))
    assert mc_hints.tail_blocks is None and mc_hints.min_grid == 24
    # objects without declared hints get the registry default
    assert refine_hints_for(object()) == RefineHints()


# ---------------------------------------------------------------------------
# hypothesis property: refinement parity over mixed link-model batches
# ---------------------------------------------------------------------------


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _rate_sets = st.sets(st.sampled_from(RATES5), min_size=1).map(
        lambda s: tuple(sorted(s)))

    @st.composite
    def _link(draw):
        rates = draw(_rate_sets)
        kind = draw(st.sampled_from(["ideal", "erasure", "fading", "ge"]))
        if kind == "erasure":
            return ErasureLink(beta=draw(st.floats(0.0, 2.0)),
                               p_base=draw(st.floats(0.0, 0.9)),
                               rates=rates)
        if kind == "fading":
            return FadingLink(snr=draw(st.floats(0.5, 100.0)), rates=rates)
        if kind == "ge":
            return GilbertElliottLink(
                p_gb=draw(st.floats(0.01, 1.0)),
                p_bg=draw(st.floats(0.01, 1.0)),
                p_good=draw(st.floats(0.0, 0.9)),
                p_bad=draw(st.floats(0.0, 0.9)),
                beta=draw(st.floats(0.0, 2.0)), rates=rates)
        return IdealLink(rates=rates)

    @st.composite
    def _scenario(draw):
        N = draw(st.integers(256, 60000))
        T = draw(st.floats(0.4, 3.0)) * N
        n_o = draw(st.floats(0.0, 2000.0))
        tau_p = draw(st.sampled_from([0.5, 1.0, 2.0]))
        D = draw(st.integers(1, 8))
        return Scenario(N=N, T=T, n_o=n_o, tau_p=tau_p, link=draw(_link()),
                        topology=MultiDevice(D) if D > 1 else SingleDevice())

    @settings(max_examples=15, deadline=None)
    @given(scs=st.lists(_scenario(), min_size=1, max_size=5),
           objective=st.sampled_from([BoundObjective(),
                                      MarkovARQObjective()]))
    def test_refinement_parity_property(scs, objective):
        """ISSUE acceptance: the coarse->fine argmin vs the dense-grid
        argmin, rate-major tie-breaking included, on arbitrary mixed
        link-model batches:

          * the refined optimum is the dense argmin over the EVALUATED
            subset, so its value can never beat the dense optimum, and
            whenever the plans coincide the values are bitwise equal;
          * any scenario whose dense argmin falls inside the guarded
            sawtooth tail (which is always evaluated densely) must
            produce the IDENTICAL plan — the tie-breaking acceptance;
          * outside the evaluated subset the refined plan stays within
            the documented residual-quality envelope of the dense one.
        """
        G = 96
        batch = ScenarioBatch.from_scenarios(scs)
        grids = fleet_grid(batch.N, G)
        dense = FleetPlanner(grid_size=G).plan_batch(
            batch, CONSTS, grid=grids, objective=objective)
        refined = FleetPlanner(grid_size=G, grid_mode="refine").plan_batch(
            batch, CONSTS, grid=grids, objective=objective)
        tail_blocks = refine_hints_for(objective).tail_blocks
        tail_start = np.sum(grids * tail_blocks < batch.N[:, None], axis=1)
        for i in range(len(batch)):
            d_nc, d_rate = int(dense.n_c[i]), float(dense.rate[i])
            r_nc, r_rate = int(refined.n_c[i]), float(refined.rate[i])
            dv, rv = float(dense.bound_value[i]), float(refined.bound_value[i])
            assert rv >= dv or (r_nc, r_rate) == (d_nc, d_rate), \
                "refined subset argmin beat the dense argmin"
            if (r_nc, r_rate) == (d_nc, d_rate):
                assert rv == dv  # same point -> bitwise-equal evaluation
            else:
                assert rv <= dv * 1.06 + 1e-12, (i, dv, rv)
            # dense argmin inside the always-evaluated guarded tail ->
            # the refined reduction must reproduce it exactly
            gi = int(np.argmin(dense.bound_grid[i]))
            if gi >= int(tail_start[i]):
                assert (r_nc, r_rate) == (d_nc, d_rate), \
                    (i, "tail-guarded dense argmin not reproduced")
                assert rv == dv

    @pytest.mark.slow
    @settings(max_examples=5, deadline=None)
    @given(data=st.data())
    def test_refinement_parity_property_montecarlo(data):
        """The refinement-parity property for the SIMULATED objective:
        the Monte-Carlo kernel has no tail guard, so the subset
        invariants (refined can never beat dense; coinciding plans are
        bitwise equal; residual gaps stay inside the documented
        envelope) are the exactness contract."""
        rng = np.random.default_rng(1)
        X = rng.normal(size=(64, 4))
        y = X @ rng.normal(size=4) + 0.1 * rng.normal(size=64)
        mc = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0)
        rates = (1.0, 2.0)
        scs = []
        for _ in range(2):   # fixed S so one kernel shape compiles
            N = data.draw(st.integers(256, 1024))
            scs.append(Scenario(
                N=N, T=data.draw(st.floats(1.05, 1.5)) * N,
                n_o=data.draw(st.floats(1.0, 300.0)), tau_p=1.0,
                link=ErasureLink(beta=data.draw(st.floats(0.0, 1.0)),
                                 p_base=data.draw(st.floats(0.0, 0.3)),
                                 rates=rates)))
        G = 32
        batch = ScenarioBatch.from_scenarios(scs)
        grids = fleet_grid(batch.N, G)
        dense = FleetPlanner(grid_size=G).plan_batch(
            batch, CONSTS, grid=grids, objective=mc)
        refined = FleetPlanner(grid_size=G, grid_mode="refine").plan_batch(
            batch, CONSTS, grid=grids, objective=mc)
        for i in range(len(batch)):
            same = (int(dense.n_c[i]), float(dense.rate[i])) == \
                (int(refined.n_c[i]), float(refined.rate[i]))
            dv = float(dense.bound_value[i])
            rv = float(refined.bound_value[i])
            if same:
                assert rv == dv
            else:
                assert rv >= dv
                assert rv <= dv * 1.06 + 1e-12
else:  # surface the missing property coverage as skips, not silence
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_refinement_parity_property():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_refinement_parity_property_montecarlo():
        pass


# ---------------------------------------------------------------------------
# grid-mode plumbing: validation, caching
# ---------------------------------------------------------------------------


def test_grid_mode_validation():
    assert resolve_grid_modes("all") == GRID_MODES
    assert resolve_grid_modes("refine,dense") == ("refine", "dense")
    with pytest.raises(ValueError):
        resolve_grid_modes("refined")   # typo must not silently fall back
    with pytest.raises(ValueError):
        resolve_grid_modes("")
    with pytest.raises(ValueError):
        FleetPlanner(grid_mode="coarse")
    with pytest.raises(ValueError):
        FleetPlanner().plan_batch(
            ScenarioBatch.from_scenarios(_fleet_scenarios(1, seed=1)),
            CONSTS, grid_mode="nope")


def test_cache_scoped_by_grid_mode():
    """Dense and refined entries never alias in a shared cache, even when
    the plans coincide (the satellite: grid mode folds into the cache
    context)."""
    planner_d = FleetPlanner(grid_size=48)
    planner_r = FleetPlanner(grid_size=48, grid_mode="refine")
    cache = PlanCache(maxsize=16)
    scs = _fleet_scenarios(2, seed=11)
    rec_d = planner_d.plan_many(scs, CONSTS, cache=cache)
    rec_r = planner_r.plan_many(scs, CONSTS, cache=cache)
    assert len(cache) == 4                       # two entries per mode
    assert planner_d.plan_many(scs, CONSTS, cache=cache) == rec_d
    assert planner_r.plan_many(scs, CONSTS, cache=cache) == rec_r
    # per-call override uses the override's scope, not the planner's
    assert planner_d.plan_many(scs, CONSTS, cache=cache,
                               grid_mode="refine") == rec_r
    assert len(cache) == 4
