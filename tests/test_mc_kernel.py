"""The Monte-Carlo simulation engines (ISSUE: Pallas kernel + CRN +
seed schedules).

Pins the three-engine contract of the fleet Monte-Carlo solve:

  * the Pallas slab kernel (interpret mode on CPU) against the numpy
    oracle and BITWISE against the ``lax.scan`` engines, for both the
    exact-RNG and the common-random-numbers update forms;
  * the fold_in / legacy per-run seed streams — fleet-vs-scalar
    seed-for-seed parity for both, the legacy collision regression, and
    the CRN-off path staying scalar-identical;
  * the seed schedules: the ``mc_seeds`` static override, the
    multi-level ``coarse_strides`` refine path (stage-for-stage equal to
    a hand-rolled schedule), its AOT warmup (zero post-warmup traces),
    and the cache keys that keep every estimator variant apart.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import (BoundConstants, ErasureLink, MonteCarloObjective,
                        MonteCarloPlanner, Scenario)
from repro.core.pipeline import mc_run_key
from repro.core.planner import coarse_indices, fleet_grid, refine_grid
from repro.fleet import FleetPlanner, ScenarioBatch, objective_token
from repro.fleet.objective_kernels import fleet_solve
from repro.fleet.tracing import trace_delta
from repro.kernels import mc_ridge_slab
from repro.kernels.ref import mc_ridge_ref

CONSTS = BoundConstants(L=1.908, c=0.061, M=1.0, M_G=1.0, D=1.0, alpha=1e-4)


def _ridge_data(n=48, d=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = X @ rng.normal(size=d) + 0.1 * rng.normal(size=n)
    return X, y


def _scenarios(n=3):
    link = ErasureLink(beta=0.4, p_base=0.05, rates=(1.0, 2.0))
    return [Scenario(N=int(N), T=1.3 * N, n_o=float(o), tau_p=2.0,
                     link=link)
            for N, o in zip((256, 384, 512, 320), (20.0, 90.0, 45.0, 60.0))
            ][:n]


def _plan(objective, scs, grid, mc_impl="scan", **planner_kw):
    pl = FleetPlanner(grid_size=8, mc_impl=mc_impl, **planner_kw)
    return pl.plan_batch(ScenarioBatch.from_scenarios(scs), CONSTS,
                         grid=np.asarray(grid), objective=objective)


# ---------------------------------------------------------------------------
# Pallas slab kernel vs the numpy oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [False, True])
def test_mc_ridge_slab_matches_numpy_ref(fused):
    """Interpret-mode kernel vs the sequential numpy oracle, both update
    forms, including a padded (L not a block multiple) lane count."""
    rng = np.random.default_rng(3)
    L, d, n, slab = 21, 4, 16, 12
    W = rng.normal(size=(L, d)).astype(np.float32)
    Xs = rng.normal(size=(n, d)).astype(np.float32)
    ys = rng.normal(size=n).astype(np.float32)
    ix = rng.integers(0, n, size=(slab, L)).astype(np.int32)
    m = (rng.random(size=(slab, L)) < 0.7).astype(np.float32)
    out = mc_ridge_slab(W, Xs, ys, ix, m, alpha=1e-3, lam=0.1,
                        fused=fused, interpret=True)
    ref = mc_ridge_ref(W, Xs, ys, ix, m, alpha=1e-3, lam=0.1, fused=fused)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-6)


def test_mc_ridge_slab_dead_lane_passthrough():
    """An all-masked lane's weights come back bitwise-unchanged (what
    makes zero-padded lanes safe)."""
    rng = np.random.default_rng(4)
    L, d, n, slab = 5, 4, 8, 6
    W = rng.normal(size=(L, d)).astype(np.float32)
    Xs = rng.normal(size=(n, d)).astype(np.float32)
    ys = rng.normal(size=n).astype(np.float32)
    ix = rng.integers(0, n, size=(slab, L)).astype(np.int32)
    m = np.ones((slab, L), np.float32)
    m[:, 2] = 0.0
    for fused in (False, True):
        out = np.asarray(mc_ridge_slab(W, Xs, ys, ix, m, alpha=1e-3,
                                       lam=0.1, fused=fused,
                                       interpret=True))
        np.testing.assert_array_equal(out[2], W[2])


# ---------------------------------------------------------------------------
# engine equivalence: pallas (interpret) bitwise == lax.scan
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("crn", [False, True])
def test_pallas_engine_bitwise_matches_scan(crn):
    """The ``mc_impl="pallas"`` fleet solve returns BITWISE the scan
    engine's plans — exact-RNG and CRN forms both (the shared host-side
    tables + one-hot MXU gather make the kernel exact, not approximate)."""
    X, y = _ridge_data()
    mc = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0,
                             crn=crn)
    scs = _scenarios()
    grid = [1, 4, 16, 64]
    scan = _plan(mc, scs, grid, mc_impl="scan")
    pallas = _plan(mc, scs, grid, mc_impl="pallas")
    np.testing.assert_array_equal(np.asarray(scan.n_c),
                                  np.asarray(pallas.n_c))
    np.testing.assert_array_equal(np.asarray(scan.rate),
                                  np.asarray(pallas.rate))
    np.testing.assert_array_equal(np.asarray(scan.bound_value),
                                  np.asarray(pallas.bound_value))
    np.testing.assert_array_equal(np.asarray(scan.bound_grid),
                                  np.asarray(pallas.bound_grid))


# ---------------------------------------------------------------------------
# seed streams: fleet == scalar seed-for-seed; legacy collision pin
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("seed_stream", ["fold_in", "legacy"])
def test_fleet_matches_scalar_seed_for_seed(seed_stream):
    """Batched MC planning matches the scalar planner seed-for-seed in
    BOTH stream modes — i.e. the CRN-off default stays scalar-identical
    and the legacy compat mode still reproduces the historical streams."""
    X, y = _ridge_data()
    mc = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=7,
                             seed_stream=seed_stream)
    scs = _scenarios()
    grid = [1, 4, 16, 64]
    fleet = _plan(mc, scs, grid)
    for i, sc in enumerate(scs):
        scalar = MonteCarloPlanner(X=X, y=y, lam=mc.lam, alpha=mc.alpha,
                                   n_runs=2, seed=7, grid=grid,
                                   seed_stream=seed_stream).plan(sc, CONSTS)
        assert int(fleet.n_c[i]) == scalar.n_c
        assert float(fleet.rate[i]) == scalar.rate
        assert np.isclose(float(fleet.bound_value[i]), scalar.bound_value,
                          rtol=1e-5)


def test_legacy_stream_collision_and_fold_in_fix():
    """Regression pin: the historical ``seed0 + 97 r`` streams ALIAS
    (seed 0 run 1 == seed 97 run 0) and stay bitwise-reproducible under
    ``seed_stream="legacy"``; the fold_in default is collision-free."""
    legacy_01 = mc_run_key(0, 1, "legacy")
    np.testing.assert_array_equal(np.asarray(legacy_01),
                                  np.asarray(jax.random.PRNGKey(97)))
    np.testing.assert_array_equal(np.asarray(legacy_01),
                                  np.asarray(mc_run_key(97, 0, "legacy")))
    fold_01 = np.asarray(mc_run_key(0, 1))
    assert not np.array_equal(fold_01, np.asarray(mc_run_key(97, 0)))
    assert not np.array_equal(fold_01, np.asarray(jax.random.PRNGKey(97)))
    with pytest.raises(ValueError):
        mc_run_key(0, 0, "bogus")


def test_objective_validates_stream_and_schedule_fields():
    X, y = _ridge_data(n=16, d=3)
    with pytest.raises(ValueError):
        MonteCarloObjective(X=X, y=y, seed_stream="bogus")
    with pytest.raises(ValueError):
        MonteCarloObjective(X=X, y=y, coarse_seeds=-1)
    with pytest.raises(ValueError):
        MonteCarloObjective(X=X, y=y, refine_rates=0)
    with pytest.raises(ValueError):
        MonteCarloObjective(X=X, y=y, coarse_strides=())
    with pytest.raises(ValueError):
        MonteCarloObjective(X=X, y=y, coarse_strides=(6, 12))  # ascending
    with pytest.raises(ValueError):
        MonteCarloObjective(X=X, y=y, coarse_strides=(12, 0))
    with pytest.raises(ValueError):
        MonteCarloObjective(X=X, y=y, fine_radius=0)
    with pytest.raises(ValueError):
        MonteCarloObjective(X=X, y=y, coarse_updates=0)
    ok = MonteCarloObjective(X=X, y=y, coarse_strides=[12, 4])
    assert ok.coarse_strides == (12, 4)  # normalised to a tuple


def test_estimator_variants_never_share_cache_tokens():
    """crn / seed_stream / seed+rate/stride schedules all key the cache:
    no estimator variant may alias a reference plan."""
    X, y = _ridge_data(n=16, d=3)
    base = MonteCarloObjective(X=X, y=y)
    variants = [
        MonteCarloObjective(X=X, y=y, crn=True),
        MonteCarloObjective(X=X, y=y, seed_stream="legacy"),
        MonteCarloObjective(X=X, y=y, coarse_seeds=1),
        MonteCarloObjective(X=X, y=y, refine_rates=1),
        MonteCarloObjective(X=X, y=y, coarse_strides=(12, 4)),
        MonteCarloObjective(X=X, y=y, fine_radius=10),
        MonteCarloObjective(X=X, y=y, coarse_updates=2048),
    ]
    tokens = [objective_token(o) for o in [base] + variants]
    assert len(set(tokens)) == len(tokens)


def test_cache_context_tags_non_default_engine():
    ctx_scan = FleetPlanner(mc_impl="scan").cache_context(CONSTS)
    ctx_pallas = FleetPlanner(mc_impl="pallas").cache_context(CONSTS)
    assert ctx_pallas[-2:] == ("mc_impl", "pallas")
    assert "mc_impl" not in ctx_scan


# ---------------------------------------------------------------------------
# seed schedules: mc_seeds override + the multi-level refine path
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_mc_seeds_override_matches_fewer_runs():
    """The ``mc_seeds`` static override truncates the seed loop to a
    PREFIX of the fold_in streams: a 2-run objective solved with
    ``mc_seeds=1`` is bitwise a 1-run objective's solve."""
    X, y = _ridge_data()
    scs = _scenarios()
    batch = ScenarioBatch.from_scenarios(scs)
    grid = np.broadcast_to(np.asarray([1, 4, 16, 64]), (len(scs), 4))
    arrays = FleetPlanner._solve_arrays(batch, grid)
    mc2 = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0)
    mc1 = MonteCarloObjective(X=X, y=y, n_runs=1, alpha=1e-3, seed=0)
    out_sched = fleet_solve(mc2)(dict(arrays, mc_seeds=1), CONSTS, False,
                                 batch)
    out_1run = fleet_solve(mc1)(arrays, CONSTS, False, batch)
    np.testing.assert_array_equal(np.asarray(out_sched["bound_value"]),
                                  np.asarray(out_1run["bound_value"]))
    np.testing.assert_array_equal(np.asarray(out_sched["n_c"]),
                                  np.asarray(out_1run["n_c"]))


@pytest.mark.slow
@pytest.mark.parametrize("hints", [{}, dict(fine_radius=6,
                                            coarse_updates=8)])
def test_multi_level_refine_matches_hand_rolled_schedule(hints):
    """The ``coarse_strides`` planner path IS the documented schedule:
    stage-for-stage equal to a hand-rolled stage0 -> rate-prune ->
    mid-stage -> fine-window sequence over the same solve.  The hinted
    variant adds the horizon schedule (``mc_updates`` cap on the coarse
    stages only, never the fine pass) and the decoupled fine-window
    radius."""
    X, y = _ridge_data()
    fast = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0,
                               grid_points=48, crn=True, coarse_seeds=1,
                               refine_rates=1, coarse_strides=(12, 4),
                               **hints)
    scs = _scenarios()
    batch = ScenarioBatch.from_scenarios(scs)
    G = 48
    grids = fleet_grid(batch.N, G)
    planner = FleetPlanner(objective=fast, grid_size=G, grid_mode="refine",
                           pow2_refine_widths=True)
    plan = planner.plan_batch(batch, CONSTS, grid=grids)

    # hand-rolled reference over the same fleet solve
    solve = fleet_solve(fast)
    arrays = FleetPlanner._solve_arrays(batch, grids)
    s0, s1 = 12, 4
    hz = ({"mc_updates": hints["coarse_updates"]} if hints else {})
    cpos = coarse_indices(G, s0)
    out0 = solve(dict(arrays, grid=np.ascontiguousarray(grids[:, cpos]),
                      mc_seeds=1, **hz), CONSTS, False, batch)
    vpr = np.asarray(out0["val_per_rate"])
    sel = np.sort(np.argsort(vpr, axis=1, kind="stable")[:, :1], axis=1)
    centers = np.take_along_axis(
        cpos[np.asarray(out0["gi_per_rate"], np.int64)], sel, axis=1)
    rates = np.ascontiguousarray(
        np.take_along_axis(np.asarray(arrays["rates"]), sel, 1))
    rmask = np.ascontiguousarray(
        np.take_along_axis(np.asarray(arrays["rate_mask"]), sel, 1))
    offs = np.arange(-(s0 // s1), s0 // s1 + 1) * s1
    win = np.clip(centers[:, :, None] + offs, 0, G - 1)
    out1 = solve(dict(arrays,
                      grid=np.ascontiguousarray(np.take_along_axis(
                          grids[:, None, :], win, axis=2)),
                      rates=rates, rate_mask=rmask, mc_seeds=1, **hz),
                 CONSTS, False, batch)
    centers = np.take_along_axis(
        win, np.asarray(out1["gi_per_rate"], np.int64)[:, :, None],
        axis=2)[..., 0]
    fine = hints.get("fine_radius", s1)    # pow2ceil(2*6+1) == pow2ceil(
    _, win_grid, _ = refine_grid(grids, centers, fine, tail_start=None,
                                 width=16)  # 2*4+1) == 16 for both cases
    out2 = solve(dict(arrays, grid=np.ascontiguousarray(win_grid),
                      rates=rates, rate_mask=rmask), CONSTS, False, batch)
    np.testing.assert_array_equal(np.asarray(plan.n_c),
                                  np.asarray(out2["n_c"]))
    np.testing.assert_array_equal(np.asarray(plan.rate),
                                  np.asarray(out2["rate"]))
    np.testing.assert_array_equal(np.asarray(plan.bound_value),
                                  np.asarray(out2["bound_value"]))


@pytest.mark.slow
def test_coarse_horizon_cap_is_a_timeline_prefix():
    """``mc_updates`` at or above the padded horizon is a bitwise no-op;
    a real cap trains a strict PREFIX of the same CRN slot stream (the
    counter-based draws make the truncated timeline a prefix, not a
    different stream)."""
    X, y = _ridge_data()
    mc = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0,
                             crn=True)
    scs = _scenarios()
    batch = ScenarioBatch.from_scenarios(scs)
    grid = np.broadcast_to(np.asarray([1, 4, 16, 64]), (len(scs), 4))
    arrays = FleetPlanner._solve_arrays(batch, grid)
    solve = fleet_solve(mc)
    full = solve(dict(arrays), CONSTS, False, batch)
    nop = solve(dict(arrays, mc_updates=1 << 20), CONSTS, False, batch)
    np.testing.assert_array_equal(np.asarray(full["bound_grid"]),
                                  np.asarray(nop["bound_grid"]))
    capped = solve(dict(arrays, mc_updates=8), CONSTS, False, batch)
    assert not np.array_equal(np.asarray(full["bound_grid"]),
                              np.asarray(capped["bound_grid"]))


@pytest.mark.slow
@pytest.mark.parametrize("mc_impl", ["scan", "pallas"])
@pytest.mark.parametrize("hints", [{}, dict(fine_radius=6,
                                            coarse_updates=8)])
def test_multi_level_warmup_is_exhaustive(hints, mc_impl):
    """AOT warmup covers every shape the multi-level schedule can hit —
    including the horizon-capped coarse stages and the widened fine
    window: zero post-warmup traces for a planned batch (the serving
    SLO), for either engine.  The batch is a pow2 length — warmup pads to
    the pow2 / bucket signature exactly like the serving layer's request
    batches."""
    X, y = _ridge_data()
    fast = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0,
                               grid_points=48, crn=True, coarse_seeds=1,
                               refine_rates=1, coarse_strides=(12, 4),
                               **hints)
    scs = _scenarios(4)
    planner = FleetPlanner(objective=fast, grid_size=48,
                           grid_mode="refine", pow2_refine_widths=True,
                           mc_impl=mc_impl)
    assert planner.warm(scs, CONSTS) > 0
    with trace_delta() as traces:
        plan = planner.plan_batch(scs, CONSTS)
    assert traces.total == 0
    assert np.all(np.asarray(plan.n_c) >= 1)
