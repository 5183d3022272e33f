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
    and the cache keys that keep every estimator variant apart;
  * the fast schedule's plans against the dense CRN and exact-stream
    solves (quality floors and search regret).
"""
import dataclasses
import functools

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
from repro.kernels.mc_ridge import row_table
from repro.kernels.ref import mc_ridge_ref
from repro.serve import LINK_FACTORIES, default_consts, resolve_objectives

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
    out = mc_ridge_slab(W, row_table(Xs, ys), ix, m, alpha=1e-3,
                        lam=0.1, fused=fused, interpret=True)
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
        out = np.asarray(mc_ridge_slab(W, row_table(Xs, ys), ix, m,
                                       alpha=1e-3, lam=0.1, fused=fused,
                                       interpret=True))
        np.testing.assert_array_equal(out[2], W[2])


@pytest.mark.parametrize("fused", [False, True])
def test_mc_ridge_slab_stops_each_block_at_its_last_live_slot(fused):
    """Given lane deadlines ``hi`` and the slab's first slot ``j0``, each
    128-lane block runs only to its lanes' last live slot: here a block
    with no slot left, one with part of the slab and one with all of it
    (the last block padded).  The weights are bitwise the whole-slab
    call's on the same tables, and meet the numpy oracle."""
    from repro.kernels.mc_ridge import block_steps
    rng = np.random.default_rng(5)
    L, d, n, slab, j0 = 300, 4, 16, 24, 48
    hi = np.empty(L, np.int32)
    hi[:128] = rng.integers(0, j0 + 1, size=128)          # all past
    hi[128:256] = rng.integers(j0, j0 + 10, size=128)     # partial
    hi[256:] = rng.integers(j0, j0 + 2 * slab, size=L - 256)
    hi[260] = j0 + slab                                   # a full block
    W = rng.normal(size=(L, d)).astype(np.float32)
    Xs = rng.normal(size=(n, d)).astype(np.float32)
    ys = rng.normal(size=n).astype(np.float32)
    ix = rng.integers(0, n, size=(slab, L)).astype(np.int32)
    live = (j0 + np.arange(slab))[:, None] < hi[None, :]
    m = (live & (rng.random(size=(slab, L)) < 0.8)).astype(np.float32)
    steps = np.asarray(block_steps(hi, j0, slab))
    assert steps[0] == 0 and 0 < steps[1] < slab and steps[2] == slab
    assert steps[1] == np.max(hi[128:256]) - j0
    kw = dict(alpha=1e-3, lam=0.1, fused=fused, interpret=True)
    rows = row_table(Xs, ys)
    bounded = np.asarray(mc_ridge_slab(W, rows, ix, m, hi, np.int32(j0),
                                       **kw))
    whole = np.asarray(mc_ridge_slab(W, rows, ix, m, **kw))
    np.testing.assert_array_equal(bounded, whole)
    np.testing.assert_array_equal(bounded[:128], W[:128])
    ref = mc_ridge_ref(W, Xs, ys, ix, m, alpha=1e-3, lam=0.1, fused=fused)
    np.testing.assert_allclose(bounded, ref, rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the row gather: one load per lane from the kernel's VMEM table of rows
# ---------------------------------------------------------------------------


def _gathered_rows(Xs, ys, ix):
    """Every slot's rows of every lane as the kernel's gather reads them:
    ``(slab, d + 1, L)``, features then the target, lanes last."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import mc_ridge as mr

    slab, L = ix.shape
    d = Xs.shape[1]

    def kernel(ix_ref, rows_ref, o_ref, g_ref):
        for j in range(slab):
            xr, yr = mr._load_rows(ix_ref, rows_ref, g_ref, j, d)
            o_ref[j, :d, :] = xr
            o_ref[j, d:, :] = yr

    return np.asarray(pl.pallas_call(
        kernel, grid=(L // mr.BLOCK_L,),
        in_specs=[pl.BlockSpec((slab, mr.BLOCK_L), lambda i: (0, i),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((slab, d + 1, mr.BLOCK_L),
                               lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((slab, d + 1, L), jnp.float32),
        scratch_shapes=[pltpu.VMEM((mr.BLOCK_L, mr.ROW_LANES),
                                   jnp.float32)],
        interpret=True)(ix, mr.row_table(Xs, ys)))


@pytest.mark.parametrize("n", [256, 1000])
def test_row_gather_is_bitwise_the_rows(n):
    """Each lane's row and target, loaded from the table by the slot's
    index, are bitwise ``Xs[ix]`` and ``ys[ix]``: indices at both ends of
    the set, and n = 1,000, not a multiple of 128, with 8 padding rows."""
    rng = np.random.default_rng(n)
    d, slab, L = 8, 3, 256
    Xs = rng.normal(size=(n, d)).astype(np.float32)
    ys = rng.normal(size=n).astype(np.float32)
    ix = rng.integers(0, n, size=(slab, L)).astype(np.int32)
    ix[0, :2] = (0, n - 1)
    got = _gathered_rows(Xs, ys, ix)
    np.testing.assert_array_equal(got[:, :d, :],
                                  np.moveaxis(Xs[ix], -1, 1))
    np.testing.assert_array_equal(got[:, d, :], ys[ix])


def _scan_slab(W, Xs, ys, ix, m, *, alpha, lam, fused):
    """The slab as a ``lax.scan`` over its slots with an ``Xs[ix]`` gather,
    every lane through every slot, in the kernel's update order (the
    einsum lane dot, as the CPU solves run it)."""
    import jax.numpy as jnp
    from repro.core.pipeline import ridge_dot
    n = Xs.shape[0]
    c_reg = np.float32(2.0 * alpha * lam / n)
    c_2a = np.float32(-2.0 * alpha)
    Xs, ys = jnp.asarray(Xs), jnp.asarray(ys)

    def step(Wt, slot):
        ixr, mr = slot
        xr, yr, mr = Xs[ixr].T, ys[ixr][None, :], mr[None, :]
        dot = ridge_dot(Wt, xr, axis=0, keepdims=True, ordered=False)
        if fused:
            c1, c2 = 1.0 - mr * c_reg, mr * c_2a * (dot - yr)
            return Wt * c1 + xr * c2, None
        g = 2.0 * (dot - yr) * xr + 2.0 * lam / n * Wt
        return jnp.where(mr > 0.0, Wt - alpha * g, Wt), None

    Wt, _ = jax.jit(lambda: jax.lax.scan(step, jnp.asarray(W).T,
                                         (jnp.asarray(ix),
                                          jnp.asarray(m))))()
    return np.asarray(Wt.T)


@pytest.mark.parametrize("n", [256, 1000])
@pytest.mark.parametrize("fused", [False, True])
def test_slab_kernel_is_bitwise_the_gathered_scan(n, fused):
    """The slab kernel returns bitwise the weights of a ``lax.scan`` that
    gathers ``Xs[ix]``, and meets the numpy oracle: per-block
    deadlines (a block past its deadline, one cut short, one running the
    whole slab), dead lanes masked over the whole slab, a part last
    block."""
    rng = np.random.default_rng(7 + n)
    L, d, slab, j0 = 300, 8, 16, 32
    hi = np.empty(L, np.int32)
    hi[:128] = rng.integers(0, j0 + 1, size=128)
    hi[128:256] = rng.integers(j0, j0 + 6, size=128)
    hi[256:] = rng.integers(j0, j0 + 2 * slab, size=L - 256)
    hi[270] = j0 + slab
    W = rng.normal(size=(L, d)).astype(np.float32)
    Xs = rng.normal(size=(n, d)).astype(np.float32)
    ys = rng.normal(size=n).astype(np.float32)
    ix = rng.integers(0, n, size=(slab, L)).astype(np.int32)
    live = (j0 + np.arange(slab))[:, None] < hi[None, :]
    m = (live & (rng.random(size=(slab, L)) < 0.8)).astype(np.float32)
    m[:, 260:264] = 0.0                               # dead lanes
    kw = dict(alpha=1e-3, lam=0.1, fused=fused)
    out = np.asarray(mc_ridge_slab(W, row_table(Xs, ys), ix, m, hi,
                                   np.int32(j0), interpret=True,
                                   ordered=False, **kw))
    np.testing.assert_array_equal(out, _scan_slab(W, Xs, ys, ix, m, **kw))
    np.testing.assert_array_equal(out[260:264], W[260:264])
    ref = mc_ridge_ref(W, Xs, ys, ix, m, alpha=1e-3, lam=0.1, fused=fused)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# engine equivalence: pallas (interpret) bitwise == lax.scan
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("crn", [False, True])
def test_pallas_engine_bitwise_matches_scan(crn):
    """The ``mc_impl="pallas"`` fleet solve returns BITWISE the scan
    engine's plans — exact-RNG and CRN forms both (the shared host-side
    tables + the bitwise row gather make the kernel exact, not
    approximate)."""
    _assert_pallas_plans_are_the_scans(_ridge_data(), crn)


@pytest.mark.parametrize("crn", [False, True])
def test_pallas_engine_bitwise_matches_scan_over_1000_rows(crn):
    """The same over 1,000 rows of 8 features, not a multiple of 128."""
    _assert_pallas_plans_are_the_scans(_ridge_data(n=1000, d=8), crn)


def _assert_pallas_plans_are_the_scans(data, crn):
    X, y = data
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


def _deadline_scenarios(slots):
    """One scenario per deadline in ``slots`` (``floor(T / tau_p)``),
    with sizes, overheads and step times that differ."""
    link = ErasureLink(beta=0.4, p_base=0.05, rates=(1.0, 2.0))
    taus = (0.5, 1.0, 2.0)
    return [Scenario(N=256 + 37 * i, T=float(h) * taus[i % 3],
                     n_o=float(10 + 13 * i), tau_p=taus[i % 3], link=link)
            for i, h in enumerate(slots)]


def _solve_three_ways(mc, scs, pad_to):
    """The pallas engine as served (scenarios in deadline order, each lane
    block bounded), the jitted pallas solve on the caller's order, and the
    CRN scan engine, which steps every lane through the whole horizon."""
    from repro.fleet.link_kernels import kernel_table_version
    from repro.fleet.objective_kernels import (_mc_args, _mc_solve_for,
                                               _unpack, pow2ceil)
    from repro.fleet.planner import _pad_batch
    batch = ScenarioBatch.from_scenarios(_pad_batch(scs, pad_to))
    grid = fleet_grid(batch.N, 6)
    arrays = FleetPlanner._solve_arrays(batch, grid)
    solve = fleet_solve(mc)
    served = solve(dict(arrays, mc_impl="pallas"), CONSTS, False, batch)
    scan = solve(dict(arrays), CONSTS, False, batch)
    fn, layout = _mc_solve_for(mc, kernel_table_version(), True)
    host, args = _mc_args(arrays)
    with jax.enable_x64(True):
        buf = fn(host, layout=args, max_updates=pow2ceil(batch.max_updates),
                 mc_impl="pallas")
    return served, _unpack(np.asarray(buf), layout(args)), scan, batch


@pytest.mark.parametrize("crn", [False, True])
def test_pallas_solve_with_deadlines_from_one_slot_to_the_horizon(crn):
    """Deadlines from 1 slot to the whole 1,024-slot horizon, in no order,
    plus padding scenarios: the served pallas solve, which sorts the
    scenarios and stops lane blocks and slabs early, returns every output
    bitwise the unsorted pallas solve's and the full-horizon scan
    engine's, in the caller's order."""
    X, y = _ridge_data()
    mc = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0,
                             crn=crn)
    scs = _deadline_scenarios((700, 1, 1024, 90, 300, 5, 511))
    served, direct, scan, batch = _solve_three_ways(mc, scs, pad_to=12)
    assert batch.max_updates == 1024
    assert set(served) == set(scan) == set(direct)
    for key in scan:
        np.testing.assert_array_equal(served[key], scan[key], err_msg=key)
        np.testing.assert_array_equal(served[key], direct[key],
                                      err_msg=key)


def test_pallas_solve_where_every_lane_runs_the_whole_horizon():
    """Every deadline at the 512-slot horizon: no block or slab stops
    early, and the served solve is bitwise the scan engine's."""
    X, y = _ridge_data()
    mc = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0,
                             crn=True)
    served, direct, scan, batch = _solve_three_ways(
        mc, _deadline_scenarios((512,) * 4), pad_to=4)
    assert batch.max_updates == 512
    for key in scan:
        np.testing.assert_array_equal(served[key], scan[key], err_msg=key)
        np.testing.assert_array_equal(served[key], direct[key],
                                      err_msg=key)


_SHARDED_ORDER_SCRIPT = """
import json
import jax, numpy as np
assert jax.device_count() == 4, jax.devices()
import test_mc_kernel as t
from repro.core import MonteCarloObjective
from repro.fleet import ScenarioBatch
from repro.fleet.objective_kernels import _mc_horizons, _mc_order, fleet_solve
from repro.fleet.planner import FleetPlanner

X, y = t._ridge_data()
mc = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0, crn=True)
scs = t._deadline_scenarios((700, 1, 1024, 90, 300, 5, 511, 64))
batch = ScenarioBatch.from_scenarios(scs)
arrays = FleetPlanner._solve_arrays(batch, t.fleet_grid(batch.N, 6))
solve = fleet_solve(mc)
sharded = solve(dict(arrays, mc_impl="pallas"), t.CONSTS, True, batch)
single = solve(dict(arrays, mc_impl="pallas"), t.CONSTS, False, batch)
scan = solve(dict(arrays), t.CONSTS, False, batch)
for key in scan:
    np.testing.assert_array_equal(sharded[key], scan[key], err_msg=key)
    np.testing.assert_array_equal(single[key], scan[key], err_msg=key)
order = _mc_order(_mc_horizons(arrays, 1024), 4)
print("ORDER", json.dumps(order.tolist()))
"""


def test_sharded_pallas_solve_returns_outputs_in_request_order():
    """On four virtual CPU devices the served pallas solve deals the
    scenarios over the devices in deadline order, and every output comes
    back in the caller's order, bitwise the one-device solve's and the
    scan engine's."""
    import json
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(here)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(repo, "src"), here,
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _SHARDED_ORDER_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600, cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("ORDER")]
    # deadlines 700, 1, 1024, 90, 300, 5, 511, 64 sorted: 1, 5, 64, 90,
    # 300, 511, 700, 1024 (scenarios 1, 5, 7, 3, 4, 6, 0, 2); device c
    # takes sorted positions c and c + 4
    assert json.loads(line[0].split(" ", 1)[1]) == [1, 4, 5, 6, 7, 0, 3, 2]


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


# ---------------------------------------------------------------------------
# The fast serving schedule's quality on a tight-deadline population
# ---------------------------------------------------------------------------

#: the fast configuration (README, "Seed, stride and horizon schedules")
MC_FAST = dict(crn=True, coarse_seeds=1, refine_rates=1,
               coarse_strides=(32, 6), fine_radius=10, coarse_updates=2048)
FAST_GRID = 128


@functools.lru_cache(maxsize=2)
def _fast_schedule_plans(partitionable: bool):
    """Dense exact-stream, dense CRN and fast-schedule plans of 16
    tight-deadline erasure scenarios (N in [4096, 11000), T within 5-40%
    of the transfer floor, seed 29) under the catalogue's Monte-Carlo
    objective, with JAX's threefry stream ``partitionable`` or not."""
    rng = np.random.default_rng(29)
    scs = []
    for _ in range(16):
        N = int(rng.integers(4096, 11000))
        scs.append(Scenario(N=N, T=float(rng.uniform(1.05, 1.4)) * N,
                            n_o=float(rng.uniform(10.0, 2000.0)), tau_p=2.0,
                            link=LINK_FACTORIES["erasure"](rng)))
    objective = resolve_objectives(("montecarlo",))["montecarlo"]
    consts = default_consts()
    batch = ScenarioBatch.from_scenarios(scs)
    grid = fleet_grid(batch.N, FAST_GRID)
    dense = FleetPlanner(grid_size=FAST_GRID)
    refine = FleetPlanner(grid_size=FAST_GRID, grid_mode="refine")
    with jax.threefry_partitionable(partitionable):
        exact = dense.plan_batch(batch, consts, grid=grid,
                                 objective=objective)
        crn = dense.plan_batch(batch, consts, grid=grid,
                               objective=dataclasses.replace(objective,
                                                             crn=True))
        fast = refine.plan_batch(
            batch, consts, grid=grid,
            objective=dataclasses.replace(objective, **MC_FAST))
    return exact, crn, fast


def test_fast_schedule_quality_floors():
    """The fast schedule's two quality floors: argmin parity >= 0.5 with
    the dense solve of the same (CRN) estimator, and objective gap <= 0.05
    with the exact-stream dense solve.

    They were set on JAX's non-partitionable threefry stream, the default
    before JAX 0.5, and hold there (parity 0.6875, gap 4.56e-2 on the
    CPU).  On today's default stream they read parity 0.375 and gap
    5.95e-2, and the dense CRN solve alone, with no schedule at all,
    reads the same 5.95e-2: at 2 runs the CRN and exact-stream estimates
    differ by up to 6% on this population, which no search schedule can
    close.  The schedule's own search loss on both streams is held by
    ``test_fast_schedule_regret_against_the_dense_crn_solve``."""
    exact, crn, fast = _fast_schedule_plans(False)
    parity = np.mean((fast.n_c == crn.n_c) & (fast.rate == crn.rate))
    gap = np.max(np.abs(fast.bound_value / exact.bound_value - 1))
    assert parity >= 0.5, parity
    assert gap <= 0.05, gap


@pytest.mark.parametrize("partitionable", [True, False])
def test_fast_schedule_regret_against_the_dense_crn_solve(partitionable):
    """Under one estimator the fast schedule only searches less: its fine
    pass values its pick exactly as the dense CRN solve values that grid
    point, so its value is never below the dense optimum, equal where the
    picks agree, and within 5% of it (the gap floor's tolerance) on
    either RNG stream."""
    _, crn, fast = _fast_schedule_plans(partitionable)
    regret = fast.bound_value / crn.bound_value - 1
    same = (fast.n_c == crn.n_c) & (fast.rate == crn.rate)
    assert np.all(regret >= 0.0), regret
    assert np.all(regret[same] == 0.0), regret
    assert np.max(regret) <= 0.05, regret
