"""Compile the main path's kernels and solves for a described TPU v5e.

Nothing runs: each case lowers and compiles at the real serving or
training shape against a ``v5e:2x2`` topology that the TPU compiler
describes without a chip, so what the chip's compiler would refuse
(unaligned blocks, too much VMEM, a solve that takes minutes to compile)
fails here.  The topology is described inside a fixture, never at import,
and the persistent compilation cache is off around these tests: entries
compiled for a described chip cannot be read back without one.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.planner import fleet_grid
from repro.fleet import FleetPlanner, ScenarioBatch
from repro.serve.catalogue import ALL_MODELS, synth_population, synth_requests


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(one_chip, tree):
    """ShapeDtypeStructs on the described chip for a tree of arrays."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=one_chip), tree)


def _compile(fn, *args, **kwargs):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    return compiled, time.perf_counter() - t0


@pytest.mark.parametrize("fused", [False, True])
def test_mc_ridge_slab_compiles(one_chip, fused):
    """Serving shape: the 256-row Monte-Carlo dataset, one 256-slot slab,
    a 256-scenario bucket x 1 rate x 32-wide fine window of lanes.  Under
    ``enable_x64``, as the Monte-Carlo solve calls it: Mosaic lowers no
    int64, so an index that widens there fails here."""
    from repro.kernels.mc_ridge import mc_ridge_slab
    n, d, lanes, slab = 256, 8, 256 * 32, 256
    f32 = jnp.float32
    s = lambda shape, dt=f32: jax.ShapeDtypeStruct(shape, dt,
                                                   sharding=one_chip)
    with jax.enable_x64(True):
        compiled, _ = _compile(
            lambda W, X, y, ix, m: mc_ridge_slab(W, X, y, ix, m, alpha=1e-3,
                                                 lam=0.05, fused=fused),
            s((lanes, d)), s((n, d)), s((n,)), s((slab, lanes), jnp.int32),
            s((slab, lanes)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fused", [False, True])
def test_mc_ridge_slab_compiles_with_lane_deadlines(one_chip, fused):
    """The slab kernel as the served solve calls it: per-lane deadlines
    and the slab's first slot, reduced to one slot count per 128-lane
    block and prefetched as scalars, so each block's slot loop has a trip
    count known only on the chip."""
    from repro.kernels.mc_ridge import mc_ridge_slab
    n, d, lanes, slab = 256, 8, 256 * 32, 256
    f32, i32 = jnp.float32, jnp.int32
    s = lambda shape, dt=f32: jax.ShapeDtypeStruct(shape, dt,
                                                   sharding=one_chip)
    with jax.enable_x64(True):
        compiled, _ = _compile(
            lambda W, X, y, ix, m, hi, j0: mc_ridge_slab(
                W, X, y, ix, m, hi, j0, alpha=1e-3, lam=0.05, fused=fused),
            s((lanes, d)), s((n, d)), s((n,)), s((slab, lanes), i32),
            s((slab, lanes)), s((lanes,), i32), s((), i32))
    assert "tpu_custom_call" in compiled.as_text()


def test_montecarlo_solve_compiles_with_pallas(one_chip):
    """The served Monte-Carlo solve with the compiled slab kernel: a
    64-request bucket over every link family, a 32-point grid, the
    16,384-slot timeline of ``n_max=2048`` serving, CRN tables."""
    from repro.fleet.link_kernels import kernel_table_version
    from repro.fleet.objective_kernels import _mc_args, _mc_solve_for
    from repro.serve.catalogue import make_montecarlo_objective, \
        mc_update_floor
    objective = make_montecarlo_objective(mc_update_floor(2048), crn=True)
    batch = ScenarioBatch.from_scenarios(
        synth_requests(64, seed=1, models=ALL_MODELS, n_max=2048))
    arrays = FleetPlanner._solve_arrays(batch, fleet_grid(batch.N, 32))
    solve, _ = _mc_solve_for(objective, kernel_table_version(), False)
    host, layout = _mc_args(arrays)
    with jax.enable_x64(True):
        compiled = solve.lower(_shapes(one_chip, host), layout=layout,
                               max_updates=mc_update_floor(2048),
                               mc_impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention_fwd
    s = jax.ShapeDtypeStruct((1, 32, 2048, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 2048, 64), jnp.bfloat16,
                              sharding=one_chip)
    compiled, _ = _compile(
        lambda q, k, v: flash_attention_fwd(q, k, v, q_block=512,
                                            kv_block=512), s, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles(one_chip):
    """Mamba2-style head: P=64, N=128, 256-step chunks."""
    from repro.kernels.ssd_scan import ssd_scan_fwd
    b, h, l, p, n = 1, 8, 2048, 64, 128
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    compiled, _ = _compile(
        lambda x, dt, a, bm, cm: ssd_scan_fwd(x, dt, a, bm, cm, chunk=256),
        s((b, h, l, p)), s((b, h, 1, l)), s((h,)), s((b, h, l, n)),
        s((b, h, l, n)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("exact_arq", [False, True],
                         ids=["corollary1", "markov_arq"])
def test_grid_solve_compiles_in_f64(one_chip, exact_arq):
    """The served bound solve: a 256-request bucket over every link
    family, G=128, float64."""
    from repro.fleet.link_kernels import kernel_table_version
    from repro.fleet.objective_kernels import (_corollary1_values,
                                               _grid_solve_for)
    scs = synth_requests(256, seed=0, models=ALL_MODELS)
    batch = ScenarioBatch.from_scenarios(scs)
    arrays = FleetPlanner._solve_arrays(batch, fleet_grid(batch.N, 128))
    dense, _, _ = _grid_solve_for(kernel_table_version(),
                                  _corollary1_values, exact_arq)
    with jax.enable_x64(True):
        consts = {k: np.float64(v) for k, v in
                  dict(sigma=0.1, e0=1.0, contraction=0.5).items()}
        _, seconds = _compile(dense, **_shapes(one_chip, arrays),
                              **_shapes(one_chip, consts))
    assert seconds < 60.0


def test_round_solve_compiles_fast_at_population_scale(one_chip):
    """The federated round at S=1024 devices, G=128: its prefix scans
    once took minutes to compile in float64."""
    from repro.federated.round_kernels import round_solve
    pop, deadline = synth_population(1024, seed=0, models=ALL_MODELS)
    batch = ScenarioBatch.from_scenarios(pop)
    arrays = {
        "N": np.asarray(batch.N, np.int64),
        "union_no": batch.union_overhead,
        "tau_p": np.asarray(batch.tau_p, np.float64),
        "rates": np.asarray(batch.rates, np.float64),
        "rate_mask": batch.rate_mask,
        "grid": fleet_grid(batch.N, 128),
        "link_model_id": np.asarray(batch.link_model_id, np.int32),
        "link_params": np.asarray(batch.link_params, np.float64),
        "valid": np.ones(1024, bool),
        "T": np.float64(deadline), "sigma": np.float64(0.1),
        "e0": np.float64(1.0), "contraction": np.float64(0.5),
    }
    with jax.enable_x64(True):
        _, seconds = _compile(round_solve(), **_shapes(one_chip, arrays))
    assert seconds < 30.0


def test_sharded_montecarlo_solve_compiles_on_four_chips(one_chip, topo,
                                                         monkeypatch):
    """On a four-chip host the served Monte-Carlo solve lays its lanes
    over every chip; XLA cannot partition the Mosaic kernel, so it must
    run per chip.  The solve reads its mesh from ``jax.local_devices()``,
    pointed here at the described chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.fleet.link_kernels import kernel_table_version
    from repro.fleet.objective_kernels import _mc_args, _mc_solve_for
    from repro.serve.catalogue import make_montecarlo_objective, \
        mc_update_floor
    del one_chip  # its fixture keeps the persistent cache off
    fleet = NamedSharding(Mesh(np.asarray(topo.devices), ("fleet",)),
                          P("fleet"))
    objective = make_montecarlo_objective(mc_update_floor(2048), crn=True)
    batch = ScenarioBatch.from_scenarios(
        synth_requests(64, seed=1, models=ALL_MODELS, n_max=2048))
    arrays = FleetPlanner._solve_arrays(batch, fleet_grid(batch.N, 32))
    solve, _ = _mc_solve_for(objective, kernel_table_version(), False)
    host, layout = _mc_args(arrays)
    monkeypatch.setattr(jax, "local_devices", lambda: list(topo.devices))
    with jax.enable_x64(True):
        compiled = solve.lower(_shapes(fleet, host), layout=layout,
                               max_updates=mc_update_floor(2048),
                               shard_lanes=True, mc_impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()
