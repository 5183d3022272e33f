"""One copy in, one copy back per Monte-Carlo solve: the solve's nine
arguments travel as one float64 host matrix and its outputs come back as
one float64 device buffer, cut into the dict the per-array path gives —
same keys, dtypes, shapes and bits.

The reference is the same jitted body without the packing: ``_mc_args``,
``_unpack`` and ``_pack`` made the identity, the deadline order applied
to each argument, and the outputs fetched one array at a time by
``_fetch``."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BoundConstants, MonteCarloObjective
from repro.core.planner import fleet_grid
from repro.core.scenario import (ErasureLink, FadingLink, GilbertElliottLink,
                                 IdealLink, MultiDevice, Scenario,
                                 SingleDevice)
from repro.fleet import FleetPlanner, ScenarioBatch, objective_kernels as ok
from repro.obs import runtime

CONSTS = BoundConstants(L=1.908, c=0.061, M=1.0, M_G=1.0, D=1.0, alpha=1e-4)
RATES5 = (1.0, 1.25, 1.5, 2.0, 3.0)
PLAN_FIELDS = ("n_c", "rate", "bound_value", "p_err", "n_o_eff",
               "full_transfer", "boundary", "n_c_per_device", "grid",
               "bound_grid")


class _Columns(dict):
    """The per-array arguments, row-indexable as the packed matrix is."""

    def __getitem__(self, rows):
        if isinstance(rows, str):
            return dict.__getitem__(self, rows)
        return _Columns({k: v[rows] for k, v in self.items()})


jax.tree_util.register_pytree_node(
    _Columns, lambda c: (tuple(c.values()), tuple(c.keys())),
    lambda keys, values: _Columns(zip(keys, values)))


def per_array(mp):
    """Until ``mp.undo()``, every Monte-Carlo solve runs the same jitted
    body on its nine arrays, each copied in and fetched on its own."""
    built, build = {}, ok._mc_solve_for.__wrapped__

    def mc_solve_for(objective, version, interpret):
        key = (objective, version, interpret)
        if key not in built:
            built[key] = build(objective, version, interpret)
        return built[key]

    def fetch(out, layout, rows=None):
        res = ok._fetch(out)
        return res if rows is None else {k: v[rows] for k, v in res.items()}

    mp.setattr(ok, "_mc_args", lambda arrays: (_Columns(
        {k: np.asarray(v) for k, v in arrays.items()}), None))
    mp.setattr(ok, "_unpack", lambda buf, layout: dict(buf.items()))
    mp.setattr(ok, "_pack", lambda out, xp=jnp: (out, None))
    mp.setattr(ok, "_fetch_packed", fetch)
    mp.setattr(ok, "_mc_solve_for", mc_solve_for)
    return mp


def ridge_objective():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(48, 4))
    y = X @ rng.normal(size=4) + 0.1 * rng.normal(size=48)
    return MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0,
                               crn=True)


def mixed_batch(n, seed):
    """Every link family in turn (so int, bool and float64 columns all
    carry real values), several topologies, deadlines in no order."""
    rng = np.random.default_rng(seed)
    links = [
        lambda: IdealLink(rates=RATES5),
        lambda: ErasureLink(beta=float(rng.uniform(0.05, 1.5)),
                            p_base=float(rng.uniform(0.0, 0.4)),
                            rates=RATES5),
        lambda: FadingLink(snr=float(rng.uniform(2.0, 50.0)),
                           rates=(1.0, 1.5)),
        lambda: GilbertElliottLink(p_gb=float(rng.uniform(0.01, 0.3)),
                                   p_bg=float(rng.uniform(0.2, 0.9)),
                                   p_good=float(rng.uniform(0.0, 0.2)),
                                   p_bad=float(rng.uniform(0.2, 0.9)),
                                   beta=float(rng.uniform(0.05, 1.0)),
                                   rates=RATES5),
    ]
    scs = []
    for i in range(n):
        N = int(rng.integers(256, 512))
        D = int(rng.choice([1, 2, 4]))
        scs.append(Scenario(
            N=N, T=float(rng.uniform(1.05, 1.6)) * N,
            n_o=float(rng.uniform(1.0, 200.0)),
            tau_p=float(rng.choice([1.0, 2.0])), link=links[i % 4](),
            topology=MultiDevice(D) if D > 1 else SingleDevice()))
    return ScenarioBatch.from_scenarios(scs)


def counted_solve(mc, batch, engine, shard):
    arrays = FleetPlanner._solve_arrays(batch, fleet_grid(batch.N, 6))
    if engine == "pallas":
        arrays["mc_impl"] = "pallas"
    runtime.open_record()
    try:
        out = ok.fleet_solve(mc)(arrays, CONSTS, shard, batch)
        _, counts, _ = runtime.take_record()
    finally:
        runtime.close_record()
    return out, counts


def assert_same_dicts(got, want):
    assert set(got) == set(want)
    for k in want:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        assert a.dtype == b.dtype and a.shape == b.shape, (k, a.dtype,
                                                          b.dtype)
        assert a.tobytes() == b.tobytes(), k


def packed_against_per_array(engine, shard, mp):
    """The packed solve's outputs against the per-array path's on one
    batch of eight; returns the packed call's counters."""
    mc = ridge_objective()
    batch = mixed_batch(8, seed=3)
    horizon = np.floor(batch.T / batch.tau_p)
    assert not np.all(np.diff(horizon) >= 0)  # deadline order differs
    per_array(mp)
    ref, ref_counts = counted_solve(mc, batch, engine, shard)
    mp.undo()
    got, counts = counted_solve(mc, batch, engine, shard)
    assert_same_dicts(got, ref)
    assert ref_counts["d2h_arrays"] == len(ref) == 9
    assert counts["dispatches"] == counts["h2d_arrays"] == 1
    assert counts["d2h_arrays"] == 1
    return counts


@pytest.mark.parametrize("engine", ["scan", "pallas"])
def test_packed_mc_solve_matches_per_array_bitwise(engine, monkeypatch):
    packed_against_per_array(engine, False, monkeypatch)


def test_packed_mc_plans_match_per_array_through_refine(monkeypatch):
    """Whole plans through the coarse -> fine refine path, whose fine
    pass hands the solve a per-rate ``(S, R, W)`` grid and gets
    ``sel_grid`` back: bit-identical to the per-array path."""
    mc = ridge_objective()
    batch = mixed_batch(8, seed=9)
    grid = fleet_grid(batch.N, 32)
    planner = FleetPlanner(grid_size=32, grid_mode="refine",
                           mc_impl="pallas")
    per_array(monkeypatch)
    ref = planner.plan_batch(batch, CONSTS, grid=grid, objective=mc)
    monkeypatch.undo()
    got = planner.plan_batch(batch, CONSTS, grid=grid, objective=mc)
    assert got.grid.shape[1] < 32  # the fine pass ran
    for name in PLAN_FIELDS:
        a, b = getattr(got, name), getattr(ref, name)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert a.tobytes() == b.tobytes(), name


def test_mc_args_round_trip_every_dtype_exactly():
    """The host matrix holds each argument as float64 columns that cut
    back to the same dtype, shape and bits: the largest block size, bools
    both ways, int32 family ids and float64 values of every magnitude."""
    S = 4
    rng = np.random.default_rng(1)
    arrays = {
        "N": np.array([32768, 1, 2047, 256], np.int64),
        "T": rng.uniform(1.0, 1e5, S),
        "union_no": np.array([0.0, 1e-300, 999.75, 3.0]),
        "tau_p": np.array([0.5, 1.0, 2.0, 1.0]),
        "rates": rng.uniform(1.0, 3.0, (S, 5)),
        "rate_mask": rng.random((S, 5)) < 0.5,
        "grid": rng.integers(1, 32769, (S, 3, 7)),
        "link_model_id": np.array([0, 1, 2, 3], np.int32),
        "link_params": rng.normal(size=(S, 8)),
    }
    host, layout = ok._mc_args(arrays)
    assert host.dtype == np.float64
    assert host.shape == (S, 1 + 1 + 1 + 1 + 5 + 5 + 21 + 1 + 8)
    assert [key for key, *_ in layout] == list(arrays)
    assert_same_dicts(ok._unpack(host, layout), arrays)
    with jax.enable_x64(True):
        traced = jax.jit(ok._unpack, static_argnums=1)(host, layout)
    assert_same_dicts({k: np.asarray(v) for k, v in traced.items()},
                      arrays)


_SHARDED_SCRIPT = """
import jax, pytest
assert jax.device_count() == 4, jax.devices()
import test_packed_mc as t
for engine in ("scan", "pallas"):
    with pytest.MonkeyPatch.context() as mp:
        counts = t.packed_against_per_array(engine, True, mp)
    assert counts["mc_sharded_dispatches"] == 1, counts
print("PACKED-MC-SHARDED-OK")
"""


def test_packed_mc_solve_sharded_over_four_devices():
    """On four (forced) host devices, scan and pallas engines, the solve
    laid over every device: one copy in, one copy back, outputs bitwise
    the per-array path's (separate process: the device-count flag must
    precede jax init)."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(here)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(repo, "src"), here,
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PACKED-MC-SHARDED-OK" in out.stdout
