"""One copy back per grid solve: the grid solves return every output
packed into one float64 device buffer, and the host cuts it back into
the dict the per-array path gives — same keys, dtypes, shapes and bits.

The reference is the same jitted body without the pack: ``_pack`` made
the identity, fetched by the per-array ``_fetch``."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BoundConstants
from repro.core import ObjectivePlanner
from repro.core.objectives import BoundObjective, MarkovARQObjective
from repro.core.planner import fleet_grid
from repro.core.scenario import (ErasureLink, FadingLink, GilbertElliottLink,
                                 IdealLink, MultiDevice, Scenario,
                                 SingleDevice)
from repro.fleet import FleetPlanner, ScenarioBatch, objective_kernels as ok
from repro.fleet.link_kernels import kernel_table
from repro.obs import runtime

CONSTS = BoundConstants(L=1.908, c=0.061, M=1.0, M_G=1.0, D=1.0, alpha=1e-4)
RATES5 = (1.0, 1.25, 1.5, 2.0, 3.0)
OBJECTIVES = {"corollary1": BoundObjective(),
              "markov_arq": MarkovARQObjective()}
PLAN_FIELDS = ("n_c", "rate", "bound_value", "p_err", "n_o_eff",
               "full_transfer", "boundary", "n_c_per_device", "grid",
               "bound_grid")


def _mixed_batch(n, seed, n_max=32768):
    """Every link family, tight and loose deadlines, several topologies."""
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
        N = int(rng.integers(256, n_max))
        D = int(rng.choice([1, 2, 4, 8]))
        scs.append(Scenario(
            N=N, T=float(rng.uniform(1.05, 3.0)) * N,
            n_o=float(rng.uniform(1.0, 1000.0)),
            tau_p=float(rng.choice([0.5, 1.0, 2.0])), link=links[i % 4](),
            topology=MultiDevice(D) if D > 1 else SingleDevice()))
    return scs


@pytest.fixture
def unpacked(monkeypatch):
    """Until ``undo``, every grid solve runs the same jitted bodies
    without the pack: they return the dict, fetched one array at a time."""
    built = {}

    def grid_solve_for(version, value_fn, exact_arq):
        key = (version, value_fn, exact_arq)
        if key not in built:
            built[key] = ok._build_grid_solve(kernel_table(), value_fn,
                                              exact_arq)
        return built[key]

    monkeypatch.setattr(ok, "_pack", lambda out: (out, None))
    monkeypatch.setattr(ok, "_fetch_packed",
                        lambda out, layout: ok._fetch(out))
    monkeypatch.setattr(ok, "_grid_solve_for", grid_solve_for)
    return monkeypatch


def _solve_inputs(G, fine, seed=5, S=64):
    batch = ScenarioBatch.from_scenarios(_mixed_batch(S, seed))
    arrays = FleetPlanner._solve_arrays(batch, fleet_grid(batch.N, G))
    if fine:
        rng = np.random.default_rng(seed)
        R = arrays["rates"].shape[1]
        arrays["centers"] = rng.integers(0, G, size=(S, R))
        arrays["tail_start"] = rng.integers(G // 2, G + 1, size=S)
        arrays["refine_stride"] = 6
        arrays["refine_width"] = 32
    return batch, arrays


def _counted_solve(solve, arrays, batch):
    runtime.open_record()
    try:
        out = solve(arrays, CONSTS, False, batch)
        _, counts, _ = runtime.take_record()
    finally:
        runtime.close_record()
    return out, counts


def _assert_same_dicts(got, want):
    assert set(got) == set(want)
    for k in want:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        assert a.dtype == b.dtype and a.shape == b.shape, (k, a.dtype,
                                                          b.dtype)
        assert a.tobytes() == b.tobytes(), k


@pytest.mark.parametrize("fine", [False, True], ids=["dense", "fine"])
@pytest.mark.parametrize("objective", list(OBJECTIVES))
def test_packed_solve_matches_unpacked_bitwise(objective, fine, unpacked):
    batch, arrays = _solve_inputs(G=128, fine=fine)
    ref, ref_counts = _counted_solve(
        ok.fleet_solve(OBJECTIVES[objective]), arrays, batch)
    unpacked.undo()
    got, counts = _counted_solve(
        ok.fleet_solve(OBJECTIVES[objective]), arrays, batch)
    _assert_same_dicts(got, ref)
    assert ("sel_grid" in got) == fine
    # one copy back for the packed call, one per array for the reference
    assert counts["dispatches"] == counts["d2h_arrays"] == 1
    assert ref_counts["d2h_arrays"] == len(ref) == 9 + fine


@pytest.mark.parametrize("objective", list(OBJECTIVES))
@pytest.mark.parametrize("G,mode", [(128, "dense"), (128, "refine"),
                                    (384, "refine")])
def test_plan_batch_matches_unpacked_and_scalar(objective, G, mode,
                                                unpacked):
    """Whole plans, through the planner's dense, fallback and two-pass
    refine paths: bit-identical to the per-array path, and the scalar
    planner's picks on a mixed-link batch."""
    obj = OBJECTIVES[objective]
    scs = _mixed_batch(48, seed=11 + G)
    batch = ScenarioBatch.from_scenarios(scs)
    planner = FleetPlanner(grid_size=G, grid_mode=mode)
    ref = planner.plan_batch(batch, CONSTS, objective=obj)
    unpacked.undo()
    got = planner.plan_batch(batch, CONSTS, objective=obj)
    for name in PLAN_FIELDS:
        a, b = getattr(got, name), getattr(ref, name)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert a.tobytes() == b.tobytes(), name
    if mode == "refine":
        assert got.grid.shape[1] < G  # the fused fine pass ran
    if mode == "dense":
        for i, sc in enumerate(scs):
            sp = ObjectivePlanner(objective=obj,
                                  grid=fleet_grid(sc.N, G)).plan(sc, CONSTS)
            assert int(got.n_c[i]) == sp.n_c and float(got.rate[i]) == sp.rate
            assert bool(got.full_transfer[i]) == sp.full_transfer
            assert int(got.n_c_per_device[i]) == sp.n_c_per_device
            assert np.isclose(got.bound_value[i], sp.bound_value, rtol=1e-12)
            assert np.isclose(got.n_o_eff[i], sp.schedule.n_o, rtol=1e-12)


def test_pack_round_trips_extreme_values():
    """The largest block size and both regimes survive the float64
    columns exactly; so do infinities in the objective grid."""
    S, R, G = 4, 3, 5
    rng = np.random.default_rng(0)
    out = {
        "n_c": np.array([32768, 1, 32767, 16384], np.int64),
        "rate": rng.uniform(1.0, 3.0, S),
        "bound_value": np.array([np.inf, 1e-300, 2.5, -0.0]),
        "p_err": rng.uniform(0.0, 0.9, S),
        "n_o_eff": rng.uniform(0.0, 1e3, S),
        "full_transfer": np.array([True, False, True, False]),
        "bound_grid": np.where(rng.random((S, G)) < 0.3, np.inf,
                               rng.normal(size=(S, G))),
        "gi_per_rate": rng.integers(0, G, (S, R)),
        "val_per_rate": rng.normal(size=(S, R)),
        "sel_grid": np.array([[1, 2, 3, 4, 32768]] * S, np.int64),
    }
    layouts = {}

    def pack(**o):
        buf, layouts["l"] = ok._pack(o)
        return buf

    with jax.enable_x64(True):
        buf = jax.jit(pack)(**{k: jnp.asarray(v) for k, v in out.items()})
        assert buf.shape == (S, 6 + G + 2 * R + G)
        assert buf.dtype == jnp.float64
        got = ok._fetch_packed(buf, layouts["l"])
    _assert_same_dicts(got, out)


_SHARD_SCRIPT = """
import numpy as np, jax
from jax.sharding import PartitionSpec as P
assert jax.device_count() == 4, jax.devices()
from repro.core.objectives import BoundObjective, MarkovARQObjective
from repro.fleet import FleetPlanner, ScenarioBatch
from repro.fleet import objective_kernels as ok
from repro.core.planner import fleet_grid
from repro.serve import ALL_MODELS, default_consts, synth_requests
scs = synth_requests(16, seed=4, dup_frac=0.0, models=ALL_MODELS)
batch = ScenarioBatch.from_scenarios(scs)
consts = default_consts()
for obj in (BoundObjective(), MarkovARQObjective()):
    for mode in ("dense", "refine"):
        got = [FleetPlanner(grid_size=384, grid_mode=mode, shard=shard)
               .plan_batch(batch, consts, objective=obj)
               for shard in (True, False)]
        for name in ("n_c", "rate", "bound_value", "full_transfer",
                     "bound_grid"):
            np.testing.assert_array_equal(getattr(got[0], name),
                                          getattr(got[1], name))
# the packed buffer keeps the scenario axis laid over the four devices
arrays = FleetPlanner._solve_arrays(batch, fleet_grid(batch.N, 384))
dense, _, _ = ok._grid_solve_for(ok.kernel_table_version(),
                                 ok._corollary1_values, False)
with jax.enable_x64(True):
    buf = dense(sigma=0.1, e0=1.0, contraction=0.5,
                **ok._maybe_shard(arrays, 16))
assert buf.sharding.spec == P("fleet"), buf.sharding
assert len(buf.sharding.device_set) == 4
print("PACKED-SHARDED-OK")
"""


def test_packed_solve_sharded_over_four_devices():
    """On four (forced) host devices the packed buffer stays sharded on
    the scenario axis and the plans equal the unsharded ones (separate
    process: the device-count flag must precede jax init)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=repo)
    assert out.returncode == 0, out.stderr
    assert "PACKED-SHARDED-OK" in out.stdout
