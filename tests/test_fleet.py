"""Fleet planning engine: ScenarioBatch round-trips, batched == scalar plan
equivalence (fixed cases + hypothesis property), the jnp bound port's
lockstep with the numpy reference, PlanCache semantics, plan_many dedup,
and the NamedSharding path (subprocess with a forced multi-device host
platform)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (BoundConstants, BoundPlanner, ErasureLink, FadingLink,
                        GilbertElliottLink, IdealLink, MultiDevice, Scenario,
                        SingleDevice)
from repro.core.bounds import corollary1_bound
from repro.core.planner import fleet_grid
from repro.fleet import (FleetPlanner, PlanCache, ScenarioBatch,
                         corollary1_bound_jax, scenario_key)

CONSTS = BoundConstants(L=1.908, c=0.061, M=1.0, M_G=1.0, D=1.0, alpha=1e-4)
RATES5 = (1.0, 1.25, 1.5, 2.0, 3.0)


def _mixed_scenarios():
    """A deterministic batch covering every registered link model (ideal,
    erasure, fading, Gilbert-Elliott) x topology cross product, ragged rate
    sets, and both regimes — the ISSUE-3 acceptance population."""
    return [
        Scenario(N=2048, T=1.5 * 2048, n_o=100.0),
        Scenario(N=18576, T=1.2 * 18576, n_o=500.0,
                 link=ErasureLink(beta=0.4, rates=RATES5)),
        Scenario(N=512, T=0.8 * 512, n_o=10.0, tau_p=2.0,
                 link=ErasureLink(beta=1.0, p_base=0.3, rates=(1.0, 2.0))),
        Scenario(N=4096, T=2.5 * 4096, n_o=50.0,
                 link=ErasureLink(beta=0.0, rates=(1.0, 4.0)),  # lossless fast
                 topology=MultiDevice(4)),
        Scenario(N=100, T=130.0, n_o=1.0, tau_p=0.5,
                 link=IdealLink(rates=(1.0, 1.5)), topology=MultiDevice(8)),
        Scenario(N=30000, T=1.1 * 30000, n_o=2000.0,
                 link=ErasureLink(beta=1.5, p_base=0.5, rates=RATES5),
                 topology=MultiDevice(2)),
        Scenario(N=4096, T=1.4 * 4096, n_o=200.0,
                 link=FadingLink(snr=8.0, rates=RATES5)),
        Scenario(N=1024, T=0.9 * 1024, n_o=20.0, tau_p=2.0,
                 link=FadingLink(snr=2.5, rates=(1.0, 1.5)),
                 topology=MultiDevice(4)),
        Scenario(N=8192, T=1.3 * 8192, n_o=300.0,
                 link=GilbertElliottLink(p_gb=0.1, p_bg=0.6, p_good=0.05,
                                         p_bad=0.6, beta=0.3, rates=RATES5),
                 topology=MultiDevice(2)),
        Scenario(N=20000, T=2.0 * 20000, n_o=1000.0,
                 link=GilbertElliottLink(p_gb=0.4, p_bg=0.1, p_good=0.0,
                                         p_bad=0.85, beta=0.0,
                                         rates=(1.0, 2.0, 3.0))),
    ]


def _assert_record_matches_scalar(sc, n_c, rate, bound, consts, grid_size):
    """Batched pick == scalar pick, or (on an argmin tie at float64
    resolution) scalar-near-optimal at the batched pick."""
    sp = BoundPlanner(grid=fleet_grid(sc.N, grid_size)).plan(sc, consts)
    assert np.isclose(bound, sp.bound_value, rtol=1e-9, atol=0.0), \
        (sc, bound, sp.bound_value)
    if int(n_c) == sp.n_c and float(rate) == sp.rate:
        return
    # tie fallback: evaluate the SCALAR objective at the batched choice
    n_o_eff = float(sc.effective_overhead(int(n_c), float(rate)))
    at_pick = float(corollary1_bound(
        np.asarray([float(n_c)]), N=sc.N, T=sc.T, n_o=n_o_eff,
        tau_p=sc.tau_p, consts=consts)[0])
    assert at_pick <= sp.bound_value * (1.0 + 1e-9), \
        f"batched pick (n_c={n_c}, rate={rate}) not scalar-optimal: " \
        f"{at_pick} vs {sp.bound_value}"


# ---------------------------------------------------------------------------
# ScenarioBatch round-trip
# ---------------------------------------------------------------------------


def test_scenario_batch_round_trip():
    scs = _mixed_scenarios()
    batch = ScenarioBatch.from_scenarios(scs)
    assert len(batch) == len(scs)
    assert batch.n_rates == 5
    for i, sc in enumerate(scs):
        assert batch[i] == sc
    assert batch.scenarios() == scs
    # padded rate columns are masked, never argmin candidates
    assert batch.rate_mask[0].sum() == 1      # IdealLink default (1.0,)
    assert batch.rate_mask[2].sum() == 2
    np.testing.assert_array_equal(batch.union_overhead,
                                  [100.0, 500.0, 10.0, 200.0, 8.0, 4000.0,
                                   200.0, 80.0, 600.0, 1000.0])
    # the registry flattening: ids follow the class table, params are the
    # packed vectors right-padded with zeros
    np.testing.assert_array_equal(batch.link_model_id,
                                  [0, 1, 1, 1, 0, 1, 2, 2, 3, 3])
    np.testing.assert_array_equal(batch.link_params[0], 0.0)     # ideal
    np.testing.assert_array_equal(batch.link_params[1][:2], [0.4, 0.0])
    np.testing.assert_array_equal(batch.link_params[6][:1], [8.0])
    np.testing.assert_array_equal(batch.link_params[8][:5],
                                  [0.3, 0.05, 0.6, 0.1, 0.6])
    np.testing.assert_array_equal(batch.link_params[8][5:], 0.0)  # padding


def test_scenario_batch_multidevice_one_normalises_to_single():
    sc = Scenario(N=64, T=96.0, n_o=1.0, topology=MultiDevice(1))
    back = ScenarioBatch.from_scenarios([sc])[0]
    assert back.topology == SingleDevice()
    assert back.N == sc.N and back.T == sc.T


def test_scenario_batch_rejects_empty_and_unknown_link():
    with pytest.raises(ValueError):
        ScenarioBatch.from_scenarios([])

    class WeirdLink:
        rates = (1.0,)

    with pytest.raises(TypeError):
        ScenarioBatch.from_scenarios(
            [Scenario(N=8, T=12.0, n_o=1.0, link=WeirdLink())])


# ---------------------------------------------------------------------------
# batched == scalar equivalence
# ---------------------------------------------------------------------------


def test_plan_batch_matches_scalar_planner_fixed_cases():
    scs = _mixed_scenarios()
    batch = ScenarioBatch.from_scenarios(scs)
    G = 40
    fp = FleetPlanner(grid_size=G).plan_batch(batch, CONSTS)
    assert len(fp) == len(scs)
    for i, sc in enumerate(scs):
        sp = BoundPlanner(grid=fleet_grid(sc.N, G)).plan(sc, CONSTS)
        assert int(fp.n_c[i]) == sp.n_c
        assert float(fp.rate[i]) == sp.rate
        assert np.isclose(fp.bound_value[i], sp.bound_value, rtol=1e-12)
        assert np.isclose(fp.p_err[i], sp.p_err, rtol=1e-12, atol=1e-300)
        assert bool(fp.full_transfer[i]) == sp.full_transfer
        assert int(fp.n_c_per_device[i]) == sp.n_c_per_device
        b1, b2 = sp.boundary, float(fp.boundary[i])
        assert (np.isinf(b1) and np.isinf(b2)) or np.isclose(b1, b2,
                                                             rtol=1e-12)
        # full Plan materialisation carries the whole grid across
        plan = fp.to_plan(batch, i)
        assert plan.n_c == sp.n_c and plan.rate == sp.rate
        np.testing.assert_allclose(plan.bound_grid, sp.bound_grid,
                                   rtol=1e-12)
        assert plan.schedule.n_o == pytest.approx(sp.schedule.n_o,
                                                  rel=1e-12)


def test_plan_batch_accepts_scenario_list_and_shared_grid():
    scs = _mixed_scenarios()[:2]
    shared = np.array([1, 8, 64, 512], np.int64)
    fp = FleetPlanner().plan_batch(scs, CONSTS, grid=shared)
    assert fp.grid.shape == (2, 4)
    for i, sc in enumerate(scs):
        sp = BoundPlanner(grid=shared).plan(sc, CONSTS)
        assert int(fp.n_c[i]) == sp.n_c and float(fp.rate[i]) == sp.rate


def test_bounds_jax_port_matches_numpy_reference():
    """The jnp port agrees with the numpy evaluator on a broadcast grid
    including negative effective overheads and both regimes."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    n_c = np.maximum(rng.uniform(1, 3e4, (5, 64)), 1.0)
    n_o = rng.uniform(0.0, 2000.0, (5, 64))
    neg = rng.random((5, 64)) < 0.15
    # negative EFFECTIVE overheads (rate > 1 links) keep dur = n_c + n_o > 0
    n_o[neg] = -rng.uniform(0.0, 0.9, neg.sum()) * n_c[neg]
    for consts in (CONSTS,
                   BoundConstants(L=1.908, c=2000.0, M=1.0, M_G=1.0,
                                  D=1.0, alpha=1e-3),      # contraction == 0
                   BoundConstants(L=0.5, c=1e-9, M=1.0, M_G=1.0,
                                  D=2.0, alpha=1e-6)):     # contraction ~ 1
        ref = corollary1_bound(n_c, N=18576, T=1.5 * 18576, n_o=n_o,
                               tau_p=1.0, consts=consts)
        with jax.enable_x64(True):
            got = np.asarray(corollary1_bound_jax(
                jnp.asarray(n_c), N=18576.0, T=1.5 * 18576, n_o=jnp.asarray(n_o),
                tau_p=1.0, sigma=consts.variance_floor, e0=consts.init_gap,
                contraction=consts.contraction))
        # 1e-10: the contraction ~ 1 - 1e-15 extreme sits right at the
        # geom-sum tie threshold where 1 - r^k cancels in both paths
        np.testing.assert_allclose(got, ref, rtol=1e-10, atol=0.0)


# ---------------------------------------------------------------------------
# hypothesis property: plan_batch == scalar BoundPlanner loop
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
        """Draw a link from EVERY registered channel family."""
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
        N = draw(st.integers(32, 30000))
        T = draw(st.floats(0.4, 3.0)) * N
        n_o = draw(st.floats(0.0, 2000.0))
        tau_p = draw(st.sampled_from([0.5, 1.0, 2.0]))
        D = draw(st.integers(1, 8))
        return Scenario(N=N, T=T, n_o=n_o, tau_p=tau_p, link=draw(_link()),
                        topology=MultiDevice(D) if D > 1 else SingleDevice())

    @settings(max_examples=15, deadline=None)
    @given(scs=st.lists(_scenario(), min_size=1, max_size=6))
    def test_plan_batch_property_matches_scalar_loop(scs):
        """ISSUE acceptance: FleetPlanner.plan_batch agrees with a scalar
        BoundPlanner loop on randomly drawn heterogeneous scenarios from
        ALL registered link models (payload, rate, and bound value within
        tolerance)."""
        G = 24
        planner = FleetPlanner(grid_size=G)
        records = planner.plan_many(scs, CONSTS)   # pads to pow2 internally
        assert len(records) == len(scs)
        for sc, rec in zip(scs, records):
            _assert_record_matches_scalar(sc, rec.n_c, rec.rate,
                                          rec.bound_value, CONSTS, G)


# ---------------------------------------------------------------------------
# PlanCache
# ---------------------------------------------------------------------------


def _sc(T=2048.0 * 1.5, n_o=100.0, **kw):
    return Scenario(N=2048, T=T, n_o=n_o, **kw)


def test_cache_quantised_key_collapses_jitter():
    a, b = _sc(T=3072.0), _sc(T=3072.0 * (1 + 1e-5))   # sub-quantisation
    c = _sc(T=3400.0)                                  # distinct
    assert scenario_key(a) == scenario_key(b)
    assert scenario_key(a) != scenario_key(c)
    # link params are part of the key
    assert scenario_key(_sc(link=ErasureLink(beta=0.4))) != \
        scenario_key(_sc(link=ErasureLink(beta=0.5)))
    assert scenario_key(_sc()) != scenario_key(_sc(link=ErasureLink()))


def test_cache_key_separates_link_model_families():
    """The (model_id, params) link signature keeps every registered family
    apart even when the packed parameter values coincide — mixed-model
    request streams can never alias across channel physics."""
    keys = [scenario_key(_sc(link=link)) for link in (
        IdealLink(rates=RATES5),
        ErasureLink(beta=0.25, p_base=0.0, rates=RATES5),
        FadingLink(snr=0.25, rates=RATES5),      # param collides with beta
        GilbertElliottLink(p_gb=0.25, p_bg=0.5, rates=RATES5),
    )]
    assert len(set(keys)) == len(keys)
    # same family, same physics, different quantised params -> distinct
    assert scenario_key(_sc(link=FadingLink(snr=8.0))) != \
        scenario_key(_sc(link=FadingLink(snr=12.0)))
    # unregistered links raise instead of silently aliasing by class name
    class Unregistered:
        rates = RATES5
    with pytest.raises(KeyError):
        scenario_key(_sc(link=Unregistered()))


def test_cache_lru_eviction_and_counters():
    cache = PlanCache(maxsize=2)
    s1, s2, s3 = _sc(n_o=1.0), _sc(n_o=2.0), _sc(n_o=3.0)
    assert cache.get(s1) is None and cache.misses == 1
    cache.put(s1, "r1")
    cache.put(s2, "r2")
    assert cache.get(s1) == "r1"            # s1 now most-recent
    cache.put(s3, "r3")                     # evicts s2 (LRU)
    assert cache.get(s2) is None
    assert cache.get(s3) == "r3"
    assert len(cache) == 2
    assert cache.hits == 2 and cache.misses == 2
    assert cache.hit_rate == pytest.approx(0.5)
    cache.clear()
    assert len(cache) == 0 and cache.hits == 0 and cache.misses == 0
    with pytest.raises(ValueError):
        PlanCache(maxsize=0)


def test_plan_many_cache_dedupes_and_replays():
    planner = FleetPlanner(grid_size=24)
    cache = PlanCache(maxsize=64)
    scs = _mixed_scenarios()
    # duplicate every scenario (with sub-quantisation jitter on T)
    stream = scs + [Scenario(N=s.N, T=s.T * (1 + 1e-6), n_o=s.n_o,
                             tau_p=s.tau_p, link=s.link, topology=s.topology)
                    for s in scs]
    recs = planner.plan_many(stream, CONSTS, cache=cache)
    assert len(recs) == len(stream)
    # the jittered duplicates were answered by the first solve
    for i, s in enumerate(scs):
        assert recs[i] == recs[len(scs) + i]
    assert len(cache) == len(scs)
    # a replay is served entirely from cache
    before = cache.hits
    again = planner.plan_many(stream, CONSTS, cache=cache)
    assert again == recs
    assert cache.hits == before + len(stream)
    # and matches the uncached batched solve
    uncached = planner.plan_many(scs, CONSTS)
    assert uncached == recs[:len(scs)]


def test_plan_many_empty():
    assert FleetPlanner().plan_many([], CONSTS) == []


def test_cache_scoped_by_consts_and_grid():
    """A shared cache must never serve a plan optimised under different
    bound constants or a different grid resolution (regression: records
    used to be keyed on the scenario alone)."""
    cache = PlanCache(maxsize=64)
    sc = Scenario(N=4096, T=1.3 * 4096, n_o=300.0,
                  link=ErasureLink(beta=0.4, rates=RATES5))
    other = BoundConstants(L=1.908, c=0.061, M=5.0, M_G=2.0, D=3.0,
                           alpha=5e-4)
    rec_a = FleetPlanner(grid_size=24).plan_many([sc], CONSTS, cache=cache)[0]
    rec_b = FleetPlanner(grid_size=24).plan_many([sc], other, cache=cache)[0]
    rec_c = FleetPlanner(grid_size=48).plan_many([sc], CONSTS, cache=cache)[0]
    assert rec_b.bound_value != rec_a.bound_value   # different constants
    assert len(cache) == 3                          # three scoped entries
    # each configuration replays from its own entry
    assert FleetPlanner(grid_size=24).plan_many([sc], CONSTS,
                                                cache=cache)[0] == rec_a
    assert FleetPlanner(grid_size=48).plan_many([sc], CONSTS,
                                                cache=cache)[0] == rec_c
    # and every record matches its own scalar solve
    _assert_record_matches_scalar(sc, rec_b.n_c, rec_b.rate,
                                  rec_b.bound_value, other, 24)


def test_plan_many_pad_to_fixed_shape():
    scs = _mixed_scenarios()[:3]
    recs = FleetPlanner(grid_size=16).plan_many(scs, CONSTS, pad_to=8)
    assert len(recs) == 3
    assert recs == FleetPlanner(grid_size=16).plan_many(scs, CONSTS)
    with pytest.raises(ValueError):
        FleetPlanner(grid_size=16).plan_many(scs, CONSTS, pad_to=2)


def test_pad_batch_repeats_smallest_scenario():
    """Padding repeats the batch's smallest-N scenario (for the simulated
    Monte-Carlo objective an arbitrary pad pick could fill the padding
    with the batch's most expensive training run), and padded/unpadded
    batches return identical records."""
    from repro.fleet.planner import _pad_batch

    scs = _mixed_scenarios()
    smallest = min(scs, key=lambda sc: sc.N)
    padded = _pad_batch(scs, pad_to=16)
    assert len(padded) == 16
    assert padded[:len(scs)] == scs
    assert all(sc == smallest for sc in padded[len(scs):])
    # shape-only padding: records are unaffected by pad_to
    planner = FleetPlanner(grid_size=16)
    for pad_to in (None, 16, 32):
        assert planner.plan_many(scs, CONSTS, pad_to=pad_to) == \
            planner.plan_many(scs, CONSTS)


def test_boundary_clamps_to_inf_at_deadline_equal_dataset():
    """Regression: the T == N, zero-effective-overhead corner must report
    the +inf regime boundary (matching the scalar ``boundary_n_c``), with
    no NaN leaking from the masked division."""
    from repro.core.protocol import boundary_n_c

    scs = [
        Scenario(N=64, T=64.0, n_o=0.0),                  # T == N, n_o == 0
        Scenario(N=128, T=128.0, n_o=5.0),                # T == N, n_o > 0
        Scenario(N=64, T=32.0, n_o=1.0),                  # T < N
        Scenario(N=64, T=640.0, n_o=3.0),                 # T > N (finite)
    ]
    fp = FleetPlanner(grid_size=16).plan_batch(scs, CONSTS)
    assert not np.isnan(fp.boundary).any()
    assert np.isinf(fp.boundary[:3]).all()
    assert np.isfinite(fp.boundary[3])
    for i, sc in enumerate(scs):
        want = boundary_n_c(sc.N, sc.T,
                            float(sc.effective_overhead(int(fp.n_c[i]),
                                                        float(fp.rate[i]))))
        assert fp.boundary[i] == want or \
            np.isclose(fp.boundary[i], want, rtol=1e-12)


# ---------------------------------------------------------------------------
# registry plugin: a custom channel goes end-to-end in ~30 lines
# ---------------------------------------------------------------------------


def test_custom_link_model_plugs_into_scalar_and_fleet_paths():
    """ISSUE tentpole: registering (numpy model + jax kernel) is ALL a new
    channel needs — ScenarioBatch packs it, the jitted kernel dispatches to
    it via lax.switch next to the built-ins, the cache keys it, and the
    batched plan matches the scalar BoundPlanner."""
    import jax.numpy as jnp
    from dataclasses import dataclass
    from typing import ClassVar, Tuple

    from repro.core.links import (P_ERR_MAX, register_link_model,
                                  unregister_link_model, _validate_rates)
    from repro.fleet import register_link_kernel, unregister_link_kernel

    @dataclass(frozen=True)
    class LinearLossLink:
        """Toy channel: p_err grows linearly with rate."""

        model_id: ClassVar[int] = 4
        N_PARAMS: ClassVar[int] = 1

        slope: float = 0.1
        rates: Tuple[float, ...] = RATES5

        def __post_init__(self):
            _validate_rates(self.rates)

        def p_err(self, rate):
            return np.minimum(self.slope * np.asarray(rate, np.float64),
                              P_ERR_MAX)

        def expected_block_time(self, n_c, n_o, rate):
            raw = np.asarray(n_c, np.float64) / rate + n_o
            return raw / (1.0 - self.p_err(rate))

        def pack_params(self):
            return np.asarray([self.slope], np.float64)

        @classmethod
        def from_params(cls, params, rates):
            return cls(slope=float(params[0]), rates=tuple(rates))

        def make_loss_process(self, rate, rng):
            p = float(self.p_err(rate))
            return lambda: bool(rng.random() < p)

    register_link_model(LinearLossLink)
    register_link_kernel(LinearLossLink.model_id, lambda params, rate:
                         jnp.minimum(params[..., 0] * rate, P_ERR_MAX))
    try:
        scs = _mixed_scenarios() + [
            Scenario(N=6000, T=1.4 * 6000, n_o=250.0,
                     link=LinearLossLink(slope=0.12, rates=RATES5))]
        batch = ScenarioBatch.from_scenarios(scs)
        assert int(batch.link_model_id[-1]) == 4
        assert batch[len(scs) - 1] == scs[-1]            # lossless round-trip
        assert scenario_key(scs[-1]) != scenario_key(scs[0])
        G = 24
        fp = FleetPlanner(grid_size=G).plan_batch(batch, CONSTS)
        for i, sc in enumerate(scs):                     # plugin AND built-ins
            _assert_record_matches_scalar(
                sc, int(fp.n_c[i]), float(fp.rate[i]),
                float(fp.bound_value[i]), CONSTS, G)
    finally:
        unregister_link_kernel(LinearLossLink.model_id)
        unregister_link_model(LinearLossLink.model_id)


# ---------------------------------------------------------------------------
# sharding across (forced) multiple host devices
# ---------------------------------------------------------------------------


_SHARD_SCRIPT = """
import numpy as np, jax
assert jax.device_count() == 4, jax.devices()
from repro.core import BoundConstants
from repro.fleet import FleetPlanner, ScenarioBatch
from repro.serve import ALL_MODELS, default_consts, synth_requests
scs = synth_requests(8, seed=3, dup_frac=0.0, models=ALL_MODELS)
batch = ScenarioBatch.from_scenarios(scs)
sharded = FleetPlanner(grid_size=16, shard=True).plan_batch(batch, default_consts())
local = FleetPlanner(grid_size=16, shard=False).plan_batch(batch, default_consts())
np.testing.assert_array_equal(sharded.n_c, local.n_c)
np.testing.assert_array_equal(sharded.rate, local.rate)
np.testing.assert_array_equal(sharded.bound_value, local.bound_value)
print("SHARDED-OK")
"""


def _run_forced_device_script(script: str, marker: str):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "src")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=src + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-c", script],
        env=env, capture_output=True, text=True, timeout=300, cwd=repo)
    assert out.returncode == 0, out.stderr
    assert marker in out.stdout


def test_plan_batch_sharded_matches_unsharded():
    """NamedSharding over 4 forced host devices returns bitwise-identical
    plans (separate process: the device-count flag must precede jax init)."""
    _run_forced_device_script(_SHARD_SCRIPT, "SHARDED-OK")


_MC_SHARD_SCRIPT = """
import numpy as np, jax
assert jax.device_count() == 4, jax.devices()
from repro.core.objectives import MonteCarloObjective
from repro.core.scenario import ErasureLink, Scenario
from repro.fleet import FleetPlanner, ScenarioBatch
from repro.serve import default_consts
rng = np.random.default_rng(0)
X = rng.normal(size=(48, 4))
y = X @ rng.normal(size=4) + 0.1 * rng.normal(size=48)
mc = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0)
scs = [Scenario(N=int(n), T=1.3 * n, n_o=float(o), tau_p=1.0,
                link=ErasureLink(beta=0.4, p_base=0.05, rates=(1.0, 2.0)))
       for n, o in zip((256, 384, 512, 320, 288, 448, 352, 400),
                       (20, 90, 45, 150, 60, 10, 120, 75))]
batch = ScenarioBatch.from_scenarios(scs)   # S = 8 divides 4 devices
consts = default_consts()
sharded = FleetPlanner(grid_size=8, shard=True, mc_impl=MC_IMPL).plan_batch(
    batch, consts, objective=mc)
local = FleetPlanner(grid_size=8, shard=False, mc_impl=MC_IMPL).plan_batch(
    batch, consts, objective=mc)
np.testing.assert_array_equal(sharded.n_c, local.n_c)
np.testing.assert_array_equal(sharded.rate, local.rate)
np.testing.assert_allclose(sharded.bound_value, local.bound_value,
                           rtol=1e-7, atol=0.0)
print("MC-SHARDED-OK")
"""


@pytest.mark.parametrize("mc_impl", ["scan", "pallas"])
def test_montecarlo_sharded_kernel_matches_unsharded(mc_impl):
    """ISSUE tentpole: the Monte-Carlo objective kernel lays its
    (S, R, G) simulation-lane axis over the forced 4-device mesh
    (scenario-sharded inputs + lane-axis sharding constraint) and its
    plans match the unsharded kernel argmin-exactly — with the Pallas
    slab kernel too, which runs per device under ``shard_map``."""
    _run_forced_device_script(f"MC_IMPL = {mc_impl!r}\n" + _MC_SHARD_SCRIPT,
                              "MC-SHARDED-OK")
