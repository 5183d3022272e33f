"""The served Monte-Carlo objective's data set size (``mc_dataset_rows``):
the service builds its objective over that many rows, services that
differ only in it never share a cache entry, a plan served over 1,000
rows is the scalar planner's, seed for seed, and each chunk's record
notes the rows its passes simulated over (``mc_rows``)."""
import numpy as np
import pytest

from repro.core import (ErasureLink, MonteCarloPlanner, Scenario)
from repro.data.synthetic import make_regression_dataset
from repro.fleet import objective_token
from repro.serve import PlanningService, ServiceConfig


def _service(rows=None, **kw):
    cfg = dict(objective_ids=("montecarlo",), grid_modes=("dense",),
               n_max=1000, batch_buckets=(8,), **kw)
    if rows is not None:
        cfg["mc_dataset_rows"] = rows
    return PlanningService(ServiceConfig(**cfg))


@pytest.mark.parametrize("rows", [None, 1000, 18576])
def test_service_builds_its_objective_over_the_configured_rows(rows):
    """256 rows unless the configuration names others, each the canonical
    generator's set of that size."""
    mc = _service(rows).objectives["montecarlo"]
    want = 256 if rows is None else rows
    X, y, _ = make_regression_dataset(n=want, d=8, seed=0)
    assert np.shape(mc.X) == (want, 8)
    np.testing.assert_array_equal(np.asarray(mc.X), X)
    np.testing.assert_array_equal(np.asarray(mc.y), y)


def test_services_that_differ_in_rows_never_share_cache_tokens():
    a, b = (_service(rows).objectives["montecarlo"] for rows in (256, 1000))
    assert a.cache_token() != b.cache_token()
    assert objective_token(a) != objective_token(b)
    assert a.data_digest != b.data_digest
    same = _service(1000).objectives["montecarlo"]
    assert same.cache_token() == b.cache_token()


def test_rows_must_be_positive():
    with pytest.raises(ValueError, match="mc_dataset_rows"):
        ServiceConfig(mc_dataset_rows=0)


def test_plan_served_over_1000_rows_is_the_scalar_planners():
    """A service over 1,000 rows answers a request of N = 1,000 with the
    scalar Monte-Carlo planner's plan, seed for seed (the exact per-slot
    stream, no common random numbers)."""
    link = ErasureLink(beta=0.4, p_base=0.05, rates=(1.0, 2.0))
    scs = [Scenario(N=1000, T=1.3 * 1000, n_o=40.0, tau_p=1.0, link=link),
           Scenario(N=1000, T=2.1 * 1000, n_o=300.0, tau_p=2.0, link=link)]
    with _service(1000) as svc:
        futures = [svc.submit(sc, objective="montecarlo",
                              grid_mode="dense") for sc in scs]
        served = [f.result(timeout=600) for f in futures]
    spans = svc.spans.snapshot()
    assert spans and all(s.mc_rows == 1000 for s in spans)
    mc = svc.objectives["montecarlo"]
    assert np.shape(mc.X)[0] == 1000
    for sc, rec in zip(scs, served):
        scalar = MonteCarloPlanner(
            X=mc.X, y=mc.y, lam=mc.lam, alpha=mc.alpha, n_runs=mc.n_runs,
            seed=mc.seed, grid_points=mc.grid_points,
            seed_stream=mc.seed_stream).plan(sc, svc.consts)
        assert rec.fallback == "full"
        assert (rec.n_c, rec.rate) == (scalar.n_c, scalar.rate)
        assert np.isclose(rec.bound_value, scalar.bound_value, rtol=1e-5)


def test_rows_are_noted_not_added():
    """Two passes of one chunk over the same data set note its rows once;
    a record with no Monte-Carlo pass notes none."""
    from repro.obs import runtime
    runtime.open_record()
    try:
        runtime.note("mc_rows", 18576)
        runtime.note("mc_rows", 18576)
        runtime.note("mc_rows", 256)
        assert runtime.take_record()[1]["mc_rows"] == 18576
        assert "mc_rows" not in runtime.take_record()[1]
    finally:
        runtime.close_record()


def test_bound_chunks_record_no_rows():
    with PlanningService(ServiceConfig(batch_buckets=(8,))) as svc:
        svc.submit(Scenario(N=1000, T=1300.0, n_o=40.0),
                   objective="corollary1").result(timeout=120)
    spans = svc.spans.snapshot()
    assert spans and all(s.mc_rows == 0 for s in spans)
