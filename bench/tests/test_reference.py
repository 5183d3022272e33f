import json
from pathlib import Path

import numpy as np
import pytest

from harness import traffic
from reference import bound, common

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads((BENCH / "configs" / "plan-bound.json").read_text())
MIX = json.loads((BENCH / "traffic" / "bound-solve-open.json").read_text())


def test_log_grid():
    g = common.log_grid(1000, 5)
    assert list(g) == [1, 6, 32, 178, 1000]
    assert common.log_grid(256, 128).shape == (128,)


def test_pick_breaks_ties_by_first_rate_then_first_point():
    vals = np.array([[3.0, 1.0, 1.0], [1.0, 2.0, 2.0]])
    rec = common.pick(np.array([10, 20, 30]), np.array([1.0, 2.0]), vals)
    assert (rec["n_c"], rec["rate"], rec["value"]) == (20, 1.0, 1.0)


def test_served_gaps():
    grid, rates = np.array([1, 5, 9]), np.array([1.0, 2.0])
    vals = np.array([[4.0, 2.0, 3.0], [5.0, 1.0, 6.0]])
    ev = [(grid, rates, vals)]
    best = {"n_c": 5, "rate": 2.0, "value": 1.0}
    assert common.served_gaps([best], ev) == {"value_rel_gap": 0.0,
                                              "regret_rel": 0.0}
    worse = {"n_c": 9, "rate": 1.0, "value": 3.3}
    out = common.served_gaps([worse], ev)
    assert out["value_rel_gap"] == pytest.approx(0.1)
    assert out["regret_rel"] == pytest.approx(2.0)
    off = {"n_c": 6, "rate": 2.0, "value": 1.0}
    assert common.served_gaps([off], ev)["value_rel_gap"] == float("inf")


def test_markov_arq_equals_corollary1_off_sticky_links():
    reqs = traffic.requests(MIX, 3, 64)
    for req in reqs:
        req["objective"] = "corollary1"
    a = bound.evaluate(reqs, CONFIG, "float64")
    for req in reqs:
        req["objective"] = "markov_arq"
    b = bound.evaluate(reqs, CONFIG, "float64")
    differs = [not np.array_equal(va, vb) for (_, _, va), (_, _, vb)
               in zip(a, b)]
    sticky = [req["link"]["family"] == "gilbert_elliott" for req in reqs]
    assert not any(d and not s for d, s in zip(differs, sticky))
    assert any(differs)


def test_control_differs_from_the_reference():
    reqs = traffic.requests(MIX, 4, 256)
    out = bound.compare(reqs, bound.control(reqs, CONFIG), CONFIG)
    assert out["value_rel_gap"] > 1e-6
