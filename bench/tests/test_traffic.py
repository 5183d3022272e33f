import json
from pathlib import Path

import numpy as np

from harness import traffic

BENCH = Path(__file__).resolve().parents[1]


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_open_schedule_is_deterministic_for_a_seed():
    mix = _mix("bound-solve-open")
    a = traffic.open_schedule(mix, 2**31 + 17, 3.0)
    b = traffic.open_schedule(mix, 2**31 + 17, 3.0)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, traffic.open_schedule(mix, 5, 3.0))


def test_every_seed_sends_the_same_requests_at_the_same_rate():
    mix = _mix("bound-solve-open")
    rate = mix["arrivals"]["rate_per_s"]
    for seed in (1, 2, 3_000_000_000):
        t = traffic.open_schedule(mix, seed, 2.0)
        assert len(t) == round(rate * 2.0)
        assert t[0] == 0.0 and np.all(np.diff(t) >= 0) and t[-1] < 2.0
    gaps = [np.sort(np.diff(traffic.open_schedule(mix, s, 2.0)))
            for s in (1, 2)]
    # the same set of gaps, in another order (but the last gap of each)
    assert np.isclose(gaps[0].sum(), gaps[1].sum(), rtol=1e-3)


def test_requests_are_deterministic_and_balanced():
    mix = _mix("bound-solve-open")
    a = traffic.requests(mix, 42, 400)
    assert a == traffic.requests(mix, 42, 400)
    assert a != traffic.requests(mix, 43, 400)
    pairs = [(r["objective"], r["grid_mode"]) for r in a]
    assert all(pairs.count(tuple(p)) == 100 for p in mix["mix"])
    for r in a:
        assert 256 <= r["N"] < 32768
        assert 1.1 * r["N"] <= r["T"] <= 3.0 * r["N"]
        assert r["link"]["family"] in mix["requests"]["links"]
        if r["link"]["family"] == "gilbert_elliott":
            assert r["link"]["p_good"] <= r["link"]["p_bad"]
