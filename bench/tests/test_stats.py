import statistics

import numpy as np
import pytest

from harness.stats import percentile, relative_spread


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_is_exact_linear_interpolation(q):
    xs = np.random.default_rng(0).exponential(size=1001)
    assert percentile(list(xs), q) == pytest.approx(np.percentile(xs, q),
                                                   rel=0, abs=1e-15)


def test_percentile_by_hand():
    # ranks 0..4 of [10, 20, 30, 40, 50]; p95 sits at rank 3.8
    assert percentile([50, 10, 40, 20, 30], 95) == pytest.approx(48.0)
    assert percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_relative_spread_uses_statistics_quantiles():
    vals = [10.0, 11.0, 9.5, 10.2, 10.8, 9.9]
    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert relative_spread(vals) == (q3 - q1) / med
