"""Percentile, rate and spread arithmetic of the end-to-end metrics."""
import statistics

import numpy as np
import pytest

from bench import stats


@pytest.mark.parametrize("values,q,want", [
    ([3.0], 95, 3.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 95, 4.8),
    ([10.0, 0.0, 5.0], 0, 0.0),
    ([10.0, 0.0, 5.0], 100, 10.0),
])
def test_percentile_known_values(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want, rel=1e-12)


def test_percentile_matches_numpy_linear_rule():
    rng = np.random.default_rng(0)
    for n in (2, 7, 100, 333):
        xs = list(rng.lognormal(0.0, 1.0, n))
        for q in (50, 90, 95, 99):
            assert stats.percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), rel=1e-12)


def test_percentile_rejects_bad_input():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_rate_is_count_over_window():
    assert stats.rate(300, 30.0) == 10.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_spread_is_iqr_over_median():
    xs = [9.0, 10.0, 10.5, 11.0, 12.0, 30.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert med == statistics.median(xs)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / med)
