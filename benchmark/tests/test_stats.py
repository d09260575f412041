"""Percentile, periods_late and latency_excess on fixed lists."""

import pytest

from harness import stats


def test_percentile_matches_linear_interpolation():
    v = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(v, 50) == 30.0
    assert stats.percentile(v, 90) == pytest.approx(46.0)
    assert stats.percentile(v, 0) == 10.0 and stats.percentile(v, 100) == 50.0
    assert stats.percentile([7.0], 90) == 7.0
    assert stats.percentile([], 50) is None
    # order does not matter
    assert stats.percentile([50.0, 10.0, 40.0, 20.0, 30.0], 90) == pytest.approx(46.0)


def test_periods_late_and_excess():
    period = 714.0
    lat = [80.0, 794.0, 800.0, 1508.0, 2222.5, 713.9]
    assert stats.periods_late(lat, period) == [0, 1, 1, 2, 3, 0]
    excess = stats.latency_excess(lat, period)
    assert excess == pytest.approx([80.0, 80.0, 86.0, 80.0, 80.5, 713.9])
    # the two parts add up to the latency
    for l, p, e in zip(lat, stats.periods_late(lat, period), excess):
        assert p * period + e == pytest.approx(l)


def test_spread_is_the_contracts():
    import statistics

    v = [43673.0, 44360.0, 44749.0, 45112.0, 45255.0, 44800.0]
    q1, _q2, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / statistics.median(v))
    assert stats.spread([1.0]) is None


def test_histogram_quantile():
    bounds = (0.001, 0.01, 0.1)
    assert stats.histogram_quantile(bounds, [9, 1, 0, 0], 0.9) == 0.001
    assert stats.histogram_quantile(bounds, [5, 4, 1, 0], 0.95) == 0.1
    assert stats.histogram_quantile(bounds, [0, 0, 0, 3], 0.5) == 0.1  # overflow clamps
    assert stats.histogram_quantile(bounds, [0, 0, 0, 0], 0.5) is None
