"""Percentile, periods_late and latency_excess on fixed lists; which closes
a barrier met; the readers of the wake share and of the tail on fixed
records."""

import pytest

from harness import cells, stats


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


def test_struck():
    closes = [(0.0, 0.08), (3.125, 3.21), (6.25, 6.33), (9.375, 9.47)]
    assert stats.struck(closes, [(3.125, 3.235)]) == [False, True, False, False]
    assert stats.struck(closes, [(3.0, 3.1)]) == [False, False, False, False]
    assert stats.struck(closes, [(3.0, 3.13)]) == [False, True, False, False]
    assert stats.struck(closes, [(3.2, 3.4), (9.4, 9.5)]) == [False, True, False, True]
    # a barrier that never became durable is in the way of everything after it
    assert stats.struck(closes, [(6.0, None)]) == [False, False, True, True]
    assert stats.struck(closes, []) == [False] * 4


def test_phase_of():
    period = 100_000 / 32_000
    assert stats.phase_of(0.4 + 7 * period, 0.4, period) == 0.0
    assert stats.phase_of(0.4 + 7.25 * period, 0.4, period) == pytest.approx(0.25)
    assert stats.phase_of(0.4 + 8 * period - 1e-9, 0.4, period) == 0.0  # not 0.9999999


def reader(name):
    return cells.Cell("q7-paced").reader(name)


def test_closes_on_wake_share():
    tasks = [{"closes_on_wake": 16, "closes_on_input": 0}, {"closes_on_wake": 15, "closes_on_input": 1},
             {"closes_on_wake": 0, "closes_on_input": 0}]
    assert reader("closes_on_wake_share")({"tasks": tasks}) == pytest.approx(100.0 * 31 / 32)
    assert reader("closes_on_wake_share")({"tasks": tasks[2:]}) is None
    # a program without the counters (before PR 26): nothing to read
    assert reader("closes_on_wake_share")({"tasks": [{"node": "agg_4"}]}) is None


def test_the_tail_and_the_worst_close():
    run = {"closes": [{"latency_ms": float(x)} for x in (80, 70, 90, 2500, 85, 60, 75, 95, 65, 100, 88)]}
    assert reader("close_max_ms")(run) == 2500.0
    assert reader("close_p90_ms")(run) == stats.percentile([c["latency_ms"] for c in run["closes"]], 90)
    assert reader("close_max_ms")({"closes": []}) is None
