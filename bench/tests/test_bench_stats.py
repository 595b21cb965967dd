"""Rate and spread arithmetic of the benchmark."""

import pytest

from bench.lib import stats


def test_rate_counts_over_the_whole_window():
    assert stats.rate(30, 12.0) == pytest.approx(2.5)
    assert stats.rate(0, 12.0) is None
    assert stats.rate(5, 0.0) is None


def test_spread_is_the_quartile_distance_over_the_median():
    # statistics.quantiles(n=4) of 1..7 (exclusive method): 2, 4, 6
    assert stats.spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(1.0)
    assert stats.spread([100.0] * 6) == 0.0
