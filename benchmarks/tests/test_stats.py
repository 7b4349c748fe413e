import pytest

from benchmarks.harness import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [4, 1, 3, 2]
    assert stats.percentile(xs, 0) == 1
    assert stats.percentile(xs, 100) == 4
    assert stats.percentile(xs, 50) == 2.5
    assert stats.percentile(xs, 95) == pytest.approx(3.85)
    assert stats.median([5]) == 5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_interquartile_range_over_median():
    # statistics.quantiles(n=4) of 1..6 (exclusive method): q1 1.75, q3 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.spread([10.0] * 6) == 0.0


def test_union_and_gaps():
    assert stats.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert stats.union_length([]) == 0
    assert stats.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert stats.gaps([(0, 5)], 0, 5) == []
    assert stats.gaps([], 1, 2) == [(1, 2)]
