"""Percentile and rate arithmetic on a window that holds a stall."""
import pytest

from perfbench import stats


def test_percentile_interpolates_over_all_values():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == pytest.approx(50.5)
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_stall_moves_the_rate_and_the_tail():
    # 100 requests a second for 10 s, 10 ms each; then the same window with
    # a 2 s stall at t=4: requests due in it wait for its end
    due = [i / 100 for i in range(1000)]
    smooth = [10.0 for _ in due]
    stalled = [10.0 + max(0.0, 6.0 - d) * 1e3 if 4.0 <= d < 6.0 else 10.0
               for d in due]
    assert stats.percentile(smooth, 95) == 10.0
    assert stats.percentile(stalled, 95) > 1000.0
    assert stats.percentile(stalled, 50) == 10.0
    # the rate is all the work over the whole window, the stall included
    assert stats.rate(800, 0.0, 10.0) == 80.0 < stats.rate(1000, 0.0, 10.0)
    with pytest.raises(ValueError):
        stats.rate(1, 2.0, 2.0)


def test_spread_is_quartile_distance_over_median():
    import statistics
    xs = [100.0, 101.0, 99.0, 100.5, 99.5, 100.2]
    q = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q[2] - q[0])
                                             / statistics.median(xs))
