"""Exact percentiles, whole-window rates and interval arithmetic."""
import numpy as np
import pytest

from benchkit import stats


@pytest.mark.parametrize("q", [0, 5, 50, 90, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100, 1001])
def test_percentile_is_exact_linear_interpolation(q, n):
    xs = np.random.default_rng(n).lognormal(size=n)
    assert stats.percentile(xs, q) == pytest.approx(
        float(np.percentile(xs, q, method="linear")), rel=1e-12)


def test_percentile_reads_raw_samples_not_buckets():
    # a single far sample moves the tail exactly as far as it lies
    xs = [10.0] * 99 + [1000.0]
    assert stats.percentile(xs, 100) == 1000.0
    assert stats.percentile(xs, 99) == pytest.approx(10.0 + 0.01 * 990.0)
    assert stats.percentile([], 95) is None
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_rate_is_everything_in_the_window_over_its_length():
    times = [0.5, 1.0, 1.5, 2.0, 9.99, 10.0, 12.0]
    n = stats.count_in(times, 1.0, 10.0)
    assert n == 4                       # [1.0, 10.0): 1.0 in, 10.0 out
    assert stats.rate(n, 1.0, 10.0) == pytest.approx(4 / 9.0)
    with pytest.raises(ValueError):
        stats.rate(1, 2.0, 2.0)


def test_union_and_gaps_partition_the_window():
    ivs = [(1, 3), (2, 4), (6, 7), (8, 20)]
    assert stats.union_length(ivs, 0, 10) == 3 + 1 + 2
    gaps = stats.gaps(ivs, 0, 10)
    assert gaps == [(0, 1), (4, 6), (7, 8)]
    assert stats.union_length(ivs, 0, 10) + sum(e - s for s, e in gaps) \
        == 10
