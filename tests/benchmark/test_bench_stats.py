"""The statistics, on samples made by hand."""

import pytest

import bench_paths  # noqa: F401
from benchmark.harness import stats


@pytest.mark.parametrize("xs,want", [
    ([1.0], 1.0), ([1, 2, 3, 4], 2.5), ([10, 0, 5], 5.0),
    ([0.1] * 10, 0.1)])
def test_mean(xs, want):
    assert stats.mean(xs) == pytest.approx(want)


@pytest.mark.parametrize("xs,q,want", [
    ([1, 2, 3, 4], 50, 2.5), ([1, 2, 3, 4], 0, 1), ([1, 2, 3, 4], 100, 4),
    ([4, 1, 3, 2], 25, 1.75), (list(range(101)), 95, 95),
    (list(range(101)), 99, 99), ([7], 90, 7), ([1, 3], 75, 2.5)])
def test_percentile_interpolates_between_order_statistics(xs, q, want):
    assert stats.percentile(xs, q) == pytest.approx(want)


@pytest.mark.parametrize("fn", [stats.mean,
                                lambda xs: stats.percentile(xs, 50)])
def test_no_samples_give_no_number(fn):
    assert fn([]) is None


@pytest.mark.parametrize("q", [-1, 100.5])
def test_percentile_outside_range_is_an_error(q):
    with pytest.raises(ValueError):
        stats.percentile([1, 2], q)


@pytest.mark.parametrize("count,seconds,want", [
    (41907, 51.0, 821.7058823529412), (16384 * 36, 51.2, 11520.0),
    (0, 2.0, 0.0)])
def test_rate(count, seconds, want):
    assert stats.rate(count, seconds) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("seconds", [0, -1.0])
def test_rate_over_no_time_is_an_error(seconds):
    with pytest.raises(ValueError):
        stats.rate(5, seconds)


@pytest.mark.parametrize("xs,want", [
    # statistics.quantiles(n=4) of 1..6: 1.75, 3.5, 5.25
    ([1, 2, 3, 4, 5, 6], 3.5 / 3.5),
    ([100, 100, 100, 100, 100, 100], 0.0),
    ([100, 101, 102, 103, 104, 105], 3.5 / 102.5)])
def test_quartile_spread_is_the_drivers_rule(xs, want):
    assert stats.quartile_spread(xs) == pytest.approx(want)
