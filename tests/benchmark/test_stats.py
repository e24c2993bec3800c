"""Every percentile and rate function on hand-made samples."""

import pytest

from benchmark import stats


@pytest.mark.parametrize("values,q,want", [
    ([], 50, None),
    ([7.0], 90, 7.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([4, 1, 3, 2], 50, 2.5),  # order does not matter
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([0, 10], 90, 9.0),
    (list(range(1, 101)), 90, 90.1),
    (list(range(1, 101)), 99, 99.01),
    ([1, 2, 3, 4, 5], 0, 1.0),
    ([1, 2, 3, 4, 5], 100, 5.0),
])
def test_percentile(values, q, want):
    got = stats.percentile(values, q)
    assert got == want if want is None else got == pytest.approx(want)


def test_median_is_the_50th_percentile():
    assert stats.median([3, 1, 2]) == 2.0


@pytest.mark.parametrize("count,seconds,want", [
    (100, 10, 10.0), (0, 5, 0.0), (5, 0, None), (5, -1, None)])
def test_rate(count, seconds, want):
    assert stats.rate(count, seconds) == want


@pytest.mark.parametrize("part,whole,want", [
    (1, 4, 25.0), (0, 4, 0.0), (3, 0, None)])
def test_share_pct(part, whole, want):
    assert stats.share_pct(part, whole) == want


@pytest.mark.parametrize("first,last,tokens,want", [
    (1.0, 2.0, 11, 0.1),   # ten gaps in one second
    (0.5, 0.5, 1, None),   # one token has no gap
    (0.0, 3.0, 2, 3.0),
])
def test_time_per_output_token(first, last, tokens, want):
    got = stats.time_per_output_token(first, last, tokens)
    assert got == want if want is None else got == pytest.approx(want)


def test_spread_is_the_interquartile_distance_over_the_median():
    # statistics.quantiles([1..6], n=4) -> 1.75, 3.5, 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.spread([10.0]) is None
    assert stats.spread([5, 5, 5, 5]) == 0.0
