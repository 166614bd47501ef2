"""Percentile, rate and the driver's spread rule on hand-made numbers."""

import pytest

from chipbench_helpers import BENCH  # noqa: F401 — puts the benchmark's lib on the path
from lib import stats


@pytest.mark.parametrize(
    "values, q, want",
    [
        ([1, 2, 3, 4, 5], 50, 3.0),
        ([1, 2, 3, 4, 5], 90, 4.6),
        ([5, 1, 4, 2, 3], 100, 5.0),
        ([10.0], 90, 10.0),
        (list(range(1, 101)), 90, 90.1),
    ],
)
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_rate_is_all_the_work_over_all_the_time():
    assert stats.rate(200, 40.0) == 5.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_spread_is_the_quartile_distance_over_the_median():
    # statistics.quantiles([1..6], n=4) gives 1.75, 3.5, 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.spread([100, 100, 100, 100, 100, 100]) == 0.0


def test_trimmed_spread_leaves_out_the_farthest_run_where_that_narrows():
    steady = [100, 101, 99, 100, 102, 98]
    one_off = [100, 101, 99, 100, 102, 130]
    assert stats.trimmed_spread(one_off) < stats.spread(one_off)
    assert stats.trimmed_spread(one_off) == pytest.approx(
        stats.iqr([100, 101, 99, 100, 102]) / 100.5)
    assert stats.trimmed_spread(steady) <= stats.spread(steady)


@pytest.mark.parametrize(
    "sets, bound, tight, loose",
    [
        # PR 25's refusal: spreads of 6.05 % and 3.80 % against a bound of 8 %
        ([[100, 106.05, 100, 106.05, 100, 106.05, 103], [100, 103.8, 100, 103.8, 100, 103.8, 102]], 0.02, True, False),
        ([[100, 100.2, 99.8, 100.1, 99.9, 100.0], [100, 100.2, 99.8, 100.1, 99.9, 100.0]], 0.10, False, True),
        ([[100, 100.2, 99.8, 100.1, 99.9, 100.0], [100, 100.2, 99.8, 100.1, 99.9, 100.0]], 0.01, False, False),
        ([[100, 101, 99, 100.5, 99.5, 100], [100, 101, 99, 100.5, 99.5, 100]], 0.05, False, False),
    ],
)
def test_verdict_on_a_bound(sets, bound, tight, loose):
    verdict = stats.verdict(sets, bound)
    assert verdict["too_tight"] is tight
    assert verdict["too_loose"] is loose
