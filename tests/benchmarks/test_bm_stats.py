"""The benchmark's metric arithmetic against hand-worked cases."""

import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import stats  # noqa: E402


@pytest.mark.parametrize("values, q, want", [
    ([15, 20, 35, 40, 50], 30, 20),      # rank ceil(1.5) = 2
    ([15, 20, 35, 40, 50], 40, 20),      # rank 2 exactly
    ([15, 20, 35, 40, 50], 50, 35),
    ([15, 20, 35, 40, 50], 100, 50),
    ([3, 1, 2], 95, 3),                  # unsorted input
    ([7], 95, 7),
    (list(range(1, 101)), 95, 95),
    (list(range(1, 21)), 95, 19),        # rank ceil(19.0) = 19
])
def test_percentile_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_rejects_nothing_and_bad_q():
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 0)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_a_failed_request_missed_every_limit():
    # 19 answered in 10 ms, 1 shed: p95 is the 19th sample, still 10 ms
    waits = [stats.latency_ms(0.0, 0.010, True)] * 19
    waits.append(stats.latency_ms(0.0, 0.001, False))
    assert waits[-1] == stats.MISSED
    assert stats.percentile(waits, 95) == pytest.approx(10.0)
    # 18 answered, 2 failed: the 19th sample is a miss
    waits[0] = stats.latency_ms(0.0, None, True)
    assert math.isinf(stats.percentile(waits, 95))
    assert stats.finite_or(stats.percentile(waits, 95), 45000.0) == 45000.0
    assert stats.finite_or(12.5, 45000.0) == 12.5


def test_latency_is_anchored_to_the_due_time():
    # due at 1.0, sent late at 1.4, answered at 1.5: the user waited 0.5 s
    assert stats.latency_ms(1.0, 1.5, True) == pytest.approx(500.0)
    assert stats.latency_ms(2.0, 1.9, True) == 0.0      # never negative


@pytest.mark.parametrize("values, want", [
    ([1, 3, 2], 2), ([4, 1, 3, 2], 2.5), ([5.0], 5.0)])
def test_median(values, want):
    assert stats.median(values) == want


def test_lateness_accounting():
    late = stats.lateness_ms([0.0, 1.0, 2.0], [0.001, 1.0, 2.050])
    assert late == pytest.approx([1.0, 0.0, 50.0])
    # mean inter-arrival 10 ms: a median lateness of 1 ms is 10 % of it
    assert stats.generator_was_late([1.0, 1.0, 0.2], 10.0)
    assert not stats.generator_was_late([0.4, 0.4, 30.0], 10.0)
    assert not stats.generator_was_late([], 10.0)


def test_spread_is_interquartile_distance_over_median():
    # quartiles of 1..5 (interpolated) are 2 and 4, the median 3
    assert stats.spread([5, 1, 4, 2, 3]) == pytest.approx(2 / 3)
    assert stats.spread([7.0]) == 0.0
    assert stats.spread([10.0, 10.0, 10.0]) == 0.0
    # a count that reads 0 in every run agrees exactly; one that is 0 at
    # the median and not elsewhere has no relative spread to give
    assert stats.spread([0.0, 0.0, 0.0, 0.0]) == 0.0
    assert stats.spread([0.0, 0.0, 0.0, 4.0, 8.0]) == math.inf
