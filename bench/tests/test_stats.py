"""Tail and rate arithmetic, with failures counted as misses."""

import math

import pytest

import stats


def test_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None


def test_misses_enter_the_tail():
    vals = [0.1] * 95 + [stats.MISS] * 5
    assert stats.percentile(vals, 95) == 0.1
    vals = [0.1] * 94 + [stats.MISS] * 6
    assert math.isinf(stats.percentile(vals, 95))
    assert stats.reading(stats.percentile(vals, 95)) == stats.MISS_READING


def test_latency_of_a_failure_is_a_miss():
    assert stats.latency(1.0, 1.5) == 0.5
    assert math.isinf(stats.latency(1.0, 1.5, ok=False))
    assert math.isinf(stats.latency(1.0, None))


def test_rate_counts_only_the_window():
    done = [(0.5, 30.0), (1.0, 30.0), (9.99, 30.0), (10.0, 30.0),
            (-0.1, 30.0)]
    assert stats.rate(done, 0.0, 10.0) == pytest.approx(9.0)
    with pytest.raises(ValueError):
        stats.rate(done, 1.0, 1.0)

