"""Tails are taken over every request of the window."""
import pytest

from stats import percentile


def test_nearest_rank():
    values = list(range(1, 101))
    assert percentile(values, 0.50) == 50
    assert percentile(values, 0.99) == 99
    assert percentile(values, 1.0) == 100


def test_tail_counts_every_request():
    # 200 requests: 197 fast, 3 stalled behind a drain; the p99 is the
    # value of rank 198 of 200, the first stalled one
    lat = [1.0] * 197 + [30.0, 31.0, 32.0]
    assert percentile(lat, 0.99) == 30.0
    assert percentile(lat, 0.50) == 1.0
    # leaving the stalled requests out would hide them
    assert percentile(lat[:197], 0.99) == 1.0


def test_empty_sample_is_an_error():
    with pytest.raises(ValueError):
        percentile([], 0.5)
