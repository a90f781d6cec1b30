"""The percentile rule."""

import math

import pytest

from benchmark.stats import percentile


def test_percentile_interpolates_between_order_statistics():
    vals = [float(v) for v in range(1, 102)]          # 1..101
    assert percentile(vals, 90.0) == 91.0
    assert percentile(vals, 50.0) == 51.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 90.0) == pytest.approx(3.7)
    assert percentile([4.0, 1.0, 3.0, 2.0], 0.0) == 1.0
    assert percentile([], 90.0) is None


def test_a_missing_sample_sorts_last_and_can_make_the_tail_missing():
    vals = [1.0] * 95 + [math.inf] * 5
    assert percentile(vals, 90.0) == 1.0
    assert percentile(vals, 99.0) is None
