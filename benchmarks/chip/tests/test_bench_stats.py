"""Tails are taken over all samples."""

import pytest

import bench_tiny  # noqa: F401
from chipbench.stats import percentile


def test_tail_over_all_samples_not_median_of_chunks():
    xs = [1.0] * 90 + [100.0] * 10
    # four chunks of 25: a median of the chunk p95s would read 1 or 100
    assert percentile(xs, 95) == pytest.approx(100.0)
    assert percentile(xs, 89) == pytest.approx(1.0)
    assert percentile(list(range(101)), 90) == pytest.approx(90.0)


def test_empty_has_no_tail():
    assert percentile([], 90) is None

