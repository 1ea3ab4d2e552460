"""Percentile and sample-count arithmetic."""

import numpy as np
import pytest

from benchmark import stats


@pytest.mark.parametrize("q", [0, 5, 50, 90, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 200, 1001])
def test_benchmark_percentile_is_numpys_linear_one(n, q):
    values = list(np.random.default_rng(n).random(n))
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)), rel=1e-12, abs=1e-15)


def test_benchmark_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


@pytest.mark.parametrize("n,q,beyond", [(200, 95, 10), (199, 95, 9),
                                        (1000, 99, 10), (100, 90, 10),
                                        (20, 50, 10)])
def test_benchmark_samples_beyond_a_percentile(n, q, beyond):
    assert stats.samples_beyond(n, q) == beyond
    assert stats.tail_is_supported(n, q) == (beyond >= 10)


def test_benchmark_p95_wants_two_hundred_samples():
    assert stats.min_samples_for(95.0) == 200
    assert stats.min_samples_for(99.0) == 1000
    assert stats.median([3.0, 1.0, 2.0]) == 2.0
