"""The arithmetic every metric shares (run by hand: `python -m pytest
benchmarks/tests -q`; tier-1 collects `tests/` only)."""

import math

import pytest

from benchmarks.harness import stats


def test_percentile_interpolates_between_ranks():
    v = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert stats.percentile(v, 0) == 1.0
    assert stats.percentile(v, 50) == 3.0
    assert stats.percentile(v, 100) == 5.0
    assert stats.percentile(v, 95) == pytest.approx(4.8)
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], 50) == pytest.approx(2.5)
    assert stats.percentile([7.0], 95) == 7.0


def test_percentile_agrees_with_numpy():
    np = pytest.importorskip("numpy")
    rng = np.random.default_rng(7)
    v = rng.exponential(10.0, 1001).tolist()
    for q in (5, 50, 95, 99):
        assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_median_and_geomean():
    assert stats.median([9.5, 9.6, 9.2]) == 9.5
    assert stats.geomean([2.0, 8.0]) == pytest.approx(4.0)
    assert stats.geomean([9.5, 8.1]) == pytest.approx(math.sqrt(9.5 * 8.1))
    with pytest.raises(ValueError):
        stats.geomean([1.0, 0.0])
    with pytest.raises(ValueError):
        stats.geomean([])


def test_merged_intervals_count_overlaps_once():
    assert stats.merge_intervals([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == \
        [[0, 2], [3, 4]]
    assert stats.merge_intervals([(3, 4), (0, 1), (1, 2)]) == [[0, 2], [3, 4]]
