import pytest

from lib import stats


def test_percentile_against_hand_values():
    assert stats.percentile([], 50) is None
    assert stats.percentile([7.0], 90) == 7.0
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert stats.percentile([4, 1, 3, 2], 50) == 2.5
    # ten samples: the 90th percentile sits 0.1 of the way from 9 to 10.
    assert stats.percentile(range(1, 11), 90) == pytest.approx(9.1)
    assert stats.percentile([1, 2, 3, 4, 5], 100) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([1], 101)


def test_percentile_from_buckets_interpolates_and_clamps():
    bounds = [0.1, 1.0, 10.0]
    assert stats.percentile_from_buckets(bounds, [0, 0, 0, 0], 50) is None
    # all ten samples in (0.1, 1.0]: the median is half way through it.
    assert stats.percentile_from_buckets(bounds, [0, 10, 0, 0], 50) == pytest.approx(0.55)
    # rank 9 of 10 falls in the third bucket, half way: 1 + 0.5 * 9.
    assert stats.percentile_from_buckets(bounds, [4, 4, 2, 0], 90) == pytest.approx(5.5)
    assert stats.percentile_from_buckets(bounds, [0, 0, 0, 5], 99) == 10.0
    with pytest.raises(ValueError):
        stats.percentile_from_buckets(bounds, [1, 2], 50)


def test_histogram_window_is_the_difference():
    before = {"boundaries": [1, 2], "buckets": [1, 1, 0], "sum": 2.5, "count": 2}
    after = {"boundaries": [1, 2], "buckets": [4, 1, 2], "sum": 12.5, "count": 7}
    assert stats.histogram_window(before, after) == {
        "boundaries": [1, 2], "buckets": [3, 0, 2], "sum": 10.0, "count": 5,
    }
    with pytest.raises(ValueError):
        stats.histogram_window(before, dict(after, boundaries=[1, 3]))
