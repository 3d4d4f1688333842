"""Rates, percentiles, interval unions and the roofline's work count, on
fixed samples."""

import pytest

from conftest import REPO  # noqa: F401  (puts the repo on sys.path)
from benchmark.harness import stats
from benchmark.rooflines import ed25519_verify as roof


def test_rate_is_all_work_over_all_time():
    assert stats.rate(166400, 16.0) == 10400.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


@pytest.mark.parametrize("q, want", [(50, 5), (95, 10), (100, 10), (0, 1),
                                     (10, 1), (11, 2)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile([7, 3, 1, 9, 5, 2, 8, 10, 4, 6], q) == want


def test_percentile_of_twenty_and_of_nothing():
    assert stats.percentile(list(range(1, 21)), 95) == 19
    assert stats.median([4.0, 1.0, 3.0]) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_union_and_gaps():
    spans = [(0, 4), (2, 6), (10, 12), (11, 11.5)]
    assert stats.union_seconds(spans) == 8
    assert stats.union_seconds([]) == 0
    assert stats.gaps(spans, 0, 15) == [(6, 10), (12, 15)]
    assert stats.gaps([], 1, 3) == [(1, 3)]


def test_roofline_count_is_a_pure_function_of_lanes_and_blocks():
    one = roof.ops(1, 2)
    assert one == 6909 * 512 + 2 * 5840
    assert roof.ops(3200, 6400) == 3200 * one
    assert roof.bytes_moved(1, 2) == 32 + 64 + 1 + 256
    peaks = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    least, bound = roof.least_seconds(166400, 332800, peaks)
    assert bound == "ops"
    assert least == pytest.approx(166400 * one / 393e12)
    # a slow-arithmetic device would be bound by bytes instead
    assert roof.least_seconds(
        1, 2, {"int8_ops_per_s": 1e18, "hbm_bytes_per_s": 1e3})[1] == "bytes"
