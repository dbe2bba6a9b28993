"""Percentile, rate and window arithmetic on hand-made samples."""

import pytest

from benchmarks.harness.serve_cell import summarise
from benchmarks.harness.stats import line_rate, percentile, weighted_mean


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 95) == 95 and percentile(xs, 50) == 50 and percentile(xs, 100) == 100
    assert percentile([5.0], 95) == 5.0
    assert percentile([3, 1, 2], 50) == 2
    assert percentile(list(range(1, 21)), 95) == 19
    with pytest.raises(ValueError):
        percentile([], 95)


def test_line_rate_is_steps_over_wall_time():
    lines = [{"step": 31, "time": 100.0}, {"step": 41, "time": 101.25}, {"step": 251, "time": 128.5}]
    assert line_rate(lines, 256) == pytest.approx(220 * 256 / 28.5)
    assert line_rate(lines[:1], 256) is None


def test_weighted_mean():
    assert weighted_mean([(10.0, 1), (20.0, 3)]) == pytest.approx(17.5)
    assert weighted_mean([(10.0, 0)]) is None


def test_summarise_counts_failures_as_the_largest_value():
    win = {
        "seconds": 10.0, "rate_rps": 0.5,
        "status": [200, 200, 503, 200, -1],
        "latency_ms": [10.0, 20.0, 5.0, 120.0, 1.0],
        "late_ms": [0.1, 0.2, 0.1, 0.3, 0.1],
        "done_s": [1.0, 2.0, 3.0, 10.5, 5.0],
        "size": [1, 2, 4, 8, 1],
    }
    s = summarise(win, slo_ms=100.0, timeout_s=30.0)
    assert s["attempted"] == 5 and s["failed"] == 2
    assert s["p95_ms"] == 30000.0  # two of five failed: the tail is the timeout
    assert s["p50_ms"] == 120.0
    assert s["img_per_s"] == pytest.approx(3 / 10.0)  # the 8-image one finished late
    assert s["within_slo_share"] == pytest.approx(2 / 5)
    assert s["completed_share"] == pytest.approx(2 / 5)
