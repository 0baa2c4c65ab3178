import math

import numpy as np
import pytest

import latency


def test_latency_counts_from_due_time_not_send_time():
    due = np.array([0.0, 1.0, 2.0])
    done = np.array([0.010, 1.500, 2.020])  # the second was sent 0.49 s late
    lat = latency.latencies_ms(due, done, np.ones(3, bool))
    assert np.allclose(lat, [10.0, 500.0, 20.0])


def test_failures_count_beyond_every_limit():
    lat = latency.latencies_ms(np.zeros(4), np.full(4, 0.001), np.array([1, 1, 0, 1], bool))
    assert math.isinf(lat[2])
    assert latency.percentile(lat, 100) == math.inf
    assert latency.percentile(lat, 50) == pytest.approx(1.0)


def test_nearest_rank_percentiles():
    lat = np.arange(1, 101, dtype=float)  # 1..100 ms
    assert latency.percentile(lat, 50) == 50.0
    assert latency.percentile(lat, 95) == 95.0
    assert latency.percentile(np.array([7.0]), 95) == 7.0


def test_goodput_needs_correct_and_within_limit():
    lat = np.array([10.0, 99.0, 101.0, math.inf, 5.0])
    right = np.array([True, True, True, False, False])
    # 10 and 99 ms are correct and in time; 101 ms is late; the last is wrong
    assert latency.goodput_qps(lat, right, 100.0, 2.0) == 1.0


def test_generator_lateness():
    late = latency.lateness_ms(np.array([0.0, 1.0]), np.array([0.001, 1.003]))
    assert late["max"] == pytest.approx(3.0)
    assert late["p50"] == pytest.approx(1.0)
