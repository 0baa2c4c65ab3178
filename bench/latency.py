"""Client-side arithmetic of an open-loop window.

Every request is timed from its *due* time, not from when the generator got
round to sending it, so a stalled generator shows as latency.  A request that
failed, was rejected or never answered has no latency: it counts as beyond
every limit (``math.inf`` here).  Percentiles are nearest-rank over all
requests due in the window.
"""
from __future__ import annotations

import math

import numpy as np


def latencies_ms(due: np.ndarray, done: np.ndarray, ok: np.ndarray) -> np.ndarray:
    """Per-request milliseconds from due to answer; inf where not ``ok``."""
    lat = (np.asarray(done, np.float64) - np.asarray(due, np.float64)) * 1e3
    return np.where(np.asarray(ok, bool), lat, math.inf)


def percentile(lat_ms: np.ndarray, q: float) -> float:
    """Nearest-rank q-th percentile (0 < q <= 100); inf if it lands on a failure."""
    v = np.sort(np.asarray(lat_ms, np.float64))
    if len(v) == 0:
        raise ValueError("no requests in the window")
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def goodput_qps(lat_ms: np.ndarray, correct: np.ndarray, limit_ms: float,
                seconds: float) -> float:
    """Requests answered correctly within ``limit_ms``, per second of window."""
    good = (np.asarray(lat_ms) <= limit_ms) & np.asarray(correct, bool)
    return float(good.sum()) / float(seconds)


def lateness_ms(due: np.ndarray, sent: np.ndarray) -> dict:
    """How late the generator sent: median, 99th percentile and maximum (ms)."""
    late = (np.asarray(sent, np.float64) - np.asarray(due, np.float64)) * 1e3
    return {"p50": percentile(late, 50), "p99": percentile(late, 99),
            "max": float(late.max())}
