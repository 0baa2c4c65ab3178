"""Host stalls in a window, attributed rather than left in the tail.

* ``GcPauses`` records the interpreter's garbage-collection passes through
  ``gc.callbacks``: it only observes, and never tunes or disables the
  collector.
* ``send_stalls`` finds the longest stretches in which the open-loop
  generator could not send a request that was due, and names for each what
  was going on: the collector passes inside it, the requests in service,
  and (in a traced run) the innermost program span open on the host.
"""
from __future__ import annotations

import gc
import time

import numpy as np


class GcPauses:
    """Collector passes as (generation, start, seconds) on the perf_counter
    clock, recorded while the context is open."""

    def __init__(self):
        self.passes: list[tuple[int, float, float]] = []
        self._t0 = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.passes.append((int(info.get("generation", -1)), self._t0,
                                time.perf_counter() - self._t0))

    def __enter__(self) -> "GcPauses":
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc) -> bool:
        gc.callbacks.remove(self._cb)
        return False

    def summary(self, lo: float, hi: float) -> dict:
        """Per generation inside [lo, hi): passes, total and longest seconds."""
        out: dict[int, dict] = {}
        for gen, s, d in self.passes:
            if lo <= s < hi:
                g = out.setdefault(gen, {"passes": 0, "total_s": 0.0, "max_s": 0.0})
                g["passes"] += 1
                g["total_s"] += d
                g["max_s"] = max(g["max_s"], d)
        return out


def send_stalls(due: np.ndarray, sent: np.ndarray, *, min_ms: float = 5.0,
                top: int = 5) -> list[tuple[float, float]]:
    """The ``top`` longest stretches (start, end) in which the generator held
    a due request unsent for ``min_ms`` or more; overlapping stretches merge.
    A request's stretch runs from when it was due, or from the previous send
    if that came later, to its own send."""
    due = np.asarray(due, np.float64)
    sent = np.asarray(sent, np.float64)
    prev = np.concatenate([[-np.inf], sent[:-1]])
    start = np.maximum(due, prev)
    late = (sent - start) * 1e3 >= min_ms
    merged: list[list[float]] = []
    for s, e in sorted(zip(start[late], sent[late])):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    merged.sort(key=lambda se: se[0] - se[1])
    return [(s, e) for s, e in merged[:top]]


def attribute(stalls, *, opened: float, sent: np.ndarray, done: np.ndarray,
              describe, gc_passes=(), spans=()) -> list[dict]:
    """One record per stall: when (seconds into the window), how long, the
    collector passes overlapping it, the requests in service at its start
    (``describe(i)`` names request i) and, given program spans as (name,
    start, end, depth) on the perf_counter clock, the innermost one open at
    its midpoint."""
    import devtrace

    sent = np.asarray(sent, np.float64)
    done = np.where(np.isfinite(done), done, np.inf)
    mids = [(s + e) / 2 for s, e in stalls]
    names = devtrace.labels(mids, list(spans)) if spans else [None] * len(stalls)
    out = []
    for (s, e), span in zip(stalls, names):
        busy = np.flatnonzero((sent <= s) & (done > s))
        gcs = [(g, d) for g, t, d in gc_passes if t < e and t + d > s]
        rec = {"at_s": round(s - opened, 4), "ms": round((e - s) * 1e3, 3),
               "gc_ms": round(1e3 * sum(d for _, d in gcs), 3),
               "gc_generations": sorted({g for g, _ in gcs}),
               "in_service": [describe(int(i)) for i in busy[:3]],
               "n_in_service": int(len(busy))}
        if span is not None:
            rec["host_span"] = span
        out.append(rec)
    return out
