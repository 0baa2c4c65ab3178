"""Two-stage RMI codec for very long posting lists.

A recursive-model-index [Kraska et al. '18] specialized to the postings
setting: stage 1 is a *linear root* over rank (ranks are uniform, so the
root reduces to the exact affine bucketing ``leaf = i * L // n``); stage 2
is one linear model per leaf, trained with closed-form least squares on
the host (segment-sum normal equations, no iterative optimizer).  Leaf models are
anchored at the leaf's first doc id and the fitted intercept is rounded into
that integer base, so the float32 regression only has to cover the
within-leaf span — corrections stay narrow even for billion-scale universes
and the decode formula is plm.py's single-multiply form.

Serialization reuses the plm.py stream layout (start, base, slope per leaf +
bit-packed corrections), so the Pallas plm_decode kernel batch-decodes RMI
streams unchanged.
"""
from __future__ import annotations

import numpy as np

from repro.postings.plm import decode_stream, emit_stream, eval_segments, _stream_size_bits

LEAF_TARGET = 64  # target postings per leaf model
MAX_LEAVES = 4096


def n_leaves(n: int, leaf_target: int = LEAF_TARGET) -> int:
    return max(1, min(MAX_LEAVES, n // max(1, leaf_target)))


def _leaf_starts(n: int, L: int) -> np.ndarray:
    """Rank boundaries of the affine root: leaf l covers ranks with i*L//n == l."""
    l = np.arange(L, dtype=np.int64)
    return np.ceil(l * n / L).astype(np.int64)


def _leaf_lstsq(
    x: np.ndarray, y: np.ndarray, leaf: np.ndarray, L: int
) -> tuple[np.ndarray, np.ndarray]:
    """Per-leaf 1D least squares via segment-sum normal equations.

    x, y are leaf-centered (rank - leaf_start, doc_id - leaf_base) integers;
    the sums run in float64 on the host, so the fit (and the stored stream)
    is the same bytes on every machine.  Returns (slopes, iceps) float64.
    """
    cnt = np.bincount(leaf, minlength=L).astype(np.float64)
    sx = np.bincount(leaf, weights=x, minlength=L)
    sy = np.bincount(leaf, weights=y, minlength=L)
    sxx = np.bincount(leaf, weights=x * x, minlength=L)
    sxy = np.bincount(leaf, weights=x * y, minlength=L)
    denom = cnt * sxx - sx * sx
    ok = denom > 0
    slope = np.where(ok, (cnt * sxy - sx * sy) / np.where(ok, denom, 1.0), 0.0)
    icep = np.where(cnt > 0, (sy - slope * sx) / np.maximum(cnt, 1.0), 0.0)
    return slope, icep


def fit_rmi(
    doc_ids: np.ndarray, leaf_target: int = LEAF_TARGET
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fit the two-stage model -> (starts i64, bases i64, slopes f32).

    The least-squares intercept is rounded into the integer base (plm.py's
    decode has no separate intercept term); the sub-integer remainder lands
    in the corrections, costing at most one extra correction value."""
    n = len(doc_ids)
    if n == 0:
        z = np.zeros(0, np.int64)
        return z, z, np.zeros(0, np.float32)
    L = n_leaves(n, leaf_target)
    starts = _leaf_starts(n, L)
    ids64 = np.asarray(doc_ids, np.int64)
    anchors = ids64[starts]
    ranks = np.arange(n, dtype=np.int64)
    leaf = (ranks * L) // n
    x = (ranks - starts[leaf]).astype(np.float64)
    y = (ids64 - anchors[leaf]).astype(np.float64)
    slopes, iceps = _leaf_lstsq(x, y, leaf, L)
    i32 = np.iinfo(np.int32)
    bases = np.clip(anchors + np.rint(iceps).astype(np.int64), i32.min, i32.max)
    return starts, bases, np.asarray(slopes, np.float32)


def rmi_encode(doc_ids: np.ndarray, leaf_target: int = LEAF_TARGET) -> np.ndarray:
    starts, bases, slopes = fit_rmi(doc_ids, leaf_target)
    return emit_stream(doc_ids, starts, bases, slopes, eps=0)


def rmi_decode(words: np.ndarray, n: int) -> np.ndarray:
    return decode_stream(words, n)


def rmi_size_bits(doc_ids: np.ndarray, leaf_target: int = LEAF_TARGET) -> int:
    starts, bases, slopes = fit_rmi(doc_ids, leaf_target)
    n = len(doc_ids)
    pred = eval_segments(starts, bases, slopes, n)
    corr = np.asarray(doc_ids, np.int64) - pred
    width = int(int(corr.max() - corr.min()).bit_length()) if n else 0
    return _stream_size_bits(n, len(starts), width)
