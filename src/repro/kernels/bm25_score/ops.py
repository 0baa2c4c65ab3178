"""Host bridge: (candidate, term) impact windows -> bm25_score kernel.

Pads the term axis to 128 lanes (zero impacts are additive identities) and
the candidate axis to the kernel block, rounding the candidate count up to
power-of-two-ish buckets so jax.jit compiles a handful of shapes instead of
one per candidate-set size (same discipline as guided_search/ops.py).
"""
from __future__ import annotations

import numpy as np

from repro.kernels.bm25_score.kernel import score_batch
from repro.obs import trace

_LANES = 128


def _bucket(n: int, quantum: int) -> int:
    b = quantum
    while b < n:
        b *= 2
    return b


def score_candidates(
    impacts: np.ndarray, scale: float, *, interpret: bool | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Score a (P, T) quantized-impact window on the Pallas kernel.

    -> (int32 scores (P,), float32 scores (P,)); bit-exact against
    ref.score_ref — integer reduction + one f32 multiply both ways.
    """
    import jax.numpy as jnp

    imp = np.asarray(impacts, np.int32)
    P, T = imp.shape
    if P == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.float32)
    Tb = _bucket(T, _LANES)
    Pb = _bucket(P, 8)
    padded = np.zeros((Pb, Tb), np.int32)
    padded[:P, :T] = imp
    with trace.span("kernel.bm25_score", candidates=int(Pb), terms=int(Tb)):
        ints, floats = score_batch(
            jnp.asarray(padded),
            jnp.asarray(np.float32(scale).reshape(1, 1)),
            interpret=interpret,
        )
    return (
        np.asarray(ints).reshape(-1)[:P],
        np.asarray(floats).reshape(-1)[:P],
    )
