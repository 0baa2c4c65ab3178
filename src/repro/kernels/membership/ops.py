"""Public wrapper: pads ragged (Q, D) to kernel tiles, gathers embeddings.

`score_terms_bitmask` is the drop-in accelerated path for Algorithm 1/3
document scans: term ids + doc-embedding table -> packed hit bitmask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.membership import term_rows
from repro.kernels.membership.kernel import D_BLK, LANE, Q_BLK, membership_bitmask


def _pad_to(x: jax.Array, m: int, axis: int, value=0) -> jax.Array:
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def score_terms_bitmask(
    params,
    terms: jax.Array,  # (Q,) int32 term ids
    tau: jax.Array,  # (n_terms,) thresholds
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """(Q,) term ids -> (Q, ceil(D/32)) packed membership bitmask."""
    te = term_rows(params, terms)
    de = params["doc_embed"]["table"]
    tq = jnp.take(tau, terms)
    n_docs = de.shape[0]
    teq = _pad_to(te, Q_BLK, 0)
    # padded tau rows = +inf so padding never fires
    tqq = _pad_to(tq, Q_BLK, 0, value=jnp.inf)
    dep = _pad_to(de, D_BLK, 0)
    mask = membership_bitmask(teq, dep, tqq, params["bias"], interpret=interpret)
    out_words = -(-n_docs // LANE)
    mask = mask[: terms.shape[0], :out_words]
    # zero the tail bits of the final word (padded docs)
    tail = n_docs % LANE
    if tail:
        last = jnp.uint32((1 << tail) - 1)
        word_mask = jnp.where(
            jnp.arange(out_words) == out_words - 1, last, jnp.uint32(0xFFFFFFFF)
        )
        mask = mask & word_mask[None, :]
    return mask
