"""Learned Bloom filter construction (Kraska et al. §5) over (term, doc) pairs.

The paper leans on Kraska's observation that a learned structure can "fallback
on traditional structures for sub-cases where a learned model performs poorly",
restoring exact guarantees. We implement that construction:

  1. fit a per-term threshold τ_t = min logit over indexed positives of t
     (so the model alone has ZERO false negatives on the collection);
  2. positives whose margin is degenerate (τ_t would admit too many false
     positives) spill into an exact backup set (sorted (t,d) key array —
     the traditional structure);
  3. query: f_hat(t,d) = logit(t,d) ≥ τ_t  OR  (t,d) ∈ backup.

τ carries a small numerical margin (NUMERIC_MARGIN): XLA fusion reorders
float reductions, so the same logit can differ by a few ulp between the
fitting pass and a later jitted query program. The margin makes the zero-FN
guarantee robust to that drift at negligible false-positive cost.

No false negatives ⇒ Boolean results are supersets; `verified` mode
re-checks survivors against tier-2 for exactness (see algorithms.py).

A LearnedBloom is the served model: it holds its term table in the packed
serving layout (``membership.pack_term_table``), packed once when it is
made; packing a packed table is a no-op, so a bloom rebuilt from another's
params, or sliced from it, keeps the one packed array.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import membership
from repro.index.build import InvertedIndex

# absolute + relative slack applied below the fitted min-positive logit
NUMERIC_MARGIN = 1e-5


@dataclass
class LearnedBloom:
    params: Any
    tau: np.ndarray  # (n_terms,) float32 per-term zero-FN threshold
    backup_keys: np.ndarray  # sorted int64 keys t*n_docs+d spilled to exact storage
    n_docs: int

    def __post_init__(self):
        self.params = membership.pack_term_table(self.params)

    def size_bits(self, embed_bits: int = 32) -> int:
        te = self.params["term_embed"]["table"]
        de = self.params["doc_embed"]["table"]
        return int(
            (te.size + de.size) * embed_bits
            + self.tau.size * 32
            + self.backup_keys.size * 64
        )


PAIR_CHUNK = 1 << 16  # (term, doc) pairs per logit dispatch: one jit shape
_pair_logits = jax.jit(membership.pair_logits)


def _positive_logits(params: Any, inv: InvertedIndex, terms: np.ndarray) -> np.ndarray:
    """f-logits of every indexed (t, d) pair of ``terms``, in posting order.

    Pairs go to the device in fixed PAIR_CHUNK slices (the tail zero-padded),
    so scoring a whole collection compiles one program, whatever its list
    lengths."""
    dfs = inv.dfs[terms]
    term_of = np.repeat(np.asarray(terms, np.int32), dfs)
    starts = inv.term_offsets[terms]
    first = np.repeat(np.cumsum(dfs) - dfs, dfs)
    docs = inv.doc_ids[np.repeat(starts, dfs) + np.arange(len(term_of)) - first]
    out = np.empty(len(term_of), np.float32)
    t_buf = np.zeros(PAIR_CHUNK, np.int32)
    d_buf = np.zeros(PAIR_CHUNK, np.int32)
    for i in range(0, len(term_of), PAIR_CHUNK):
        m = min(PAIR_CHUNK, len(term_of) - i)
        t_buf[:m], t_buf[m:] = term_of[i : i + m], 0
        d_buf[:m], d_buf[m:] = docs[i : i + m], 0
        out[i : i + m] = np.asarray(_pair_logits(params, t_buf, d_buf))[:m]
    return out


def fit_thresholds(
    params: Any,
    inv: InvertedIndex,
    *,
    terms: np.ndarray | None = None,
    backup_quantile: float = 0.0,
) -> LearnedBloom:
    """Scan indexed positives per term; τ_t = quantile of positive logits.

    backup_quantile=0 → τ is the exact min (no backup needed). Larger values
    trade backup storage for higher τ (fewer false positives): positives below
    τ_t spill to the exact backup set.
    """
    n_terms, n_docs = inv.n_terms, inv.n_docs
    all_terms = np.arange(n_terms) if terms is None else np.asarray(terms)
    # terms without postings keep τ = inf: never fires, exhaustive scans
    # treat them as no match
    tau = np.full(n_terms, np.inf, dtype=np.float32)
    backup: list[np.ndarray] = []

    live = all_terms[inv.dfs[all_terms] > 0]
    logits = _positive_logits(params, inv, live)
    dfs = inv.dfs[live]
    bounds = np.concatenate([[0], np.cumsum(dfs)])
    if backup_quantile > 0.0:
        for j, t in enumerate(live):
            lg = logits[bounds[j] : bounds[j + 1]]
            if len(lg) <= 8:
                tau[t] = lg.min()
                continue
            q = float(np.quantile(lg, backup_quantile))
            spill = inv.postings(int(t))[lg < q]
            if len(spill):
                backup.append(t * np.int64(n_docs) + spill.astype(np.int64))
            tau[t] = q
    elif len(live):
        tau[live] = np.minimum.reduceat(logits, bounds[:-1])
    finite = np.isfinite(tau)
    tau[finite] -= NUMERIC_MARGIN * (1.0 + np.abs(tau[finite]))
    keys = np.sort(np.concatenate(backup)) if backup else np.zeros(0, np.int64)
    return LearnedBloom(params=params, tau=tau, backup_keys=keys, n_docs=n_docs)


def bloom_predict(
    lb: LearnedBloom, terms: jax.Array, docs: jax.Array
) -> jax.Array:
    """Vectorized f_hat with guarantee: logit ≥ τ_t OR exact-backup hit."""
    logits = membership.pair_logits(lb.params, terms, docs)
    tau = jnp.take(jnp.asarray(lb.tau), terms)
    hit = logits >= tau
    if len(lb.backup_keys):
        keys = terms.astype(jnp.int64) * lb.n_docs + docs.astype(jnp.int64)
        bk = jnp.asarray(lb.backup_keys)
        idx = jnp.clip(jnp.searchsorted(bk, keys), 0, len(lb.backup_keys) - 1)
        hit = hit | (jnp.take(bk, idx) == keys)
    return hit


def false_negative_rate(lb: LearnedBloom, inv: InvertedIndex, sample: int = 20000, seed: int = 0) -> float:
    """Must be exactly 0.0 on indexed pairs — property-tested."""
    rng = np.random.default_rng(seed)
    term_of = np.repeat(np.arange(inv.n_terms, dtype=np.int64), inv.dfs)
    idx = rng.integers(0, inv.n_postings, size=min(sample, inv.n_postings))
    t, d = term_of[idx].astype(np.int32), inv.doc_ids[idx]
    pred = np.asarray(bloom_predict(lb, jnp.asarray(t), jnp.asarray(d)))
    return float(1.0 - pred.mean())


def false_positive_rate(lb: LearnedBloom, inv: InvertedIndex, sample: int = 20000, seed: int = 0) -> float:
    rng = np.random.default_rng(seed)
    t = rng.integers(0, inv.n_terms, size=sample).astype(np.int32)
    d = rng.integers(0, inv.n_docs, size=sample).astype(np.int32)
    pred = np.asarray(bloom_predict(lb, jnp.asarray(t), jnp.asarray(d)))
    # remove true positives from the sample
    truth = np.zeros(sample, dtype=bool)
    for i in range(sample):
        p = inv.postings(int(t[i]))
        j = np.searchsorted(p, d[i])
        truth[i] = j < len(p) and p[j] == d[i]
    neg = ~truth
    return float(pred[neg].mean()) if neg.any() else 0.0
