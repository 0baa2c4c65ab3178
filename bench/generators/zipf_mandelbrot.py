"""Generator ``zipf_mandelbrot``: the benchmark's own copy of the program's
collection generator.

The same Zipf-Mandelbrot unigram model as ``repro.data.corpus.synthesize_corpus``
(copied here so that a change to the
program cannot move the data it is measured on): each document's length is
log-normal around ``avg_doc_len``, its tokens are drawn i.i.d. from
p(rank r) ~ 1 / (r + b)^a (as multinomial counts in a random order), and repeated (doc, term) draws fold into one
posting whose term frequency is the repeat count.  Docids are i.i.d., so no
docid order is implied.
"""
from __future__ import annotations

import numpy as np

from collection import Collection


def zipf_mandelbrot_probs(n_terms: int, a: float, b: float) -> np.ndarray:
    ranks = np.arange(1, n_terms + 1, dtype=np.float64)
    w = 1.0 / np.power(ranks + b, a)
    return w / w.sum()


def synthesize(shape: dict, seed: int) -> Collection:
    """One collection from the configuration's ``collection`` block and a seed."""
    n_docs, n_terms = int(shape["n_docs"]), int(shape["n_terms"])
    rng = np.random.default_rng([seed, 0])
    probs = zipf_mandelbrot_probs(n_terms, shape["zipf_a"], shape["zipf_b"])
    sigma = float(shape["doc_len_sigma"])
    mu = np.log(shape["avg_doc_len"]) - 0.5 * sigma**2
    lengths = np.maximum(8, rng.lognormal(mu, sigma, size=n_docs).astype(np.int64))
    # i.i.d. draws, made as their multinomial counts in a uniformly random
    # order: the same distribution, without a search per draw
    counts = rng.multinomial(int(lengths.sum()), probs)
    draws = rng.permutation(np.repeat(np.arange(n_terms, dtype=np.int64), counts))
    # one sort orders every document's draws and brings repeats together
    key = np.repeat(np.arange(n_docs, dtype=np.int64) * n_terms, lengths)
    key += draws
    del draws
    key.sort()
    first = np.empty(len(key), bool)
    first[0] = True
    np.not_equal(key[1:], key[:-1], out=first[1:])
    starts = np.flatnonzero(first)
    tf = np.diff(np.append(starts, len(key))).astype(np.int32)
    key = key[starts]
    counts = np.bincount(key // n_terms, minlength=n_docs)
    offsets = np.zeros(n_docs + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    return Collection(n_docs, n_terms, offsets, (key % n_terms).astype(np.int32), tf)
