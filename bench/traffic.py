"""One general generator for every traffic mix: a data file of parameters in,
requests with due times out.

A mix gives the share of its queries of each length (``length_share``, from
a published query log) and how each query's terms are picked by df.  The
term samplers are the benchmark's copies of the program's
``repro.data.queries.sample_queries`` (df-biased) and ``_zipf_term_queries``
(Zipf over df rank).  Arrivals are an open loop: Poisson at the cell's fixed
rate.

Every seed gets the same set of requests in another order, so that runs
with different seeds do the same work: the query lengths, the uniforms that
pick each query's terms by df rank and the inter-arrival gaps (the
exponential's quantiles) come from a fixed stream; the seed's own
collection maps the ranks to its term ids, and the seed shuffles the order
of the queries and of the gaps.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


WINDOW_STREAM = 1  # the measured window's requests
WARM_STREAM = 2  # warm-up requests: the same mix, other queries


@dataclass
class Schedule:
    """Requests of one window: ``terms[i]`` (-1 padded) is due at ``due_s[i]``
    seconds after the window opens."""

    mode: str  # "boolean" | "ranked"
    k: int
    terms: np.ndarray  # (n, max_terms) int32
    due_s: np.ndarray  # (n,) float64, ascending, in [0, seconds)

    def __len__(self) -> int:
        return len(self.due_s)


def _lengths(n: int, share: dict) -> np.ndarray:
    """n query lengths in the mix's shares (``{"<terms>": share}``), by
    largest remainder: the same counts for every seed."""
    lengths = np.array([int(k) for k in share])
    w = np.array([float(v) for v in share.values()])
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    counts[np.argsort(counts - exact, kind="stable")[: n - counts.sum()]] += 1
    return np.repeat(lengths, counts)


def max_terms(mix: dict) -> int:
    """The longest query of the mix."""
    return max(int(k) for k in mix["terms"]["length_share"])


def _vocab(dfs: np.ndarray) -> np.ndarray:
    """Terms that occur, most frequent first."""
    by_df = np.argsort(-dfs, kind="stable")
    return by_df[dfs[by_df] > 0]


def df_biased(rng, dfs: np.ndarray, lengths: np.ndarray, temperature: float) -> list:
    """Terms drawn with probability ~ df^temperature (repeats allowed)."""
    vocab = _vocab(dfs)
    w = np.power(dfs[vocab].astype(np.float64), temperature)
    flat = vocab[rng.choice(len(vocab), size=int(lengths.sum()), p=w / w.sum())]
    cuts = np.cumsum(lengths)[:-1]
    return [row.astype(np.int32) for row in np.split(flat, cuts)]


def zipf_df_rank(rng, dfs: np.ndarray, lengths: np.ndarray, zipf_a: float) -> list:
    """Distinct terms per query; rank r of the df-descending vocabulary with
    probability ~ r^-zipf_a."""
    vocab = _vocab(dfs)
    p = np.arange(1, len(vocab) + 1, dtype=np.float64) ** -zipf_a
    p /= p.sum()
    return [vocab[rng.choice(len(vocab), size=int(n), replace=False, p=p)]
            .astype(np.int32) for n in lengths]


SAMPLERS = {
    "df_biased": lambda rng, dfs, lengths, t: df_biased(rng, dfs, lengths, t["df_temperature"]),
    "zipf_df_rank": lambda rng, dfs, lengths, t: zipf_df_rank(rng, dfs, lengths, t["zipf_a"]),
}


def queries(mix: dict, dfs: np.ndarray, n: int, stream: int) -> np.ndarray:
    """(n, max_terms) int32 query rows, -1 padded, drawn from a fixed stream
    over the collection's df ranking."""
    t = mix["terms"]
    lengths = _lengths(n, t["length_share"])
    rows = SAMPLERS[t["sampler"]](np.random.default_rng([stream, n]), dfs, lengths, t)
    out = np.full((n, max_terms(mix)), -1, np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


def poisson_due(rng, n: int, rate: float) -> np.ndarray:
    """n Poisson arrivals at ``rate``/s, first at 0: the gaps are the n
    midpoint quantiles of Exp(rate), shuffled."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])


def schedule(mix: dict, rate: float, seconds: float, dfs: np.ndarray, seed: int,
             stream: int = WINDOW_STREAM) -> Schedule:
    """The requests due in a window of ``seconds`` at ``rate`` requests/s."""
    if mix["arrivals"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']['process']!r}")
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([seed, stream])
    return Schedule(
        mode=mix["mode"],
        k=int(mix.get("k") or 0),
        terms=rng.permutation(queries(mix, dfs, n, stream)),
        due_s=poisson_due(rng, n, rate),
    )
