"""Plain reference: what every answer must be, from the collection alone.

Independent of the program (it imports nothing of it and reads nothing it
made): posting lists are inverted here from the seeded collection, and the
ranked tier's quantized BM25 is computed here from the configuration's
parameters.

* Boolean: the conjunction of the query's distinct terms, as ascending doc
  ids.
* Ranked: quantized-impact BM25.  impact(t, d) = idf(t) * tf * (k1 + 1) /
  (tf + k1 * (1 - b + b * dl(d) / avgdl)) in float64, with
  idf(t) = log1p((N - df + 0.5) / (df + 0.5)) and dl(d) the document's token
  count; quantized as clip(ceil(impact / max_impact * (2^bits - 1)), 1,
  2^bits - 1); a document's score is the integer sum over its matched terms;
  top-k by score descending, then doc id ascending; documents scoring 0 are
  not answers.
"""
from __future__ import annotations

import numpy as np

from collection import Collection


class Reference:
    """Answers for the queries whose terms are in ``needed`` (built once a
    window has closed)."""

    def __init__(self, col: Collection, needed: np.ndarray, scoring: dict | None):
        self.n_docs = col.n_docs
        needed = np.unique(needed[needed >= 0]).astype(np.int32)
        doc_of = np.repeat(np.arange(col.n_docs, dtype=np.int32), np.diff(col.doc_offsets))
        sel = np.isin(col.term_ids, needed)
        terms = col.term_ids[sel]
        order = np.argsort(terms, kind="stable")  # doc-major input: docs stay ascending
        self._docs = doc_of[sel][order]
        starts = np.searchsorted(terms[order], needed)
        ends = np.searchsorted(terms[order], needed, side="right")
        self._span = {int(t): (int(s), int(e)) for t, s, e in zip(needed, starts, ends)}
        self._quant = None
        if scoring is not None:
            self._quant = self._quantized(col, scoring, sel, doc_of)[order]

    @staticmethod
    def _quantized(col, scoring, sel, doc_of) -> np.ndarray:
        """Quantized impacts of the selected postings; the scale is the
        largest float impact over the whole collection."""
        k1, b, bits = float(scoring["k1"]), float(scoring["b"]), int(scoring["bits"])
        tf_all = col.term_freqs.astype(np.float64)
        dl = np.add.reduceat(col.term_freqs.astype(np.int64), col.doc_offsets[:-1])
        dl = dl.astype(np.float64)
        avgdl = float(dl.mean())
        df = np.bincount(col.term_ids, minlength=col.n_terms).astype(np.float64)
        idf = np.log1p((col.n_docs - df + 0.5) / (df + 0.5))

        def impact(term, tf, dls):
            norm = tf + k1 * (1.0 - b + b * dls / avgdl)
            return idf[term] * tf * (k1 + 1.0) / norm

        scale = float(impact(col.term_ids, tf_all, dl[doc_of]).max())
        top = (1 << bits) - 1
        imp = impact(col.term_ids[sel], tf_all[sel], dl[doc_of[sel]])
        return np.clip(np.ceil(imp / scale * top), 1, top).astype(np.int64)

    def _postings(self, t: int) -> slice:
        s, e = self._span[t]
        return slice(s, e)

    def boolean(self, row: np.ndarray) -> np.ndarray:
        terms = sorted({int(t) for t in row if t >= 0})
        if not terms:
            return np.zeros(0, np.int32)
        hits = np.zeros(self.n_docs, np.int8)
        for t in terms:
            hits[self._docs[self._postings(t)]] += 1
        return np.flatnonzero(hits == len(terms)).astype(np.int32)

    def topk(self, row: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
        score = np.zeros(self.n_docs, np.int64)
        for t in sorted({int(t) for t in row if t >= 0}):
            sl = self._postings(t)
            score[self._docs[sl]] += self._quant[sl]
        docs = np.flatnonzero(score)
        s = score[docs]
        if len(docs) > k:
            kth = np.partition(s, len(s) - k)[len(s) - k]
            keep = s >= kth
            docs, s = docs[keep], s[keep]
        order = np.lexsort((docs, -s))[:k]
        return docs[order].astype(np.int32), s[order]
