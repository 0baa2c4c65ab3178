"""The collection every generator returns: doc-major term incidence."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Collection:
    """Terms of doc d are term_ids[doc_offsets[d]:doc_offsets[d+1]], ascending,
    with their term frequencies."""

    n_docs: int
    n_terms: int
    doc_offsets: np.ndarray  # (n_docs + 1,) int64
    term_ids: np.ndarray  # (n_postings,) int32
    term_freqs: np.ndarray  # (n_postings,) int32, >= 1

    @property
    def n_postings(self) -> int:
        return int(self.term_ids.shape[0])
