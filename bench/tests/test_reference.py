"""The plain reference against a direct evaluation on a small collection."""
import numpy as np

from conftest import zipf
import reference

SHAPE = {"n_docs": 300, "n_terms": 400, "avg_doc_len": 40, "zipf_a": 1.2,
         "zipf_b": 2.7, "doc_len_sigma": 0.6}
SCORING = {"k1": 0.9, "b": 0.4, "bits": 8}


def _docs_of(col):
    return [set(col.term_ids[col.doc_offsets[d]:col.doc_offsets[d + 1]].tolist())
            for d in range(col.n_docs)]


def test_boolean_is_the_conjunction():
    col = zipf(SHAPE, 4)
    q = np.array([[0, 1, -1], [3, -1, -1], [0, 2, 5]], np.int32)
    ref = reference.Reference(col, q, None)
    docs = _docs_of(col)
    for row in q:
        terms = {int(t) for t in row if t >= 0}
        want = [d for d in range(col.n_docs) if terms <= docs[d]]
        assert ref.boolean(row).tolist() == want


def test_topk_orders_by_score_then_id():
    col = zipf(SHAPE, 6)
    q = np.array([[0, 1, 7, -1]], np.int32)
    ref = reference.Reference(col, q, SCORING)
    ids, scores = ref.topk(q[0], 10)
    assert len(ids) == 10 and np.all(np.diff(scores) <= 0)
    for a in range(len(ids) - 1):
        if scores[a] == scores[a + 1]:
            assert ids[a] < ids[a + 1]
    assert scores.min() >= 1 and scores.max() <= 3 * 255


def test_collection_is_deduplicated_with_term_frequencies():
    col = zipf(SHAPE, 8)
    for d in range(0, col.n_docs, 37):
        t = col.term_ids[col.doc_offsets[d]:col.doc_offsets[d + 1]]
        assert np.all(np.diff(t) > 0)
    assert col.term_freqs.min() >= 1 and col.term_freqs.sum() > col.n_postings
