"""Every configuration's generator is found by name and gives the same
collection for the same seed, another for another seed."""
import json

import numpy as np
import pytest

import spec
from conftest import BENCH, TINY


@pytest.mark.parametrize("config", sorted(p.stem for p in (BENCH / "configs").glob("*.json")))
def test_same_seed_same_collection(config):
    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    conf["collection"] = dict(conf["collection"], n_docs=TINY["n_docs"],
                              n_terms=TINY["n_terms"])
    seed = 2**40 + 11
    a, b, c = (spec.collection(conf, s) for s in (seed, seed, seed + 1))
    for f in ("doc_offsets", "term_ids", "term_freqs"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.term_ids, c.term_ids[: len(a.term_ids)])
    assert a.n_docs == TINY["n_docs"] and a.term_freqs.min() >= 1
    # each document's terms ascend
    d = np.repeat(np.arange(a.n_docs), np.diff(a.doc_offsets))
    assert np.all((np.diff(a.term_ids) > 0) | (np.diff(d) > 0))
