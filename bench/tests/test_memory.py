import numpy as np
import pytest

import memory


class _Store:
    def __init__(self, flat):
        self.flat = flat
        self.views = [flat[:10], flat[10:]]  # views of one buffer: counted once
        self.meta = {"lens": np.zeros(5, np.int64)}


def test_walk_counts_each_buffer_once():
    flat = np.zeros(100, np.uint32)
    seen: dict = {}
    s = _Store(flat)
    assert memory.walk_bytes(s, seen) == 400 + 40
    assert memory.walk_bytes([s, flat], seen) == 0  # already seen


def test_backup_keys_count_once_with_the_model():
    params = {"term_embed": {"table": np.zeros((3, 4), np.float32)},
              "doc_embed": {"table": np.zeros((5, 4), np.float32)}}
    tau = np.zeros(3, np.float32)
    keys = np.arange(7, dtype=np.int64)
    seen: dict = {}
    got = memory.walk_bytes([params, tau, keys], seen)
    assert got == (12 + 20) * 4 + 12 + 7 * 8


def test_walk_follows_slots_and_leaves_out_decode_caches():
    class Slotted:
        __slots__ = ("a", "_decode_cache")

        def __init__(self):
            self.a = np.zeros(4, np.uint8)
            self._decode_cache = {1: np.zeros(1000, np.uint8)}

    assert memory.walk_bytes([Slotted()], {}) == 4


@pytest.fixture(scope="module")
def engine():
    import copy
    import json

    import system
    from conftest import BENCH, TINY, zipf

    conf = copy.deepcopy(json.loads((BENCH / "configs" / "robust04-1of4.json").read_text()))
    conf["collection"] = dict(TINY)
    conf["serve"]["n_shards"] = 2
    conf["train"]["steps"] = 5
    col = zipf(conf["collection"], 3)
    eng, _, _ = system.build(conf, col, log=lambda *_: None)
    return eng, col


def test_every_resident_copy_of_the_postings_counts(engine):
    eng, col = engine
    parts = memory.index_bytes(eng)
    # the global inverted index and each shard's slice: ids and tfs, 32 bits each
    assert parts["uncompressed"] >= 2 * 8 * col.n_postings
    assert all(parts[k] > 0 for k in ("tier2", "candidate_tables", "model", "other"))


def test_a_copy_under_a_new_name_counts_and_a_decoded_list_does_not(engine):
    eng, _ = engine
    before = sum(memory.index_bytes(eng).values())
    sh = eng.shards[0]
    decoded = np.arange(5000, dtype=np.int64)
    sh._decode_cache.put(0, decoded, decoded.nbytes)
    assert sum(memory.index_bytes(eng).values()) == before
    sh.unnamed_copy = {"ids": np.array(eng.inv.doc_ids)}  # a decompressed copy
    try:
        after = memory.index_bytes(eng)
    finally:
        del sh.unnamed_copy
    assert sum(after.values()) == before + eng.inv.doc_ids.nbytes
