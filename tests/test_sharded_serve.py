"""Doc-partitioned serving + persistent store tests.

Covers the acceptance edges of the planner/executor refactor: shard-range
geometry (boundary docs, empty shards, 32-word alignment), K=1 exact
agreement with brute force and bit-identity across K, store round-trip
bit-exactness per codec, planner liveness/skip logic, empty query batches,
and per-shard stats aggregation.
"""
import jax
import numpy as np
import pytest

from repro.common.config import CorpusConfig, LearnedIndexConfig
from repro.core import fit_thresholds, init_membership
from repro.data.corpus import synthesize_corpus
from repro.data.queries import brute_force_answers, sample_queries, zipf_conjunctions
from repro.index.build import InvertedIndex, build_inverted_index, slice_index
from repro.index.store import load_index, load_sharded, save_index, save_sharded
from repro.postings import HybridPostings
from repro.serve import BooleanEngine, ServeConfig, plan_batch, shard_ranges
from repro.serve.shard import pack_ids, unpack_row


# ------------------------------------------------------------------ fixtures
@pytest.fixture(scope="module")
def system():
    corpus = synthesize_corpus(CorpusConfig(n_docs=400, n_terms=1600, avg_doc_len=50, seed=31))
    inv = build_inverted_index(corpus)
    li_cfg = LearnedIndexConfig(embed_dim=16, truncation_k=16, block_size=64)
    params, _ = init_membership(jax.random.key(2), li_cfg, corpus.n_terms, corpus.n_docs)
    lb = fit_thresholds(params, inv)  # untrained: zero FN still guaranteed
    return corpus, inv, li_cfg, lb


def _mixed_store(universe=6000):
    """HybridPostings whose terms exercise several codecs."""
    rng = np.random.default_rng(7)
    lists = [
        np.arange(100, 1700, 4, dtype=np.int32),  # arithmetic run: learned wins
        (np.arange(300) * 17 + rng.integers(0, 4, 300)).astype(np.int32),  # smooth
        np.sort(rng.choice(universe, 60, replace=False)).astype(np.int32),  # rough
        np.sort(rng.choice(universe, 5000, replace=False)).astype(np.int32),  # dense
        np.array([5, 900], np.int32),  # tiny
        np.zeros(0, np.int32),  # empty term
    ]
    lists = [np.unique(x) for x in lists]
    offsets = np.zeros(len(lists) + 1, np.int64)
    np.cumsum([len(x) for x in lists], out=offsets[1:])
    doc_ids = np.concatenate(lists).astype(np.int32)
    inv = InvertedIndex(universe, len(lists), offsets, doc_ids)
    return inv, HybridPostings.build(offsets, doc_ids, universe)


# ------------------------------------------------------------------ geometry
def test_shard_ranges_cover_and_align():
    for n_docs, k in [(400, 1), (400, 4), (4096, 8), (1000, 3), (31, 2)]:
        r = shard_ranges(n_docs, k)
        assert len(r) == k
        assert r[0][0] == 0 and r[-1][1] == n_docs
        for (a, b), (c, d) in zip(r, r[1:]):
            assert b == c and a <= b  # contiguous, monotone
        for lo, hi in r[:-1]:
            assert hi % 32 == 0  # interior boundaries word-aligned


def test_shard_ranges_small_collection_empty_shards():
    r = shard_ranges(40, 8)
    assert sum(hi - lo for lo, hi in r) == 40
    assert any(lo == hi for lo, hi in r)  # tiny collection: some shards empty


def test_slice_index_boundaries():
    inv, _ = _mixed_store()
    lo, hi = 96, 1696
    sl = slice_index(inv, lo, hi)
    assert sl.n_docs == hi - lo
    for t in range(inv.n_terms):
        p = inv.postings(t)
        expect = p[(p >= lo) & (p < hi)] - lo
        assert np.array_equal(sl.postings(t), expect)
    # identity slice preserves everything
    ident = slice_index(inv, 0, inv.n_docs)
    assert np.array_equal(ident.doc_ids, inv.doc_ids)
    assert np.array_equal(ident.term_offsets, inv.term_offsets)


def test_pack_unpack_round_trip_boundary_bits():
    n = 100
    ids = np.array([0, 31, 32, 63, 64, 99], np.int32)  # word-boundary docs
    assert np.array_equal(unpack_row(pack_ids(ids, n), n), ids)
    assert np.array_equal(unpack_row(pack_ids(np.zeros(0, np.int32), n), n),
                          np.zeros(0, np.int32))


# ------------------------------------------------------------------- serving
def test_k1_exact_and_all_k_bit_identical(system):
    corpus, inv, li_cfg, lb = system
    q = np.vstack([sample_queries(corpus, 12, seed=8),
                   zipf_conjunctions(inv.dfs, 8, seed=3)[:, :5]])
    exact = brute_force_answers(corpus, q)
    ref = None
    for k in (1, 2, 4, 8):
        eng = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=k))
        res = eng.query_batch(q)
        if k == 1:
            ref = res
            for r, e in zip(res, exact):
                assert np.array_equal(r, e)  # K=1 ≡ unsharded engine ≡ exact
        else:
            for r, e in zip(res, ref):
                assert np.array_equal(r, e)  # sharded results bit-identical
        bm = eng.query_batch_bitmap(q)
        for i in range(len(q)):
            assert np.array_equal(unpack_row(bm[i], eng.n_docs), res[i])


def test_boundary_docs_served_exactly(system):
    """Docs sitting exactly on shard boundaries survive the bitmap merge."""
    corpus, inv, li_cfg, lb = system
    eng = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=4))
    boundary_docs = {lo for lo, hi in eng._ranges} | {hi - 1 for lo, hi in eng._ranges if hi > lo}
    # single-term queries whose postings include boundary docs
    hits = []
    for t in range(inv.n_terms):
        if set(inv.postings(t).tolist()) & boundary_docs:
            hits.append(t)
        if len(hits) >= 8:
            break
    assert hits, "no term touches a shard boundary doc"
    q = np.full((len(hits), 1), -1, np.int32)
    q[:, 0] = hits
    res = eng.query_batch(q)
    for t, r in zip(hits, res):
        assert np.array_equal(r, inv.postings(t))  # boundary docs included


def test_raw_store_sharded_agrees(system):
    corpus, inv, li_cfg, lb = system
    q = sample_queries(corpus, 10, seed=5)
    raw = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=3, postings_store="raw"))
    hyb = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=3))
    for a, b in zip(raw.query_batch(q), hyb.query_batch(q)):
        assert np.array_equal(a, b)


def test_empty_query_batches(system):
    corpus, inv, li_cfg, lb = system
    eng = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=2))
    assert eng.query_batch(np.zeros((0, 5), np.int32)) == []
    assert eng.query_batch_bitmap(np.zeros((0, 5), np.int32)).shape[0] == 0
    allpad = np.full((3, 5), -1, np.int32)
    res = eng.query_batch(allpad)
    assert all(len(r) == 0 for r in res)
    assert not eng.query_batch_bitmap(allpad).any()
    s = eng.serving_stats()["summary"]
    assert s["probe_bytes"] == 0 and s["cache_misses"] == 0  # probe path untouched


def test_mixed_padding_and_dead_terms(system):
    """All-pad rows and zero-df terms inside a live batch stay empty."""
    corpus, inv, li_cfg, lb = system
    dead = int(np.nonzero(inv.dfs == 0)[0][0]) if (inv.dfs == 0).any() else None
    live = int(np.argmax(inv.dfs))
    rows = [[live, -1], [-1, -1]]
    if dead is not None:
        rows.append([live, dead])
    q = np.asarray(rows, np.int32)
    res = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=2)).query_batch(q)
    assert np.array_equal(res[0], inv.postings(live))
    assert len(res[1]) == 0
    if dead is not None:
        assert len(res[2]) == 0


def test_serving_stats_aggregation(system):
    corpus, inv, li_cfg, lb = system
    eng = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=4))
    eng.query_batch(zipf_conjunctions(inv.dfs, 8, seed=11))
    stats = eng.serving_stats()
    assert len(stats["shards"]) == len(eng.shards)
    for key in ("hits", "misses", "evictions"):
        assert stats["decode_cache"][key] == sum(
            s["decode_cache"][key] for s in stats["shards"]
        )
    if "guided" in stats:
        assert stats["guided"]["probes"] == sum(
            s["guided"]["probes"] for s in stats["shards"] if "guided" in s
        )
    summary = stats["summary"]
    assert summary["cache_hits"] == stats["decode_cache"]["hits"]
    assert summary["n_shards"] == len(eng.shards)
    assert summary["probe_bytes"] >= 0


def test_shards_share_the_packed_term_table(system):
    """The global bloom and all four shard slices hold one packed term table,
    and each shard reports it through its membership gauge."""
    corpus, inv, li_cfg, lb = system
    table = lb.params["term_embed"]["table"]
    assert table.shape == (1600 // 8, 128)
    eng = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=4))
    assert len(eng.shards) == 4
    for sh in eng.shards:
        assert sh.lb.params["term_embed"]["table"] is table
        assert sh.state.params["term_embed"]["table"] is table
    eng.metrics.reset()  # a gauge of the layout, not of a window: kept
    stats = eng.metrics.snapshot()
    assert [s["membership"]["term_rows_per_lane_row"] for s in stats["shards"]] == [8] * 4


def test_planner_skips_shards_missing_terms(system):
    """A shard where some query term has zero local df must not run it."""
    corpus, inv, li_cfg, lb = system
    eng = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=4))
    shards = eng.shards
    # find a term present on shard 0 but absent on some other shard
    target = None
    for t in np.argsort(-inv.dfs)[:400]:
        t = int(t)
        present = [int(sh.local_dfs[t]) > 0 for sh in shards]
        if present[0] and not all(present):
            target = t
            break
    if target is None:
        pytest.skip("synthetic corpus too dense: every term on every shard")
    q = np.array([[target]], np.int32)
    plan = plan_batch(eng._padded(q), inv.dfs, shards)
    for sh, sp in zip(shards, plan.shard_plans):
        assert sp.run[0] == (int(sh.local_dfs[target]) > 0)
    res = eng.query_batch(q)
    assert np.array_equal(res[0], inv.postings(target))


def test_planner_orders_terms_by_global_df(system):
    corpus, inv, li_cfg, lb = system
    eng = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=2))
    q = zipf_conjunctions(inv.dfs, 4, seed=13)
    plan = plan_batch(eng._padded(q), inv.dfs, eng.shards)
    for qp in plan.qplans:
        dfs = [int(inv.dfs[t]) for t in qp.terms]
        assert dfs == sorted(dfs)


# -------------------------------------------------------------------- store
def test_store_round_trip_bit_exact_per_codec(tmp_path):
    inv, store = _mixed_store()
    assert len(store.codec_histogram()) >= 2  # several codecs exercised
    save_index(str(tmp_path / "idx"), inv, store)
    inv2, store2 = load_index(str(tmp_path / "idx"), verify=True)
    assert inv2.n_docs == inv.n_docs and inv2.n_terms == inv.n_terms
    assert np.array_equal(np.asarray(inv2.doc_ids), inv.doc_ids)
    assert np.array_equal(np.asarray(store2.tags), store.tags)
    assert np.array_equal(np.asarray(store2.bits), store.bits)
    for t in range(inv.n_terms):
        assert np.array_equal(np.asarray(store2.streams[t]), store.streams[t])
        assert np.array_equal(store2.postings(t), store.postings(t))  # bit-exact decode
    assert store2.size_bits() == store.size_bits()


def test_store_version_and_corruption_guards(tmp_path):
    import json

    inv, store = _mixed_store()
    p = tmp_path / "idx"
    save_index(str(p), inv, store)
    meta = json.loads((p / "meta.json").read_text())
    meta["version"] = 999
    (p / "meta.json").write_text(json.dumps(meta))
    with pytest.raises(ValueError, match="version"):
        load_index(str(p))
    with pytest.raises(FileNotFoundError):
        load_index(str(tmp_path / "nope"))


def test_sharded_store_round_trip_with_empty_shard(tmp_path):
    inv, store = _mixed_store()
    ranges = [(0, 2016), (2016, 2016), (2016, 6000)]  # middle shard empty
    entries = []
    for lo, hi in ranges:
        sl = slice_index(inv, lo, hi)
        entries.append(((lo, hi), sl, HybridPostings.from_index(sl)))
    save_sharded(str(tmp_path / "sh"), inv.n_docs, entries)
    n_docs, loaded = load_sharded(str(tmp_path / "sh"))
    assert n_docs == inv.n_docs
    assert loaded[1][1] is None and loaded[1][2] is None  # empty shard
    for ((lo, hi), linv, lstore), (_, orig_inv, orig_store) in zip(loaded, entries):
        if linv is None:
            continue
        for t in range(orig_inv.n_terms):
            assert np.array_equal(np.asarray(linv.postings(t)), orig_inv.postings(t))
            assert np.array_equal(lstore.postings(t), orig_store.postings(t))


def test_engine_save_reload_identical_results(system, tmp_path):
    corpus, inv, li_cfg, lb = system
    cfg = ServeConfig(n_shards=4)
    eng = BooleanEngine(lb, inv, li_cfg, cfg)
    q = sample_queries(corpus, 12, seed=21)
    ref = eng.query_batch(q)
    eng.save(str(tmp_path / "idx"))
    loaded = BooleanEngine.from_store(lb, li_cfg, cfg, str(tmp_path / "idx"))
    for a, b in zip(loaded.query_batch(q), ref):
        assert np.array_equal(a, b)
    # reloaded stores must be byte-identical to the built ones, per shard
    for sh_new, sh_old in zip(loaded.shards, eng.shards):
        assert np.array_equal(np.asarray(sh_new.tier2.tags), np.asarray(sh_old.tier2.tags))
        assert sh_new.tier2.size_bits() == sh_old.tier2.size_bits()
