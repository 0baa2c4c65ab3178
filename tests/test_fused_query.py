"""Fused ranked-query kernel: one dispatch from candidates to top-k.

The load-bearing property is the same bit-exactness bar as the multi-phase
ranked path: `ServeConfig.fused_kernel` must reproduce the multi-phase
engine AND the brute-force quantized-BM25 oracle — ids and integer scores,
ties broken by ascending doc id — across shard counts, codec tiers
(learned plm/rmi windows and classical host-resolved lanes in one tile),
k ∈ {1, 10, > candidates}, required-term mixes, and all-pad batches.  The
interpret-mode Pallas kernel is additionally pinned bit-identical to its
numpy reference (`fused_topk_ref`).
"""
import warnings

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.common.config import CorpusConfig, LearnedIndexConfig
from repro.core import fit_thresholds, init_membership
from repro.data.corpus import synthesize_corpus
from repro.data.queries import zipf_disjunctions
from repro.index.build import build_inverted_index
from repro.rank.score import BM25Params, ImpactModel, brute_force_topk
from repro.serve import BooleanEngine, ServeConfig

K = 10
N_TERMS = 3000


# the hypothesis-shim wrapper hides fixture params from pytest, so the
# @given property tests reach the shared system through this module cache;
# the fixtures below delegate to it (everything is built exactly once)
_SHARED: dict = {}


def _shared_system():
    if "system" not in _SHARED:
        corpus = synthesize_corpus(
            CorpusConfig(n_docs=800, n_terms=N_TERMS, avg_doc_len=50, seed=11)
        )
        inv = build_inverted_index(corpus)
        li = LearnedIndexConfig(embed_dim=16, truncation_k=16, block_size=128)
        params, _ = init_membership(
            jax.random.key(0), li, corpus.n_terms, corpus.n_docs
        )
        lb = fit_thresholds(params, inv)
        im = ImpactModel.build(inv, BM25Params())
        _SHARED["system"] = (corpus, inv, li, lb, im)
    return _SHARED["system"]


def _shared_engines():
    if "engines" not in _SHARED:
        _SHARED["engines"] = {
            (fused, ns): _engine(_shared_system(), fused=fused, n_shards=ns)
            for fused in (False, True)
            for ns in (1, 3)
        }
    return _SHARED["engines"]


@pytest.fixture(scope="module")
def system():
    return _shared_system()


def _engine(system, *, fused, n_shards=1, cutoff=0):
    # cutoff=0 disables the exhaustive shortcut so the peel/kernel path is
    # exercised even on this small corpus
    _, inv, li, lb, _ = system
    cfg = ServeConfig(
        n_shards=n_shards,
        ranked=dict(fused_kernel=fused, topk_exhaustive_cutoff=cutoff),
    )
    return BooleanEngine(lb, inv, li, cfg)


@pytest.fixture(scope="module")
def engines(system):
    return _shared_engines()


def _check(a, b, ctx=""):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.ids, y.ids), ctx
        assert np.array_equal(x.scores, y.scores), ctx


# ------------------------------------------------------------ bit-exactness
@pytest.mark.parametrize("n_shards", [1, 3])
@pytest.mark.parametrize("k", [1, K])
def test_fused_matches_multiphase_and_oracle(system, engines, n_shards, k):
    _, inv, _, _, im = system
    q, _ = zipf_disjunctions(inv.dfs, 24, seed=5)
    fused = engines[(True, n_shards)].query_topk(q, k)
    multi = engines[(False, n_shards)].query_topk(q, k)
    oracle = brute_force_topk(inv, im, q, k)
    _check(fused, multi, f"fused != multiphase at K={n_shards} k={k}")
    _check(fused, oracle, f"fused != oracle at K={n_shards} k={k}")


def test_fused_kernel_actually_ran(system, engines):
    eng = engines[(True, 1)]
    _, inv, *_ = system
    q, _ = zipf_disjunctions(inv.dfs, 24, seed=5)
    eng.reset_stats()
    eng.query_topk(q, K)
    s = eng.metrics.snapshot()["ranked"]
    assert s["fused_queries"] > 0 and s["fused_lanes"] > 0
    assert s["fused_stream_bytes"] > 0 and s["fused_device_bytes"] > 0


@settings(max_examples=20, deadline=None)
@given(
    st.lists(st.integers(0, N_TERMS - 1), min_size=1, max_size=6, unique=True),
    st.integers(0, 2),  # k ∈ {1, 10, 2000 > any candidate set}
    st.integers(0, 2),  # required prefix length
)
def test_fused_property_vs_multiphase(terms, k_idx, n_req):
    engines = _shared_engines()
    k = (1, K, 2000)[k_idx]
    row = np.full((1, 6), -1, np.int32)
    row[0, : len(terms)] = terms
    req = np.zeros_like(row, dtype=bool)
    req[0, : min(n_req, len(terms))] = True
    req &= row >= 0
    for ns in (1, 3):
        fused = engines[(True, ns)].query_topk(row, k, required=req)
        multi = engines[(False, ns)].query_topk(row, k, required=req)
        _check(fused, multi, f"terms={terms} k={k} n_req={n_req} K={ns}")


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(0, N_TERMS - 1), min_size=1, max_size=6, unique=True))
def test_fused_property_vs_oracle(terms):
    engines = _shared_engines()
    _, inv, _, _, im = _shared_system()
    row = np.full((1, 6), -1, np.int32)
    row[0, : len(terms)] = terms
    fused = engines[(True, 1)].query_topk(row, K)
    oracle = brute_force_topk(inv, im, row, K)
    _check(fused, oracle, f"terms={terms}")


def test_fused_k_exceeds_candidates(system, engines):
    _, inv, _, _, im = system
    q, _ = zipf_disjunctions(inv.dfs, 8, seed=6)
    fused = engines[(True, 1)].query_topk(q, 2000)
    oracle = brute_force_topk(inv, im, q, 2000)
    _check(fused, oracle, "k > n_candidates must return every match, ranked")


def test_fused_all_pad_batch(system, engines):
    pad = np.full((4, 5), -1, np.int32)
    for ns in (1, 3):
        res = engines[(True, ns)].query_topk(pad, K)
        assert all(r.ids.size == 0 and r.scores.size == 0 for r in res)


def test_fused_mixed_pad_batch(system, engines):
    _, inv, _, _, im = system
    q, _ = zipf_disjunctions(inv.dfs, 6, seed=7)
    q[1] = -1  # dead rows interleaved with live ones
    q[4] = -1
    fused = engines[(True, 3)].query_topk(q, K)
    oracle = brute_force_topk(inv, im, q, K)
    _check(fused, oracle, "pad rows must stay empty, live rows exact")
    assert fused[1].ids.size == 0 and fused[4].ids.size == 0


# ------------------------------------------------------------- codec tiers
def _tiered_system():
    """Engineered index where codec choice is forced, not hoped for.

    Uniform synthetic corpora never hand a posting list to the learned
    codecs (the id gaps are too irregular), so this builds the inverted
    index directly: smooth strided-with-jitter lists that plm wins with a
    small nonzero ε (real guided-window lanes in the kernel), next to
    random sparse lists that stay classical.
    """
    if "tiered" not in _SHARED:
        from repro.index.build import InvertedIndex

        rng = np.random.default_rng(3)
        universe = 101_000
        lists = [np.arange(2000) * 50 + rng.integers(0, 12, 2000) + s
                 for s in range(6)]
        lists += [np.sort(rng.choice(universe, 900, replace=False))
                  for _ in range(6)]
        offsets = np.zeros(len(lists) + 1, np.int64)
        np.cumsum([len(l) for l in lists], out=offsets[1:])
        inv = InvertedIndex(
            n_docs=universe,
            n_terms=len(lists),
            term_offsets=offsets,
            doc_ids=np.concatenate(lists).astype(np.int32),
            tfs=rng.integers(1, 8, int(offsets[-1])).astype(np.int32),
        )
        li = LearnedIndexConfig(embed_dim=16, truncation_k=16, block_size=128)
        params, _ = init_membership(jax.random.key(1), li, inv.n_terms, inv.n_docs)
        lb = fit_thresholds(params, inv)
        im = ImpactModel.build(inv, BM25Params())
        engs = {
            fused: BooleanEngine(lb, inv, li, ServeConfig(
                n_shards=1,
                ranked=dict(fused_kernel=fused, topk_exhaustive_cutoff=0),
            ))
            for fused in (False, True)
        }
        _SHARED["tiered"] = (inv, im, engs)
    return _SHARED["tiered"]


def test_fused_across_codec_tiers():
    """One query mixing learned-window and classical host-resolved lanes."""
    inv, im, engs = _tiered_system()
    src = engs[True].shards[0].ranked
    learned, classical = [], []
    for t in range(inv.n_terms):
        tm = src.term_model(t)
        (learned if tm is not None and 0 < tm.width < 32 else classical).append(t)
    assert learned and classical, "index must exercise both lane flavours"
    row = np.full((1, 6), -1, np.int32)
    mix = (learned[:3] + classical[:3])[:6]
    row[0, : len(mix)] = mix
    fused = engs[True].query_topk(row, K)
    _check(fused, engs[False].query_topk(row, K), f"mixed-tier vs multiphase {mix}")
    _check(fused, brute_force_topk(inv, im, row, K), f"mixed-tier query {mix}")
    s = engs[True].metrics.snapshot()["ranked"]
    assert s["fused_queries"] > 0 and s["fused_lanes"] > 0


# ---------------------------------------------------- kernel vs reference
def test_kernel_bit_identical_to_reference(system, engines):
    from repro.kernels.fused_query.ops import fused_topk_batch
    from repro.rank.topk import RankedStats

    _, inv, *_ = system
    src = engines[(True, 1)].shards[0].ranked
    q, _ = zipf_disjunctions(inv.dfs, 16, seed=9)
    items = [(tuple(int(t) for t in row[row >= 0]), K, (), 0) for row in q]
    kern = fused_topk_batch(src, items, exhaustive_cutoff=0, stats=RankedStats())
    ref = fused_topk_batch(
        src, items, exhaustive_cutoff=0, stats=RankedStats(), use_kernel=False
    )
    _check(kern, ref, "Pallas kernel must match the numpy reference bit-for-bit")


@pytest.mark.parametrize("k", [7, 40])
def test_kernel_block_merge_matches_reference(k):
    """A candidate axis of two C_BLK blocks: per-block heaps merged on the
    device equal the reference's single heap, score ties included."""
    from repro.kernels.fused_query.kernel import C_BLK, NEVER, fused_topk
    from repro.kernels.fused_query.ref import fused_topk_ref

    rng = np.random.default_rng(k)
    Q, T, C, W, pbits = 3, 2, 2 * C_BLK, 2, 4
    cand = np.full((Q, C), NEVER, np.int32)
    for r in range(Q):
        n = C - 100 * r  # ragged rows: the tail stays NEVER-padded
        cand[r, :n] = np.sort(rng.choice(1 << 20, n, replace=False))
    # resolved lanes: the segment line reproduces the candidate exactly
    rlo = rng.integers(0, 1 << 12, (Q, T, C)).astype(np.int32)
    wlen = rng.integers(0, W + 1, (Q, T, C)).astype(np.int32)
    arrays = (
        np.zeros((Q, T), np.uint32), np.zeros((Q, T), np.int32), rlo, wlen,
        rlo.copy(), np.broadcast_to(cand[:, None, :], (Q, T, C)).copy(),
        np.zeros((Q, T, C), np.float32),
        np.zeros((Q, T, C, W), np.uint32), np.zeros((Q, T, C, W), np.uint32),
        rng.integers(0, 1 << 32, (Q, T, C, W), dtype=np.uint32),
        rng.integers(0, 1 << 32, (Q, T, C, W), dtype=np.uint32),
        cand, rng.integers(0, 3, (Q, C)).astype(np.int32),
        np.array([[0], [5], [12]], np.int32),
    )
    ids, scores = fused_topk(*(jax.numpy.asarray(a) for a in arrays), k=k, pbits=pbits)
    want_ids, want_scores = fused_topk_ref(*arrays, k=k, pbits=pbits)
    assert (want_scores[:, 0] > 0).all()
    np.testing.assert_array_equal(np.asarray(scores), want_scores)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)


# -------------------------------------------------------- serve-path wiring
def test_empty_run_shards_short_circuit(system, engines, monkeypatch):
    """A shard whose every run mask is empty is skipped before heap setup."""
    _, inv, *_ = system
    eng = engines[(False, 3)]
    lo1 = eng.shards[1].lo
    t = next(
        int(t) for t in range(inv.n_terms)
        if 0 < inv.dfs[t] and int(inv.postings(t).max()) < lo1
    )
    calls = {i: 0 for i in range(len(eng.shards))}

    def _wrap(i, orig):
        def counted(*a, **kw):
            calls[i] += 1
            return orig(*a, **kw)
        return counted

    for i, sh in enumerate(eng.shards):
        monkeypatch.setattr(sh, "query_topk_local", _wrap(i, sh.query_topk_local))
    res = eng.query_topk(np.array([[t]], np.int32), K)
    assert res[0].ids.size > 0
    assert calls[0] >= 1 and calls[1] == 0 and calls[2] == 0


def test_scheduler_inline_fused_parity(system):
    from repro.serve.sched import MODE_RANKED, QueryRequest, Session

    _, inv, *_ = system
    eng = _engine(system, fused=True, n_shards=2)
    q, _ = zipf_disjunctions(inv.dfs, 8, seed=13)
    want = eng.query_topk(q, K)
    with Session(eng) as s:
        got = [
            s.submit_async(
                QueryRequest(terms=row, mode=MODE_RANKED, k=K), block=True
            ).result(timeout=30)
            for row in q
        ]
    for g, w in zip(got, want):
        assert g.ok
        assert np.array_equal(g.ids, w.ids) and np.array_equal(g.scores, w.scores)


def test_fused_kernel_flat_kwarg_forwards():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        cfg = ServeConfig(fused_kernel=True)  # legacy flat spelling
    assert cfg.ranked.fused_kernel is True and cfg.fused_kernel is True
    # process replicas must inherit the flag through the picklable spec
    spec = ServeConfig(ranked=dict(fused_kernel=True)).worker_spec()
    assert spec["ranked"].fused_kernel is True


# ----------------------------------------------------------- arena residence
def test_arena_residence_zero_reuploads(system, engines):
    """The impact table is uploaded once per shard per process: repeated
    dispatches hit the resident buffers, uploads/upload_bytes never move."""
    eng = engines[(True, 1)]
    _, inv, *_ = system
    q, _ = zipf_disjunctions(inv.dfs, 16, seed=6)
    eng.query_topk(q, K)  # builds the arena lazily on the first fused use
    sh = eng.shards[0]
    snap0 = sh.metrics.snapshot()["arena"]
    assert snap0 is not None
    assert snap0["uploads"] == 1 and snap0["upload_bytes"] > 0
    for _ in range(3):
        eng.query_topk(q, K)
    snap1 = sh.metrics.snapshot()["arena"]
    assert snap1["uploads"] == 1
    assert snap1["upload_bytes"] == snap0["upload_bytes"]
    assert snap1["hits"] > snap0["hits"]


def test_arena_disabled_by_config(system):
    """ranked.device_arena=False routes every item down the legacy peel path
    (no arena is ever built) and stays bit-identical."""
    _, inv, li, lb, _ = system
    cfg = ServeConfig(
        n_shards=1,
        ranked=dict(fused_kernel=True, topk_exhaustive_cutoff=0, device_arena=False),
    )
    eng = BooleanEngine(lb, inv, li, cfg)
    q, _ = zipf_disjunctions(inv.dfs, 12, seed=7)
    want = _shared_engines()[(False, 1)].query_topk(q, K)
    got = eng.query_topk(q, K)
    _check(got, want, "device_arena=False must stay bit-identical")
    assert eng.shards[0].metrics.snapshot().get("arena") is None
