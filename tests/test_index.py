"""Index substrate: corpus synthesis, codecs, truncation, block lists."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.common.config import CorpusConfig
from repro.data.corpus import document_frequencies, synthesize_corpus, zipf_mandelbrot_probs
from repro.index.build import block_lists, build_inverted_index, truncate_index
from repro.index.compress import (
    compressed_size_bits,
    decode_postings,
    dgaps,
    encode_postings,
    optpfd_size_bits,
    pack_bits,
    undgaps,
    unpack_bits,
    varbyte_size_bits,
)


@pytest.fixture(scope="module")
def corpus():
    return synthesize_corpus(CorpusConfig(n_docs=600, n_terms=3000, avg_doc_len=50, seed=1))


@pytest.fixture(scope="module")
def inv(corpus):
    return build_inverted_index(corpus)


def test_corpus_structure(corpus):
    assert corpus.doc_offsets[0] == 0
    assert corpus.doc_offsets[-1] == corpus.n_postings
    # per-doc term lists sorted + unique
    for d in range(0, corpus.n_docs, 97):
        terms = corpus.doc_terms(d)
        assert (np.diff(terms) > 0).all()


def test_zipf_probs_normalized():
    p = zipf_mandelbrot_probs(1000, 1.2, 2.7)
    assert abs(p.sum() - 1.0) < 1e-9
    assert (np.diff(p) <= 0).all()  # monotone decreasing in rank


def test_inverted_index_is_exact_transpose(corpus, inv):
    assert inv.n_postings == corpus.n_postings
    rng = np.random.default_rng(0)
    for d in rng.integers(0, corpus.n_docs, 30):
        for t in corpus.doc_terms(int(d))[:5]:
            assert int(d) in inv.postings(int(t))


def test_postings_sorted_unique(inv):
    for t in np.nonzero(inv.dfs > 1)[0][:50]:
        p = inv.postings(int(t))
        assert (np.diff(p) > 0).all()


@given(st.lists(st.integers(0, 2**27), min_size=1, max_size=400, unique=True))
@settings(max_examples=30, deadline=None)
def test_codec_roundtrip(ids):
    docs = np.sort(np.array(ids, dtype=np.int32))
    for codec in ("optpfd", "varbyte"):
        enc = encode_postings(docs, codec)
        dec = decode_postings(enc, len(docs), codec)
        assert np.array_equal(dec, docs), codec


@given(st.integers(1, 32), st.integers(1, 500))
@settings(max_examples=30, deadline=None)
def test_pack_unpack_roundtrip(width, n):
    rng = np.random.default_rng(width * 1000 + n)
    hi = 2**width if width < 32 else 2**32
    vals = rng.integers(0, hi, size=n, dtype=np.uint64).astype(np.uint32)
    assert np.array_equal(unpack_bits(pack_bits(vals, width), width, n), vals)


def test_size_models_are_bit_exact_for_encoders(inv):
    rng = np.random.default_rng(3)
    for t in rng.choice(np.nonzero(inv.dfs > 4)[0], 20):
        g = dgaps(inv.postings(int(t)))
        # size model counts exact bits; encoder pads to u32 words
        assert optpfd_size_bits(g) <= encode_postings(undgaps(g)).size * 32 + 31


def test_optpfd_beats_raw(inv):
    sizes = [compressed_size_bits(inv.postings(int(t)), inv.n_docs, "optpfd")
             for t in np.nonzero(inv.dfs > 16)[0][:30]]
    raws = [32 * int(inv.dfs[t]) for t in np.nonzero(inv.dfs > 16)[0][:30]]
    assert sum(sizes) < sum(raws)


def test_truncate_index(inv):
    tr = truncate_index(inv, 7)
    assert (tr.dfs <= 7).all()
    assert (tr.dfs == np.minimum(inv.dfs, 7)).all()
    for t in np.nonzero(inv.dfs > 7)[0][:10]:
        assert np.array_equal(tr.postings(int(t)), inv.postings(int(t))[:7])


def test_block_lists_bits(inv):
    bm, n_blocks = block_lists(inv, 64)
    assert n_blocks == -(-inv.n_docs // 64)
    rng = np.random.default_rng(5)
    for t in rng.choice(np.nonzero(inv.dfs > 0)[0], 20):
        blocks = set((inv.postings(int(t)) // 64).tolist())
        for b in range(n_blocks):
            bit = bool((bm[t, b // 32] >> np.uint32(b % 32)) & 1)
            assert bit == (b in blocks)


# --------------------------------------------- store build: compiles, bytes
def _backend_compiles(fn) -> int:
    """Backend compilations that ``fn()`` triggered."""
    import jax

    seen = []

    def listener(name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append(name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    return len(seen)


def test_store_build_compiles_independent_of_list_lengths():
    """Thresholds + hybrid store over two collections with different list
    lengths: the first compiles at most the one logit program, the second
    nothing, however many distinct lengths the RMI candidates see."""
    import jax

    from repro.common.config import LearnedIndexConfig
    from repro.core import fit_thresholds, init_membership
    from repro.postings import HybridPostings
    from repro.postings.hybrid import RMI_MIN_N

    li_cfg = LearnedIndexConfig(embed_dim=16)
    params, _ = init_membership(jax.random.key(0), li_cfg, 400, 3000)
    counts, lengths = [], set()
    for seed in (1, 2):
        inv = build_inverted_index(synthesize_corpus(
            CorpusConfig(n_docs=3000, n_terms=400, avg_doc_len=60, seed=seed)
        ))
        lengths |= {int(n) for n in inv.dfs if n >= RMI_MIN_N}
        counts.append(_backend_compiles(
            lambda: (fit_thresholds(params, inv), HybridPostings.from_index(inv))
        ))
    assert len(lengths) > 50
    assert counts[0] <= 1 and counts[1] == 0, counts


def test_store_built_twice_is_byte_identical(tmp_path):
    """The persisted store is a pure function of the seed: two independent
    builds write the same files, byte for byte."""
    import jax

    from repro.common.config import LearnedIndexConfig
    from repro.core import fit_thresholds, init_membership
    from repro.serve import BooleanEngine, ServeConfig

    li_cfg = LearnedIndexConfig(embed_dim=16, truncation_k=16, block_size=64)
    for out in ("a", "b"):
        corpus = synthesize_corpus(
            CorpusConfig(n_docs=1500, n_terms=300, avg_doc_len=60, seed=4)
        )
        inv = build_inverted_index(corpus)
        params, _ = init_membership(jax.random.key(0), li_cfg, 300, 1500)
        lb = fit_thresholds(params, inv)
        BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=2)).save(str(tmp_path / out))
    files = sorted(p.relative_to(tmp_path / "a") for p in (tmp_path / "a").rglob("*") if p.is_file())
    assert files and files == sorted(
        p.relative_to(tmp_path / "b") for p in (tmp_path / "b").rglob("*") if p.is_file()
    )
    for f in files:
        assert (tmp_path / "a" / f).read_bytes() == (tmp_path / "b" / f).read_bytes(), f
