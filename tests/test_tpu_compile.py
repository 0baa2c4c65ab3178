"""Compile the served path's kernels for a described TPU v5e, at real widths.

Nothing runs: each program is lowered and compiled by the TPU compiler for
a chip that is described, not attached (``topologies.get_topology_desc``),
which refuses what interpret-mode tests cannot see — illegal block shapes,
Mosaic ops without a TPU lowering, VMEM overruns.  Widths are the chip
smoke's (``chip_smoke.py``): the robust vocabulary of 60,000 terms, shards
of 13,200 documents, 64-wide embeddings, batches of 16 queries of up to 8
terms, k = 10 and k = 100.  The term table is given in the served layout
(``membership.pack_term_table``), and the programs that gather its rows are
checked to read it where it lies, with no copy of the whole table.

The topology is described inside a module fixture (only the worker that
runs these tests loads the TPU library), and the tests skip where it
cannot be described.  The persistent compilation cache is off around them:
an entry compiled for a described chip cannot be read back without one.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

N_TERMS = 60_000
SHARD_DOCS = 13_200
EMBED = 64
QUERIES = 16
MAX_TERMS = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _served_params(sharding):
    """Membership params as the served model holds them: the term table
    packed by the program's own pack function (on host zeros, for its
    shape), one smoke shard's docs."""
    from repro.core.membership import pack_term_table

    params = {
        "term_embed": {"table": np.zeros((N_TERMS, EMBED), np.float32)},
        "doc_embed": {"table": np.zeros((SHARD_DOCS, EMBED), np.float32)},
        "bias": np.zeros((), np.float32),
    }
    packed = pack_term_table(params)
    return jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype), packed)


def _table_copies(hlo: str, params) -> list[str]:
    """``copy(`` instructions whose result is the whole term table, whatever
    its layout and whatever feeds them (the parameter or its prefetch): a
    relayout of the table on every call."""
    rows, cols = params["term_embed"]["table"].shape
    result = re.compile(rf"=\s*f32\[{rows},{cols}\]\{{[^}}]*\}}\s+copy\(")
    return [line.strip()[:160] for line in hlo.splitlines() if result.search(line)]


@pytest.mark.parametrize(
    "C,W,k",
    [(128, 1, 10), (16384, 32, 100)],
    ids=["C128-W1-k10", "C16384-W32-k100"],
)
def test_fused_topk_compiles(one_chip, C, W, k):
    """Both ends of the candidate buckets; C=16384 spans two C_BLK blocks
    and takes the on-device heap merge.  T is the longest probe tail a
    MAX_TERMS-term query leaves (one term is always peeled)."""
    from repro.kernels.fused_query.kernel import fused_topk

    Q, T = QUERIES, MAX_TERMS - 1
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    args = (
        [s((Q, T), jnp.uint32), s((Q, T), jnp.int32)]
        + [s((Q, T, C), jnp.int32)] * 4
        + [s((Q, T, C), jnp.float32)]
        + [s((Q, T, C, W), jnp.uint32)] * 4
        + [s((Q, C), jnp.int32)] * 2
        + [s((Q, 1), jnp.int32)]
    )
    hlo = _compile(lambda *a: fused_topk(*a, k=k, pbits=8, interpret=False), *args)
    assert "tpu_custom_call" in hlo


def test_membership_bitmask_compiles(one_chip):
    from repro.kernels.membership.kernel import D_BLK, Q_BLK, membership_bitmask
    from repro.kernels.membership.ops import score_terms_bitmask

    d = -(-SHARD_DOCS // D_BLK) * D_BLK
    q = -(-QUERIES * MAX_TERMS // Q_BLK) * Q_BLK
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    hlo = _compile(
        lambda *a: membership_bitmask(*a, interpret=False),
        s((q, EMBED), jnp.float32), s((d, EMBED), jnp.float32),
        s((q,), jnp.float32), s((), jnp.float32),
    )
    assert "tpu_custom_call" in hlo
    # the whole wrapper, term-row gather included, over the served table
    params = _served_params(one_chip)
    hlo = _compile(
        lambda p, t, tau: score_terms_bitmask(p, t, tau, interpret=False),
        params, s((QUERIES * MAX_TERMS,), jnp.int32), s((N_TERMS,), jnp.float32),
    )
    assert "tpu_custom_call" in hlo
    assert _table_copies(hlo, params) == []


def test_guided_probe_batch_compiles(one_chip):
    from repro.kernels.guided_search.kernel import probe_batch

    P, W = 1024, 256
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    cols = [s((P, 1), jnp.int32)] * 2 + [s((P, 1), jnp.float32)] + [s((P, 1), jnp.int32)] * 3
    hlo = _compile(
        lambda *a: probe_batch(*a, interpret=False), *cols, s((P, W), jnp.int32)
    )
    assert "tpu_custom_call" in hlo


def test_bm25_score_batch_compiles(one_chip):
    from repro.kernels.bm25_score.kernel import score_batch

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    hlo = _compile(
        lambda *a: score_batch(*a, interpret=False),
        s((1024, 128), jnp.int32), s((1, 1), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


def test_dense_topk_compiles(one_chip):
    """The resident-arena loop at the largest table the arena admits at the
    robust vocabulary (plain XLA, no Pallas)."""
    from repro.kernels.arena import DeviceArena
    from repro.kernels.fused_query import dense

    n_docs = 1024
    assert DeviceArena.eligible(N_TERMS, n_docs)
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    _compile(
        lambda t, q, f: dense._dense_impl(t, q, f, k=dense.DENSE_MAX_K),
        s((N_TERMS + 1, n_docs), jnp.uint8), s((QUERIES, MAX_TERMS), jnp.int32),
        s((QUERIES,), jnp.int32),
    )


@pytest.mark.parametrize("Q", [1, QUERIES], ids=["Q1", f"Q{QUERIES}"])
def test_block_query_compiles(one_chip, Q):
    """Algorithm 3 over one smoke shard: the Boolean candidate program, for
    a lone request and a full batch (each batch size is its own program)."""
    from repro.core.algorithms import block_query

    block = 128
    n_blocks = -(-SHARD_DOCS // block)
    words = -(-n_blocks // 32)
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    params = _served_params(one_chip)
    hlo = _compile(
        lambda b, p, t, q: block_query(b, p, t, q, n_docs=SHARD_DOCS, block_size=block),
        s((N_TERMS, words), jnp.uint32), params, s((N_TERMS,), jnp.float32),
        s((Q, MAX_TERMS), jnp.int32),
    )
    assert _table_copies(hlo, params) == []
