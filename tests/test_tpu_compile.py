"""Compile the served path's kernels for a described TPU v5e, at real widths.

Nothing runs: each program is lowered and compiled by the TPU compiler for
a chip that is described, not attached (``topologies.get_topology_desc``),
which refuses what interpret-mode tests cannot see — illegal block shapes,
Mosaic ops without a TPU lowering, VMEM overruns.  Widths are the chip
smoke's (``chip_smoke.py``): the robust vocabulary of 60,000 terms, shards
of 13,200 documents, 64-wide embeddings, batches of 16 queries of up to 8
terms, k = 10 and k = 100.

The topology is described inside a module fixture (only the worker that
runs these tests loads the TPU library), and the tests skip where it
cannot be described.  The persistent compilation cache is off around them:
an entry compiled for a described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
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

    d = -(-SHARD_DOCS // D_BLK) * D_BLK
    q = -(-QUERIES * MAX_TERMS // Q_BLK) * Q_BLK
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    hlo = _compile(
        lambda *a: membership_bitmask(*a, interpret=False),
        s((q, EMBED), jnp.float32), s((d, EMBED), jnp.float32),
        s((q,), jnp.float32), s((), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


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


def test_block_query_compiles(one_chip):
    """Algorithm 3 over one smoke shard: the Boolean candidate program."""
    from repro.core.algorithms import block_query

    block = 128
    n_blocks = -(-SHARD_DOCS // block)
    words = -(-n_blocks // 32)
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    params = {
        "term_embed": {"table": s((N_TERMS, EMBED), jnp.float32)},
        "doc_embed": {"table": s((SHARD_DOCS, EMBED), jnp.float32)},
        "bias": s((), jnp.float32),
    }
    _compile(
        lambda b, p, t, q: block_query(b, p, t, q, n_docs=SHARD_DOCS, block_size=block),
        s((N_TERMS, words), jnp.uint32), params, s((N_TERMS,), jnp.float32),
        s((QUERIES, MAX_TERMS), jnp.int32),
    )

