"""The learned membership function f(t, d) — the paper's central object.

The paper assumes a model f(t,d) ∈ {0,1} with f(t,d)=1 iff t ∈ d (Eq. 1) and
explicitly sizes its worst case as "a compressed 128 unit embedding for every
document and for every term" (s = 512 bits, §4). We realize exactly that
family: term/doc embedding tables + dot product (+ optional MLP head), scored
on the MXU as tiled matmuls.

Params are a plain pytree; `axes` is the twin logical-sharding pytree:
  term table  -> ("terms",  None)   sharded over `model`
  doc table   -> ("docs",   None)   sharded over `data` (+pod)
so scoring f(q, all docs) is doc-parallel with a bitmap all-gather at the end.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.common.config import LearnedIndexConfig
from repro.common import nn


def init_membership(
    key: jax.Array, cfg: LearnedIndexConfig, n_terms: int, n_docs: int, dtype=jnp.float32
) -> tuple[Any, Any]:
    k_t, k_d, k_m, k_b = jax.random.split(key, 4)
    params: dict[str, Any] = {}
    axes: dict[str, Any] = {}
    params["term_embed"], axes["term_embed"] = nn.embedding_init(
        k_t, n_terms, cfg.embed_dim, axes=("terms", None), dtype=dtype
    )
    params["doc_embed"], axes["doc_embed"] = nn.embedding_init(
        k_d, n_docs, cfg.embed_dim, axes=("docs", None), dtype=dtype
    )
    params["bias"] = jnp.zeros((), dtype)
    axes["bias"] = ()
    if cfg.mlp_hidden:
        dims = [2 * cfg.embed_dim, *cfg.mlp_hidden, 1]
        params["mlp"], axes["mlp"] = nn.mlp_init(k_m, dims, dtype=dtype)
    return params, axes


def pair_logits(params: Any, terms: jax.Array, docs: jax.Array) -> jax.Array:
    """f-logit for aligned (term, doc) id vectors — the training path."""
    te = nn.embed(params["term_embed"], terms)
    de = nn.embed(params["doc_embed"], docs)
    if "mlp" in params:
        h = jnp.concatenate([te, de], axis=-1)
        return nn.mlp(params["mlp"], h, act=jax.nn.gelu)[..., 0] + params["bias"]
    return jnp.sum(te * de, axis=-1) + params["bias"]


def term_doc_logits(params: Any, terms: jax.Array, doc_tile: jax.Array | None = None) -> jax.Array:
    """Logits of f(t, ·) for every doc (or a doc-id tile): (Q, D) matmul.

    This is the Algorithm-1/3 hot loop; on TPU it lowers to an MXU matmul
    against the (doc-sharded) embedding table. kernels/membership provides the
    fused Pallas version that also packs the thresholded bitmask.
    """
    te = nn.embed(params["term_embed"], terms)  # (Q, E)
    dt = params["doc_embed"]["table"]
    if doc_tile is not None:
        dt = jnp.take(dt, doc_tile, axis=0)
    if "mlp" in params:
        # MLP head: broadcast pairing (Q, D, 2E) — only viable on doc tiles
        q, d = te.shape[0], dt.shape[0]
        h = jnp.concatenate(
            [jnp.broadcast_to(te[:, None, :], (q, d, te.shape[-1])),
             jnp.broadcast_to(dt[None, :, :], (q, d, dt.shape[-1]))],
            axis=-1,
        )
        return nn.mlp(params["mlp"], h, act=jax.nn.gelu)[..., 0] + params["bias"]
    # full float32 on the MXU: the zero-FN thresholds were fitted on exact
    # float32 pair logits, and the TPU's default one-pass bf16 matmul would
    # drift far past their margin
    return jnp.matmul(te, dt.T, precision=jax.lax.Precision.HIGHEST) + params["bias"]


def membership_loss(params: Any, batch: dict[str, jax.Array]) -> jax.Array:
    """Weighted BCE; positives upweighted so the zero-FN threshold stays tight."""
    logits = pair_logits(params, batch["terms"], batch["docs"])
    labels = batch["labels"]
    per = -(labels * jax.nn.log_sigmoid(logits) + (1 - labels) * jax.nn.log_sigmoid(-logits))
    w = jnp.where(labels > 0.5, 2.0, 1.0)
    return jnp.sum(per * w) / jnp.sum(w)


def predict(params: Any, terms: jax.Array, docs: jax.Array, threshold: float = 0.0) -> jax.Array:
    return pair_logits(params, terms, docs) >= threshold
