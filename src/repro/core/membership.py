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

Served layout.  Training holds the term table as (n_terms, E).  The served
model (``LearnedBloom``) holds it packed by ``pack_term_table``: r = 128 // E
term rows side by side in each 128-lane row, (ceil(n_terms / r), 128), the
tail zero-padded.  A TPU keeps a narrow (n_terms, 64) table with the terms
on the lanes, so a row gather from it first relayouts the whole table; the
packed table is lane-dense and a gather reads it where it lies.  Same f32
values, same bytes.  Every reader of term rows goes through ``term_rows``,
which finds r from the shapes (packed width / doc-embedding width), so one
code path serves both layouts.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

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


LANES = 128  # a TPU vreg's lane count: the packed term table's row width


def term_rows_per_lane_row(params: Any) -> int:
    """Term rows held side by side in one row of the term table (1: unpacked)."""
    return params["term_embed"]["table"].shape[1] // params["doc_embed"]["table"].shape[1]


def pack_term_table(params: Any) -> Any:
    """Params with the term table packed to 128-lane rows (see module doc).

    Applies only where the embedding width E is below 128 and divides it;
    otherwise, or when the table is packed already, returns ``params``.
    Packs on the host (a row-major reshape, no program to compile) and
    puts a device table back on the device."""
    table = params["term_embed"]["table"]
    n, e = table.shape
    if term_rows_per_lane_row(params) != 1 or e >= LANES or LANES % e:
        return params
    r = LANES // e
    host = np.asarray(table)
    if n % r:
        host = np.concatenate([host, np.zeros((r - n % r, e), host.dtype)])
    host = host.reshape(-1, LANES)
    packed = host if isinstance(table, np.ndarray) else jnp.asarray(host)
    return {**params, "term_embed": {**params["term_embed"], "table": packed}}


def term_rows(params: Any, terms: jax.Array) -> jax.Array:
    """(..., E) embedding rows of ``terms``, from either table layout.

    A packed table is gathered at row t // r; lane slice t % r is picked by
    static slices and selects, so each row is bit-identical to the unpacked
    table's."""
    table = params["term_embed"]["table"]
    r = term_rows_per_lane_row(params)
    if r == 1:
        return jnp.take(table, terms, axis=0)
    e = table.shape[1] // r
    rows = jnp.take(table, terms // r, axis=0)
    lane = (terms % r)[..., None]
    out = rows[..., :e]
    for j in range(1, r):
        out = jnp.where(lane == j, rows[..., j * e : (j + 1) * e], out)
    return out


def pair_logits(params: Any, terms: jax.Array, docs: jax.Array) -> jax.Array:
    """f-logit for aligned (term, doc) id vectors — the training path."""
    te = term_rows(params, terms)
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
    te = term_rows(params, terms)  # (Q, E)
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
