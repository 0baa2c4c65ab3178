"""Fused membership scoring: (Q,E)x(E,D) MXU matmul + threshold + bit-pack.

TPU adaptation of the paper's f(t, d) hot loop (DESIGN.md §3): instead of a
per-pair pointer-chase, a whole (128-query × 4096-doc) tile is scored on the
MXU per grid step and immediately reduced to a packed u32 bitmask in VMEM —
the bitmask is 32× smaller than the logits, so HBM write-back is negligible
and the op stays compute-bound.

Block shapes: Q_BLK=128 rows (MXU-aligned), D_BLK=4096 docs -> 128 output
words per query row, one full lane width.  E (embed dim) is loaded whole per
tile (E<=512: 4096·512·4B = 8 MiB for the doc tile at the largest E).

Bit-packing without a lane reshape: the wrapper permutes each doc tile so
that logit column b·128 + w holds doc 32·w + b.  Word w of a row is then
the OR over b of lane-aligned (Q_BLK, 128) slices shifted by b — 32 aligned
slices, no cross-lane shuffle — and the bit order is the little-endian one
of ``ref.pack_bool_u32``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

Q_BLK = 128
D_BLK = 4096
LANE = 32  # bits per packed word
WORDS = D_BLK // LANE  # packed words per row per doc tile (128 lanes)


def _membership_kernel(q_ref, d_ref, tau_ref, bias_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)  # (Q_BLK, E)
    d = d_ref[...].astype(jnp.float32)  # (D_BLK, E), bit-major permuted
    logits = jax.lax.dot_general(
        q, d, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (Q_BLK, D_BLK)
    hits = logits + bias_ref[...] >= tau_ref[...]
    word = jnp.zeros((Q_BLK, WORDS), jnp.uint32)
    for b in range(LANE):
        bit = hits[:, b * WORDS:(b + 1) * WORDS].astype(jnp.uint32)
        word = word | (bit << jnp.uint32(b))
    out_ref[...] = word


@partial(jax.jit, static_argnames=("interpret",))
def membership_bitmask(
    q_embed: jax.Array,  # (Q, E), Q % Q_BLK == 0
    d_embed: jax.Array,  # (D, E), D % D_BLK == 0
    tau: jax.Array,  # (Q,)
    bias: jax.Array,  # ()
    *,
    interpret: bool | None = None,
) -> jax.Array:
    q, e = q_embed.shape
    d = d_embed.shape[0]
    assert q % Q_BLK == 0 and d % D_BLK == 0, (q, d)
    # doc 32·w + b of each tile -> row b·WORDS + w (see module docstring)
    d_perm = (
        d_embed.reshape(d // D_BLK, WORDS, LANE, e)
        .transpose(0, 2, 1, 3)
        .reshape(d, e)
    )
    grid = (q // Q_BLK, d // D_BLK)
    return pl.pallas_call(
        _membership_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((Q_BLK, e), lambda i, j: (i, 0)),
            pl.BlockSpec((D_BLK, e), lambda i, j: (j, 0)),
            pl.BlockSpec((Q_BLK, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((Q_BLK, WORDS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, d // LANE), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(q_embed, d_perm, tau.reshape(q, 1), jnp.reshape(bias, (1, 1)).astype(jnp.float32))
