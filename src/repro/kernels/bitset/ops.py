"""Public wrapper for the bitset kernel: gathers per-term block bitmaps,
pads W to kernel tiles, returns AND-mask + surviving-block counts."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bitset.kernel import W_BLK, bitset_and_popcount


def query_block_intersect(
    bitmaps: jax.Array,  # (n_terms, W) uint32 — per-term block bitmaps
    queries: jax.Array,  # (Q, T) int32 padded with -1
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns ((Q, W) AND bitmap, (Q,) popcount of surviving blocks)."""
    w = bitmaps.shape[1]
    valid = queries >= 0
    qmaps = jnp.take(bitmaps, jnp.maximum(queries, 0), axis=0)  # (Q, T, W)
    pad = (-w) % W_BLK
    if pad:
        # pad words are all-ones in every row so AND keeps them; they are
        # stripped from the returned mask and do inflate popcount — mask them
        # to zero instead (padded rows -> 0 contributes nothing).
        qmaps = jnp.pad(qmaps, ((0, 0), (0, 0), (0, pad)))
    anded, cnt = bitset_and_popcount(qmaps, valid, interpret=interpret)
    return anded[:, :w], cnt
