"""Packed-bitset conjunctive AND + popcount — Algorithm 3's block intersect.

(Q, T, W) per-query per-term block bitmaps -> (Q, W) AND + (Q,) surviving
block count. W is tiled into VMEM-sized chunks; the T-way AND runs as an
unrolled reduction inside the tile (T = max query terms is small, ≤ 8).

Popcount uses the SWAR ladder (no popcnt primitive in Mosaic): classic
Hacker's-Delight bit-slicing, all vectorizable u32 ops on the VPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

W_BLK = 1024  # u32 words per tile = 32k blocks per grid step


def _popcount_u32(x: jnp.ndarray) -> jnp.ndarray:
    m1 = jnp.uint32(0x55555555)
    m2 = jnp.uint32(0x33333333)
    m4 = jnp.uint32(0x0F0F0F0F)
    x = x - ((x >> jnp.uint32(1)) & m1)
    x = (x & m2) + ((x >> jnp.uint32(2)) & m2)
    x = (x + (x >> jnp.uint32(4))) & m4
    return ((x * jnp.uint32(0x01010101)) >> jnp.uint32(24)).astype(jnp.int32)


def _bitset_kernel(maps_ref, valid_ref, and_ref, cnt_ref):
    t = maps_ref.shape[1]
    full = jnp.uint32(0xFFFFFFFF)
    acc = jnp.full((maps_ref.shape[2],), full, dtype=jnp.uint32)
    for i in range(t):  # T is tiny and static -> unrolled vector ANDs
        row = jnp.where(valid_ref[0, i] > 0, maps_ref[0, i, :], full)
        acc = acc & row
    and_ref[0, :] = acc
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    cnt_ref[0] += _popcount_u32(acc).sum()


@partial(jax.jit, static_argnames=("interpret",))
def bitset_and_popcount(
    bitmaps: jax.Array,  # (Q, T, W) uint32, W % W_BLK == 0
    valid: jax.Array,  # (Q, T) int32 (bool as int for SMEM-friendliness)
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    q, t, w = bitmaps.shape
    assert w % W_BLK == 0, w
    grid = (q, w // W_BLK)
    return pl.pallas_call(
        _bitset_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, W_BLK), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, t), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, W_BLK), lambda i, j: (i, j)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, w), jnp.uint32),
            jax.ShapeDtypeStruct((q,), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(bitmaps, valid.astype(jnp.int32))
