"""PFor fixed-width bit-unpack as a Pallas kernel.

TPU adaptation of SIMD PFor decode (Lemire & Boytsov; DESIGN.md §3): the
serving path groups compressed blocks by bit width, so each kernel launch
decodes a batch of same-width blocks — width is a *static* kernel parameter,
making every gather index and shift a compile-time constant vector. One
128-value block per grid row = one VREG-shaped tile; B_BLK blocks per grid
step amortize grid overhead.

Exceptions (the 'patch' in patched frame-of-reference) are scatter-applied
outside the kernel — they are <2% of values by construction of OptPFD's cost
model, so the patch pass is bandwidth-trivial.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

from repro.kernels.pfor.ref import BLOCK, words_per_block

B_BLK = 64  # blocks decoded per grid step


def _make_kernel(width: int, wpb: int):
    def kernel(w_ref, o_ref):
        # all index math is rebuilt in-kernel from the static width so no
        # host-side array constants are captured (Pallas restriction)
        mask = jnp.uint32(0xFFFFFFFF) if width == 32 else jnp.uint32((1 << width) - 1)
        bitpos = jnp.arange(BLOCK, dtype=jnp.uint32) * jnp.uint32(width)
        word_idx = (bitpos // jnp.uint32(32)).astype(jnp.int32)
        off = bitpos % jnp.uint32(32)
        shift = jnp.where(off == 0, jnp.uint32(0), jnp.uint32(32) - off)
        nxt_idx = jnp.minimum(word_idx + 1, wpb - 1)
        w = w_ref[...]  # (B_BLK, wpb)
        lo = jnp.take(w, word_idx, axis=1) >> off[None, :]
        nxt = jnp.take(w, nxt_idx, axis=1)
        hi = jnp.where((off == 0)[None, :], jnp.uint32(0), nxt << shift[None, :])
        o_ref[...] = (lo | hi) & mask

    return kernel


@partial(jax.jit, static_argnames=("width", "interpret"))
def unpack_blocks(
    words: jax.Array,  # (n_blocks, words_per_block(width)) uint32
    *,
    width: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode same-width PFor blocks -> (n_blocks, 128) uint32 values."""
    n, wpb = words.shape
    assert wpb == words_per_block(width), (wpb, width)
    if width == 0:
        return jnp.zeros((n, BLOCK), dtype=jnp.uint32)
    pad = (-n) % B_BLK
    if pad:
        words = jnp.pad(words, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _make_kernel(width, wpb),
        grid=((n + pad) // B_BLK,),
        in_specs=[pl.BlockSpec((B_BLK, wpb), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((B_BLK, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, BLOCK), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(words)
    return out[:n]
