"""Batched quantized-BM25 scoring as a Pallas kernel.

The ranked tier's exhaustive scorer produces a dense (candidate, term)
window of quantized impacts — impact q(t, d) where candidate d matched term
t, 0 elsewhere.  Scoring it is one fused VPU pass per (B_BLK, T) tile: mask,
reduce the integer impacts per row, and dequantize with a single float32
multiply.

Scores are *integer* sums of <= 2^bits - 1 impacts over <= T terms, so the
reduction is associative and the kernel is bit-exact against the jnp
reference and host numpy with no ordering caveats; the float score is one
f32 multiply of that exact integer (same single-rounding discipline as the
plm_decode / guided_search kernels), so it is bit-exact too.

T is the padded term axis: the host bridge pads to 128 lanes with zero
impacts, which are additive identities — no separate valid mask is needed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

B_BLK = 8  # candidate rows per grid step


def _kernel(imp_ref, scale_ref, int_ref, f32_ref):
    imp = imp_ref[...]  # (B, T) int32 quantized impacts, 0 where unmatched
    total = imp.sum(axis=1, keepdims=True)  # exact: integer add is associative
    int_ref[...] = total
    f32_ref[...] = total.astype(jnp.float32) * scale_ref[...]


@partial(jax.jit, static_argnames=("interpret",))
def score_batch(
    impacts: jax.Array,  # (P, T) int32
    scale: jax.Array,  # (1, 1) float32 dequantization scale
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Score P candidate windows -> (int scores (P,1) i32, float (P,1) f32)."""
    P, T = impacts.shape
    pad = (-P) % B_BLK
    if pad:
        impacts = jnp.pad(impacts, ((0, pad), (0, 0)))
    win_spec = pl.BlockSpec((B_BLK, T), lambda i: (i, 0))
    col_spec = pl.BlockSpec((B_BLK, 1), lambda i: (i, 0))
    scale_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    ints, floats = pl.pallas_call(
        _kernel,
        grid=((P + pad) // B_BLK,),
        in_specs=[win_spec, scale_spec],
        out_specs=[col_spec, col_spec],
        out_shape=[
            jax.ShapeDtypeStruct((P + pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((P + pad, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(impacts, scale)
    return ints[:P], floats[:P]
