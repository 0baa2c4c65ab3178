"""Batched PLM/RMI decode as a Pallas kernel.

Learned-codec decompression is a fused gather + FMA + add: locate each rank's
segment (a comparison one-hot over the per-list segment table), evaluate the
segment's line in float32, round, add the bit-unpacked correction.  The whole
batch of lists decodes in one launch — the serving-path analogue of the
width-bucketed PFor kernel, but for the learned representation.

Shapes per grid step: B_BLK lists × S segments × R ranks.  S and R are static
(host pads to the batch maxima), so every comparison and select lowers to
vector ops with compile-time shapes; the (B_BLK, R, S) one-hot lives in VMEM
and is the only intermediate.  Padding rows/segments use start = SENTINEL and
decode to corr (0), trimmed by the host bridge in ops.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

from repro.kernels.plm_decode.ref import SENTINEL

B_BLK = 8  # lists decoded per grid step


def _kernel(starts_ref, bases_ref, slopes_ref, corr_ref, out_ref):
    starts = starts_ref[...]  # (B_BLK, S)
    R = corr_ref.shape[1]
    ranks = jnp.arange(R, dtype=jnp.int32)
    active = starts[:, None, :] <= ranks[None, :, None]  # (B_BLK, R, S)
    nxt = jnp.concatenate(
        [starts[:, 1:], jnp.full((starts.shape[0], 1), SENTINEL, jnp.int32)], axis=1
    )
    onehot = active & (nxt[:, None, :] > ranks[None, :, None])
    ohf = onehot.astype(jnp.float32)
    ohi = onehot.astype(jnp.int32)
    sel_slope = (ohf * slopes_ref[...][:, None, :]).sum(-1)
    sel_base = (ohi * bases_ref[...][:, None, :]).sum(-1)
    sel_start = (ohi * starts[:, None, :]).sum(-1)
    di = (ranks[None, :] - sel_start).astype(jnp.float32)
    frac = jnp.rint(sel_slope * di).astype(jnp.int32)
    out_ref[...] = sel_base + frac + corr_ref[...]


@partial(jax.jit, static_argnames=("interpret",))
def decode_batch(
    starts: jax.Array,  # (B, S) int32, SENTINEL-padded
    bases: jax.Array,  # (B, S) int32
    slopes: jax.Array,  # (B, S) float32
    corr: jax.Array,  # (B, R) int32
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode B padded lists -> (B, R) int32 doc ids."""
    B, S = starts.shape
    R = corr.shape[1]
    pad = (-B) % B_BLK
    if pad:
        starts = jnp.pad(starts, ((0, pad), (0, 0)), constant_values=SENTINEL)
        bases = jnp.pad(bases, ((0, pad), (0, 0)))
        slopes = jnp.pad(slopes, ((0, pad), (0, 0)))
        corr = jnp.pad(corr, ((0, pad), (0, 0)))
    seg_spec = pl.BlockSpec((B_BLK, S), lambda i: (i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=((B + pad) // B_BLK,),
        in_specs=[seg_spec, seg_spec, seg_spec, pl.BlockSpec((B_BLK, R), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((B_BLK, R), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B + pad, R), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(starts, bases, slopes, corr)
    return out[:B]
