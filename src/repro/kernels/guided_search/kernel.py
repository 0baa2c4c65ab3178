"""Batched ε-window probes as a Pallas kernel.

The guided-intersection hot path issues many independent (term, candidate)
probes per verification round; each is a tiny decode (one segment line over a
±ε rank window) + compare + count.  Batched, that is one fused VPU pass over
a (B_BLK, W) tile: evaluate the line, add corrections, compare against the
candidate, reduce to found/lt per row — the probe analogue of the
plm_decode full-list kernel, with the same single-multiply float32 + rint
formula so verdicts are bit-exact against the jnp reference and host numpy.

Per-probe scalars arrive as (P, 1) columns; W is the padded window length
(host pads to a multiple of 128 lanes).  Invalid lanes (j >= n_valid) are
masked out of both reductions, so empty windows yield found=0, lt=0.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

B_BLK = 8  # probes per grid step


def _kernel(seg_ref, base_ref, slope_ref, rlo_ref, nval_ref, cand_ref, corr_ref,
            found_ref, lt_ref):
    W = corr_ref.shape[1]
    j = jax.lax.broadcasted_iota(jnp.int32, (corr_ref.shape[0], W), 1)
    ranks = rlo_ref[...] + j
    di = (ranks - seg_ref[...]).astype(jnp.float32)
    pred = base_ref[...] + jnp.rint(slope_ref[...] * di).astype(jnp.int32)
    ids = pred + corr_ref[...]
    valid = j < nval_ref[...]
    eq = valid & (ids == cand_ref[...])
    lt = valid & (ids < cand_ref[...])
    found_ref[...] = eq.any(axis=1, keepdims=True).astype(jnp.int32)
    lt_ref[...] = lt.sum(axis=1, keepdims=True).astype(jnp.int32)


@partial(jax.jit, static_argnames=("interpret",))
def probe_batch(
    seg_starts: jax.Array,  # (P, 1) int32
    bases: jax.Array,  # (P, 1) int32
    slopes: jax.Array,  # (P, 1) float32
    r_lo: jax.Array,  # (P, 1) int32
    n_valid: jax.Array,  # (P, 1) int32
    cands: jax.Array,  # (P, 1) int32
    corr: jax.Array,  # (P, W) int32
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Probe P windows -> (found (P,1) int32, lt (P,1) int32)."""
    P, W = corr.shape
    pad = (-P) % B_BLK
    scalars = [seg_starts, bases, slopes, r_lo, n_valid, cands]
    if pad:
        scalars = [jnp.pad(a, ((0, pad), (0, 0))) for a in scalars]
        corr = jnp.pad(corr, ((0, pad), (0, 0)))
    col_spec = pl.BlockSpec((B_BLK, 1), lambda i: (i, 0))
    win_spec = pl.BlockSpec((B_BLK, W), lambda i: (i, 0))
    found, lt = pl.pallas_call(
        _kernel,
        grid=((P + pad) // B_BLK,),
        in_specs=[col_spec] * 6 + [win_spec],
        out_specs=[col_spec, col_spec],
        out_shape=[
            jax.ShapeDtypeStruct((P + pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((P + pad, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(*scalars, corr)
    return found[:P], lt[:P]
