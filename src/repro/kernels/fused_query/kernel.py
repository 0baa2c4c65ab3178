"""Fused ranked-query kernel: candidates -> ε-window probe -> top-k, one dispatch.

The multi-phase ranked path answers a batch with five host<->device hops:
guided ε-window probes, correction unpack, payload unpack, impact summation,
and host-side top-k selection.  This kernel collapses the tail of that
pipeline into a single Pallas dispatch over (query, term, candidate, window)
tiles: per lane it evaluates the rank-model segment line (same
single-multiply float32 + rint formula as plm_decode / guided_search),
unpacks the bit-packed correction *and* payload words in-register from
pre-gathered word pairs (the shift/or/mask math of
repro.index.compress.unpack_bits_at, width < 32), compares the reconstructed
doc id against the candidate, and accumulates int32 BM25 impact sums.  The
per-query top-k heap lives in VMEM scratch: K peeled rounds over the
surviving scores, each an exact integer max followed by the smallest lane
holding it.  Candidates arrive sorted ascending, so score ties resolve to the
smaller doc id — bit-identical to rank.score.select_topk's (score desc, id
asc) ordering.

Shapes (Q = padded queries, T = tail terms, C = candidates, W = window):
  per (Q, T):       width u32, corr_min i32
  per (Q, T, C):    rlo, wlen, segstart, base i32; slope f32
  per (Q, T, C, W): corr/payload lo+hi word pairs u32
  per (Q, C):       candidate ids (pad = NEVER), partial scores i32
  per (Q, 1):       score floor i32
Outputs (Q, K) ids / scores; empty slots are id -1, score 0 (floor >= 0 and
quantized impacts >= 1 guarantee real hits score > 0).

Tiling: the grid is (query, candidate block, window lane).  Each step holds
one query's (T, C_BLK) tiles with candidates on the 128-wide lane axis and
terms on sublanes; the window axis is the innermost grid axis, accumulating
matched impacts into a (1, C_BLK) VMEM scratch, and the last window step
peels that block's top-K.  A query whose candidate axis spans several blocks
comes back as one top-K per block, and ``fused_topk`` merges them on the
device by (score desc, id asc), which is exact because each block's top-K
holds every candidate of that block that can reach the global top-K.
The per-step footprint is bounded by C_BLK whatever the candidate bucket.

MaxScore-style early termination happens at two levels: the host bridge
(ops.py) peels essential terms and drops candidates whose per-segment upper
bound cannot reach the running threshold, and in-kernel the floor mask
zeroes lanes that cannot enter the heap.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

C_BLK = 8192  # candidate lanes per grid step (a multiple of 128)
# scoped VMEM per step grows with the term slots T (sublanes) x C_BLK.
# Compiled for v5e at C_BLK lanes, W = 32, k = 100: T <= 8 fits 8 MiB,
# T = 16 overflows the 16 MiB default, 32 MiB holds T = 32
VMEM_LIMIT = 32 << 20
NEVER = 1 << 30  # candidate-pad sentinel: above any doc id a stream can hold


def _unpack(lo, hi, shift, mask):
    """In-register word-pair unpack, the unpack_bits_at little-endian layout."""
    up = jnp.where(shift > jnp.uint32(0), hi << (jnp.uint32(32) - shift), jnp.uint32(0))
    return ((lo >> shift) | up) & mask


def _make_kernel(k: int, pbits: int):
    def _kernel(width_ref, cmin_ref, rlo_ref, wlen_ref, start_ref, base_ref,
                slope_ref, clo_ref, chi_ref, plo_ref, phi_ref, cand_ref,
                part_ref, floor_ref, ids_ref, scores_ref, acc_ref, alive_ref):
        j = pl.program_id(2)  # window lane

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # guided ε-window search: evaluate the segment line at rank rlo + j
        ranks = rlo_ref[0] + j  # (T, C)
        di = (ranks - start_ref[0]).astype(jnp.float32)
        pred = base_ref[0] + jnp.rint(slope_ref[0] * di).astype(jnp.int32)
        w = width_ref[0].astype(jnp.uint32)  # (T, 1)
        cmask = (jnp.uint32(1) << w) - jnp.uint32(1)
        cshift = (ranks.astype(jnp.uint32) * w) & jnp.uint32(31)
        corr = _unpack(clo_ref[0, 0], chi_ref[0, 0], cshift, cmask).astype(jnp.int32)
        ids = pred + corr + cmin_ref[0]
        # list ids strictly increase inside a window: at most one lane matches
        eq = (j < wlen_ref[0]) & (ids == cand_ref[0])
        pshift = (ranks.astype(jnp.uint32) * jnp.uint32(pbits)) & jnp.uint32(31)
        pmask = jnp.uint32((1 << pbits) - 1)
        imp = _unpack(plo_ref[0, 0], phi_ref[0, 0], pshift, pmask).astype(jnp.int32)
        acc_ref[...] += jnp.where(eq, imp, 0).sum(axis=0, keepdims=True)

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            # top-k heap in scratch: floor-mask, then K peeled rounds
            score = part_ref[0] + acc_ref[...]  # (1, C)
            alive_ref[...] = jnp.where(score > floor_ref[0], score, 0)
            cand = cand_ref[0]
            n = cand.shape[1]
            ci = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
            ki = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

            def peel(r, carry):
                out_i, out_s = carry
                m = alive_ref[...]
                best = jnp.max(m, axis=1, keepdims=True)
                # first lane holding the max: the smaller doc id on ties
                pos = jnp.min(jnp.where(m == best, ci, n), axis=1, keepdims=True)
                oh = ci == pos
                sid = jnp.where(
                    best > 0, jnp.where(oh, cand, 0).sum(axis=1, keepdims=True), -1
                )
                alive_ref[...] = jnp.where(oh, 0, m)
                return (jnp.where(ki == r, sid, out_i), jnp.where(ki == r, best, out_s))

            out_i, out_s = jax.lax.fori_loop(
                0, k, peel,
                (jnp.full((1, k), -1, jnp.int32), jnp.zeros((1, k), jnp.int32)),
            )
            ids_ref[0, 0] = out_i
            scores_ref[0, 0] = out_s

    return _kernel


def _block_topk(width, cmin, rlo, wlen, start, base, slope, clo, chi, plo,
                phi, cand, part, floor, *, k, pbits, interpret):
    """The pallas_call: -> (Q, n_blocks, k) ids + scores, one top-k per
    query per candidate block."""
    Q, T, C = rlo.shape
    W = clo.shape[3]
    cb = min(C, C_BLK)
    nb = C // cb

    def win(a):  # (Q, T, C, W) -> (Q, W, T, C): candidates on lanes
        return jnp.transpose(a, (0, 3, 1, 2))

    qt = pl.BlockSpec((1, T, 1), lambda q, c, j: (q, 0, 0))
    qtc = pl.BlockSpec((1, T, cb), lambda q, c, j: (q, 0, c))
    qwtc = pl.BlockSpec((1, 1, T, cb), lambda q, c, j: (q, j, 0, c))
    qc = pl.BlockSpec((1, 1, cb), lambda q, c, j: (q, 0, c))
    q1 = pl.BlockSpec((1, 1, 1), lambda q, c, j: (q, 0, 0))
    out = pl.BlockSpec((1, 1, 1, k), lambda q, c, j: (q, c, 0, 0))
    ids, scores = pl.pallas_call(
        _make_kernel(k, pbits),
        grid=(Q, nb, W),
        in_specs=[qt, qt, qtc, qtc, qtc, qtc, qtc, qwtc, qwtc, qwtc, qwtc,
                  qc, qc, q1],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((Q, nb, 1, k), jnp.int32),
                   jax.ShapeDtypeStruct((Q, nb, 1, k), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, cb), jnp.int32),
                        pltpu.VMEM((1, cb), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )(width[..., None], cmin[..., None], rlo, wlen, start, base, slope,
      win(clo), win(chi), win(plo), win(phi), cand[:, None, :], part[:, None, :],
      floor[:, :, None])
    return ids[:, :, 0], scores[:, :, 0]


@partial(jax.jit, static_argnames=("k", "pbits", "interpret"))
def fused_topk(width, cmin, rlo, wlen, start, base, slope, clo, chi, plo, phi,
               cand, part, floor, *, k: int, pbits: int,
               interpret: bool | None = None):
    """One dispatch: (Q, T, C, W) probe tiles -> (Q, k) top-k ids + scores.

    C is a multiple of C_BLK or below it, and k <= C."""
    C = rlo.shape[2]
    cb = min(C, C_BLK)
    assert C % cb == 0 and k <= C, (C, cb, k)
    ids, scores = _block_topk(
        width, cmin, rlo, wlen, start, base, slope, clo, chi, plo, phi, cand,
        part, floor, k=min(k, cb), pbits=pbits,
        interpret=resolve_interpret(interpret),
    )
    if ids.shape[1] == 1:
        return ids[:, 0], scores[:, 0]
    # merge the blocks' heaps: score desc, then id asc (empty slots score 0)
    Q = ids.shape[0]
    neg, ids = jax.lax.sort(
        (-scores.reshape(Q, -1), ids.reshape(Q, -1)), dimension=1, num_keys=2
    )
    return ids[:, :k], -neg[:, :k]
