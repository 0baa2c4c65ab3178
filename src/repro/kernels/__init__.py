"""Pallas TPU kernels for the perf-critical compute of the learned index.

  membership/    f(t, ·) scoring over doc tiles: MXU matmul + threshold + bit-pack
  bitset/        Algorithm-3 block-bitmap AND + popcount over packed u32 words
  pfor/          OptPFD fixed-width bit-unpack (tier-2 postings decode)
  plm_decode/    learned-codec (plm/rmi) batched segment-eval + correction add
  guided_search/ batched ε-window probes
  bm25_score/    quantized-impact row sums
  fused_query/   the ranked tail (probe, unpack, score, top-k) in one dispatch

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (public
wrapper), ref.py (pure-jnp/numpy oracle).  Every ``interpret`` argument
defaults to None, which ``resolve_interpret`` turns into "interpret unless
the backend is a TPU": compiled Mosaic on the chip, the Pallas interpreter
on the CPU.
"""
from __future__ import annotations


def resolve_interpret(interpret: bool | None = None) -> bool:
    """None -> run the Pallas interpreter exactly when the backend is not a
    TPU; an explicit bool is returned as given (tests force True)."""
    if interpret is not None:
        return bool(interpret)
    import jax

    return jax.default_backend() != "tpu"
