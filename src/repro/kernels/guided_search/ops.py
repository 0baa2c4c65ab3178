"""Host bridge: TermModel + candidates -> padded probe windows -> kernel.

Computes the exact rank brackets on the host (repro.postings.search), gathers
each window's packed corrections with a scattered unpack (only the touched
stream words are read — the count is returned for byte accounting), pads the
window axis to a multiple of 128 lanes and the probe axis to the kernel
block, and launches one probe_batch call for the whole candidate set.

Two guards keep the dense (P, W) layout sane:
  * probes whose bracket exceeds MAX_W ranks (degenerate/low-slope segments
    scan whole segments) are answered on the host instead of inflating every
    row's padding to the outlier's width;
  * P and W are rounded up to power-of-two-ish buckets so jax.jit compiles
    a handful of shapes instead of one per candidate-set size.

The term model's segment tables (starts/bases/slopes) are index-derived and
live as long as the store, so they ride the device-residency cache
(kernels.arena.resident): uploaded once per model per process, gathered by
segment id *on device* per dispatch — the host boundary only carries the
query-dependent arrays (segment column, brackets, corrections).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.arena import resident
from repro.kernels.guided_search.kernel import probe_batch
from repro.obs import trace

_LANES = 128
MAX_W = 1024  # widest window the kernel pads to; wider brackets go to host


def _bucket(n: int, quantum: int) -> int:
    """Round n up to quantum * 2^k — bounds the number of jit shapes."""
    b = quantum
    while b < n:
        b *= 2
    return b


def probe_windows(
    tm, cands: np.ndarray, *, interpret: bool | None = None
) -> tuple[np.ndarray, np.ndarray, int]:
    """Batched guided probes of one term -> (found bool, rank int64, bytes).

    `tm` is a repro.postings.search.TermModel; `bytes` counts the packed
    correction stream bytes the windows touched (metadata is accounted by
    the caller at model-load time).
    """
    from repro.postings.search import _touched_words, decode_window, flatten_windows
    from repro.index.compress import unpack_bits_at

    d = np.asarray(cands, np.int64)
    P = len(d)
    seg, r_lo, lens, probe_of, col, flat_ranks = flatten_windows(tm, d)
    if len(flat_ranks) == 0:
        return np.zeros(P, bool), r_lo.copy(), 0
    touched = 4 * _touched_words(flat_ranks, tm.width)
    found = np.zeros(P, bool)
    rank = r_lo.copy()

    wide = lens > MAX_W
    if wide.any():  # outlier brackets: host-decode, don't widen the batch
        in_wide = wide[probe_of]
        ids = decode_window(tm, seg[probe_of[in_wide]], flat_ranks[in_wide])
        dw = d[probe_of[in_wide]]
        np.logical_or.at(found, probe_of[in_wide], ids == dw)
        np.add.at(rank, probe_of[in_wide], (ids < dw).astype(np.int64))
        keep = ~in_wide
        probe_of, col, flat_ranks = probe_of[keep], col[keep], flat_ranks[keep]
        lens = np.where(wide, 0, lens)
        if len(flat_ranks) == 0:
            return found, rank, touched

    W = _bucket(int(lens.max()), _LANES)
    Pb = _bucket(P, 8)
    corr_vals = unpack_bits_at(tm.corr_words, tm.width, flat_ranks).astype(np.int64)
    corr = np.zeros((Pb, W), np.int32)
    corr[probe_of, col] = (corr_vals + tm.corr_min).astype(np.int32)

    def colv(a, dtype):
        out = np.zeros(Pb, dtype)
        out[:P] = np.asarray(a, dtype)
        return jnp.asarray(out.reshape(Pb, 1))

    # resident segment tables, gathered on device by the padded seg column
    # (pad rows gather segment 0; their lens column is 0, so the kernel
    # never reads the gathered values)
    segd = colv(seg, np.int64)
    with trace.span("kernel.guided_search", probes=int(Pb), window=int(W),
                    bytes=int(touched)):
        kf, lt = probe_batch(
            jnp.take(resident(tm.starts), segd, axis=0).astype(jnp.int32),
            jnp.take(resident(tm.bases), segd, axis=0).astype(jnp.int32),
            jnp.take(resident(tm.slopes), segd, axis=0).astype(jnp.float32),
            colv(r_lo, np.int32),
            colv(lens, np.int32),
            colv(d, np.int32),
            jnp.asarray(corr),
            interpret=interpret,
        )
    kf = np.asarray(kf).reshape(-1)[:P].astype(bool)
    lt = np.asarray(lt).reshape(-1)[:P].astype(np.int64)
    narrow = lens > 0
    found[narrow] |= kf[narrow]
    rank[narrow] += lt[narrow]
    return found, rank, touched
