"""Host bridge: plm/rmi word streams -> padded batches -> Pallas decode.

Parses each stream (postings/plm.py layout), bit-unpacks corrections on the
host, pads segment tables to the batch max S and rank axes to a multiple of
128, launches one kernel call for the whole batch, and trims per-list
results.  The uint32 stream fields are reinterpreted as int32 for the kernel
(doc ids < 2^31 by the index contract, enforced in the host decoder)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.plm_decode.kernel import decode_batch
from repro.kernels.plm_decode.ref import SENTINEL
from repro.obs import trace
from repro.postings.plm import parse_stream

_SENTINEL = int(SENTINEL)


def decode_lists(
    streams: list[np.ndarray], lens: list[int], *, interpret: bool | None = None
) -> list[np.ndarray]:
    """Batched exact decode of many plm/rmi streams -> list of int32 id arrays."""
    nonempty = [i for i, n in enumerate(lens) if n > 0]
    out: list[np.ndarray] = [np.zeros(0, np.int32)] * len(lens)
    if not nonempty:
        return out
    parsed = [parse_stream(streams[i], lens[i]) for i in nonempty]
    S = max(len(p[0]) for p in parsed)
    R = -(-max(lens[i] for i in nonempty) // 128) * 128
    B = len(parsed)
    starts = np.full((B, S), _SENTINEL, np.int32)
    bases = np.zeros((B, S), np.int32)
    slopes = np.zeros((B, S), np.float32)
    corr = np.zeros((B, R), np.int32)
    for row, (st, ba, sl, co) in enumerate(parsed):
        s = len(st)
        starts[row, :s] = st.astype(np.int32)
        bases[row, :s] = ba.astype(np.int32)
        slopes[row, :s] = sl
        corr[row, : len(co)] = co.astype(np.int32)
    with trace.span("kernel.plm_decode", lists=int(B), ranks=int(R)):
        ids = np.asarray(
            decode_batch(
                jnp.asarray(starts),
                jnp.asarray(bases),
                jnp.asarray(slopes),
                jnp.asarray(corr),
                interpret=interpret,
            )
        )
    for row, i in enumerate(nonempty):
        out[i] = ids[row, : lens[i]].astype(np.int32)
    return out
