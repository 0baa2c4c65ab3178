"""Host-facing PFor decode: width-bucketed batch decode + exception patching
+ gap prefix-sum, bridging index/compress.py streams to the Pallas kernel."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.index.compress import BLOCK as CBLOCK
from repro.kernels.pfor.kernel import unpack_blocks
from repro.kernels.pfor.ref import BLOCK, words_per_block

assert CBLOCK == BLOCK


def parse_stream(words: np.ndarray, n: int):
    """Split an optpfd_encode stream into per-width block batches.

    Returns (batches, layout): batches[width] = (n_blocks_w, wpb) u32 array;
    layout = list of (width, slot_in_batch, block_len, exceptions[(pos, hi)]).
    """
    batches: dict[int, list[np.ndarray]] = {}
    layout = []
    pos, done = 0, 0
    while done < n:
        h = int(words[pos]); pos += 1
        b, n_exc, blen = h & 0xFF, (h >> 8) & 0xFFFF, h >> 24
        wpb = words_per_block(b)
        n_words = (blen * b + 31) // 32
        chunk = np.zeros(wpb, dtype=np.uint32)
        chunk[:n_words] = words[pos : pos + n_words]
        pos += n_words
        exc = []
        for _ in range(n_exc):
            exc.append((int(words[pos]), int(words[pos + 1])))
            pos += 2
        slot = len(batches.setdefault(b, []))
        batches[b].append(chunk)
        layout.append((b, slot, blen, exc))
        done += blen
    return {w: np.stack(c) for w, c in batches.items()}, layout


def decode_stream(words: np.ndarray, n: int, *, interpret: bool | None = None) -> np.ndarray:
    """Full OptPFD decode via the Pallas kernel; returns doc ids (gaps summed)."""
    batches, layout = parse_stream(words, n)
    decoded = {
        w: np.asarray(unpack_blocks(jnp.asarray(batch), width=w, interpret=interpret))
        for w, batch in batches.items()
    }
    gaps = np.empty(n, dtype=np.uint32)
    out_pos = 0
    for width, slot, blen, exc in layout:
        vals = decoded[width][slot, :blen].copy()
        for p, hi in exc:  # patch pass (<2% of values; host-side)
            vals[p] |= np.uint32(hi << width)
        gaps[out_pos : out_pos + blen] = vals
        out_pos += blen
    return np.cumsum(gaps.astype(np.int64)).astype(np.int32)
