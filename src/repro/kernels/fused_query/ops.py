"""Host bridge: a ranked batch -> MaxScore peel -> fused kernel dispatches.

``fused_topk_batch`` answers a whole shard batch of ranked queries with one
Pallas dispatch per candidate-size bucket.  Per item it first mirrors ``rank.topk.topk_query``'s
host phases *exactly* — required-term conjunctive seeding, the essential-term
peel (terms by descending upper bound, merged while an unseen document could
still reach the running threshold θ), and the exhaustive-cutoff shortcut —
because those phases are sequential by nature (θ tightens after every
decode).  What remains per item is the probe tail: surviving candidates ×
non-essential terms.  The multi-phase path walks that tail as hundreds of
tiny host<->device round trips (ε-window probe, correction unpack, payload
unpack, impact add, host select per term); here the tail of *every* item in
the batch becomes lanes of one (query, term, candidate, window) tile and a
``fused_topk`` dispatch per bucket returns each query's final top-k.

Exactness: candidates are dropped only when
``partial + Σ_tail seg_ub < max(floor + 1, θ)`` — θ is the kth largest
partial, so at least k candidates finish >= θ and nothing below the bound can
enter the top-k; ties at the bound are kept.  Survivors get *complete*
scores in-kernel (every tail term probed), so the final selection is the
oracle's — bit-identical to the multi-phase path, which the tests and
benchmarks assert.

Tail lanes come in two flavours:
  * learned-codec terms with a narrow rank bracket -> real ε-window lanes
    (the kernel re-runs guided search + in-register unpack);
  * classical-codec terms, width >= 32, or brackets wider than W_CAP ->
    resolved on the host (binary search / window decode) into a 1-lane
    window whose segment line reproduces the known doc id, with the payload
    words still unpacked in-register at the found rank.

Axes are padded to power-of-two buckets (rows to 8·2^k, candidates to
128·2^k, windows to 2^k) so jax.jit compiles a handful of
shapes — the same recompile-convoy discipline as the boolean path, which
``Session.warm()`` pre-triggers.  Candidate counts are heavy-tailed, so rows
are *grouped* by candidate bucket, one dispatch per populated bucket: a
handful of dispatches per batch instead of one maximally-padded tile (or
hundreds of multi-phase host hops).

When the shard carries a resident ``DeviceArena`` (kernels.arena), items
without required terms and with k <= DENSE_MAX_K skip the host peel
entirely: the whole scoring loop — gather, accumulate, θ-peel — runs as
**one** jitted dispatch over the resident impact table
(kernels.fused_query.dense), the host contributing only the (Q, T) term-id
tile.  Dispatches are *pipelined*: dense groups launch first and stay in
flight while the host peels and packs the legacy items, and their outputs
are materialized only at merge time — host plan/pack of the next group
overlaps device execution of the previous one.  ``RankedStats`` splits the
wall into ``fused_kernel_ns`` (blocked on device) and ``fused_bridge_ns``
(host bridge) so the roofline measures the kernel, not Python.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from repro.kernels.fused_query.dense import DENSE_MAX_K

from repro.kernels.fused_query.kernel import NEVER, fused_topk
from repro.kernels.fused_query.ref import fused_topk_ref
from repro.obs import trace
from repro.rank.score import TopKResult, select_topk
from repro.rank.topk import _EMPTY, _exhaustive, _kth_partial, _merge_add

_CANDQ = 128  # candidate-axis bucket quantum
_ROWQ = 8  # query-row bucket quantum
W_CAP = 32  # widest ε-window shipped to the kernel; wider lanes resolve on host


def _bucket(n: int, quantum: int) -> int:
    """Round n up to quantum * 2^k — bounds the number of jit shapes."""
    b = quantum
    while b < n:
        b *= 2
    return b


@dataclass
class _Pending:
    """One item's kernel-bound remainder after the host peel."""

    cands: np.ndarray  # (C,) int64 surviving candidates, ascending
    partial: np.ndarray  # (C,) int64 partial scores from essential terms
    tail: list  # non-essential term ids, descending upper bound
    k: int
    floor: int


def _peel(src, terms, k, required, floor, cutoff, stats):
    """topk_query's host phases, stopping where the probe tail begins.

    Returns a finished TopKResult when the item never reaches the tail
    (trivial/exhaustive/fully-peeled), else a _Pending for the kernel.
    """
    if k <= 0:
        return _EMPTY
    stats.queries += 1
    terms = sorted({int(t) for t in terms if src.n(int(t)) > 0})
    req_all = {int(r) for r in required}
    req = [t for t in sorted(req_all) if src.n(t) > 0]
    if len(req) < len(req_all):
        return _EMPTY  # a required term absent on this shard: empty AND
    if not terms:
        return _EMPTY
    stats.exhaustive_postings += sum(src.n(t) for t in terms)

    if not req and sum(src.n(t) for t in terms) <= cutoff:
        stats.exhaustive_queries += 1
        return _exhaustive(src, terms, k, floor, stats, None)

    optional = [t for t in terms if t not in set(req)]
    if req:
        req = sorted(req, key=src.n)
        cands, partial = src.full(req[0])
        partial = partial.astype(np.int64)
        stats.scored_postings += len(cands)
        for t in req[1:]:
            if len(cands) == 0:
                return _EMPTY
            found, q = src.probe(t, cands)
            stats.probed_postings += len(cands)
            cands, partial = cands[found], partial[found] + q[found]
        if len(cands) == 0:
            return _EMPTY
        accepting_new = False
    else:
        cands = np.zeros(0, np.int32)
        partial = np.zeros(0, np.int64)
        accepting_new = True

    optional.sort(key=lambda t: (-src.ub(t), t))
    ubs = np.array([src.ub(t) for t in optional], np.int64)
    suffix = np.concatenate([np.cumsum(ubs[::-1])[::-1], [0]])
    theta = _kth_partial(partial, k)
    j = 0
    while j < len(optional):
        if not (accepting_new and suffix[j] >= max(floor + 1, theta)):
            break
        ids, q = src.full(optional[j])
        stats.scored_postings += len(ids)
        cands, partial = _merge_add(cands, partial, ids, q)
        theta = max(theta, _kth_partial(partial, k))
        j += 1
    tail = optional[j:]
    if not tail or len(cands) == 0:
        return select_topk(cands, partial, k, floor)

    # joint candidate prune at segment granularity: everything below cannot
    # reach the threshold even if every tail term pays its block max
    alive_min = max(floor + 1, theta)
    bound = partial.copy()
    for t in tail:
        bound += src.seg_ub(t, np.asarray(cands, np.int64)).astype(np.int64)
    keep = bound >= alive_min
    cands, partial = cands[keep], partial[keep]
    if len(cands) == 0:
        return select_topk(cands, partial, k, floor)
    stats.probed_postings += len(cands) * len(tail)
    return _Pending(np.asarray(cands, np.int64), partial, tail, k, floor)


def _window_ranks(rlo, wlen):
    """Flatten per-candidate [rlo, rlo+wlen) brackets into one rank vector."""
    lens = np.asarray(wlen, np.int64)
    if lens.max(initial=0) <= 1:  # the common case: every window resolved
        return np.asarray(rlo, np.int64)
    first = np.repeat(np.cumsum(lens) - lens, lens)
    return np.repeat(rlo, lens) + np.arange(len(first), dtype=np.int64) - first


def _gather_words(stream, word_idx, use):
    """Lo/hi packed-word pairs at word_idx where use, 0 elsewhere/out-of-range
    — the host half of the kernel's unpack_bits_at replication."""
    s = np.asarray(stream, np.uint32)
    lo = np.zeros(word_idx.shape, np.uint32)
    hi = np.zeros(word_idx.shape, np.uint32)
    n = len(s)
    if n and use.any():
        wi = np.clip(word_idx, 0, n - 1)
        lo[use] = s[wi[use]]
        nxt = use & (word_idx + 1 < n)
        hi[nxt] = s[(wi + 1)[nxt]]
    return lo, hi


def _term_lanes(src, t, cands, pbits):
    """One (item, tail-term) slot -> per-candidate window lanes + streams.

    Returns (rlo, wlen, start, base, slope, width, cmin, corr_words,
    use_corr, stream_bytes); resolved lanes carry use_corr=False and a
    segment line that reproduces the known doc id exactly.
    """
    from repro.postings.search import _touched_words, decode_window, rank_windows

    C = len(cands)
    rlo = np.zeros(C, np.int64)
    wlen = np.zeros(C, np.int64)
    start = np.zeros(C, np.int64)
    base = np.zeros(C, np.int64)
    slope = np.zeros(C, np.float32)
    use_corr = np.zeros(C, bool)
    tm = src.term_model(t)
    stream_bytes = 0

    if tm is not None and 0 < tm.width < 32:
        width, cmin, corr_words = int(tm.width), int(tm.corr_min), tm.corr_words
        seg, r_lo, r_hi = rank_windows(tm, cands)
        lens = np.maximum(r_hi - r_lo + 1, 0)
        wide = lens > W_CAP
        narrow = ~wide & (lens > 0)
        rlo[narrow] = r_lo[narrow]
        wlen[narrow] = lens[narrow]
        start[narrow] = tm.starts[seg[narrow]]
        base[narrow] = tm.bases[seg[narrow]]
        slope[narrow] = tm.slopes[seg[narrow]]
        use_corr[narrow] = True
        if narrow.any():
            # touched correction words of every narrow lane, for the roofline
            stream_bytes += 4 * _touched_words(
                _window_ranks(rlo[narrow], wlen[narrow]), width
            )
        if wide.any():  # outlier brackets: host-decode, don't widen the batch
            widx = np.nonzero(wide)[0]
            lens_w = lens[widx].astype(np.int64)
            probe_of = np.repeat(widx, lens_w)
            loc = np.repeat(np.arange(len(widx)), lens_w)
            first = np.repeat(np.cumsum(lens_w) - lens_w, lens_w)
            fl_ranks = r_lo[probe_of] + (np.arange(len(probe_of)) - first)
            ids_dec = decode_window(tm, seg[probe_of], fl_ranks)
            dw = cands[probe_of]
            eqc = np.bincount(loc, weights=(ids_dec == dw), minlength=len(widx))
            ltc = np.bincount(loc, weights=(ids_dec < dw), minlength=len(widx))
            stream_bytes += 4 * _touched_words(fl_ranks, width)
            hit = eqc > 0
            h = widx[hit]
            rlo[h] = (r_lo[widx] + ltc.astype(np.int64))[hit]
            wlen[h] = 1
            base[h] = cands[h] - cmin  # line reproduces the id; corr zeroed
    else:
        # classical codec (or width >= 32): rank by binary search in the
        # cached decode; a found candidate becomes a 1-lane resolved window
        width, cmin, corr_words = 0, 0, np.zeros(0, np.uint32)
        p = src.postings(t)
        rank = np.searchsorted(p, cands).astype(np.int64)
        found = (rank < len(p)) & (p[np.minimum(rank, max(len(p) - 1, 0))] == cands)
        rlo[found] = rank[found]
        wlen[found] = 1
        base[found] = cands[found]

    valid = wlen > 0
    if valid.any():
        stream_bytes += 4 * _touched_words(_window_ranks(rlo[valid], wlen[valid]), pbits)
    return rlo, wlen, start, base, slope, width, cmin, corr_words, use_corr, stream_bytes


def fused_topk_batch(
    src,
    items,
    *,
    exhaustive_cutoff: int = 2048,
    stats=None,
    use_kernel: bool = True,
    interpret: bool | None = None,
):
    """Answer [(terms, k, required, floor), ...] with fused dispatches.

    ``src`` is a shard _RankedSource (needs the RankedSource protocol plus
    term_model/postings/payload_words/payload_bits).  Returns one TopKResult
    per item, in *local* doc ids, bit-identical to looping topk_query.
    """
    from repro.rank.topk import RankedStats

    stats = stats if stats is not None else RankedStats()
    t_all0 = time.perf_counter_ns()
    kernel_ns0 = stats.fused_kernel_ns
    results: list = [None] * len(items)

    # split: items a resident arena can answer in one dense dispatch (no
    # required terms, peelable k) never touch the host peel at all
    arena = getattr(src, "arena", None) if use_kernel else None
    dense_items: list[tuple[int, list[int], int, int]] = []
    legacy: list[int] = []
    for i, (terms, k, required, floor) in enumerate(items):
        if arena is None or len(required) or not (0 < k <= DENSE_MAX_K):
            legacy.append(i)
            continue
        stats.queries += 1
        tt = sorted({int(t) for t in terms if src.n(int(t)) > 0})
        if not tt:
            results[i] = _EMPTY
            continue
        n_sum = sum(src.n(t) for t in tt)
        stats.exhaustive_postings += n_sum
        stats.scored_postings += n_sum
        stats.exhaustive_queries += 1
        dense_items.append((i, tt, int(k), int(floor)))

    # pipelined dispatch: dense groups launch first and stay in flight on
    # the device while the host peels and packs the legacy items below
    inflight = _dispatch_dense(arena, dense_items, stats) if dense_items else []

    pend: list[tuple[int, _Pending]] = []
    for i in legacy:
        terms, k, required, floor = items[i]
        r = _peel(src, terms, k, required, floor, exhaustive_cutoff, stats)
        if isinstance(r, _Pending):
            pend.append((i, r))
        else:
            results[i] = r

    if pend:
        # Candidate counts are heavy-tailed (median ~100, max = shard size):
        # a single dense C = max(C_i) tile would make every query pay the
        # widest query's candidate axis.  Group rows by power-of-two
        # candidate bucket instead — one dispatch per populated bucket (a
        # handful per batch, vs hundreds of per-term hops on the multi-phase
        # path), each with a tight (T, C, W) tile for its rows.
        pbits = int(src.payload_bits)
        groups: dict[int, list[tuple[int, _Pending]]] = {}
        for i, p in pend:
            groups.setdefault(_bucket(len(p.cands), _CANDQ), []).append((i, p))
        for C, grp in sorted(groups.items()):
            _dispatch_group(
                src, grp, C, pbits, stats, results,
                use_kernel=use_kernel, interpret=interpret,
            )

    # deferred merge: only now block on the in-flight dense outputs
    for fut in inflight:
        _extract_dense(fut, stats, results)
    stats.fused_bridge_ns += max(
        0, (time.perf_counter_ns() - t_all0) - (stats.fused_kernel_ns - kernel_ns0)
    )
    return results


def _dispatch_dense(arena, dense_items, stats):
    """Dense-eligible items -> one resident-arena dispatch per (k) bucket.

    Returns in-flight handles (device arrays still executing); the caller
    materializes them at merge time — that deferral is the pipeline.
    """
    from repro.kernels.fused_query import dense

    tp = dense.tile_params()
    groups: dict[int, list] = {}
    for it in dense_items:
        groups.setdefault(_bucket(it[2], 1), []).append(it)
    inflight = []
    for kb, grp in sorted(groups.items()):
        Qb = _bucket(len(grp), tp["row_quantum"])
        T = _bucket(max(len(tt) for _, tt, _, _ in grp), tp["term_quantum"])
        qt = np.full((Qb, T), -1, np.int32)
        floors = np.zeros(Qb, np.int32)
        for row, (_, tt, _, fl) in enumerate(grp):
            qt[row, : len(tt)] = tt
            floors[row] = fl
        stats.fused_queries += len(grp)
        stats.fused_lanes += sum(arena.lanes(tt) for _, tt, _, _ in grp)
        # stream traffic: the table rows each live term slot gathers
        stats.fused_stream_bytes += (
            sum(len(tt) for _, tt, _, _ in grp) * arena.n_docs * arena.itemsize
        )
        out = dense.dense_topk(arena, qt, floors, k=kb)
        inflight.append((arena, grp, kb, Qb, T, out))
    return inflight


def _extract_dense(fut, stats, results):
    """Materialize one in-flight dense dispatch and merge its rows."""
    arena, grp, kb, Qb, T, out = fut
    n_docs, isz = arena.n_docs, arena.itemsize
    with trace.span("kernel.fused_query", queries=int(Qb), terms=int(T),
                    k=int(kb), dense=1, candidates=int(n_docs)):
        t0 = time.perf_counter_ns()
        ids_o, sc_o, rounds = (np.asarray(x) for x in out)
        stats.fused_kernel_ns += time.perf_counter_ns() - t0
    # device traffic actually performed: table-row gather, accumulator,
    # one accumulator scan per peel round performed, in/out tiles
    stats.fused_device_bytes += (
        Qb * T * n_docs * isz
        + Qb * n_docs * 4
        + int(rounds) * Qb * n_docs * 4
        + Qb * T * 4 + Qb * 4
        + 2 * Qb * kb * 4
    )
    for row, (i, _tt, k, _fl) in enumerate(grp):
        hit = sc_o[row] > 0  # non-empty heap slots form a prefix
        results[i] = TopKResult(
            ids=ids_o[row][hit][:k].astype(np.int32),
            scores=sc_o[row][hit][:k].astype(np.int64),
        )


def _dispatch_group(src, pend, C, pbits, stats, results, *, use_kernel, interpret):
    """One candidate-bucket group -> one fused kernel dispatch."""
    T = max(len(p.tail) for _, p in pend)
    K = min(max(p.k for _, p in pend), C)
    Qb = _bucket(len(pend), _ROWQ)

    lanes = []  # (row, slot, C_i, lane data) from the host window builder
    Wmax, stream_bytes = 1, 0
    for row, (_, p) in enumerate(pend):
        for slot, t in enumerate(p.tail):
            ln = _term_lanes(src, t, p.cands, pbits)
            Wmax = max(Wmax, int(ln[1].max()) if len(ln[1]) else 1)
            stream_bytes += ln[9]
            lanes.append((row, slot, t, len(p.cands), ln))
    W = _bucket(Wmax, 1)  # power of two from 1: most windows resolve to 1 lane

    width_a = np.zeros((Qb, T), np.uint32)
    cmin_a = np.zeros((Qb, T), np.int32)
    rlo_a = np.zeros((Qb, T, C), np.int32)
    wlen_a = np.zeros((Qb, T, C), np.int32)
    start_a = np.zeros((Qb, T, C), np.int32)
    base_a = np.zeros((Qb, T, C), np.int32)
    slope_a = np.zeros((Qb, T, C), np.float32)
    clo_a = np.zeros((Qb, T, C, W), np.uint32)
    chi_a = np.zeros((Qb, T, C, W), np.uint32)
    plo_a = np.zeros((Qb, T, C, W), np.uint32)
    phi_a = np.zeros((Qb, T, C, W), np.uint32)
    cand_a = np.full((Qb, C), NEVER, np.int32)
    part_a = np.zeros((Qb, C), np.int32)
    floor_a = np.zeros((Qb, 1), np.int32)

    for row, (_, p) in enumerate(pend):
        n = len(p.cands)
        cand_a[row, :n] = p.cands
        part_a[row, :n] = p.partial
        floor_a[row, 0] = p.floor
    jw = np.arange(W, dtype=np.int64)
    for row, slot, t, n, ln in lanes:
        rlo, wlen, start, base, slope, width, cmin, corr_words, use_corr, _ = ln
        width_a[row, slot] = width
        cmin_a[row, slot] = cmin
        rlo_a[row, slot, :n] = rlo
        wlen_a[row, slot, :n] = wlen
        start_a[row, slot, :n] = start
        base_a[row, slot, :n] = base
        slope_a[row, slot, :n] = slope
        ranks = rlo[:, None] + jw[None, :]
        use = jw[None, :] < wlen[:, None]
        if width:
            clo, chi = _gather_words(
                corr_words, (ranks * width) >> 5, use & use_corr[:, None]
            )
            clo_a[row, slot, :n], chi_a[row, slot, :n] = clo, chi
        plo, phi = _gather_words(src.payload_words(t), (ranks * pbits) >> 5, use)
        plo_a[row, slot, :n], phi_a[row, slot, :n] = plo, phi

    arrays = (width_a, cmin_a, rlo_a, wlen_a, start_a, base_a, slope_a,
              clo_a, chi_a, plo_a, phi_a, cand_a, part_a, floor_a)
    n_lanes = int(wlen_a.sum())
    device_bytes = sum(a.nbytes for a in arrays) + 2 * Qb * K * 4
    stats.fused_queries += len(pend)
    stats.fused_lanes += n_lanes
    stats.fused_stream_bytes += stream_bytes
    stats.fused_device_bytes += device_bytes
    with trace.span("kernel.fused_query", queries=int(Qb), terms=int(T),
                    candidates=int(C), window=int(W), k=int(K),
                    lanes=n_lanes, bytes=int(device_bytes)):
        if use_kernel:
            import jax.numpy as jnp

            t0 = time.perf_counter_ns()
            ids_o, sc_o = fused_topk(
                *(jnp.asarray(a) for a in arrays), k=K, pbits=pbits,
                interpret=interpret,
            )
            ids_o, sc_o = np.asarray(ids_o), np.asarray(sc_o)
            stats.fused_kernel_ns += time.perf_counter_ns() - t0
        else:
            ids_o, sc_o = fused_topk_ref(*arrays, k=K, pbits=pbits)

    for row, (i, p) in enumerate(pend):
        hit = sc_o[row] > 0  # non-empty heap slots form a prefix
        results[i] = TopKResult(
            ids=ids_o[row][hit][: p.k].astype(np.int32),
            scores=sc_o[row][hit][: p.k].astype(np.int64),
        )
