"""Doc-partitioned shard executor: one shard of the Boolean serving engine.

``ShardEngine`` owns everything one document partition needs to serve its
slice of a query batch end to end:

  * a learned-Bloom slice (doc-embedding rows [lo, hi) of the global model +
    the global per-term zero-FN thresholds — a min over a superset of each
    shard's positives, so the zero-false-negative guarantee survives
    partitioning) and the dense EngineState built from it;
  * a local compressed tier-2 store (HybridPostings over local doc ids,
    built lazily or preloaded from the persistent shard-store);
  * its own guided-probe ``TermModel``s (GuidedPostings) and decode-cost
    budgeted ``CostLRU``, with per-shard ``serving_stats()``.

``execute`` consumes the planner's ShardPlan (run mask + probe routes) and
returns its results as a *packed bitmap* over local doc ids — 32x cheaper to
move to the merging facade than id lists, and word-copyable into the global
bitmap because shard boundaries are aligned to 32-doc words
(``shard_ranges``).

``query_topk_local`` is the ranked path: the shard runs MaxScore dynamic
pruning (repro.rank.topk) against its tier-2 payload streams — full decodes
through the CostLRU, candidate probes through the guided ε-window rank
models landing directly on rank-aligned payloads, segment-granularity score
bounds from the store — and returns its local top-k in *global* doc ids so
the facade can merge shard heaps and forward score floors.
"""
from __future__ import annotations

import numpy as np

from repro.common.config import LearnedIndexConfig
from repro.core import algorithms as alg
from repro.core.learned_bloom import LearnedBloom
from repro.core.membership import term_rows_per_lane_row
from repro.index.build import InvertedIndex, slice_index
from repro.index.intersect import gallop_membership
from repro.obs import trace
from repro.obs.metrics import Registry
from repro.obs.trace import NULL_SPAN
from repro.rank.score import TopKResult
from repro.rank.topk import RankedStats, topk_query
from repro.serve.cache import CostLRU
from repro.serve.planner import QueryPlan, ShardPlan

WORD_BITS = 32  # packed-bitmap word width; shard boundaries align to this


def shard_ranges(n_docs: int, k: int, *, align: int = WORD_BITS) -> list[tuple[int, int]]:
    """K contiguous doc-id ranges covering [0, n_docs), boundaries aligned.

    Alignment to 32-doc words lets per-shard packed result bitmaps merge into
    the global bitmap by pure word copy (no cross-shard bit shifting).  Small
    collections can yield empty ranges (lo == hi) — the facade skips them.
    """
    if k <= 0:
        raise ValueError(f"need k >= 1 shards, got {k}")
    cuts = [0]
    for i in range(1, k):
        c = int(round(i * n_docs / k / align)) * align
        cuts.append(min(max(c, cuts[-1]), n_docs))
    cuts.append(n_docs)
    return [(cuts[i], cuts[i + 1]) for i in range(k)]


def slice_bloom(lb: LearnedBloom, lo: int, hi: int) -> LearnedBloom:
    """Learned-Bloom restriction to docs [lo, hi), rebased to local ids.

    Slices the doc-embedding table rows (term table, MLP head and τ are
    shared — τ_t fitted over *all* positives lower-bounds the shard's, so
    zero-FN holds locally) and remaps spilled backup keys into the local
    t*n_local + d encoding.
    """
    params = dict(lb.params)
    doc_embed = dict(params["doc_embed"])
    doc_embed["table"] = params["doc_embed"]["table"][lo:hi]
    params["doc_embed"] = doc_embed
    n_local = hi - lo
    keys = lb.backup_keys
    if len(keys):
        t, d = keys // lb.n_docs, keys % lb.n_docs
        sel = (d >= lo) & (d < hi)
        keys = t[sel] * np.int64(n_local) + (d[sel] - lo)  # stays sorted
    return LearnedBloom(params=params, tau=lb.tau, backup_keys=keys, n_docs=n_local)


def pack_ids(ids: np.ndarray, n_docs: int) -> np.ndarray:
    """Sorted unique doc ids -> packed uint32 bitmap (bit d%32 of word d//32)."""
    out = np.zeros((n_docs + WORD_BITS - 1) // WORD_BITS, dtype=np.uint32)
    if len(ids):
        ids = np.asarray(ids, np.int64)
        np.bitwise_or.at(out, ids // WORD_BITS, np.uint32(1) << (ids % WORD_BITS).astype(np.uint32))
    return out


def unpack_row(words: np.ndarray, n_docs: int) -> np.ndarray:
    """Packed uint32 bitmap row -> sorted int32 doc ids (inverse of pack_ids)."""
    bits = np.unpackbits(
        np.ascontiguousarray(words).view(np.uint8), bitorder="little"
    )[:n_docs]
    return np.nonzero(bits)[0].astype(np.int32)


class ShardEngine:
    """Executor for one document partition (the former BooleanEngine core)."""

    def __init__(
        self,
        lb: LearnedBloom,
        inv: InvertedIndex,
        li_cfg: LearnedIndexConfig,
        cfg,  # ServeConfig (typed loosely to avoid a circular import)
        *,
        lo: int = 0,
        hi: int | None = None,
        tier2=None,  # preloaded HybridPostings (the persistent shard-store)
        # global rank.score.ImpactModel, or a zero-arg provider of one (the
        # facade defers the O(n_postings) quantizer fit to first ranked use)
        impact_model=None,
    ):
        self.cfg = cfg
        self.inv = inv
        self.lb = lb
        self.lo = lo
        self.hi = inv.n_docs if hi is None else hi
        self.shard_id = 0  # position in the facade's shard list (it sets this)
        self._tier2 = tier2 if cfg.postings_store == "hybrid" else None
        self._guided = None  # lazy GuidedPostings over tier-2
        self._impact_model = impact_model
        self._ranked = None  # lazy _RankedSource over tier-2 payloads
        self.ranked_stats = RankedStats()
        self._dfs = inv.dfs  # local document frequencies, materialized once
        self._decode_cache: CostLRU[int, np.ndarray] = CostLRU(cfg.cache_budget_bytes)
        self.state = alg.build_engine(
            lb.params, lb.tau, inv,
            truncation_k=li_cfg.truncation_k, block_size=li_cfg.block_size,
        )

    @classmethod
    def from_range(
        cls, lb, inv, li_cfg, cfg, lo: int, hi: int, tier2=None, impact_model=None
    ) -> "ShardEngine":
        """Build the shard by slicing a global model + index to [lo, hi)."""
        return cls(
            slice_bloom(lb, lo, hi), slice_index(inv, lo, hi), li_cfg, cfg,
            lo=lo, hi=hi, tier2=tier2, impact_model=impact_model,
        )

    # ------------------------------------------------------------- stores
    @property
    def n_docs(self) -> int:
        return self.inv.n_docs

    @property
    def local_dfs(self) -> np.ndarray:
        """Per-term local document frequencies (the planner's run/est input)."""
        return self._dfs

    @property
    def tier2(self):
        """Compressed tier-2 postings store (hybrid per-term codec choice)."""
        if self._tier2 is None and self.cfg.postings_store == "hybrid":
            from repro.postings import HybridPostings

            self._tier2 = HybridPostings.from_index(self.inv)
        return self._tier2

    def ensure_payloads(self) -> None:
        """Quantize + attach this shard's payload stream if it can and hasn't.

        Deferred off the Boolean-only path (packing every term costs real
        startup time); the ranked path and the persisting save() force it.
        The values are bit-identical to the global stream's slice because
        the ImpactModel's statistics are collection-global.
        """
        store = self.tier2
        if (
            store is None
            or store.has_payloads
            or self._impact_model is None
            or self.inv.tfs is None
        ):
            return
        if callable(self._impact_model):
            self._impact_model = self._impact_model()
        im = self._impact_model
        store.attach_payloads(
            im.quantize_index(self.inv, lo=self.lo),
            bits=im.params.bits,
            scale=im.scale,
        )

    @property
    def guided(self):
        """Model-guided prober over tier-2 (None when serving raw postings)."""
        if self._guided is None:
            store = self.tier2
            if store is not None and self.cfg.use_guided:
                from repro.postings import GuidedPostings

                self._guided = GuidedPostings(
                    store, fallback=self._postings,
                    use_kernel=self.cfg.guided_kernel,
                    probe_log=getattr(self.cfg, "probe_log", None),
                )
        return self._guided

    def _postings(self, t: int) -> np.ndarray:
        """Fully-decoded postings of term t, via the cost-budgeted LRU."""
        store = self.tier2
        if store is None:
            return self.inv.postings(t)
        hit = self._decode_cache.get(t)
        if hit is None:
            with trace.span("decode.postings", term=int(t)) as sp:
                hit = store.postings(t)
                sp.set(bytes=int(hit.nbytes))
            self._decode_cache.put(t, hit, hit.nbytes)
        return hit

    # ------------------------------------------------------------- ranked
    @property
    def ranked(self) -> "_RankedSource":
        """RankedSource over this shard's payload streams (built on demand)."""
        if self._ranked is None:
            self.ensure_payloads()
            store = self.tier2
            if store is None or not store.has_payloads:
                raise ValueError(
                    "ranked serving needs tier-2 payload streams: build the "
                    "engine from an index with term frequencies (ImpactModel) "
                    "or load a layout-v2 store saved with payloads"
                )
            self._ranked = _RankedSource(self)
        return self._ranked

    @property
    def can_rank(self) -> bool:
        """Whether this shard can serve the ranked tier: its store carries
        payload streams, or can quantize them from the index's tfs."""
        if self.cfg.postings_store != "hybrid":
            return False
        if self._tier2 is not None and self._tier2.has_payloads:
            return True
        return self._impact_model is not None and self.inv.tfs is not None

    def query_topk_local(
        self,
        terms,
        k: int,
        *,
        required=(),
        floor: int = 0,
    ) -> TopKResult:
        """This shard's exact top-k in *global* doc ids — descending score
        with ties ascending id.  ``floor`` is the facade's running k-th best
        score: only strictly better docs can matter here (later shards hold
        larger ids, so floor ties lose)."""
        if self.cfg.ranked.fused_kernel:
            return self.query_topk_batch([(tuple(terms), k, tuple(required), floor)])[0]
        src = self.ranked
        scorer = self._batch_scorer() if self.cfg.ranked.score_kernel else None
        with trace.span("shard.topk", shard=self.shard_id, k=int(k),
                        terms=len(tuple(terms))):
            ans = topk_query(
                src, terms, k,
                required=required, floor=floor,
                exhaustive_cutoff=self.cfg.ranked.topk_exhaustive_cutoff,
                stats=self.ranked_stats, batch_scorer=scorer,
            )
        return TopKResult(
            ids=(ans.ids.astype(np.int64) + self.lo).astype(np.int32),
            scores=ans.scores,
        )

    def query_topk_batch(self, items) -> list[TopKResult]:
        """Batched ranked entry point: [(terms, k, required, floor), ...] ->
        one TopKResult per item, global doc ids.

        With ``ranked.fused_kernel`` the whole batch's probe tail is answered
        by a single ``kernel.fused_query`` dispatch (replacing the per-term
        guided-probe / payload-unpack / score host bridge spans); otherwise
        it loops the multi-phase ``query_topk_local``.  Both paths are
        bit-identical by construction and asserted so in tests/benchmarks.
        """
        # shard-attribute any probe records from in here (query inherited:
        # the facade sets it per query outside, workers leave it batch-wide)
        log = getattr(self.cfg, "probe_log", None)
        ctx = (
            log.context(query=None, shard=self.shard_id)
            if log is not None
            else NULL_SPAN
        )
        if not self.cfg.ranked.fused_kernel:
            with ctx:
                return [
                    self.query_topk_local(t, k, required=r, floor=f)
                    for (t, k, r, f) in items
                ]
        from repro.kernels.fused_query.ops import fused_topk_batch

        src = self.ranked
        with ctx, trace.span("shard.topk_batch", shard=self.shard_id,
                             items=len(items)):
            answers = fused_topk_batch(
                src, items,
                exhaustive_cutoff=self.cfg.ranked.topk_exhaustive_cutoff,
                stats=self.ranked_stats,
            )
        return [
            TopKResult(
                ids=(a.ids.astype(np.int64) + self.lo).astype(np.int32),
                scores=a.scores,
            )
            for a in answers
        ]

    def _batch_scorer(self):
        from repro.kernels.bm25_score.ops import score_candidates

        scale = self.tier2.payload_scale / max(
            (1 << self.tier2.payload_bits) - 1, 1
        )
        return lambda imp: score_candidates(imp, scale)[0]

    # ------------------------------------------------------------- planning
    def route_term(self, t: int, est_cands: int) -> str | None:
        """Cost-model route for term t at the planner's candidate estimate:
        'guided' | 'decode' for learned-codec terms, None when no model
        applies (classical codec, raw store, or guided probing disabled)."""
        g = self.guided
        if g is None:
            return None
        tm = g.term_model(t)
        if tm is None:
            return None
        return "guided" if est_cands * tm.avg_window < tm.n else "decode"

    # ------------------------------------------------------------- execute
    def candidate_mask(self, q: np.ndarray) -> np.ndarray:
        """(Q, T) padded terms -> (Q, n_docs) bool learned-Bloom candidates."""
        if self.cfg.use_kernel and self.cfg.algorithm == "exhaustive":
            return self._kernel_exhaustive(q)
        return alg.run_queries(self.state, q, self.cfg.algorithm)

    def execute(
        self,
        q: np.ndarray,
        plan: ShardPlan | None = None,
        qplans: list[QueryPlan] | None = None,
        mask: np.ndarray | None = None,
    ) -> np.ndarray:
        """Serve the batch's slice on this shard -> (Q, words) packed bitmap
        over local doc ids.  Honors the planner's run mask and probe routes
        when given; without a plan every query runs with local term order.

        ``mask`` lets the facade precompute the learned-Bloom candidates:
        model scoring is one jit dispatch per shard and contends badly when
        issued from concurrent threads, so the facade runs that phase
        serially and fans out only this (numpy probe) phase to its pool.
        The whole call is one ``shard.execute`` span.
        """
        n_queries = q.shape[0]
        with trace.span("shard.execute", shard=self.shard_id, queries=n_queries):
            words = (self.n_docs + WORD_BITS - 1) // WORD_BITS
            out = np.zeros((n_queries, words), dtype=np.uint32)
            run = plan.run if plan is not None else None
            if self.n_docs == 0 or (run is not None and not run.any()):
                return out
            if mask is None:
                # worker path (no facade precompute): span the jit probe so a
                # replica's shipped trace shows model time vs verify time
                with trace.span(
                    "shard.candidate_mask", shard=self.shard_id, queries=n_queries
                ):
                    mask = self.candidate_mask(q)
            log = getattr(self.cfg, "probe_log", None)
            for i in range(n_queries):
                if run is not None and not run[i]:
                    continue
                # probe records inside attribute to (batch-local query i, shard)
                ctx = log.context(query=i, shard=self.shard_id) if log is not None else NULL_SPAN
                with ctx, trace.span("shard.verify", shard=self.shard_id, query=i) as sp:
                    ids = np.nonzero(mask[i])[0].astype(np.int32)
                    sp.set(candidates=int(len(ids)))
                    if self.cfg.verified:
                        if qplans is not None:
                            routes = plan.routes[i] if plan is not None else None
                            ids = self._verify_terms(qplans[i].terms, ids, routes)
                        else:
                            ids = self._verify(q[i], ids)
                    sp.set(results=int(len(ids)))
                out[i] = pack_ids(ids, self.n_docs)
            return out

    def _kernel_exhaustive(self, q: np.ndarray) -> np.ndarray:
        """Pallas path: per-term packed bitmasks, AND-combined per query."""
        import jax.numpy as jnp

        from repro.kernels.membership.ops import score_terms_bitmask

        valid = q >= 0
        flat_terms = jnp.asarray(np.maximum(q, 0).reshape(-1))
        bm = score_terms_bitmask(self.state.params, flat_terms, self.state.tau)
        bm = np.array(bm).reshape(q.shape[0], q.shape[1], -1)  # writable copy
        full = np.uint32(0xFFFFFFFF)
        bm[~valid] = full
        anded = bm[:, 0]
        for t in range(1, q.shape[1]):
            anded = anded & bm[:, t]
        # unpack to bool (D,)
        bits = np.unpackbits(
            anded.view(np.uint8), axis=-1, bitorder="little"
        )[:, : self.state.n_docs].astype(bool)
        bits[~valid.any(axis=1)] = False
        return bits

    # ------------------------------------------------------------- verify
    def _verify(self, query: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Exact candidate re-check, smallest *local* list first (the
        plan-less path: direct shard use and unit tests)."""
        terms = sorted({int(t) for t in query if t >= 0})  # dedupe repeats
        if not terms or len(ids) == 0:
            return ids
        terms.sort(key=lambda t: int(self._dfs[t]))
        return self._verify_terms(tuple(terms), ids)

    def _verify_terms(
        self,
        terms: tuple[int, ...],
        ids: np.ndarray,
        routes: dict[int, str] | None = None,
    ) -> np.ndarray:
        """Exact re-check of candidates against tier-2 in the given term
        order.  Each term filters the (sorted) survivors either by guided
        ε-window probes (learned-codec terms, honoring the planner's route
        hint) or by galloping search over the fully-decoded list."""
        out = ids
        if not terms or len(out) == 0:
            return out
        if int(self._dfs[np.asarray(terms)].min()) == 0:
            return out[:0]  # some term occurs nowhere locally: empty AND
        guided = self.guided
        for t in terms:
            if len(out) == 0:
                break
            if guided is not None:
                hint = routes.get(t) if routes else None
                out = out[guided.contains(t, out, route=hint)]
            else:
                out = out[gallop_membership(self._postings(t), out)]
        return out

    # ------------------------------------------------------------- stats
    def memory_bits(self) -> dict[str, int]:
        """This shard's dense-state + tier-2 bits (facade sums across shards)."""
        s = self.state
        bits = {
            "tier1_bits": int(s.tier1.size * 32),
            "block_bitmap_bits": int(s.block_bitmaps.size * 32),
        }
        if self._tier2 is not None:
            bits["tier2_bits"] = int(self._tier2.size_bits())
            if self._tier2.has_payloads:
                bits["payload_bits"] = int(self._tier2.payload_size_bits())
        return bits

    @property
    def metrics(self) -> Registry:
        """This shard's metrics registry (built lazily so partially-
        constructed test doubles work; collectors close over self, so the
        registry tracks later cache/guided/ranked replacements)."""
        reg = getattr(self, "_metrics", None)
        if reg is None:
            reg = Registry()
            reg.register("range", lambda: {"lo": int(self.lo), "hi": int(self.hi)})
            # 1 = unpacked; r > 1 = the served term table holds r rows per lane row
            reg.register(
                "membership",
                lambda: {"term_rows_per_lane_row": term_rows_per_lane_row(self.state.params)},
            )
            reg.register(
                "decode_cache",
                lambda: self._decode_cache.stats(),
                reset=lambda: self._decode_cache.reset_counters(),
            )
            reg.register(
                "guided",
                lambda: self._guided.stats.as_dict() if self._guided is not None else None,
                reset=lambda: self._guided.reset_stats() if self._guided is not None else None,
            )
            reg.register(
                "ranked",
                lambda: self.ranked_stats.as_dict() if self.ranked_stats.queries else None,
                reset=lambda: setattr(self, "ranked_stats", RankedStats()),
            )
            reg.register(
                "arena",
                lambda: (
                    self._ranked._arena.counters.as_dict()
                    if self._ranked is not None
                    and getattr(self._ranked, "_arena", None)
                    else None
                ),
            )
            self._metrics = reg
        return reg

    def serving_stats(self) -> dict[str, dict]:
        """Hot-path accounting: decode-cache behaviour + guided-probe bytes
        (one registry snapshot — see repro.obs.metrics)."""
        return self.metrics.snapshot()

    def reset_stats(self) -> None:
        """Zero this shard's probe/cache/ranked accounting window.  Owns all
        shard-local state (the facade never reaches into privates); cached
        decodes stay resident so the next pass measures warm serving."""
        self.metrics.reset()


class _RankedSource:
    """rank.topk.RankedSource over one shard's tier-2 payload streams.

    Full decodes go through the shard's decode-cost-budgeted CostLRU (ids
    under the term key the Boolean path shares, payload vectors under a
    ("pay", t) key); probes ride the guided ε-window rank models where the
    term's codec is learned and fall back to binary search in the cached
    decode otherwise.  Either way the payload read is rank-aligned —
    ``payload_at`` touches only the probe's packed words.
    """

    def __init__(self, shard: ShardEngine):
        self._sh = shard
        self._store = shard.tier2
        self._arena = None  # lazy DeviceArena (False = checked, ineligible)

    def n(self, t: int) -> int:
        return int(self._sh._dfs[t])

    def ub(self, t: int) -> int:
        return self._store.term_ub(t)

    def _payloads(self, t: int) -> np.ndarray:
        key = ("pay", t)
        hit = self._sh._decode_cache.get(key)
        if hit is None:
            with trace.span("decode.payloads", term=int(t)) as sp:
                hit = self._store.payloads(t).astype(np.int64)
                sp.set(bytes=int(hit.nbytes))
            self._sh._decode_cache.put(key, hit, hit.nbytes)
        return hit

    def full(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        return self._sh._postings(t), self._payloads(t)

    def probe(self, t: int, cands: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        g = self._sh.guided
        if g is not None:
            # one probe path for every codec: GuidedPostings routes learned
            # terms through ε-windows and classical terms through the cached
            # decode, and its ProbeStats accounting covers both uniformly
            found, rank = g.probe(t, cands)
        else:  # use_guided=False: binary search in the cached decode
            p = self._sh._postings(t)
            rank = np.searchsorted(p, cands).astype(np.int64)
            found = (rank < len(p)) & (p[np.minimum(rank, len(p) - 1)] == cands)
        q = np.zeros(len(cands), np.int64)
        if found.any():
            q[found] = self._store.payload_at(t, rank[found]).astype(np.int64)
        return found, q

    # ---- fused-kernel extensions (kernels.fused_query.ops) ----
    @property
    def arena(self):
        """This shard's device-resident impact arena, or None.

        Built lazily on the first fused dispatch that could use it (decode +
        upload is startup cost, not serving) and cached for the shard's
        lifetime — the zero-re-upload property the residence test asserts.
        ``False`` caches a failed eligibility check so it runs once.
        """
        if self._arena is None:
            from repro.kernels.arena import DeviceArena

            cfg = getattr(self._sh.cfg, "ranked", None)
            if (
                cfg is None
                or not getattr(cfg, "device_arena", False)
                or not DeviceArena.eligible(self._store.n_terms, self._sh.n_docs)
            ):
                self._arena = False
            else:
                self._arena = DeviceArena.build(
                    self, self._store.n_terms, self._sh.n_docs
                )
        return self._arena or None

    @property
    def payload_bits(self) -> int:
        """Quantized-impact width — static per store, so per kernel dispatch."""
        return int(self._store.payload_bits)

    def payload_words(self, t: int) -> np.ndarray:
        """Term t's packed payload stream (uint32 words, rank-aligned)."""
        return self._store.payload_streams[t]

    def postings(self, t: int) -> np.ndarray:
        """Fully-decoded ids only (host rank fallback for classical codecs)."""
        return self._sh._postings(t)

    def term_model(self, t: int):
        """Guided ε-window rank model, or None (classical codec/no guiding)."""
        g = self._sh.guided
        return g.term_model(t) if g is not None else None

    def seg_ub(self, t: int, cands: np.ndarray) -> np.ndarray:
        """Block-max bound per candidate: its bracketing segment's max impact
        (learned codecs), the whole-list bound otherwise."""
        g = self._sh.guided
        tm = g.term_model(t) if g is not None else None
        if tm is None:
            return np.full(len(cands), self._store.term_ub(t), np.int64)
        seg = np.searchsorted(tm.seg_first, np.asarray(cands, np.int64), side="right") - 1
        ubs = self._store.term_seg_ubs(t).astype(np.int64)
        out = ubs[np.maximum(seg, 0)]
        out[seg < 0] = 0  # candidate precedes the whole list: cannot match
        return out
