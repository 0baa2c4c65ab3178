"""Batched Boolean-query serving engine — doc-partitioned planner/executor.

The paper's system in deployable form, refactored into three layers:

  1. **plan** (serve/planner.py) — a query batch becomes per-shard probe
     plans: smallest-global-df term ordering, per-shard run masks (a shard
     skips conjunctions provably empty on its partition), and cost-model
     routes pinning each learned-codec term to guided ε-window probes or
     full decode;
  2. **execute** (serve/shard.py) — K document-partitioned ShardEngines,
     each owning its learned-Bloom slice, guided-probe TermModels and
     decode-cost-budgeted CostLRU, serve their plan (one candidate-mask
     dispatch + one guided probe batch per shard) and return packed result
     bitmaps over local doc ids.  Parallel shard execution belongs to the
     continuous-batching scheduler (serve/sched): its Session dispatches
     per-shard work to process-replica groups, which is what removed the
     retired thread pool's ~8x GIL convoy at K=4;
  3. **merge** — shard bitmaps word-copy into the global bitmap at their
     doc-id offset (shard boundaries are 32-aligned), then materialize to
     per-query sorted doc-id arrays.

``BooleanEngine`` is the thin facade over all three.  K=1 reproduces the
unsharded engine bit-for-bit; engines can also start from the persistent
shard-store (index/store.py) via ``from_store`` — no re-encoding, stream
bytes page in lazily via mmap.

``query_topk`` is the ranked path over the same shards: the planner dedupes
terms and computes per-shard run masks, each ShardEngine returns its local
top-k by MaxScore dynamic pruning over the tier-2 payload streams, and the
facade folds shard heaps in ascending doc-range order, forwarding the
running k-th best score as the next shard's pruning floor.  Scores are
integer quantized-impact sums with ties broken by ascending doc id, so the
merged top-k is bit-identical for K=1 and any K>1 — and to the brute-force
BM25 oracle (rank.score.brute_force_topk).
"""
from __future__ import annotations

import time
import warnings

import numpy as np

from repro.common.config import LearnedIndexConfig
from repro.core.learned_bloom import LearnedBloom
from repro.index.build import InvertedIndex
from repro.obs import trace
from repro.obs.metrics import Registry
from repro.obs.trace import NULL_SPAN
from repro.postings.search import ProbeStats
from repro.rank.score import BM25Params, ImpactModel, TopKResult, select_topk
from repro.rank.topk import RankedStats
from repro.serve.config import ObsConfig, RankedConfig, SchedConfig, ServeConfig
from repro.serve.planner import BatchPlan, plan_batch, plan_ranked, ranked_run_mask
from repro.serve.shard import WORD_BITS, ShardEngine, shard_ranges, slice_bloom, unpack_row

__all__ = [
    "BooleanEngine",
    "ObsConfig",
    "RankedConfig",
    "SchedConfig",
    "ServeConfig",
]


class BooleanEngine:
    """Facade: plans a batch, fans it out across shards, merges bitmaps."""

    def __init__(
        self,
        lb: LearnedBloom,
        inv: InvertedIndex | None,
        li_cfg: LearnedIndexConfig,
        cfg: ServeConfig | None = None,
        *,
        shards: list[tuple[tuple[int, int], ShardEngine | None]] | None = None,
    ):
        self.cfg = cfg or ServeConfig()
        self.lb = lb
        self.inv = inv
        self.li_cfg = li_cfg
        self.n_docs = lb.n_docs
        self._impact_model = None
        can_rank = (
            self.cfg.ranked.enabled
            and inv is not None
            and inv.tfs is not None
            and self.cfg.postings_store == "hybrid"
        )
        # shards get the *provider*, not the model: quantizer fitting is an
        # O(n_postings) float64 pass that Boolean-only serving never needs,
        # so it runs at first ranked use (ensure_payloads), not construction
        provider = self._build_impact_model if can_rank else None
        if shards is None:
            if inv is None:
                raise ValueError("need an InvertedIndex (or prebuilt shards)")
            shards = [
                (
                    (lo, hi),
                    ShardEngine.from_range(
                        lb, inv, li_cfg, self.cfg, lo, hi,
                        impact_model=provider,
                    )
                    if hi > lo else None,
                )
                for lo, hi in shard_ranges(inv.n_docs, self.cfg.n_shards)
            ]
        self._ranges = [r for r, _ in shards]
        self._shards = [s for _, s in shards]
        active = self.shards
        for sid, sh in enumerate(active):
            sh.shard_id = sid
        if inv is not None:
            self._global_dfs = inv.dfs
        else:
            self._global_dfs = sum((s.local_dfs for s in active), start=0)
        # one registry per facade: primitives (query counters, per-phase
        # latency histograms) plus collectors aggregating the shards
        obs = self.cfg.obs
        self.metrics = obs.metrics if obs.metrics is not None else Registry()
        self._ranked_queries = self.metrics.counter("queries.ranked")
        self._boolean_queries = self.metrics.counter("queries.boolean")
        self._register_collectors()

    def _build_impact_model(self) -> ImpactModel:
        """Fit (once) the collection-global quantizer: every shard's payload
        stream is then a bit-exact slice of the global one (rank/score.py)."""
        if self._impact_model is None:
            self._impact_model = ImpactModel.build(
                self.inv, BM25Params(bits=self.cfg.ranked.payload_bits)
            )
        return self._impact_model

    @property
    def impact_model(self) -> ImpactModel | None:
        """The fitted global quantizer, or None for engines that cannot rank
        from live arrays (no tfs / raw store / loaded-store payloads)."""
        return self._impact_model

    @classmethod
    def from_store(
        cls,
        lb: LearnedBloom,
        li_cfg: LearnedIndexConfig,
        cfg: ServeConfig | None,
        index_dir: str,
        *,
        mmap: bool = True,
    ) -> "BooleanEngine":
        """Start from a persistent shard-store: no re-encoding, lazy streams."""
        from repro.index.store import load_sharded

        cfg = cfg or ServeConfig()
        n_docs, entries = load_sharded(index_dir, mmap=mmap)
        if n_docs != lb.n_docs:
            raise ValueError(f"store has {n_docs} docs, model {lb.n_docs}")
        shards = [
            (
                (lo, hi),
                ShardEngine(
                    slice_bloom(lb, lo, hi), inv, li_cfg, cfg,
                    lo=lo, hi=hi, tier2=store,
                )
                if inv is not None else None,
            )
            for (lo, hi), inv, store in entries
        ]
        return cls(lb, None, li_cfg, cfg, shards=shards)

    def save(self, index_dir: str) -> None:
        """Persist every shard's index + compressed store (build-then-serve).

        Forces tier-2 builds (hybrid codec selection) so the saved layout is
        complete; a reloaded engine never re-encodes.
        """
        from repro.index.store import save_sharded

        if self.cfg.postings_store != "hybrid":
            raise ValueError("only the hybrid postings store is persistable")
        for sh in self.shards:
            sh.ensure_payloads()  # the saved layout carries the ranked tier
        entries = [
            ((lo, hi), sh.inv if sh else None, sh.tier2 if sh else None)
            for (lo, hi), sh in zip(self._ranges, self._shards)
        ]
        save_sharded(index_dir, self.n_docs, entries)

    # ------------------------------------------------------------- shards
    @property
    def shards(self) -> list[ShardEngine]:
        """Non-empty shard executors, ascending doc range."""
        return [s for s in self._shards if s is not None]

    @property
    def n_shards(self) -> int:
        return len(self._ranges)

    @property
    def tier2(self):
        """K=1 convenience: the single shard's compressed tier-2 store."""
        active = self.shards
        return active[0].tier2 if len(active) == 1 else None

    # ------------------------------------------------------------- query
    def query_batch(self, queries: np.ndarray) -> list[np.ndarray]:
        """(Q, T) padded term ids -> list of result doc-id arrays."""
        q = self._padded(queries)
        if q.shape[0] == 0:
            return []
        if (q < 0).all():  # all-padding batch: empty without touching a probe
            return [np.zeros(0, np.int32) for _ in range(q.shape[0])]
        bitmap = self._execute(q)
        return [unpack_row(bitmap[i], self.n_docs) for i in range(q.shape[0])]

    def _observe_us(self, name: str, t0_ns: int) -> None:
        self.metrics.histogram("latency." + name).observe(
            (time.perf_counter_ns() - t0_ns) / 1e3
        )

    def query_batch_bitmap(self, queries: np.ndarray) -> np.ndarray:
        """(Q, T) padded term ids -> (Q, ceil(n_docs/32)) packed uint32 bitmap."""
        q = self._padded(queries)
        words = (self.n_docs + WORD_BITS - 1) // WORD_BITS
        if q.shape[0] == 0 or (q < 0).all():
            return np.zeros((q.shape[0], words), dtype=np.uint32)
        return self._execute(q)

    def query_topk(
        self,
        queries: np.ndarray,
        k: int = 10,
        *,
        mode: str = "or",
        required: np.ndarray | None = None,
    ) -> list[TopKResult]:
        """(Q, T) padded term ids -> exact ranked top-k per query.

        ``mode`` "or" scores any matching term (disjunctive), "and" requires
        every term; a boolean ``required`` mask of queries' shape marks a
        per-position required subset for mixed AND/OR.  Results order by
        (score desc, doc id asc) and are bit-identical across shard counts
        and to brute-force quantized-BM25 over decoded postings.
        """
        q = np.asarray(queries, dtype=np.int32)
        if q.ndim != 2:
            raise ValueError(f"queries must be (Q, T), got shape {q.shape}")
        empty = TopKResult(ids=np.zeros(0, np.int32), scores=np.zeros(0, np.int64))
        if k <= 0:
            return [empty for _ in range(q.shape[0])]
        self._ranked_queries.inc(int(q.shape[0]))
        log = self.cfg.obs.probe_log
        active = self.shards
        out: list[TopKResult] = []
        with trace.activate(self.cfg.obs.trace), \
                trace.span("serve.topk_batch", queries=int(q.shape[0]), k=int(k)):
            qplans = plan_ranked(q, self._global_dfs, mode=mode, required=required)
            runs = [ranked_run_mask(qplans, sh.local_dfs) for sh in active]
            # a shard whose run mask is all-empty contributes nothing to any
            # heap: drop it here instead of re-deriving floors against it
            live = [(sh, run) for sh, run in zip(active, runs) if run.any()]
            if self.cfg.ranked.fused_kernel:
                return self._query_topk_fused(qplans, live, k, empty)
            for i, qp in enumerate(qplans):
                if qp.dead:
                    out.append(empty)
                    continue
                t_query = time.perf_counter_ns()
                heap = empty
                # ascending doc ranges + ascending-id tie break make the floor
                # a strict bar: a later shard's tie can never displace the heap
                for sh, run in live:
                    if not run[i]:
                        continue
                    floor = int(heap.scores[k - 1]) if len(heap.scores) == k else 0
                    ctx = (log.context(query=i, shard=sh.shard_id)
                           if log is not None else NULL_SPAN)
                    with ctx:
                        part = sh.query_topk_local(
                            qp.terms, k, required=qp.required, floor=floor
                        )
                    if len(part.ids) == 0:
                        continue
                    with trace.span("serve.heap_merge", query=i, shard=sh.shard_id):
                        heap = select_topk(
                            np.concatenate([heap.ids, part.ids]),
                            np.concatenate([heap.scores, part.scores]),
                            k,
                        )
                self._observe_us("topk_query_us", t_query)
                out.append(heap)
        return out

    def _query_topk_fused(self, qplans, live, k: int, empty) -> list[TopKResult]:
        """Fused-kernel ranked execution: shards outer, one batched dispatch
        per shard (``shard.query_topk_batch``), heap floors forwarded between
        shards exactly as the per-query loop does — shard doc ranges ascend,
        so each shard sees the floors the previous shards established.
        Bit-identical to the multi-phase loop (asserted in tests/benchmarks).
        """
        t_batch = time.perf_counter_ns()
        heaps = [empty] * len(qplans)
        n_live_q = sum(1 for qp in qplans if not qp.dead)
        for sh, run in live:
            idx = [i for i, qp in enumerate(qplans) if not qp.dead and run[i]]
            if not idx:
                continue
            items = []
            for i in idx:
                floor = (int(heaps[i].scores[k - 1])
                         if len(heaps[i].scores) == k else 0)
                items.append((qplans[i].terms, k, qplans[i].required, floor))
            parts = sh.query_topk_batch(items)
            for i, part in zip(idx, parts):
                if len(part.ids) == 0:
                    continue
                with trace.span("serve.heap_merge", query=i, shard=sh.shard_id):
                    heaps[i] = select_topk(
                        np.concatenate([heaps[i].ids, part.ids]),
                        np.concatenate([heaps[i].scores, part.scores]),
                        k,
                    )
        if n_live_q:  # batch wall spread over queries: same metric, one pass
            per_q = (time.perf_counter_ns() - t_batch) // n_live_q
            for _ in range(n_live_q):
                self._observe_us("topk_query_us", time.perf_counter_ns() - per_q)
        return heaps

    def _padded(self, queries: np.ndarray) -> np.ndarray:
        q = np.asarray(queries, dtype=np.int32)
        if q.ndim != 2:
            raise ValueError(f"queries must be (Q, T), got shape {q.shape}")
        if q.shape[1] < self.cfg.max_query_terms:
            q = np.pad(q, ((0, 0), (0, self.cfg.max_query_terms - q.shape[1])),
                       constant_values=-1)
        return q

    def _execute(self, q: np.ndarray) -> np.ndarray:
        """Plan, fan out across shards, merge packed bitmaps by doc offset.

        Two phases per the executor contract: learned-Bloom candidate masks
        are one jit dispatch per shard, issued serially (concurrent dispatch
        contends on the device client); the probe/verify phase — guided
        ε-window probes and cache decodes, pure numpy — runs shard by shard
        on the calling thread.  Parallel shard execution lives one level up:
        serve.sched.Session dispatches to process replicas (no GIL convoy,
        the retired ThreadPoolExecutor's measured ~8x slowdown at K=4).
        """
        active = self.shards
        t_batch = time.perf_counter_ns()
        self._boolean_queries.inc(int(q.shape[0]))
        with trace.activate(self.cfg.obs.trace), \
                trace.span("serve.batch", queries=int(q.shape[0]),
                           shards=len(active)):
            t0 = time.perf_counter_ns()
            plan = plan_batch(q, self._global_dfs, active,
                              verified=self.cfg.verified)
            self._observe_us("plan_us", t0)
            t0 = time.perf_counter_ns()
            masks = []
            for sh, sp in zip(active, plan.shard_plans):
                if sh.n_docs > 0 and sp.run.any():
                    with trace.span("serve.candidate_mask", shard=sh.shard_id):
                        masks.append(sh.candidate_mask(q))
                else:
                    masks.append(None)
            self._observe_us("mask_us", t0)
            t0 = time.perf_counter_ns()
            parts = []
            for sh, sp, m in zip(active, plan.shard_plans, masks):
                with trace.span("serve.probe_phase", shard=sh.shard_id):
                    parts.append(sh.execute(q, sp, plan.qplans, mask=m))
            self._observe_us("probe_us", t0)
            t0 = time.perf_counter_ns()
            with trace.span("serve.merge"):
                out = self._merge(parts, active)
            self._observe_us("merge_us", t0)
        # per-query latency at batch granularity: each query is charged the
        # batch mean, so histogram counts tally queries and percentiles
        # weight batches by their size (batch-of-1 harnesses record the true
        # per-query wall)
        n_q = max(int(q.shape[0]), 1)
        us = (time.perf_counter_ns() - t_batch) / 1e3 / n_q
        hist = self.metrics.histogram("latency.query_us")
        for _ in range(n_q):
            hist.observe(us)
        return out

    def _merge(self, parts: list[np.ndarray], active: list[ShardEngine]) -> np.ndarray:
        """Word-copy each shard's packed bitmap at its doc-id offset (shard
        boundaries are 32-aligned, so no cross-shard bit arithmetic)."""
        n_queries = parts[0].shape[0] if parts else 0
        out = np.zeros((n_queries, (self.n_docs + WORD_BITS - 1) // WORD_BITS), np.uint32)
        for sh, bm in zip(active, parts):
            off = sh.lo // WORD_BITS
            out[:, off : off + bm.shape[1]] = bm
        return out

    # ------------------------------------------------------------- stats
    def memory_report(self) -> dict[str, int]:
        """Bits used by each component (feeds the Eq.(2) comparison);
        dense-state and tier-2 bits summed over shards."""
        report = {
            "model_bits": self.lb.size_bits(),
            "tier1_bits": 0,
            "block_bitmap_bits": 0,
            "backup_bits": int(self.lb.backup_keys.size * 64),
        }
        tier2_bits = payload_bits = None
        for sh in self.shards:
            bits = sh.memory_bits()
            report["tier1_bits"] += bits["tier1_bits"]
            report["block_bitmap_bits"] += bits["block_bitmap_bits"]
            if "tier2_bits" in bits:
                tier2_bits = (tier2_bits or 0) + bits["tier2_bits"]
            if "payload_bits" in bits:
                payload_bits = (payload_bits or 0) + bits["payload_bits"]
        if tier2_bits is not None:
            report["tier2_bits"] = tier2_bits
        if payload_bits is not None:
            report["payload_bits"] = payload_bits
        return report

    def _register_collectors(self) -> None:
        """Aggregating collectors over the shards, all read through one
        ``Registry.snapshot()`` (the keys serving_stats always reported)."""
        reg = self.metrics
        reg.register("decode_cache", self._collect_cache)
        reg.register(
            "shards",
            lambda: [sh.serving_stats() for sh in self.shards],
            reset=lambda: [sh.reset_stats() for sh in self.shards],
        )
        reg.register("guided", self._collect_guided)
        reg.register("ranked", self._collect_ranked)
        reg.register("summary", self._collect_summary)

    def _collect_cache(self) -> dict[str, int]:
        keys = ("entries", "cost_bytes", "budget_bytes", "hits", "misses", "evictions")
        per = [sh._decode_cache.stats() for sh in self.shards]
        return {k: sum(s[k] for s in per) for k in keys}

    def _collect_guided(self) -> dict | None:
        """'guided' keeps the single-engine shape: counters summed across
        shards, ratios recomputed by ProbeStats.as_dict."""
        per = [sh._guided.stats for sh in self.shards if sh._guided is not None]
        if not per:
            return None
        return ProbeStats(**{
            f: sum(int(getattr(g, f)) for g in per)
            for f in ("probes", "guided_terms", "fallback_terms", "routed_terms",
                      "window_bytes", "metadata_bytes", "fallback_bytes",
                      "full_equiv_bytes")
        }).as_dict()

    def _collect_ranked(self) -> dict | None:
        per = [sh.ranked_stats for sh in self.shards if sh.ranked_stats.queries]
        if not per:
            return None
        agg = RankedStats(**{
            f: sum(int(getattr(r, f)) for r in per)
            for f in ("queries", "exhaustive_queries", "scored_postings",
                      "probed_postings", "exhaustive_postings",
                      "fused_queries", "fused_lanes", "fused_stream_bytes",
                      "fused_device_bytes", "fused_kernel_ns",
                      "fused_bridge_ns")
        }).as_dict()
        # shard counters tally (query, shard) pairs; report the facade's
        # query count on top so per-query averages come out right
        agg["shard_queries"] = agg.pop("queries")
        agg["queries"] = self._ranked_queries.value
        return agg

    def _collect_summary(self) -> dict:
        """The one-number view benchmarks report (stable legacy keys)."""
        cache = self._collect_cache()
        guided = self._collect_guided()
        ranked = self._collect_ranked()
        return {
            "n_shards": len(self.shards),
            "cache_hits": cache["hits"],
            "cache_misses": cache["misses"],
            "cache_evictions": cache["evictions"],
            "probe_bytes": guided["guided_bytes"] if guided else 0,
            "bytes_ratio": guided["bytes_ratio"] if guided else 0.0,
            "scored_fraction": ranked["scored_fraction"] if ranked else 0.0,
        }

    def serving_stats(self) -> dict[str, dict]:
        """Deprecated: one snapshot of the facade metrics registry.

        Kept as a thin wrapper so existing callers see the same shape
        ('decode_cache', 'shards', 'guided', 'ranked', 'summary' — plus the
        registry's own 'queries' counters and 'latency' histograms).  New
        code should read ``engine.metrics.snapshot()`` directly.
        """
        warnings.warn(
            "serving_stats() is deprecated; read engine.metrics.snapshot()",
            DeprecationWarning,
            stacklevel=2,
        )
        return self.metrics.snapshot()

    def reset_stats(self) -> None:
        """Zero every accounting window through the metrics registry: facade
        counters/histograms reset, and each shard's public reset_stats()
        zeroes its own guided/ranked/cache state (cached decodes stay
        resident, so the next pass measures warm serving)."""
        self.metrics.reset()
