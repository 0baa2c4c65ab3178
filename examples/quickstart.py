"""Quickstart: build a collection, train the learned membership index, serve
exact Boolean queries — the paper's full pipeline in ~60 lines.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import CorpusConfig, LearnedIndexConfig, OptimizerConfig
from repro.core import estimate_gain, fit_thresholds, init_membership, membership_loss
from repro.data.corpus import synthesize_corpus
from repro.data.loader import membership_batches
from repro.data.queries import brute_force_answers, sample_queries, zipf_conjunctions
from repro.index.build import build_inverted_index
from repro.serve import BooleanEngine, ServeConfig
from repro.train import init_train_state, make_train_step


def main():
    # 1. a Robust-like collection (synthetic, df-calibrated — DESIGN.md §5)
    corpus = synthesize_corpus(CorpusConfig(n_docs=1500, n_terms=6000, avg_doc_len=70))
    inv = build_inverted_index(corpus)
    print(f"collection: {corpus.n_docs} docs, {corpus.n_postings} postings")

    # 2. the paper's Eq.(2): how much storage could the learned index save?
    g = estimate_gain(inv, k=48)
    print(f"Eq.(2) @ k=48: upper {g.gain_upper_frac:.1%}, "
          f"lower (s=512b) {g.gain_lower_frac:.1%}, |R|={g.n_replaced}")

    # 3. train f(t,d) — the learned index model
    li_cfg = LearnedIndexConfig(embed_dim=64, truncation_k=48, block_size=128)
    params, _ = init_membership(jax.random.key(0), li_cfg, corpus.n_terms, corpus.n_docs)
    ocfg = OptimizerConfig(lr=0.05, warmup_steps=10, total_steps=200, weight_decay=0.0)
    step = jax.jit(make_train_step(lambda p, b: membership_loss(p, b), ocfg))
    state = init_train_state(params, ocfg)
    for i, batch in zip(range(200), membership_batches(corpus, batch_size=2048)):
        params, state, m = step(params, state, {k: jnp.asarray(v) for k, v in batch.items()})
    print(f"membership model trained, final loss {float(m['loss']):.4f}")

    # 4. learned-Bloom construction: zero false negatives by construction
    lb = fit_thresholds(params, inv)

    # 5. serve conjunctive Boolean queries (Algorithm 3 + exact verification)
    eng = BooleanEngine(lb, inv, li_cfg, ServeConfig(algorithm="block", verified=True))
    queries = sample_queries(corpus, 16, seed=1)
    results = eng.query_batch(queries)
    exact = brute_force_answers(corpus, queries)
    ok = all(np.array_equal(r, e) for r, e in zip(results, exact))
    print(f"16 queries served, exact={ok}")
    print("memory report (bits):", eng.memory_report())

    # 6. the §3.3 hybrid tier-2 store: per-term min-bits codec (learned or
    # classical), decoded exactly during verification above
    bpp = eng.tier2.size_bits() / inv.n_postings
    print(f"tier-2 hybrid store: {bpp:.2f} bits/posting (raw 32.00), "
          f"codec split {eng.tier2.codec_histogram()}")
    assert ok

    # 7. model-guided conjunctive serving: a batched 2-5-term AND workload
    # verified by ε-window probes on the learned streams (no full decode on
    # the learned terms) — see README "Serving" and BENCH_guided_intersect
    conj = zipf_conjunctions(inv.dfs, 8, seed=3)
    conj_results = eng.query_batch(conj)
    conj_exact = brute_force_answers(corpus, conj)
    assert all(np.array_equal(r, e) for r, e in zip(conj_results, conj_exact))
    report = eng.memory_report()
    print(f"guided conjunctive batch: {len(conj)} queries, "
          f"{sum(len(r) for r in conj_results)} result docs")
    print("memory report (bits):", report)
    assert "tier2_bits" in report
    guided = eng.metrics.snapshot()["guided"]
    print(f"guided probes: {guided['probes']}, bytes touched "
          f"{guided['guided_bytes']} vs full-decode {guided['full_equiv_bytes']} "
          f"(ratio {guided['bytes_ratio']:.3f})")

    # 8. restartable, doc-partitioned serving: persist the sharded index
    # (index/store.py), reload it mmap-lazily, and serve identical results —
    # no re-encoding on restart, 4 shards fanned out by the planner/executor
    import tempfile

    sharded_cfg = ServeConfig(algorithm="block", verified=True, n_shards=4)
    sharded = BooleanEngine(lb, inv, li_cfg, sharded_cfg)
    with tempfile.TemporaryDirectory() as index_dir:
        sharded.save(index_dir)
        restarted = BooleanEngine.from_store(lb, li_cfg, sharded_cfg, index_dir)
        reload_results = restarted.query_batch(conj)
    assert all(np.array_equal(r, e) for r, e in zip(reload_results, conj_exact))
    summary = restarted.metrics.snapshot()["summary"]
    print(f"sharded round trip: {summary['n_shards']} shards served "
          f"{len(conj)} queries from the reloaded store, cache "
          f"{summary['cache_hits']}h/{summary['cache_misses']}m, "
          f"probe bytes {summary['probe_bytes']}")

    # 9. ranked retrieval: a top-10 BM25 disjunction over the tf payload
    # streams — quantized-impact scores, MaxScore pruning, checked against
    # brute-force BM25 over fully decoded postings (bit-identical)
    from repro.data.queries import zipf_disjunctions
    from repro.rank.score import brute_force_topk, dequantize_scores

    ranked_q, _ = zipf_disjunctions(inv.dfs, 1, min_terms=4, max_terms=5, seed=9)
    (top,) = eng.query_topk(ranked_q, 10)
    (oracle,) = brute_force_topk(inv, eng.impact_model, ranked_q, 10)
    assert np.array_equal(top.ids, oracle.ids)
    assert np.array_equal(top.scores, oracle.scores)
    terms = [int(t) for t in ranked_q[0] if t >= 0]
    print(f"top-10 BM25 for OR query {terms} (scores vs brute force: equal):")
    for doc, q_score, f_score in zip(
        top.ids, top.scores, dequantize_scores(top.scores, eng.impact_model)
    ):
        print(f"  doc {int(doc):5d}  impact {int(q_score):4d}  bm25≈{f_score:.3f}")
    rs = eng.metrics.snapshot()["ranked"]
    print(f"ranked path scored {rs['touched_postings']} of "
          f"{rs['exhaustive_postings']} postings "
          f"(fraction {rs['scored_fraction']:.3f})")

    # 10. observability: re-serve the same workloads with the span tracer and
    # probe log on (ServeConfig(obs=dict(trace=..., probe_log=...)) — or
    # `repro.launch.serve --trace-out --probe-log` from the CLI), then read
    # per-phase latency percentiles from the metrics registry and drop the
    # Chrome-trace JSON into ui.perfetto.dev to see the query path
    from repro.obs import ProbeLog, Tracer

    tracer, plog = Tracer(), ProbeLog()  # path-less log collects in memory
    obs_cfg = ServeConfig(algorithm="block", verified=True,
                          obs=dict(trace=tracer, probe_log=plog))
    obs_eng = BooleanEngine(lb, inv, li_cfg, obs_cfg)
    obs_eng.query_batch(conj)
    obs_eng.query_topk(ranked_q, 10)
    lat = obs_eng.metrics.snapshot()["latency"]
    for name in ("query_us", "topk_query_us"):
        h = lat[name]
        print(f"latency {name}: p50 {h['p50'] / 1e3:.2f} ms, "
              f"p99 {h['p99'] / 1e3:.2f} ms over {h['count']} queries")
    routes = sorted({r.route for r in plog.records})
    print(f"traced {len(tracer.spans)} spans across "
          f"{len({s.name for s in tracer.spans})} phases; "
          f"{plog.n_records} probe records, routes {routes}")
    with tempfile.TemporaryDirectory() as d:
        tracer.save(f"{d}/quickstart.trace.json")
        print(f"Chrome trace saved (open in ui.perfetto.dev): "
              f"{len(tracer.chrome_trace()['traceEvents'])} events")

    # 11. the serving front-end: submit everything through one request type.
    # The Session coalesces arrivals into batches (continuous batching),
    # fans them out per shard, and resolves each request to a QueryResult or
    # a typed Rejected — here inline (n_replicas=0); set
    # sched=dict(n_replicas=R) plus store_dir= for process replicas, and see
    # README "Serving front-end" for tenants/priorities/deadlines
    from repro.serve import QueryRequest, Session

    with Session(sharded) as session:
        r = session.submit(QueryRequest(terms=conj[0]))
        assert r.ok and np.array_equal(r.ids, conj_results[0])
        rr = session.submit(QueryRequest(terms=ranked_q[0], mode="ranked", k=10))
        assert np.array_equal(rr.ids, top.ids)
        never = session.submit(QueryRequest(terms=conj[1], deadline_ms=0.0))
        sm = sharded.metrics.snapshot()["sched"]
    print(f"scheduler: served boolean+ranked via Session.submit "
          f"(parity with steps 7/9), queue wait "
          f"{r.queue_us / 1e3:.2f} ms; an already-expired deadline came "
          f"back typed: ok={never.ok} reason={never.reason!r}; "
          f"{sm['batches']} batches dispatched, {sm['shed']['deadline']} shed")
    assert not never.ok and never.reason == "deadline"

    # 12. distributed tracing + SLO telemetry: the same ranked query through
    # a real process replica.  The scheduler propagates a TraceContext over
    # the worker pipe; the worker ships its span buffer back with the reply;
    # the host collator aligns the two monotonic clocks (min-RTT ping
    # offset) and merges everything onto ONE timeline — each worker is its
    # own named pid lane next to the host's.  (`repro.launch.serve
    # --replicas 1 --slo` drives the same path from the CLI.)
    from repro.obs import nesting_violations, render_prometheus

    dist_tracer = Tracer()
    dist_cfg = ServeConfig(algorithm="block", verified=True, n_shards=2,
                           sched=dict(n_replicas=1),
                           obs=dict(trace=dist_tracer, probe_log=ProbeLog()))
    dist_eng = BooleanEngine(lb, inv, li_cfg, dist_cfg)
    with tempfile.TemporaryDirectory() as store_dir:
        with Session(dist_eng, store_dir=store_dir) as session:
            session.warm()  # spawn replicas + pre-compile outside the timing
            rr = session.submit(QueryRequest(terms=ranked_q[0], mode="ranked",
                                             k=10), timeout=60)
            assert rr.ok and np.array_equal(rr.ids, top.ids)  # still bit-exact
            a = rr.autopsy()
            slo = session.slo_report()
    lanes = sorted({s.pid for s in dist_tracer.spans})
    worker_names = {s.name for s in dist_tracer.spans if s.pid != 0}
    assert len(lanes) > 1, "worker spans must merge into the host timeline"
    assert nesting_violations(dist_tracer.spans, slack_us=0.5) == []
    print(f"distributed trace: {len(lanes)} pid lanes (host + "
          f"{len(lanes) - 1} workers), worker phases "
          f"{sorted(worker_names)[:4]}...")
    print(f"autopsy: total {a['total_us'] / 1e3:.2f} ms = queue "
          f"{a['queue_us'] / 1e3:.2f} + dispatch {a['dispatch_us'] / 1e3:.2f}"
          f" + execute {a['execute_us'] / 1e3:.2f} + merge "
          f"{a['merge_us'] / 1e3:.2f} ms ({a['execute_frac']:.0%} execute)")
    ten = slo["tenants"]["default"]
    print(f"slo window: {ten['requests']} request(s), hit rate "
          f"{ten['deadline_hit_rate']:.0%}, p99 {ten['p99_ms']:.2f} ms, "
          f"burn {ten['burn_rate']:.2f}x of target {slo['target']:.0%}")
    prom = render_prometheus({"sched": slo["sched"]})
    print("prometheus exposition (first 3 lines):")
    for line in prom.splitlines()[:3]:
        print(f"  {line}")

    # 13. the device-resident fused ranked path: candidate scoring through
    # the θ-peel top-k loop runs as ONE jitted dispatch over a per-shard
    # device arena — the impact table is uploaded once per process
    # (residency counters prove it) and the host bridge only pads queries
    # and extracts results.  RankedStats times the device execution
    # separately from that bridge (fused_kernel_ns vs fused_bridge_ns)
    fused_eng = BooleanEngine(lb, inv, li_cfg,
                              ServeConfig(ranked=dict(fused_kernel=True)))
    (ftop,) = fused_eng.query_topk(ranked_q, 10)
    assert np.array_equal(ftop.ids, top.ids)       # still bit-identical to
    assert np.array_equal(ftop.scores, top.scores)  # steps 9's oracle check
    fused_eng.reset_stats()
    fused_eng.query_topk(ranked_q, 10)
    fs = fused_eng.metrics.snapshot()["ranked"]
    arena = fused_eng.shards[0].metrics.snapshot()["arena"]
    print(f"fused dispatch: kernel {fs['fused_kernel_ns'] / 1e6:.2f} ms vs "
          f"host bridge {fs['fused_bridge_ns'] / 1e6:.2f} ms; arena "
          f"{arena['upload_bytes'] / 1e6:.1f} MB uploaded "
          f"{arena['uploads']}x, {arena['hits']} resident dispatch(es)")
    assert arena["uploads"] == 1  # uploaded once, no matter how many queries


if __name__ == "__main__":
    main()
