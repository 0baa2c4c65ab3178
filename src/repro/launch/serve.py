"""Serving launcher for the paper's Boolean-query engine.

Builds a synthetic collection, trains the membership model briefly, fits
zero-FN thresholds, and serves batched conjunctive queries with the chosen
algorithm. --verified re-checks against tier-2 for exact results.

--shards K serves through K document partitions (planner/executor fan-out);
--index-dir DIR persists the sharded index (index/store.py) and then serves
from the reloaded store — the build-then-serve round trip that proves a
restart needs no re-encoding.

--topk K additionally serves a ranked (BM25 top-K) disjunctive batch over
the tier-2 payload streams, checked bit-exact against brute-force scoring.

--trace-out FILE records every served batch as Chrome-trace JSON (open in
chrome://tracing or https://ui.perfetto.dev); --probe-log FILE streams one
JSONL record per routed probe with its route decision and bytes touched.

--replicas R additionally drives the same batch through the continuous-
batching scheduler (serve/sched.Session.submit): R=0 serves inline on the
facade's own shards, R>0 spawns R process replicas per shard over the
persistent store (refused on a TPU, which belongs to one process);
--deadline-ms bounds each request's queue wait (late requests come back as
typed Rejected, never silently dropped).

With --replicas and --trace-out together the trace is *distributed*: worker
replicas ship their span buffers back with every response and the launcher
exports one timeline where each process replica renders as its own named
pid lane next to the host scheduler.  --slo prints the per-tenant rolling
SLO report (deadline-hit-rate, p99, burn-rate), a per-request latency
autopsy (queue/dispatch/execute/merge), and a Prometheus rendering of the
scheduler metrics; --probe-log-max-bytes size-caps the probe JSONL sink.

  PYTHONPATH=src python -m repro.launch.serve --algorithm block --queries 64
  PYTHONPATH=src python -m repro.launch.serve --shards 4 --index-dir /tmp/idx
  PYTHONPATH=src python -m repro.launch.serve --shards 4 --topk 10
  PYTHONPATH=src python -m repro.launch.serve --shards 4 --topk 10 --fused
  PYTHONPATH=src python -m repro.launch.serve --shards 4 --replicas 1 \\
      --deadline-ms 100 --slo
  PYTHONPATH=src python -m repro.launch.serve --shards 2 --replicas 1 \\
      --trace-out serve.trace.json  # end-to-end distributed trace
  PYTHONPATH=src python -m repro.launch.serve --trace-out serve.trace.json \\
      --probe-log probes.jsonl --probe-log-max-bytes 1048576
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import configure_compile_cache
from repro.common.config import CorpusConfig, LearnedIndexConfig, OptimizerConfig
from repro.core import fit_thresholds, init_membership, membership_loss
from repro.data.corpus import synthesize_corpus
from repro.data.loader import membership_batches
from repro.data.queries import brute_force_answers, sample_queries, zipf_disjunctions
from repro.index.build import build_inverted_index
from repro.obs import ProbeLog, Tracer
from repro.serve import BooleanEngine, RankedConfig, ServeConfig
from repro.train import init_train_state, make_train_step


def build_collection(ccfg: CorpusConfig):
    """Synthesize the seeded collection and invert it -> (corpus, inv)."""
    corpus = synthesize_corpus(ccfg)
    return corpus, build_inverted_index(corpus)


def train_membership(corpus, inv, li_cfg: LearnedIndexConfig, steps=300, lr=0.05):
    params, _ = init_membership(
        jax.random.key(0), li_cfg, corpus.n_terms, corpus.n_docs
    )
    replaced = np.nonzero(inv.dfs > li_cfg.truncation_k)[0]
    it = membership_batches(
        corpus, batch_size=2048,
        negatives_per_positive=li_cfg.train_negatives_per_positive,
        replaced_terms=replaced if len(replaced) else None,
    )
    ocfg = OptimizerConfig(lr=lr, warmup_steps=20, total_steps=steps, weight_decay=0.0)
    step = jax.jit(make_train_step(lambda p, b: membership_loss(p, b), ocfg))
    st = init_train_state(params, ocfg)
    for i, batch in zip(range(steps), it):
        params, st, m = step(params, st, {k: jnp.asarray(v) for k, v in batch.items()})
        if i % 100 == 0:
            print(f"[serve] membership train step {i} loss {float(m['loss']):.4f}")
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algorithm", default="block",
                    choices=["exhaustive", "two_tier", "block"])
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--terms", type=int, default=8000)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=128)
    ap.add_argument("--train-steps", type=int, default=300)
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--shards", type=int, default=1,
                    help="document partitions served by the planner/executor")
    ap.add_argument("--index-dir", default=None,
                    help="persist the sharded index here, then serve from the "
                         "reloaded store (build-then-serve round trip)")
    ap.add_argument("--topk", type=int, default=10,
                    help="also serve a ranked top-K disjunctive batch "
                         "(0 disables the ranked path)")
    ap.add_argument("--fused", action="store_true",
                    help="answer each shard's ranked batch with one fused "
                         "Pallas dispatch (kernels.fused_query) instead of "
                         "the multi-phase probe/unpack/score pipeline "
                         "(disables the small-query exhaustive shortcut so "
                         "the kernel actually runs on demo-sized corpora)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON of every served batch here")
    ap.add_argument("--probe-log", default=None,
                    help="stream per-(query, term, shard) probe records (JSONL)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="also serve through the scheduler (Session.submit): "
                         "0 = inline, N>0 = N process replicas per shard")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="scheduler default deadline; requests queued past it "
                         "are shed with a typed Rejected")
    ap.add_argument("--slo", action="store_true",
                    help="print the scheduler's rolling SLO report (per-tenant "
                         "deadline-hit-rate/p99/burn-rate), a per-request "
                         "latency autopsy, and Prometheus-rendered metrics "
                         "(implies --replicas 0 when --replicas is unset)")
    ap.add_argument("--probe-log-max-bytes", type=int, default=None,
                    help="rotate the probe log past this size (<path>.1 keeps "
                         "the previous window; unset = unbounded)")
    args = ap.parse_args()
    if args.slo and args.replicas is None:
        args.replicas = 0  # the SLO report reads the scheduler's window
    if args.replicas and jax.default_backend() == "tpu":
        ap.error(f"--replicas {args.replicas}: process replicas are separate "
                 "processes, and a TPU belongs to one process; use --replicas 0")
    configure_compile_cache()

    corpus, inv = build_collection(
        CorpusConfig(n_docs=args.docs, n_terms=args.terms, avg_doc_len=80)
    )
    li_cfg = LearnedIndexConfig(
        embed_dim=64, truncation_k=args.k, block_size=args.block_size
    )
    params = train_membership(corpus, inv, li_cfg, steps=args.train_steps)
    lb = fit_thresholds(params, inv)
    tracer = Tracer() if args.trace_out else None
    probe_log = (
        ProbeLog(args.probe_log, max_bytes=args.probe_log_max_bytes)
        if args.probe_log
        else None
    )
    cfg = ServeConfig(algorithm=args.algorithm, verified=not args.no_verify,
                      use_kernel=args.use_kernel, n_shards=args.shards,
                      obs=dict(trace=tracer, probe_log=probe_log,
                               probe_log_max_bytes=args.probe_log_max_bytes),
                      ranked=dict(fused_kernel=args.fused,
                                  # the exhaustive shortcut would swallow every
                                  # demo-sized query before the fused dispatch
                                  topk_exhaustive_cutoff=0 if args.fused
                                  else RankedConfig.topk_exhaustive_cutoff))
    eng = BooleanEngine(lb, inv, li_cfg, cfg)
    if args.index_dir:
        t0 = time.time()
        eng.save(args.index_dir)
        save_s = time.time() - t0
        t0 = time.time()
        eng = BooleanEngine.from_store(lb, li_cfg, cfg, args.index_dir)
        print(f"[serve] index saved to {args.index_dir} in {save_s:.2f}s, "
              f"reloaded in {time.time() - t0:.2f}s — serving from the store")
    print(f"[serve] {len(eng.shards)} active shard(s), ranges {eng._ranges}")
    print("[serve] memory report (bits):", eng.memory_report())

    q = sample_queries(corpus, args.queries, seed=3)
    t0 = time.time()
    results = eng.query_batch(q)
    dt = (time.time() - t0) / args.queries * 1e3
    exact = brute_force_answers(corpus, q)
    n_exact = sum(np.array_equal(r, e) for r, e in zip(results, exact))
    n_super = sum(np.setdiff1d(e, r).size == 0 for r, e in zip(results, exact))
    print(f"[serve] {args.queries} queries, {dt:.2f} ms/query, "
          f"exact={n_exact}/{args.queries}, superset={n_super}/{args.queries}")
    if not args.no_verify:
        assert n_exact == args.queries, "verified mode must be exact"
        print("[serve] verified mode: all results exact ✓")
    s = eng.metrics.snapshot()["summary"]
    print(f"[serve] summary: {s['n_shards']} shards, cache "
          f"{s['cache_hits']}h/{s['cache_misses']}m/{s['cache_evictions']}e, "
          f"probe bytes {s['probe_bytes']} (ratio {s['bytes_ratio']:.3f})")

    if args.topk > 0:
        from repro.rank.score import ImpactModel, brute_force_topk

        ranked_q, _ = zipf_disjunctions(inv.dfs, args.queries, seed=7)
        t0 = time.time()
        ranked = eng.query_topk(ranked_q, args.topk)
        dt = (time.time() - t0) / args.queries * 1e3
        im = eng.impact_model or ImpactModel.build(inv)
        oracle = brute_force_topk(inv, im, ranked_q, args.topk)
        ok = all(
            np.array_equal(r.ids, e.ids) and np.array_equal(r.scores, e.scores)
            for r, e in zip(ranked, oracle)
        )
        rs = eng.metrics.snapshot()["ranked"]
        print(f"[serve] ranked top-{args.topk}: {args.queries} OR queries, "
              f"{dt:.2f} ms/query, exact-vs-BM25-brute-force={ok}, "
              f"scored {rs['touched_postings']}/{rs['exhaustive_postings']} "
              f"postings (fraction {rs['scored_fraction']:.3f})")
        if args.fused:
            print(f"[serve] fused kernel: {rs['fused_queries']} shard-queries "
                  f"in one-dispatch batches, {rs['fused_lanes']} probe lanes, "
                  f"{rs['fused_stream_bytes']} stream bytes touched")
        assert ok, "ranked serving must match brute-force BM25"

    if args.replicas is not None:
        import tempfile

        from repro.serve import QueryRequest, Session

        eng.cfg.sched.n_replicas = args.replicas
        eng.cfg.sched.default_deadline_ms = args.deadline_ms
        store = args.index_dir or (
            tempfile.mkdtemp(prefix="repro-shards-") if args.replicas > 0 else None
        )
        with Session(eng, store_dir=store) as session:
            if args.replicas > 0:
                session.warm()  # spawn + jit warmup outside the timed region
            t0 = time.time()
            futs = [
                session.submit_async(QueryRequest(terms=row), block=True)
                for row in q
            ]
            outs = [f.result() for f in futs]
            dt = (time.time() - t0) / len(q) * 1e3
            served = [o for o in outs if o.ok]
            shed = [o for o in outs if not o.ok]
            n_same = sum(
                np.array_equal(o.ids, r) for o, r in zip(outs, results) if o.ok
            )
            sm = eng.metrics.snapshot()["sched"]
            kind = f"{args.replicas} process replica(s)/shard" if args.replicas \
                else "inline"
            print(f"[serve] scheduler ({kind}): {len(served)} served in "
                  f"{sm['batches']} batches (mean size "
                  f"{sm['batch_size']['mean']:.1f}), {dt:.2f} ms/query, "
                  f"parity-with-facade={n_same}/{len(served)}")
            if shed:
                print(f"[serve] scheduler shed {len(shed)} request(s): "
                      f"{sorted({o.reason for o in shed})}")
            assert n_same == len(served), "Session.submit must match query_batch"
            if served:
                a = served[0].autopsy()
                print(f"[serve] autopsy (first served): "
                      f"total {a['total_us'] / 1e3:.2f} ms = "
                      f"queue {a['queue_us'] / 1e3:.2f} + "
                      f"dispatch {a['dispatch_us'] / 1e3:.2f} + "
                      f"execute {a['execute_us'] / 1e3:.2f} + "
                      f"merge {a['merge_us'] / 1e3:.2f} ms "
                      f"(execute {a['execute_frac']:.0%} of total)")
            if tracer is not None and args.replicas > 0:
                lanes = sorted({s.pid for s in tracer.spans if s.pid != 0})
                wspans = sum(1 for s in tracer.spans if s.pid != 0)
                print(f"[serve] distributed trace: {wspans} worker spans "
                      f"across {len(lanes)} replica lane(s) collated onto "
                      f"the host timeline")
            if args.slo:
                from repro.obs import render_prometheus

                rep = session.slo_report()
                print(f"[serve] SLO report (window {rep['window_s']:.0f}s, "
                      f"target {rep['target']:.0%}):")
                for tenant, t in sorted(rep["tenants"].items()):
                    print(f"[serve]   tenant {tenant!r}: {t['requests']} req "
                          f"({t['shed']} shed), hit-rate "
                          f"{t['deadline_hit_rate']:.1%}, p99 "
                          f"{t['p99_ms']:.2f} ms, burn {t['burn_rate']:.2f}x")
                prom = render_prometheus({"sched": rep["sched"]})
                print(f"[serve] prometheus ({len(prom.splitlines())} lines):")
                for line in prom.splitlines()[:6]:
                    print(f"[serve]   {line}")

    lat = eng.metrics.snapshot().get("latency", {})
    for name in ("query_us", "topk_query_us"):
        h = lat.get(name)
        if h:
            print(f"[serve] latency {name}: p50 {h['p50'] / 1e3:.2f} ms, "
                  f"p99 {h['p99'] / 1e3:.2f} ms over {h['count']} queries")
    if probe_log is not None:
        probe_log.close()
        print(f"[serve] probe log written to {args.probe_log}")
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"[serve] trace written to {args.trace_out} "
              f"({len(tracer.spans)} spans) — open in ui.perfetto.dev")


if __name__ == "__main__":
    main()
