"""Ranked top-k serving: MaxScore pruning vs exhaustive scoring, K shards.

The ranked-workload question the ROADMAP north-star asks: what does BM25
top-k cost over the learned postings store, and how much work does MaxScore
dynamic pruning (rank/topk.py) + segment-granularity score bounds actually
skip?  Every configuration must return *bit-identical* (ids and integer
scores) results to the brute-force quantized-BM25 oracle over decoded
postings — pruning and sharding are pure work-skippers, asserted as such
(K=1 vs K=4 equality included).

Emits BENCH_ranked_topk.json:
  k.<K>.qps / seconds       verified top-10 throughput at K shards
  k.<K>.scored_fraction     (decoded + probed postings) / exhaustive postings
  scored_fraction           the K=1 pruned fraction — the paper-facing number
                            (MaxScore must touch < 0.5x of exhaustive on the
                            Zipf disjunctive workload; gated)
  latency_ratio             pruned seconds / exhaustive seconds on the same
                            run — machine-normalized, gated by
                            check_regression.py (pruning must never cost
                            more than it saves)
  fused.latency_ratio       fused one-dispatch seconds / multi-phase seconds
                            on the *kernel-enabled* multi-phase configuration
                            (guided_kernel + score_kernel — the hundreds of
                            small host<->device hops the fused kernel
                            replaces), same run; machine-normalized and
                            gated < 1.0
  fused.latency_ratio_host  fused seconds / the default all-numpy multi-phase
                            seconds — gated < 1.0: with the device-resident
                            arena the dense one-dispatch path must beat the
                            host path outright, not just the dispatch count
  fused.roofline            inverted-index cost model (benchmarks/roofline
                            index_roofline): index bytes the dispatch lanes
                            read, dispatch device bytes, achieved bytes/s vs
                            the HBM roof — timed against fused_kernel_ns
                            (device-blocked time), with the host bridge
                            reported separately as bridge_seconds
                            (fraction_of_hbm_roof gated as a floor in
                            check_regression.py)

Every fused result is asserted bit-identical to the multi-phase results and
the brute-force oracle, for K=1 and K=4 sharding.  The fused pass also
writes a Chrome-trace of one traced batch (kernel.fused_query spans) to
artifacts/ranked_topk.fused.trace.json for the CI artifact.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

BENCH_PATH = "BENCH_ranked_topk.json"
FUSED_TRACE_PATH = os.path.join("artifacts", "ranked_topk.fused.trace.json")

N_DOCS = 4096
N_TERMS = 5000
AVG_DOC_LEN = 60
N_QUERIES = 64
TOP_K = 10
REPS = 3
K_SWEEP = (1, 4)
SEED = 23


def _system():
    import jax

    from repro.common.config import CorpusConfig, LearnedIndexConfig
    from repro.core import fit_thresholds, init_membership
    from repro.data.corpus import synthesize_corpus
    from repro.index.build import build_inverted_index

    corpus = synthesize_corpus(
        CorpusConfig(n_docs=N_DOCS, n_terms=N_TERMS, avg_doc_len=AVG_DOC_LEN, seed=SEED)
    )
    inv = build_inverted_index(corpus)
    li_cfg = LearnedIndexConfig(embed_dim=32, truncation_k=32, block_size=128)
    # the ranked path never consults the membership model, so thresholds are
    # fitted on untrained params — engine construction cost only
    params, _ = init_membership(jax.random.key(0), li_cfg, corpus.n_terms, corpus.n_docs)
    lb = fit_thresholds(params, inv)
    return inv, li_cfg, lb


def ranked_rows(write_json: bool = True):
    from repro.data.queries import zipf_disjunctions
    from repro.rank.score import ImpactModel, brute_force_topk
    from repro.serve import BooleanEngine, ServeConfig

    inv, li_cfg, lb = _system()
    queries, _ = zipf_disjunctions(inv.dfs, N_QUERIES, seed=SEED + 1)
    im = ImpactModel.build(inv)
    oracle = brute_force_topk(inv, im, queries, TOP_K)

    def run(eng):
        best, results = np.inf, None
        for _ in range(REPS):
            t0 = time.time()
            results = eng.query_topk(queries, TOP_K)
            best = min(best, time.time() - t0)
        return best, results

    per_k: dict[str, dict] = {}
    pruned_seconds = None
    multiphase_results = None
    for k in K_SWEEP:
        eng = BooleanEngine(lb, inv, li_cfg, ServeConfig(n_shards=k))
        for sh in eng.shards:
            sh.ensure_payloads()  # quantize+pack is startup cost, not timed
        best, results = run(eng)
        if k == 1:
            multiphase_results = results
        for r, e in zip(results, oracle):
            assert np.array_equal(r.ids, e.ids) and np.array_equal(r.scores, e.scores), (
                f"K={k} must be bit-identical to brute-force BM25"
            )
        eng.reset_stats()
        eng.query_topk(queries, TOP_K)  # accounting for exactly one pass
        s = eng.metrics.snapshot()["ranked"]
        per_k[str(k)] = {
            "seconds": best,
            "qps": N_QUERIES / best,
            "scored_fraction": s["scored_fraction"],
            "touched_postings": s["touched_postings"],
            "exhaustive_postings": s["exhaustive_postings"],
        }
        if k == 1:
            pruned_seconds = best

    # exhaustive baseline on the same build: cutoff swallows every query
    exh = BooleanEngine(
        lb, inv, li_cfg, ServeConfig(n_shards=1, ranked=dict(topk_exhaustive_cutoff=1 << 30))
    )
    for sh in exh.shards:
        sh.ensure_payloads()
    exh_seconds, exh_results = run(exh)
    for r, e in zip(exh_results, oracle):
        assert np.array_equal(r.ids, e.ids) and np.array_equal(r.scores, e.scores)

    # ---- fused one-dispatch kernel: exactness at K=1/K=4, then the ratios
    from repro.obs import Tracer

    fused_secs = {}
    fused_stats = None
    for k in K_SWEEP:
        eng_f = BooleanEngine(
            lb, inv, li_cfg, ServeConfig(n_shards=k, ranked=dict(fused_kernel=True))
        )
        for sh in eng_f.shards:
            sh.ensure_payloads()
        best_f, results_f = run(eng_f)
        fused_secs[k] = best_f
        for r, e, m in zip(results_f, oracle, multiphase_results):
            assert np.array_equal(r.ids, e.ids) and np.array_equal(r.scores, e.scores), (
                f"fused K={k} must be bit-identical to brute-force BM25"
            )
            assert np.array_equal(r.ids, m.ids) and np.array_equal(r.scores, m.scores), (
                f"fused K={k} must be bit-identical to the multi-phase path"
            )
        if k == 1:
            eng_f.reset_stats()
            t0 = time.time()
            eng_f.query_topk(queries, TOP_K)  # accounting pass for the roofline
            fused_acct_seconds = time.time() - t0
            fused_stats = eng_f.metrics.snapshot()["ranked"]
            tracer = Tracer()  # one traced batch -> the CI fused-trace artifact
            eng_f.cfg.trace = tracer
            eng_f.query_topk(queries, TOP_K)
            eng_f.cfg.trace = None
            os.makedirs(os.path.dirname(FUSED_TRACE_PATH), exist_ok=True)
            tracer.save(FUSED_TRACE_PATH)

    # the configuration the fused kernel replaces: multi-phase with its probe
    # and scoring stages already on (interpret-mode) Pallas — hundreds of
    # small dispatches per batch vs one fused dispatch
    dev = BooleanEngine(
        lb, inv, li_cfg,
        ServeConfig(n_shards=1, guided_kernel=True, ranked=dict(score_kernel=True)),
    )
    for sh in dev.shards:
        sh.ensure_payloads()
    dev_seconds, dev_results = run(dev)
    for r, e in zip(dev_results, oracle):
        assert np.array_equal(r.ids, e.ids) and np.array_equal(r.scores, e.scores)

    try:
        from benchmarks.roofline import PEAKS, index_roofline
    except ImportError:  # script mode: benchmarks/ itself is sys.path[0]
        from roofline import PEAKS, index_roofline

    import jax

    kind = jax.devices()[0].device_kind
    # a device roof only for a chip with published peaks; a CPU run has none
    fused_roof = "not measured" if kind not in PEAKS else index_roofline(
        fused_stats["fused_stream_bytes"],
        fused_stats["fused_device_bytes"],
        fused_stats["fused_lanes"],
        fused_acct_seconds,
        N_QUERIES,
        device_kind=kind,
        # device-timed roofline: the bridge's perf-counter split charges the
        # roof fraction to time actually blocked on device execution
        kernel_seconds=fused_stats["fused_kernel_ns"] / 1e9,
        bridge_seconds=fused_stats["fused_bridge_ns"] / 1e9,
    )
    fused = {
        "seconds": fused_secs[1],
        "qps": N_QUERIES / fused_secs[1],
        "per_k_seconds": {str(k): fused_secs[k] for k in K_SWEEP},
        # gated: one dispatch must beat the many-dispatch kernel pipeline
        "latency_ratio": fused_secs[1] / dev_seconds,
        "kernel_multiphase_seconds": dev_seconds,
        # gated: the arena-resident dense path must also beat the all-numpy
        # multi-phase host path outright
        "latency_ratio_host": fused_secs[1] / pruned_seconds,
        "fused_queries": fused_stats["fused_queries"],
        "fused_lanes": fused_stats["fused_lanes"],
        "kernel_seconds": fused_stats["fused_kernel_ns"] / 1e9,
        "bridge_seconds": fused_stats["fused_bridge_ns"] / 1e9,
        "roofline": fused_roof,
    }
    assert fused["latency_ratio"] < 1.0, (
        f"fused dispatch must beat the kernel multi-phase pipeline, got "
        f"{fused['latency_ratio']:.3f}"
    )
    assert fused["latency_ratio_host"] < 1.0, (
        f"arena-resident fused path must beat the numpy multi-phase path, "
        f"got {fused['latency_ratio_host']:.3f}"
    )

    scored_fraction = per_k["1"]["scored_fraction"]
    latency_ratio = pruned_seconds / exh_seconds
    traj = {
        "workload": {
            "n_docs": N_DOCS,
            "n_terms": N_TERMS,
            "n_postings": int(inv.n_postings),
            "n_queries": N_QUERIES,
            "top_k": TOP_K,
        },
        "k": per_k,
        # MaxScore + segment bounds vs exhaustive scoring, same run: the
        # fraction is deterministic (seeded corpus), the ratio machine-
        # normalized; both lower-is-better and gated
        "scored_fraction": scored_fraction,
        "latency_ratio": latency_ratio,
        "exhaustive": {"seconds": exh_seconds, "qps": N_QUERIES / exh_seconds},
        "fused": fused,
    }
    assert scored_fraction < 0.5, (
        f"MaxScore pruning must score < 0.5x of exhaustive, got {scored_fraction:.3f}"
    )
    rows = [
        (f"ranked/k{k}", 1e6 * per_k[str(k)]["seconds"] / N_QUERIES,
         f"qps={per_k[str(k)]['qps']:.1f}_scored_frac={per_k[str(k)]['scored_fraction']:.3f}")
        for k in K_SWEEP
    ]
    rows.append(("ranked/exhaustive", 1e6 * exh_seconds / N_QUERIES,
                 f"qps={N_QUERIES / exh_seconds:.1f}"))
    rows.append(("ranked/latency_ratio", 0.0, f"pruned_vs_exhaustive={latency_ratio:.3f}"))
    rows.append(("ranked/fused", 1e6 * fused_secs[1] / N_QUERIES,
                 f"qps={fused['qps']:.1f}_vs_kernel_multiphase={fused['latency_ratio']:.3f}"
                 f"_vs_host={fused['latency_ratio_host']:.3f}"))
    if isinstance(fused_roof, dict):
        rows.append(("ranked/fused_roofline", 1e6 * fused_roof["hbm_roof_s"],
                     f"hbm_frac={fused_roof['fraction_of_hbm_roof']:.2e}"
                     f"_stream_bytes={fused_roof['stream_bytes']}"))
    else:
        rows.append(("ranked/fused_roofline", 0.0, f"{fused_roof}_on_{kind}"))
    if write_json:
        with open(BENCH_PATH, "w") as f:
            json.dump(traj, f, indent=2)
        rows.append(("ranked/json", 0.0, f"wrote {BENCH_PATH}"))
    return rows


if __name__ == "__main__":
    for name, us, derived in ranked_rows():
        print(f"{name},{us:.1f},{derived}")
