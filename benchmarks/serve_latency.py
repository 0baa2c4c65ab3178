"""Open-loop serving latency under Poisson arrivals + tracing overhead.

The ROADMAP's throughput-serving question needs tail latency, not just
mean qps: a closed loop (issue the next query when the previous returns)
hides queueing entirely, so this bench replays a Poisson arrival process
against measured per-query service times — the standard open-loop replay:
each query is executed once for its real service time, and completion
times follow the single-server queue recurrence

    start_i = max(arrival_i, completion_{i-1});  latency = completion - arrival

at an offered load of UTILIZATION x the calibrated service rate.  The
workload mixes batch-of-1 conjunctive Boolean queries with ranked top-K
disjunctions, both checked exact against brute force during warmup.

The second question this answers is what observability costs: interleaved
closed-loop passes with the span tracer off/on give trace_overhead_ratio
(best-of-N mean service time, traced / untraced — wall-clock but machine-
normalized within one run, gated by check_regression.py with a 1.05 floor:
tracing must stay within ~5% everywhere).  The probe log stays enabled for
every pass so the ratio isolates the tracer itself.  The gated ratio is
measured on the *distributed* path — the continuous-batching Session over
one process replica per shard, where tracing additionally pays TraceContext
IPC, worker span shipping, and host-side collation — because that is the
path a deployment actually runs; the in-process facade measure is kept as
trace_overhead_ratio_inline.  The traced sched passes also self-check the
distributed timeline: merged worker spans must be present (pid != 0 lanes)
and nesting_violations() must come back empty after clock alignment.

Emits BENCH_serve_latency.json:
  open_loop.p50_ms / p99_ms / qps   queue latency percentiles at UTILIZATION
  closed_loop.*_ms                  calibrated per-kind service means
  trace_overhead_ratio              traced / untraced service time through
                                    the sched/process-replica path (gated)
  trace_overhead_ratio_inline       same measure on the in-process facade
  latency_ratio                     open-loop p99/p50 — tail amplification
                                    from queueing, machine-normalized (gated)
  fused.roofline                    the ranked workload re-served through the
                                    fused kernel (ServeConfig.fused_kernel),
                                    positioned by benchmarks/roofline
                                    index_roofline against the HBM roof
plus, under the gitignored artifacts/ dir (CI uploads from there):
  serve_latency.trace.json    Chrome-trace of the final traced sched pass —
                              host + worker pid lanes on one clock-aligned
                              timeline; open in ui.perfetto.dev
  serve_latency.probes.jsonl  routed-probe records (worker records forwarded
                              to the host sink)
  serve_latency.slo.json      Session.slo_report() after the sched passes
  serve_latency.prom          the same report in Prometheus text exposition

``--sustained`` runs the sustained-load mode instead (``sustained_rows``):
the continuous-batching Session over process replicas vs the serial facade
— a closed-loop saturation pass for the gated qps_ratio (submit-all/drain,
timed exactly like the serial baseline), a real-time Poisson rate sweep
with exactness asserted for every admitted result (the latency curve), and
an overload pass with deadlines.  Emits
BENCH_serve_sustained.json (summary.qps_ratio and overload.p99_over_deadline
are gated) and artifacts/serve_sustained.curve.json (the rate->latency
curve, uploaded as a CI artifact).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

# telemetry artifacts (traces, probe logs, SLO reports, curves) land in a
# gitignored dir; only the BENCH_*.json summaries live at the repo root
ART_DIR = "artifacts"
BENCH_PATH = "BENCH_serve_latency.json"
TRACE_PATH = os.path.join(ART_DIR, "serve_latency.trace.json")
PROBE_PATH = os.path.join(ART_DIR, "serve_latency.probes.jsonl")
SLO_PATH = os.path.join(ART_DIR, "serve_latency.slo.json")
PROM_PATH = os.path.join(ART_DIR, "serve_latency.prom")

N_DOCS = 2048
N_TERMS = 4000
AVG_DOC_LEN = 60
N_BOOLEAN = 48
N_RANKED = 24
TOPK = 10
TRAIN_STEPS = 100
N_SHARDS = 2
UTILIZATION = 0.6  # offered load relative to the calibrated service rate
REPS = 3  # off/on passes per tracer state (mean service, best pass taken)
SCHED_REPLICAS = 1  # process replicas per shard for the sched-path measure
SEED = 23

# ---- sustained-load mode (scheduler vs serial fan-out)
SUSTAINED_PATH = "BENCH_serve_sustained.json"
CURVE_PATH = os.path.join(ART_DIR, "serve_sustained.curve.json")
SUS_SHARDS = 4  # the K where the retired thread fan-out convoyed
SUS_REPLICAS = 1  # process replicas per shard
SUS_MAX_BATCH = 16
SUS_REQUESTS = 160  # requests per sweep rate
RATE_MULTIPLIERS = (0.5, 1.0, 2.0, 4.0)  # offered load relative to serial qps
OVERLOAD_MULTIPLIER = 4.0
OVERLOAD_REQUESTS = 400
# deadline expiry happens at dispatch time, so an admitted request's worst
# case is ~deadline + one batch service time; the budget must dominate the
# per-batch service cost (~15-40 ms here) for p99_over_deadline to measure
# shedding rather than service jitter
OVERLOAD_DEADLINE_MS = 100.0


def _system():
    import jax
    import jax.numpy as jnp

    from repro.common.config import CorpusConfig, LearnedIndexConfig, OptimizerConfig
    from repro.core import fit_thresholds, init_membership, membership_loss
    from repro.data.corpus import synthesize_corpus
    from repro.data.loader import membership_batches
    from repro.index.build import build_inverted_index
    from repro.train import init_train_state, make_train_step

    corpus = synthesize_corpus(
        CorpusConfig(n_docs=N_DOCS, n_terms=N_TERMS, avg_doc_len=AVG_DOC_LEN, seed=SEED)
    )
    inv = build_inverted_index(corpus)
    li_cfg = LearnedIndexConfig(embed_dim=32, truncation_k=32, block_size=128)
    params, _ = init_membership(jax.random.key(0), li_cfg, corpus.n_terms, corpus.n_docs)
    ocfg = OptimizerConfig(lr=0.05, warmup_steps=10, total_steps=TRAIN_STEPS,
                           weight_decay=0.0)
    step = jax.jit(make_train_step(lambda p, b: membership_loss(p, b), ocfg))
    st = init_train_state(params, ocfg)
    for _, batch in zip(range(TRAIN_STEPS), membership_batches(corpus, batch_size=2048)):
        params, st, _ = step(params, st, {k: jnp.asarray(v) for k, v in batch.items()})
    lb = fit_thresholds(params, inv)
    return corpus, inv, li_cfg, lb


def _mean_service(eng, work) -> float:
    """One closed-loop pass over the mixed workload -> mean seconds/query."""
    t0 = time.perf_counter()
    for kind, q in work:
        if kind == "bool":
            eng.query_batch([q])
        else:
            eng.query_topk([q], TOPK)
    return (time.perf_counter() - t0) / len(work)


def _sched_service(session, work) -> float:
    """Closed-loop pass through the Session -> mean seconds/query.

    One request in flight at a time, so every dispatch is a batch of one and
    the per-request trace cost (context IPC + span shipping + collation) is
    maximally exposed rather than amortized over coalesced batches.
    """
    from repro.serve.sched import MODE_RANKED, QueryRequest

    t0 = time.perf_counter()
    for kind, q in work:
        req = (QueryRequest(terms=q) if kind == "bool"
               else QueryRequest(terms=q, mode=MODE_RANKED, k=TOPK))
        r = session.submit_async(req, block=True).result(timeout=60)
        assert r.ok, r
    return (time.perf_counter() - t0) / len(work)


def latency_rows(write_json: bool = True):
    from repro.data.queries import (
        brute_force_answers, zipf_conjunctions, zipf_disjunctions,
    )
    from repro.obs import ProbeLog, Tracer
    from repro.rank.score import ImpactModel, brute_force_topk
    from repro.serve import BooleanEngine, ServeConfig

    if write_json:
        os.makedirs(ART_DIR, exist_ok=True)
    corpus, inv, li_cfg, lb = _system()
    probe_log = ProbeLog(PROBE_PATH if write_json else None)
    cfg = ServeConfig(n_shards=N_SHARDS, obs=dict(probe_log=probe_log))
    eng = BooleanEngine(lb, inv, li_cfg, cfg)
    for sh in eng.shards:
        sh.tier2  # codec selection out of every timed region

    bool_q = zipf_conjunctions(inv.dfs, N_BOOLEAN, seed=SEED + 1)
    ranked_q, _ = zipf_disjunctions(inv.dfs, N_RANKED, seed=SEED + 2)
    rng = np.random.default_rng(SEED)
    work = [("bool", q) for q in bool_q] + [("topk", q) for q in ranked_q]
    work = [work[i] for i in rng.permutation(len(work))]

    # ---- warmup + exactness: the engine must stay bit-exact while observed
    res = eng.query_batch(bool_q)
    for r, e in zip(res, brute_force_answers(corpus, bool_q)):
        assert np.array_equal(r, e), "boolean serving must be exact"
    im = eng.impact_model or ImpactModel.build(inv)
    oracle = brute_force_topk(inv, im, ranked_q, TOPK)
    for r, e in zip(eng.query_topk(ranked_q, TOPK), oracle):
        assert np.array_equal(r.ids, e.ids) and np.array_equal(r.scores, e.scores), \
            "ranked serving must match brute-force BM25"

    # ---- tracing overhead (facade): interleaved off/on closed-loop passes
    tracer = Tracer()
    off_s, on_s = [], []
    for _ in range(REPS):
        eng.cfg.trace = None
        off_s.append(_mean_service(eng, work))
        eng.cfg.trace = tracer
        tracer.reset()
        on_s.append(_mean_service(eng, work))
    eng.cfg.trace = None
    trace_overhead_inline = min(on_s) / min(off_s)

    # ---- open loop: Poisson arrivals at UTILIZATION x the service rate
    service = min(off_s)
    rate = UTILIZATION / service
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=len(work)))
    lat = np.zeros(len(work))
    clock = 0.0
    t_wall = time.perf_counter()
    for i, (kind, q) in enumerate(work):
        t0 = time.perf_counter()
        if kind == "bool":
            eng.query_batch([q])
        else:
            eng.query_topk([q], TOPK)
        svc = time.perf_counter() - t0
        clock = max(clock, arrivals[i]) + svc
        lat[i] = clock - arrivals[i]
    wall = time.perf_counter() - t_wall
    p50, p90, p99 = (float(np.percentile(lat, p)) for p in (50, 90, 99))

    # ---- fused ranked path: the same ranked workload through the fused
    # kernel (ServeConfig.fused_kernel), positioned against the HBM roof
    try:
        from benchmarks.roofline import PEAKS, index_roofline
    except ImportError:  # script mode: benchmarks/ itself is sys.path[0]
        from roofline import PEAKS, index_roofline

    feng = BooleanEngine(
        lb, inv, li_cfg, ServeConfig(n_shards=N_SHARDS, ranked=dict(fused_kernel=True))
    )
    for sh in feng.shards:
        sh.ensure_payloads()
    for r, e in zip(feng.query_topk(ranked_q, TOPK), oracle):
        assert np.array_equal(r.ids, e.ids) and np.array_equal(r.scores, e.scores), \
            "fused ranked serving must match brute-force BM25"
    feng.reset_stats()
    t0 = time.perf_counter()
    feng.query_topk(ranked_q, TOPK)  # accounting pass (jit warmed above)
    fused_seconds = time.perf_counter() - t0
    fs = feng.metrics.snapshot()["ranked"]
    import jax

    kind = jax.devices()[0].device_kind
    # a device roof only for a chip with published peaks; a CPU run has none
    fused_roof = "not measured" if kind not in PEAKS else index_roofline(
        fs["fused_stream_bytes"], fs["fused_device_bytes"], fs["fused_lanes"],
        fused_seconds, N_RANKED,
        device_kind=kind,
        kernel_seconds=fs["fused_kernel_ns"] / 1e9,
        bridge_seconds=fs["fused_bridge_ns"] / 1e9,
    )

    # ---- tracing overhead (gated): the same interleaved off/on measure
    # through the continuous-batching Session over process replicas, where
    # tracing also pays TraceContext IPC, worker span shipping, and host-side
    # clock-aligned collation.  The probe log stays on for every pass here
    # too (worker records forward to the host sink regardless of the tracer)
    # so the ratio again isolates the tracer.
    from repro.obs import nesting_violations
    from repro.serve import Session

    sched_tracer = Tracer()
    eng.cfg.sched.n_replicas = SCHED_REPLICAS
    sched_off, sched_on = [], []
    try:
        with tempfile.TemporaryDirectory() as store_dir:
            with Session(eng, store_dir=store_dir) as session:
                session.warm()  # spawn + jit outside every timed region
                for _ in range(REPS):
                    eng.cfg.trace = None
                    sched_off.append(_sched_service(session, work))
                    eng.cfg.trace = sched_tracer
                    sched_tracer.reset()
                    sched_on.append(_sched_service(session, work))
                eng.cfg.trace = None
                slo_rep = session.slo_report()
    finally:
        eng.cfg.trace = None
        eng.cfg.sched.n_replicas = 0
    trace_overhead = min(sched_on) / min(sched_off)

    # the final traced pass must have produced a coherent distributed
    # timeline: worker spans merged into the host tracer on non-host pid
    # lanes, and every lane stack-consistent after clock alignment
    worker_spans = [s for s in sched_tracer.spans if s.pid != 0]
    assert worker_spans, "traced sched pass merged no worker spans"
    wnames = {s.name for s in worker_spans}
    assert wnames & {"probe.term", "decode.postings", "shard.verify",
                     "shard.topk_batch", "worker.bool", "worker.topk"}, wnames
    violations = nesting_violations(sched_tracer.spans, slack_us=0.5)
    assert not violations, violations[:3]

    metrics_lat = eng.metrics.snapshot().get("latency", {})
    traj = {
        "workload": {
            "n_docs": N_DOCS,
            "n_terms": N_TERMS,
            "n_postings": int(inv.n_postings),
            "n_boolean": N_BOOLEAN,
            "n_ranked": N_RANKED,
            "topk": TOPK,
            "n_shards": N_SHARDS,
            "utilization": UTILIZATION,
        },
        "closed_loop": {
            "service_ms": 1e3 * service,
            "untraced_ms": [1e3 * s for s in off_s],
            "traced_ms": [1e3 * s for s in on_s],
        },
        "sched_loop": {
            "n_replicas": SCHED_REPLICAS,
            "untraced_ms": [1e3 * s for s in sched_off],
            "traced_ms": [1e3 * s for s in sched_on],
            "worker_span_names": sorted(wnames),
            "worker_pids": sorted({s.pid for s in worker_spans}),
        },
        "open_loop": {
            "offered_qps": rate,
            "qps": len(work) / wall,
            "p50_ms": 1e3 * p50,
            "p90_ms": 1e3 * p90,
            "p99_ms": 1e3 * p99,
            "n_queries": len(work),
        },
        # traced/untraced mean service within one run — machine-normalized;
        # the span tracer must cost ~nothing when off and <5% when on.  The
        # gated ratio runs through the sched/process-replica path (context
        # IPC + span shipping + collation included); _inline is the facade.
        "trace_overhead_ratio": trace_overhead,
        "trace_overhead_ratio_inline": trace_overhead_inline,
        # open-loop tail amplification (queueing + service variance) within
        # one run; a generous floor absorbs scheduler noise on shared CI
        "latency_ratio": p99 / p50,
        "fused": {
            "seconds": fused_seconds,
            "fused_queries": fs["fused_queries"],
            "fused_lanes": fs["fused_lanes"],
            "roofline": fused_roof,
        },
        "engine_histograms": metrics_lat,
    }
    rows = [
        ("serve_latency/p50", 1e6 * p50, f"p99_ms={1e3 * p99:.2f}"),
        ("serve_latency/qps", 0.0,
         f"qps={traj['open_loop']['qps']:.1f}_offered={rate:.1f}"),
        ("serve_latency/trace_overhead", 0.0,
         f"sched={trace_overhead:.3f}_inline={trace_overhead_inline:.3f}"
         f"_worker_lanes={len(set(s.pid for s in worker_spans))}"),
        ("serve_latency/fused_roofline",
         1e6 * fused_roof["hbm_roof_s"] if isinstance(fused_roof, dict) else 0.0,
         f"hbm_frac={fused_roof['fraction_of_hbm_roof']:.2e}"
         if isinstance(fused_roof, dict) else f"{fused_roof}_on_{kind}"),
    ]
    if write_json:
        with open(BENCH_PATH, "w") as f:
            json.dump(traj, f, indent=2)
        # the distributed trace (host + worker lanes) is the artifact worth
        # keeping — the inline tracer's spans are a strict subset of it
        sched_tracer.save(TRACE_PATH)
        probe_log.close()
        with open(SLO_PATH, "w") as f:
            json.dump(slo_rep, f, indent=2)
        from repro.obs import write_prometheus

        write_prometheus({"sched": slo_rep["sched"], "latency": metrics_lat},
                         PROM_PATH)
        rows.append(("serve_latency/json", 0.0,
                     f"wrote {BENCH_PATH}+{ART_DIR}/(trace+probes+slo+prom)"))
    return rows


def _sustained_workload(corpus, inv, eng):
    """The request mix + its exact answers (asserted at every rate)."""
    from repro.data.queries import (
        brute_force_answers, zipf_conjunctions, zipf_disjunctions,
    )
    from repro.serve.sched import MODE_RANKED, QueryRequest

    bool_q = zipf_conjunctions(inv.dfs, N_BOOLEAN, seed=SEED + 1)
    ranked_q, _ = zipf_disjunctions(inv.dfs, N_RANKED, seed=SEED + 2)
    bool_ans = eng.query_batch(bool_q)
    for r, e in zip(bool_ans, brute_force_answers(corpus, bool_q)):
        assert np.array_equal(r, e), "boolean serving must be exact"
    ranked_ans = eng.query_topk(ranked_q, TOPK)
    work = [
        (QueryRequest(terms=q), (a, None)) for q, a in zip(bool_q, bool_ans)
    ] + [
        (QueryRequest(terms=q, mode=MODE_RANKED, k=TOPK), (a.ids, a.scores))
        for q, a in zip(ranked_q, ranked_ans)
    ]
    rng = np.random.default_rng(SEED + 3)
    return [work[i] for i in rng.permutation(len(work))]


def _open_loop(session, work, rate, n_requests, rng, *, deadline_ms=None):
    """Submit ``n_requests`` at real-time Poisson arrivals; collect outcomes.

    Returns (admitted latencies seconds, shed outcomes, wall seconds).
    Every admitted result is asserted bit-identical to the engine's answer.
    """
    from repro.serve.sched import QueryRequest, Rejected

    gaps = rng.exponential(1.0 / rate, size=n_requests)
    arrivals = np.cumsum(gaps)
    submitted_at = np.zeros(n_requests)
    done_at = np.zeros(n_requests)

    def _done(i):
        def cb(_fut):
            done_at[i] = time.monotonic()
        return cb

    futs = []
    t0 = time.monotonic()
    for i in range(n_requests):
        req, _ = work[i % len(work)]
        wait = t0 + arrivals[i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        # latency is measured from the actual submit instant: sleep()
        # overshoot at sub-ms inter-arrival gaps is pacing drift on the
        # load generator, not scheduler queueing
        submitted_at[i] = time.monotonic()
        f = session.submit_async(
            QueryRequest(terms=req.terms, mode=req.mode, k=req.k,
                         deadline_ms=deadline_ms)
        )
        f.add_done_callback(_done(i))
        futs.append(f)
    results = [f.result(timeout=60) for f in futs]
    wall = time.monotonic() - t0

    lat, shed = [], []
    for i, r in enumerate(results):
        if isinstance(r, Rejected):
            shed.append(r)
            continue
        _, (ids, scores) = work[i % len(work)]
        assert np.array_equal(r.ids, ids), "scheduler must stay bit-exact"
        if scores is not None:
            assert np.array_equal(r.scores, scores)
        lat.append(done_at[i] - submitted_at[i])
    return np.asarray(lat), shed, wall


def sustained_rows(write_json: bool = True):
    """Sustained-load mode: the scheduler vs serial fan-out at K shards."""
    from repro.serve import BooleanEngine, ServeConfig, Session

    if write_json:
        os.makedirs(ART_DIR, exist_ok=True)
    corpus, inv, li_cfg, lb = _system()
    cfg = ServeConfig(
        n_shards=SUS_SHARDS,
        sched=dict(n_replicas=SUS_REPLICAS, max_batch=SUS_MAX_BATCH),
    )
    eng = BooleanEngine(lb, inv, li_cfg, cfg)
    for sh in eng.shards:
        sh.tier2  # codec selection out of every timed region
    work = _sustained_workload(corpus, inv, eng)
    rng = np.random.default_rng(SEED + 4)

    # ---- serial baseline: the facade engine, one request at a time (what a
    # caller got before the scheduler existed: in-process serial fan-out)
    serial_qps = 0.0
    for _ in range(2):  # best of 2 (first pass absorbs any remaining warmup)
        t0 = time.perf_counter()
        for req, _ in work:
            if req.mode == "boolean":
                eng.query_batch([req.terms])
            else:
                eng.query_topk([req.terms], TOPK)
        serial_qps = max(serial_qps, len(work) / (time.perf_counter() - t0))

    sweep = []
    with tempfile.TemporaryDirectory() as store_dir:
        with Session(eng, store_dir=store_dir) as session:
            session.warm()  # spawn + engine rebuild outside every timed region

            # ---- scheduler saturation throughput, measured closed-loop
            # exactly like the serial baseline (submit everything, drain,
            # best of 2).  The gated qps_ratio compares like with like: the
            # open-loop sweep below is kept for the latency curve, but its
            # achieved qps rides on Poisson pacing from a GIL-contended
            # generator thread and is too noisy to gate on.
            sched_qps = 0.0
            for _ in range(2):
                t0 = time.perf_counter()
                futs = [session.submit_async(req, block=True)
                        for req, _ in work]
                results = [f.result(timeout=60) for f in futs]
                dt = time.perf_counter() - t0
                for r, (_, (ids, scores)) in zip(results, work):
                    assert r.ok and np.array_equal(r.ids, ids), \
                        "scheduler must stay bit-exact"
                    if scores is not None:
                        assert np.array_equal(r.scores, scores)
                sched_qps = max(sched_qps, len(work) / dt)

            for mult in RATE_MULTIPLIERS:
                rate = mult * serial_qps
                lat, shed, wall = _open_loop(
                    session, work, rate, SUS_REQUESTS, rng
                )
                assert not shed, "no deadline, queue below bound: nothing sheds"
                sweep.append({
                    "rate_x": mult,
                    "offered_qps": rate,
                    "qps": len(lat) / wall,
                    "p50_ms": 1e3 * float(np.percentile(lat, 50)),
                    "p99_ms": 1e3 * float(np.percentile(lat, 99)),
                    "admitted": len(lat),
                    "shed": 0,
                })

            # ---- overload: offered far past capacity with a deadline; the
            # admitted tail stays bounded and the rest sheds *typed*
            lat, shed, wall = _open_loop(
                session, work, OVERLOAD_MULTIPLIER * serial_qps,
                OVERLOAD_REQUESTS, rng, deadline_ms=OVERLOAD_DEADLINE_MS,
            )
            assert shed, "overload past capacity must shed"
            reasons = sorted({r.reason for r in shed})
            assert set(reasons) <= {"deadline", "queue_full"}, reasons
            overload = {
                "offered_qps": OVERLOAD_MULTIPLIER * serial_qps,
                "deadline_ms": OVERLOAD_DEADLINE_MS,
                "admitted": len(lat),
                "shed": len(shed),
                "shed_reasons": reasons,
                "p99_ms": 1e3 * float(np.percentile(lat, 99)),
                # gated: deadline shedding must keep the admitted tail near
                # the deadline budget even at 4x offered load
                "p99_over_deadline": float(np.percentile(lat, 99))
                / (OVERLOAD_DEADLINE_MS / 1e3),
            }
            sched_snapshot = eng.metrics.snapshot().get("sched", {})

    traj = {
        "workload": {
            "n_docs": N_DOCS,
            "n_terms": N_TERMS,
            "n_boolean": N_BOOLEAN,
            "n_ranked": N_RANKED,
            "topk": TOPK,
            "n_shards": SUS_SHARDS,
            "n_replicas": SUS_REPLICAS,
            "max_batch": SUS_MAX_BATCH,
            "requests_per_rate": SUS_REQUESTS,
        },
        "summary": {
            "serial_qps": serial_qps,
            "sched_qps": sched_qps,
            # gated (lower is better, floor 1.0): the process-worker
            # scheduler must at least match serial fan-out qps at K shards
            "qps_ratio": serial_qps / sched_qps,
        },
        "sweep": sweep,
        "overload": overload,
        "sched_metrics": sched_snapshot,
    }
    rows = [
        ("serve_sustained/qps", 0.0,
         f"serial={serial_qps:.1f}_sched={sched_qps:.1f}"
         f"_ratio={traj['summary']['qps_ratio']:.3f}"),
        ("serve_sustained/overload", 0.0,
         f"admitted_p99_ms={overload['p99_ms']:.1f}_shed={overload['shed']}"),
    ]
    if write_json:
        with open(SUSTAINED_PATH, "w") as f:
            json.dump(traj, f, indent=2)
        with open(CURVE_PATH, "w") as f:
            json.dump({"sweep": sweep, "overload": overload}, f, indent=2)
        rows.append(
            ("serve_sustained/json", 0.0, f"wrote {SUSTAINED_PATH}+{CURVE_PATH}")
        )
    return rows


if __name__ == "__main__":
    mode = sustained_rows if "--sustained" in sys.argv[1:] else latency_rows
    for name, us, derived in mode():
        print(f"{name},{us:.1f},{derived}")
