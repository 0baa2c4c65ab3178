"""Observability: span tracer, metrics registry, probe log, engine wiring.

The unit half pins the primitives — span nesting/ordering and Chrome-trace
schema, histogram percentile math against numpy quantiles, probe-log JSONL
round-trips, registry snapshot/reset semantics.  The integration half serves
real batches through a traced engine and checks the contract the rest of the
repo relies on: every query phase shows up as a span, one probe record per
routed (query, term, shard), `serving_stats()` stays bit-compatible with the
pre-registry dict shape, and tracing off records nothing.
"""
import json
import os
import tempfile
import threading
import time
import warnings

import jax
import numpy as np
import pytest

from repro.common.config import CorpusConfig, LearnedIndexConfig
from repro.core import fit_thresholds, init_membership
from repro.data.corpus import synthesize_corpus
from repro.data.queries import sample_queries, zipf_disjunctions
from repro.index.build import build_inverted_index
from repro.obs import (
    ASYNC_TID, NULL_SPAN, Counter, Gauge, Histogram, ProbeLog, ProbeRecord,
    Registry, Span, SpanRef, Tracer, trace,
)
from repro.serve import BooleanEngine, ServeConfig


# ---------------------------------------------------------------- tracer
def test_span_nesting_order_and_depth():
    tr = Tracer()
    with tr.activate():
        with trace.span("outer", level=0):
            with trace.span("inner") as sp:
                sp.set(bytes=42)
    # spans record at __exit__, innermost first
    assert [s.name for s in tr.spans] == ["inner", "outer"]
    inner, outer = tr.spans
    assert (inner.depth, outer.depth) == (1, 0)
    assert inner.attrs == {"bytes": 42} and outer.attrs == {"level": 0}
    # wall-clock containment: the outer span brackets the inner one
    assert outer.ts_us <= inner.ts_us
    assert outer.ts_us + outer.dur_us >= inner.ts_us + inner.dur_us


def test_chrome_trace_schema():
    tr = Tracer()
    with tr.activate():
        with trace.span("a", k=1):
            with trace.span("b"):
                pass
    doc = tr.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["n_spans"] == 2
    spans = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    meta = [ev for ev in doc["traceEvents"] if ev["ph"] == "M"]
    assert len(spans) == 2
    for ev in spans:
        assert set(ev) == {"name", "cat", "ph", "ts", "dur", "pid", "tid", "args"}
        assert ev["cat"] == "serve"
        assert ev["dur"] >= 0.0
    # the host lane is prenamed after the tracer
    assert {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": tr.name}} in meta
    json.dumps(doc)  # must be valid JSON end to end
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.trace.json")
        tr.save(path)
        with open(path) as f:
            assert json.load(f) == doc


def test_trace_off_is_the_null_singleton():
    assert trace.current() is None
    h = trace.span("anything", bytes=1)
    assert h is NULL_SPAN  # shared instance: no allocation when tracing is off
    assert h.set(more=2) is NULL_SPAN
    with h:
        pass


def test_activate_none_preserves_outer_tracer():
    tr = Tracer()
    with tr.activate():
        # an engine whose config carries no tracer must not mask the caller's
        with trace.activate(None):
            assert trace.current() is tr
            with trace.span("seen"):
                pass
    assert [s.name for s in tr.spans] == ["seen"]
    assert trace.current() is None


def test_spans_carry_worker_thread_ids():
    tr = Tracer()
    barrier = threading.Barrier(2)  # overlap lifetimes so idents differ

    def worker():
        barrier.wait()
        with trace.activate(tr), trace.span("w"):
            pass
        barrier.wait()

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tids = {s.tid for s in tr.spans}
    assert len(tr.spans) == 2 and len(tids) == 2


def test_tracer_reset_clears_spans_and_epoch():
    tr = Tracer()
    with tr.activate(), trace.span("x"):
        pass
    assert tr.spans
    tr.reset()
    assert tr.spans == []
    with tr.activate(), trace.span("y"):
        pass
    assert tr.spans[0].ts_us >= 0.0  # new epoch: timestamps restart near zero


def test_spans_carry_ids_and_causal_parents():
    tr = Tracer()
    with tr.activate():
        with trace.span("root"):
            with trace.span("a"):
                pass
            with trace.span("b"):
                with trace.span("c"):
                    pass
    by = {s.name: s for s in tr.spans}
    assert len({s.id for s in tr.spans}) == 4 and 0 not in {s.id for s in tr.spans}
    assert by["root"].parent == 0 and by["root"].depth == 0
    assert by["a"].parent == by["b"].parent == by["root"].id
    assert by["c"].parent == by["b"].id and by["c"].depth == 2


def test_activate_parent_hangs_another_threads_spans_under_it():
    tr = Tracer()
    with tr.activate(), trace.span("dispatch") as sp:
        def work():
            with trace.activate(tr, sp), trace.span("shard"):
                with trace.span("verify"):
                    pass
            # the base parent leaves with the activation
            with trace.activate(tr), trace.span("after"):
                pass

        t = threading.Thread(target=work)
        t.start()
        t.join()
    by = {s.name: s for s in tr.spans}
    assert by["shard"].tid != by["dispatch"].tid
    assert by["shard"].parent == by["dispatch"].id and by["shard"].depth == 1
    assert by["verify"].parent == by["shard"].id and by["verify"].depth == 2
    assert by["after"].parent == 0 and by["after"].depth == 0
    # NULL_SPAN and a SpanRef are parents too
    with trace.activate(tr, NULL_SPAN), trace.span("orphan"):
        pass
    with trace.activate(tr, SpanRef(99, 4)), trace.span("remote"):
        pass
    by = {s.name: s for s in tr.spans}
    assert (by["orphan"].parent, by["orphan"].depth) == (0, 0)
    assert (by["remote"].parent, by["remote"].depth) == (99, 5)


def test_chrome_trace_shows_ids_and_async_request_spans():
    tr = Tracer()
    root = tr.root()
    with trace.activate(tr, root), trace.span("child"):
        pass
    tr.add_span(Span(name="sched.request", ts_us=0.0, dur_us=50.0, tid=ASYNC_TID,
                     depth=0, attrs={"request": 1}, id=root.id))
    doc = tr.chrome_trace()
    [child] = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert child["args"] == {"id": 2, "parent": root.id}
    begin, end = [e for e in doc["traceEvents"] if e["ph"] in ("b", "e")]
    assert (begin["ph"], end["ph"]) == ("b", "e") and begin["id"] == end["id"] == root.id
    assert begin["args"] == {"request": 1, "id": root.id, "parent": 0}
    assert end["ts"] == 50.0 and doc["otherData"]["n_spans"] == 2
    json.dumps(doc)


def test_trace_off_draws_no_span_id():
    tr = Tracer()
    with trace.activate(None, SpanRef(5, 0)):
        assert trace.span("x") is NULL_SPAN
        with trace.span("x"):
            pass
    assert tr.spans == [] and tr.next_id() == 1


def test_profiler_host_clock_keeps_a_constant_offset_to_perf_counter(tmp_path):
    """The traced benchmark maps program spans onto the profiler's clock
    with one marker: two markers 0.2 s apart must agree within 50 us."""
    import glob

    from jax.profiler import ProfileData, TraceAnnotation

    bracket = {}
    jax.profiler.start_trace(str(tmp_path))
    try:
        for name in ("clock.a", "clock.b"):
            t0 = time.perf_counter_ns()
            with TraceAnnotation(name):
                bracket[name] = (t0, time.perf_counter_ns())
            time.sleep(0.2)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in bracket:
                    seen[e.name] = int(e.start_ns)
    (a0, a1), (b0, b1) = bracket["clock.a"], bracket["clock.b"]
    traced = seen["clock.b"] - seen["clock.a"]
    # each marker starts inside its bracket of perf_counter_ns readings
    slack = 50_000 + (a1 - a0) + (b1 - b0)
    assert abs(traced - ((b0 + b1) - (a0 + a1)) / 2) <= slack
    assert 0.19e9 < traced < 0.5e9


# ---------------------------------------------------------------- metrics
def test_counter_gauge_basics():
    c, g = Counter(), Gauge()
    c.inc()
    c.inc(4)
    g.set(2.5)
    assert c.snapshot() == 5 and g.snapshot() == 2.5
    c.reset()
    g.reset()
    assert c.snapshot() == 0 and g.snapshot() == 0.0


def test_histogram_percentiles_linear_buckets():
    # controlled edges: interpolation error is bounded by one bucket width
    values = np.arange(1.0, 1001.0)
    h = Histogram(buckets=list(np.arange(0.0, 1001.0, 10.0)))
    for v in np.random.default_rng(0).permutation(values):
        h.observe(v)
    for q in (1, 10, 25, 50, 75, 90, 99):
        assert abs(h.percentile(q) - np.percentile(values, q)) <= 10.5, q
    s = h.snapshot()
    assert s["count"] == 1000 and s["min"] == 1.0 and s["max"] == 1000.0
    assert abs(s["mean"] - values.mean()) < 1e-9


def test_histogram_percentiles_default_log_buckets():
    # default buckets are quarter-decade: estimates stay within ~one bucket
    # (factor 10**0.25) of the numpy quantile on a heavy-tailed sample
    rng = np.random.default_rng(7)
    values = np.clip(rng.lognormal(np.log(500.0), 1.0, size=5000), 1.0, 1e6)
    h = Histogram()
    for v in values:
        h.observe(v)
    for q in (50, 90, 99):
        est, ref = h.percentile(q), float(np.percentile(values, q))
        assert ref / 10**0.3 <= est <= ref * 10**0.3, (q, est, ref)
    # clamped to observed extremes
    assert h.percentile(0) == values.min()
    assert h.percentile(100) == values.max()


def test_histogram_empty_and_reset():
    h = Histogram()
    assert h.snapshot() is None and h.percentile(50) == 0.0
    h.observe(3.0)
    assert h.snapshot()["count"] == 1
    with pytest.raises(ValueError):
        h.percentile(101)
    h.reset()
    assert h.snapshot() is None


def test_registry_dotted_names_collectors_and_reset():
    reg = Registry()
    reg.counter("latency.plan_us")  # histogram name collision must be loud
    with pytest.raises(TypeError):
        reg.histogram("latency.plan_us")
    reg.counter("queries.ranked").inc(3)
    reg.histogram("latency.query_us").observe(100.0)
    section = {"hits": 1}
    resets = []
    reg.register("cache", lambda: section, reset=lambda: resets.append(True))
    reg.register("ranked", lambda: None)  # None -> key omitted
    snap = reg.snapshot()
    assert snap["queries"]["ranked"] == 3
    assert snap["latency"]["query_us"]["count"] == 1
    assert snap["cache"] == {"hits": 1} and "ranked" not in snap
    reg.reset()
    assert resets == [True]
    snap = reg.snapshot()
    assert snap["queries"]["ranked"] == 0 and "query_us" not in snap.get("latency", {})


# ---------------------------------------------------------------- probe log
def test_probelog_jsonl_round_trip():
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "probes.jsonl")
        log = ProbeLog(path)
        with log.context(query=3, shard=1):
            log.log(17, "guided", n_cands=8, n_found=2, n_postings=100,
                    eps_window=6.5, bytes=96, wall_us=12.25)
        log.log(9, "fallback", n_cands=4, n_found=4, n_postings=4,
                eps_window=0.0, bytes=16, wall_us=3.0)  # outside any context
        log.close()
        back = ProbeLog.read(path)
    assert back == [
        ProbeRecord(query=3, shard=1, term=17, route="guided", n_cands=8,
                    n_found=2, n_postings=100, eps_window=6.5, bytes=96,
                    wall_us=12.25),
        ProbeRecord(query=-1, shard=-1, term=9, route="fallback", n_cands=4,
                    n_found=4, n_postings=4, eps_window=0.0, bytes=16,
                    wall_us=3.0),
    ]


def test_probelog_in_memory_and_context_restore():
    log = ProbeLog()
    with log.context(query=1, shard=0):
        with log.context(query=2, shard=1):
            log.log(5, "guided", n_cands=1, n_found=1, n_postings=9,
                    eps_window=2.0, bytes=8, wall_us=1.0)
        log.log(6, "decode", n_cands=1, n_found=0, n_postings=9,
                eps_window=2.0, bytes=8, wall_us=1.0)
    assert [(r.query, r.shard) for r in log.records] == [(2, 1), (1, 0)]
    assert log.n_records == 2


# ---------------------------------------------------------------- engine
@pytest.fixture(scope="module")
def served():
    """One engine serving boolean + ranked batches with full observability."""
    corpus = synthesize_corpus(
        CorpusConfig(n_docs=600, n_terms=2000, avg_doc_len=40, seed=13)
    )
    inv = build_inverted_index(corpus)
    li = LearnedIndexConfig(embed_dim=16, truncation_k=16, block_size=64)
    params, _ = init_membership(jax.random.key(0), li, corpus.n_terms, corpus.n_docs)
    lb = fit_thresholds(params, inv)
    tracer, plog = Tracer(), ProbeLog()
    cfg = ServeConfig(n_shards=2, obs=dict(trace=tracer, probe_log=plog))
    eng = BooleanEngine(lb, inv, li, cfg)
    bool_q = sample_queries(corpus, 8, seed=3)
    ranked_q, _ = zipf_disjunctions(inv.dfs, 8, seed=5)
    eng.query_batch(bool_q)
    eng.query_topk(ranked_q, 5)
    return eng, tracer, plog, bool_q


def test_traced_batch_covers_every_phase(served):
    _, tracer, _, _ = served
    names = {s.name for s in tracer.spans}
    # boolean path: plan -> per-shard mask -> probe fan-out -> merge
    assert {"serve.batch", "serve.plan", "serve.candidate_mask",
            "serve.probe_phase", "shard.verify", "probe.term",
            "serve.merge"} <= names
    # ranked path: plan -> per-shard topk -> heap merge
    assert {"serve.topk_batch", "shard.topk", "serve.heap_merge"} <= names
    # probe spans carry the route decision + candidate count as attrs
    probes = [s for s in tracer.spans if s.name == "probe.term"]
    assert probes and all(
        {"term", "route", "n_cands"} <= set(s.attrs) for s in probes
    )


def test_one_probe_record_per_routed_probe(served):
    eng, _, plog, _ = served
    g = eng.metrics.snapshot()["guided"]
    recs = plog.records
    # every non-empty probe call bumps exactly one route counter and logs
    # exactly one record
    routed = sum(1 for r in recs if r.route != "empty")
    assert routed == g["guided_terms"] + g["fallback_terms"] + g["routed_terms"]
    assert plog.n_records == len(recs) > 0
    # executor context attributes every record to a live (query, shard)
    assert all(r.query >= 0 and r.shard in (0, 1) for r in recs)
    assert all(r.route in ("empty", "fallback", "decode", "guided") for r in recs)
    assert all(r.wall_us >= 0.0 and r.bytes >= 0 for r in recs)


def test_serving_stats_is_a_deprecated_snapshot_alias(served):
    eng, *_ = served
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = eng.serving_stats()
        eng.serving_stats()  # exactly one warning per call, not per process
    deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(deps) == 2
    snap = eng.metrics.snapshot()
    assert legacy.keys() == snap.keys()
    assert legacy["summary"] == snap["summary"]
    # the summary block keeps its pre-registry keys exactly
    assert set(legacy["summary"]) == {
        "n_shards", "cache_hits", "cache_misses", "cache_evictions",
        "probe_bytes", "bytes_ratio", "scored_fraction",
    }
    # facade summary aggregates the per-shard registries
    assert legacy["summary"]["cache_hits"] == sum(
        s["decode_cache"]["hits"] for s in legacy["shards"]
    )
    assert legacy["queries"]["boolean"] == 8 and legacy["queries"]["ranked"] == 8
    for name in ("plan_us", "mask_us", "probe_us", "merge_us", "query_us",
                 "topk_query_us"):
        assert legacy["latency"][name]["count"] > 0, name


def test_trace_off_records_nothing(served):
    eng, tracer, _, bool_q = served
    n = len(tracer.spans)
    saved = eng.cfg.trace
    eng.cfg.trace = None
    try:
        eng.query_batch(bool_q[:2])
    finally:
        eng.cfg.trace = saved
    assert len(tracer.spans) == n


def test_public_reset_clears_every_window(served):
    eng, _, _, bool_q = served
    eng.query_batch(bool_q[:2])
    # per-shard public reset: no caller reaches into sh._guided anymore
    for sh in eng.shards:
        assert hasattr(sh, "reset_stats")
    eng.reset_stats()
    snap = eng.metrics.snapshot()
    assert "ranked" not in snap  # ranked section reappears only after queries
    assert snap["summary"]["cache_hits"] == 0
    assert snap["summary"]["probe_bytes"] == 0
    assert snap["queries"] == {"ranked": 0, "boolean": 0}
    assert "latency" not in snap or all(
        v is None for v in snap["latency"].values()
    )
