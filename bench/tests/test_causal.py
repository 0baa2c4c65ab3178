"""Idle gaps and the program's causal spans: a gap is charged to the work a
fan-out thread was doing, and the two readers of the new spans."""
from types import SimpleNamespace

import pytest

import devtrace
import spec
from repro.obs.trace import ASYNC_TID


@pytest.fixture(scope="module")
def traced_spans():
    """A CPU-traced Session over 4 inline shards: its program spans as
    (name, start, end, depth), and the tracer's spans."""
    import jax

    from repro.common.config import CorpusConfig, LearnedIndexConfig
    from repro.core import fit_thresholds, init_membership
    from repro.data.corpus import synthesize_corpus
    from repro.data.queries import sample_queries
    from repro.index.build import build_inverted_index
    from repro.obs import Tracer
    from repro.serve import BooleanEngine, QueryRequest, ServeConfig, Session

    corpus = synthesize_corpus(CorpusConfig(n_docs=800, n_terms=1600, avg_doc_len=50,
                                            seed=31))
    inv = build_inverted_index(corpus)
    li = LearnedIndexConfig(embed_dim=16, truncation_k=16, block_size=64)
    params, _ = init_membership(jax.random.key(2), li, corpus.n_terms, corpus.n_docs)
    tracer = Tracer()
    eng = BooleanEngine(fit_thresholds(params, inv), inv, li,
                        ServeConfig(n_shards=4, obs=dict(trace=tracer)))
    with Session(eng) as s:
        futs = [s.submit_async(QueryRequest(terms=row))
                for row in sample_queries(corpus, 24, max_terms=4, seed=5)]
        assert all(f.result(timeout=60).ok for f in futs)
    return devtrace.program_spans(tracer, 0), list(tracer.spans)


def test_a_gap_inside_a_fan_thread_verify_is_charged_to_verify(traced_spans):
    spans, raw = traced_spans
    dispatch_tids = {s.tid for s in raw if s.name == "sched.dispatch"}
    fan_verify = [(s, sp) for s, sp in zip(raw, spans)
                  if s.name == "shard.verify" and s.tid not in dispatch_tids]
    assert fan_verify
    found = 0
    for s, (_, start, _, _) in fan_verify:
        # an instant just inside this verify while no other fan thread works:
        # open then are the runner's batch and dispatch, this thread's
        # spans, and the requests in the program
        t = start + 1
        others = [r for r, sp in zip(raw, spans) if sp[1] <= t < sp[2]
                  and r.tid not in dispatch_tids | {s.tid, ASYNC_TID}]
        if others:
            continue
        assert any(sp[0] == "sched.dispatch" and sp[1] <= t < sp[2] for sp in spans)
        assert devtrace.labels([t], spans) == ["shard.verify"]
        found += 1
    assert found


def test_idle_in_service_share_reads_idle_less_no_request_gaps():
    read = spec.reader("device.idle_in_service_share")
    req = SimpleNamespace(name="sched.request")
    dev = {"window_s": 50.0, "busy_s": 10.0,
           "idle_gaps": [["host.no_span", 30.0], ["shard.verify", 8.0]]}
    assert read({"device": dev, "spans": [req]}) == pytest.approx(20.0)
    # every idle gap had a request in the program
    dev_busy = dict(dev, idle_gaps=[["shard.verify", 40.0]])
    assert read({"device": dev_busy, "spans": [req]}) == pytest.approx(80.0)
    assert read({"device": None, "spans": [req]}) is None
    # a program without request spans cannot tell the two idles apart
    assert read({"device": dev, "spans": [SimpleNamespace(name="sched.batch")]}) is None


def test_plan_ms_reads_plan_span_time_per_boolean_query():
    read = spec.reader("boolean.plan_ms")

    def plan(us):
        return SimpleNamespace(name="serve.plan", dur_us=us)

    spans = [plan(300.0), plan(500.0), SimpleNamespace(name="shard.verify", dur_us=9e6)]
    assert read({"spans": spans, "n_boolean": 4}) == pytest.approx(0.2)
    assert read({"spans": spans, "n_boolean": 0}) is None
    assert read({"spans": spans[2:], "n_boolean": 4}) is None
