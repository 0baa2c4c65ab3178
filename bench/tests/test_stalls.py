"""Host stalls: collector passes observed, generator stalls found and named."""
import gc

import numpy as np

import stalls


def test_gc_passes_are_recorded_and_the_callback_removed():
    before = list(gc.callbacks)
    with stalls.GcPauses() as rec:
        gc.collect()
    assert gc.callbacks == before
    assert any(gen == 2 and secs >= 0 for gen, _, secs in rec.passes)
    t = rec.passes[-1][1]
    summary = rec.summary(t - 1, t + 1)
    assert summary[2]["passes"] >= 1 and summary[2]["max_s"] <= summary[2]["total_s"]


def test_send_stalls_are_the_longest_merged_stretches():
    due = np.array([0.0, 0.1, 0.2, 0.3, 1.0, 1.1])
    sent = np.array([0.0, 0.1, 0.5, 0.5, 1.0, 1.13])  # 2 and 3 held back together
    got = stalls.send_stalls(due, sent, min_ms=5.0, top=5)
    assert got[0] == (0.2, 0.5) and got[1] == (1.1, 1.13) and len(got) == 2
    assert stalls.send_stalls(due, sent, min_ms=5.0, top=1) == [(0.2, 0.5)]


def test_a_stall_is_named_by_collector_pass_request_in_service_and_span():
    sent = np.array([0.0, 0.1, 0.5])
    done = np.array([0.05, 0.6, np.nan])
    spans = [("sched.batch", 0.1, 0.6, 0), ("shard.verify", 0.15, 0.55, 1)]
    recs = stalls.attribute([(0.2, 0.5)], opened=0.0, sent=sent, done=done,
                            describe=lambda i: f"#{i}", gc_passes=[(2, 0.3, 0.1)],
                            spans=spans)
    r = recs[0]
    assert r["ms"] == 300.0 and r["gc_ms"] == 100.0 and r["gc_generations"] == [2]
    assert r["in_service"] == ["#1"] and r["host_span"] == "shard.verify"
    assert "host_span" not in stalls.attribute(
        [(0.2, 0.5)], opened=0.0, sent=sent, done=done, describe=str)[0]
