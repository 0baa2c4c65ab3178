"""The trace reduction on synthetic events (and on a recorded chip trace,
when bench/testdata holds one)."""
from pathlib import Path

import pytest

import devtrace

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def test_union_merges_and_clips():
    ev = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 95, 130)]
    assert devtrace.union(ev, 2, 100) == [(2, 20), (30, 40), (95, 100)]


def test_gaps_cover_the_rest_of_the_window():
    busy = [(2, 20), (30, 40)]
    assert devtrace.gaps(busy, 0, 50) == [(0, 2), (20, 30), (40, 50)]
    assert devtrace.gaps([], 0, 5) == [(0, 5)]


def test_label_takes_the_innermost_open_span():
    spans = [("sched.batch", 0, 100, 0), ("shard.verify", 10, 30, 2), ("serve.plan", 5, 12, 1)]
    assert devtrace.labels([150, 20, 6, 11, 40], spans) == [
        "host.no_span", "shard.verify", "serve.plan", "shard.verify", "sched.batch"]
    assert devtrace.labels([], spans) == []


def test_labelling_many_gaps_stays_fast():
    import time

    spans = [(f"s{i}", 10 * i, 10 * i + 15, i % 3) for i in range(100_000)]
    t0 = time.perf_counter()
    got = devtrace.labels(list(range(5, 1_100_000, 7)), spans)
    assert time.perf_counter() - t0 < 5.0
    assert got[0] == "s0" and got[-1] == "host.no_span"


def test_reduce_busy_idle_and_programs():
    tr = devtrace.DeviceTrace(
        ops={"/device:TPU:0": [("fusion.1", 100, 300), ("custom-call.2", 250, 400),
                               ("fusion.1", 700, 800)]},
        modules={"/device:TPU:0": [("jit_fused_topk(7)", 100, 400), ("jit_block_query(3)", 700, 800)]},
    )
    spans = [("shard.verify", 400, 700, 2), ("sched.batch", 0, 1000, 0)]
    out = devtrace.reduce(tr, 0, 1000, spans)
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert dict((k, v) for k, v in out["device_ops"]) == {
        "fusion.1": pytest.approx(300e-9), "custom-call.2": pytest.approx(150e-9)}
    assert dict(out["idle_gaps"]) == {"sched.batch": pytest.approx(300e-9),
                                      "shard.verify": pytest.approx(300e-9)}
    assert out["modules_s"]["jit_fused_topk(7)"] == pytest.approx(300e-9)


def test_reduce_refuses_a_trace_without_device_operations():
    with pytest.raises(ValueError):
        devtrace.reduce(devtrace.DeviceTrace(), 0, 10)


RECORDED = TESTDATA / "tiny.xplane.pb"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A short trace recorded on one TPU v5e: two jitted programs run five
    times each between sleeps, after the harness's marker."""
    tr = devtrace.load(str(RECORDED))
    assert list(tr.ops) == ["/device:TPU:0"]
    lo = tr.markers["bench.window_open"]
    hi = max(e for _, _, e in tr.ops["/device:TPU:0"])
    out = devtrace.reduce(tr, lo, hi)
    assert 0 < out["busy_s"] < out["window_s"]
    names = [n for n, _ in out["device_ops"]]
    assert all(n.startswith("jit_") and ":%" in n for n in names)
    assert {n.split("(")[0] for n in out["modules_s"]} == {"jit__lambda"}
    idle = sum(v for _, v in out["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
