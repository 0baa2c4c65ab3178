"""A run at a CPU size, driven past the chip check: sound it is correct; the
configuration's control and each fault planted in the timed path make
``correct`` come out false."""
import numpy as np
import pytest

import openloop
import run
from conftest import tiny_cell

SEED = 2**35 + 17


def _run(cell, cpu_device, **kw):
    return run.run_cell(cell, SEED, 2.0, False, cpu_device, profile=False, **kw)


@pytest.mark.parametrize("traffic", ["boolean-weblog", "ranked-or-k10"])
def test_sound_run_is_correct(traffic, cpu_device):
    res = _run(tiny_cell(traffic), cpu_device)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 80
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"p50_ms", "goodput_qps", "index_bits_per_posting",
                                   "setup_s"}


def test_warm_up_never_sends_the_window_requests(monkeypatch, cpu_device):
    import system

    seen = {}
    warm, window = system.warm, openloop.run

    def record_warm(session, engine, sched, max_terms):
        seen["warm"] = sched.terms.copy()
        return warm(session, engine, sched, max_terms)

    def record_window(session, reqs, due_s, seconds, **kw):
        seen["window"] = np.stack([r.terms for r in reqs])
        return window(session, reqs, due_s, seconds, **kw)

    monkeypatch.setattr(system, "warm", record_warm)
    monkeypatch.setattr(openloop, "run", record_window)
    res = _run(tiny_cell("ranked-or-k10"), cpu_device)
    assert res["correct"]
    a, b = seen["warm"], seen["window"]
    assert a.shape == b.shape  # as many warm-up requests as the window sends
    window_rows = {tuple(r) for r in b}
    assert sum(tuple(r) in window_rows for r in a) < len(a) // 4


@pytest.mark.parametrize("traffic", ["boolean-weblog", "ranked-or-k10"])
def test_control_is_not_correct(traffic):
    import control

    got = control.readings(tiny_cell(traffic), SEED, 2.0)
    assert got["program"]["wrong_answers"] == got["program"]["missing_answers"] == 0
    assert got["control"]["wrong_answers"] > 0 and got["control"]["compared"] == 80


def test_boolean_answer_altered_where_produced(monkeypatch, cpu_device):
    from repro.serve.shard import ShardEngine

    execute = ShardEngine.execute

    def drop_a_doc(self, q, *a, **kw):
        out = execute(self, q, *a, **kw)
        rows = np.flatnonzero(out.any(axis=1))
        if len(rows):
            w = np.flatnonzero(out[rows[0]])[0]
            out[rows[0], w] &= out[rows[0], w] - np.uint32(1)  # clear the lowest set bit
        return out

    monkeypatch.setattr(ShardEngine, "execute", drop_a_doc)
    res = _run(tiny_cell("boolean-weblog"), cpu_device)
    assert not res["correct"] and res["checks"]["wrong_answers"]["value"] > 0


def test_ranked_answer_altered_where_produced(monkeypatch, cpu_device):
    from repro.rank.score import TopKResult
    from repro.serve.shard import ShardEngine

    batch = ShardEngine.query_topk_batch

    def bump_a_score(self, items):
        out = batch(self, items)
        if out and len(out[0].scores):
            out[0] = TopKResult(ids=out[0].ids, scores=out[0].scores + 1)
        return out

    monkeypatch.setattr(ShardEngine, "query_topk_batch", bump_a_score)
    res = _run(tiny_cell("ranked-or-k10"), cpu_device)
    assert not res["correct"] and res["checks"]["wrong_answers"]["value"] > 0


def test_answer_that_never_comes(monkeypatch, cpu_device):
    from repro.serve.sched import session as sched_session

    run_batch = sched_session.Session._run_batch
    window = openloop.run
    state = {"open": False, "n": 0}

    def open_window(*a, **kw):
        state["open"] = True
        return window(*a, **kw)

    def lose_one_batch(self, batch):
        state["n"] += state["open"]
        if state["n"] == 3:  # the window's third batch is never answered
            self._slots.release()
            return
        run_batch(self, batch)

    monkeypatch.setattr(openloop, "run", open_window)

    monkeypatch.setattr(sched_session.Session, "_run_batch", lose_one_batch)
    monkeypatch.setattr(openloop, "WAIT_PAST_CLOSE_S", 2.0)
    res = _run(tiny_cell("boolean-weblog"), cpu_device)
    assert not res["correct"] and res["checks"]["missing_answers"]["value"] > 0


def test_control_script_reads_program_and_control_on_each_seed(monkeypatch, capsys):
    import json

    import control
    import spec
    from conftest import CPU

    cell = tiny_cell("boolean-weblog")
    monkeypatch.setattr(spec, "cell", lambda name: cell)
    monkeypatch.setattr(run, "chip", lambda chips: dict(CPU))
    monkeypatch.setattr(run, "configure_compile_cache", lambda: "")
    assert control.main(["--workload", "tiny", "--seeds", f"{SEED},{SEED + 1}",
                         "--seconds", "1.0"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert [r["seed"] for r in lines[:2]] == [SEED, SEED + 1]
    assert all(r["program"]["compared"] == 40 for r in lines[:2])
    assert lines[-1]["lower"] == 0 and lines[-1]["upper"] > 0
