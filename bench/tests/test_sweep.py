"""The knee rule, and one sweep driven at a CPU size past the chip check."""
import json

import pytest

import sweep

LIMIT = 100.0


def _row(rate, p95, failed=0, trail=0.0, unanswered=0, compiles=0, seed=1):
    return {"rate_qps": rate, "traffic_seed": seed, "failed": failed, "p95_ms": p95,
            "trail_s": trail, "unanswered_at_close": unanswered, "compiles": compiles}


def test_knee_is_the_top_of_the_passing_run_from_the_lowest_rate():
    rows = [_row(40, 60), _row(10, 20), _row(20, 30), _row(80, 150)]
    assert sweep.knee(rows, LIMIT) == 40


def test_a_rate_passes_only_on_every_seed():
    rows = [_row(10, 20, seed=1), _row(10, 25, seed=2),
            _row(20, 30, seed=1), _row(20, 130, seed=2), _row(30, 40, seed=1)]
    assert sweep.knee(rows, LIMIT) == 10


def test_a_failing_lower_rate_caps_the_knee():
    # a pass above a failure is noise, not a knee
    rows = [_row(10, 105), _row(20, 50), _row(30, 70)]
    assert sweep.knee(rows, LIMIT) is None


def test_the_sweep_stops_only_after_a_rate_fails_on_every_seed():
    rows = [dict(r, passes=sweep.passes(r, LIMIT)) for r in
            (_row(10, 20, seed=1), _row(10, 25, seed=2),
             _row(20, 30, seed=1), _row(20, 130, seed=2),
             _row(30, 140, seed=1), _row(30, 150, seed=2))]
    assert not sweep.failed_everywhere(rows, 10) and not sweep.failed_everywhere(rows, 20)
    assert sweep.failed_everywhere(rows, 30) and not sweep.failed_everywhere(rows, 40)


def test_whole_window_p95_decides_not_a_third():
    # the rule has no thirds: one window's p95 under the limit passes it
    assert sweep.passes(_row(12, 54.3), LIMIT)


def test_failures_backlog_and_compiles_fail_a_rate():
    assert not sweep.passes(_row(10, 50, failed=1), LIMIT)
    assert not sweep.passes(_row(10, 50, trail=1.5), LIMIT)
    assert not sweep.passes(_row(10, 50, compiles=1), LIMIT)
    # 20 q/s x 100 ms: 2 in flight, plus one
    assert sweep.passes(_row(20, 50, unanswered=3), LIMIT)
    assert not sweep.passes(_row(20, 50, unanswered=4), LIMIT)
    assert sweep.passes(_row(10, 99.9), LIMIT)


def test_sweep_at_cpu_size_warms_then_measures_without_compiling(monkeypatch, tmp_path,
                                                                  capsys):
    import run
    import spec
    from conftest import CPU, tiny_cell

    cell = tiny_cell("ranked-or-k10")
    monkeypatch.setattr(spec, "cell", lambda name: cell)
    monkeypatch.setattr(run, "chip", lambda chips: dict(CPU))
    monkeypatch.setattr(run, "configure_compile_cache", lambda: "")
    code = sweep.main(["--cell", "tiny.ranked-or-k10=5,2000", "--seconds", "1.0",
                       "--seed", str(2**34 + 1), "--out", str(tmp_path)])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    rows = out["sweep"]
    assert len(rows) == 4 and {r["traffic_seed"] for r in rows} == {2**34 + 1, 2**34 + 2}
    assert all(r["compiles"] == 0 for r in rows)  # the warm-up covered every shape
    assert code == 0 and out["knee_qps"] == 5.0 and out["rate_qps"] == pytest.approx(4.0)
    assert out["knee_reached"]  # 2000 q/s failed on both seeds
    written = json.loads((tmp_path / "tiny.ranked-or-k10.json").read_text())
    assert written["rate_qps"] == pytest.approx(4.0)
