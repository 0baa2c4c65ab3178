"""Knee sweep: one set-up, then open-loop windows at rising fixed rates, each
on two traffic seeds or more, and each cell's fixed rate written from the rule.

    python3 bench/sweep.py --seed 11 --seconds 20 --seeds-per-rate 2 \
        --cell robust04-1of4.boolean=30,60,100,140

The cells (entries of BENCHMARK.json) must share one configuration, which is
built once from ``--seed``, as ``run.py`` builds it.  Each cell's mix is
warmed as a run warms it (``system.warm``: the program's warm-up, every
fused-kernel shape the mix reaches, then a separate stream of the mix as long
as the sweep's longest window), and then a window is sent at each rate on
each traffic seed, each window on a stream of its own.  Per window it prints
p50/p95 over the whole window, the share over the mix's latency limit, the
requests still unanswered when the window closed and how long the last
answer trailed it (a growing backlog shows in both), the generator's
lateness and the compilations inside the window.

The rule (``RULE``): a window passes when every request was answered, its
p95 is under the latency limit, nothing compiled inside it, no more
requests were unanswered at its close than a server answering each within
the limit holds in flight (rate x limit, rounded up, plus one) and the last
answer came less than ``TRAIL_S`` after the close.  A rate passes when every
one of its windows passes.  The knee is the highest swept rate at which that
rate and every lower swept rate pass; the cell's rate is 0.8 x the knee,
written with the rows to ``<out>/<cell>.json``.  The sweep goes on past a
rate that fails on one seed, and stops after the first rate that fails on
every seed, so that the rows show the server past its capacity and the knee
is not the top of the sweep.  Where no rate passes,
nothing is written and the exit code is 1.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import run  # puts src/ and bench/ on sys.path

RATE_SHARE = 0.8
TRAIL_S = 1.0
RULE = ("knee: the highest swept rate at which it and every lower swept rate passed on "
        "every traffic seed: every request answered, the whole window's p95 under the "
        "latency limit, no compilation in the window, at most ceil(rate x limit) + 1 "
        "requests unanswered at the window's close and the last answer less than 1 s "
        "after it; rate: 0.8 x knee")


def backlog_allowance(rate_qps: float, limit_ms: float) -> int:
    """Requests a server answering each within the limit holds in flight, plus one."""
    return math.ceil(rate_qps * limit_ms / 1e3) + 1


def passes(row: dict, limit_ms: float) -> bool:
    return (row["failed"] == 0 and row["p95_ms"] < limit_ms and row["compiles"] == 0
            and row["unanswered_at_close"] <= backlog_allowance(row["rate_qps"], limit_ms)
            and row["trail_s"] < TRAIL_S)


def knee(rows: list[dict], limit_ms: float) -> float | None:
    """The highest rate that passes on every row, with every lower rate."""
    best = None
    for rate in sorted({r["rate_qps"] for r in rows}):
        if not all(passes(r, limit_ms) for r in rows if r["rate_qps"] == rate):
            break
        best = rate
    return best


def failed_everywhere(rows: list[dict], rate: float) -> bool:
    """Whether ``rate`` was swept and failed on every traffic seed."""
    at = [r for r in rows if r["rate_qps"] == rate]
    return bool(at) and not any(r["passes"] for r in at)


def window_row(win, rate: float, traffic_seed: int, compiles: int, limit_ms: float) -> dict:
    """One swept window's row, with whether it passes."""
    import numpy as np

    import latency

    lat = latency.latencies_ms(win.due, win.done, win.answered)
    row = {
        "rate_qps": rate, "traffic_seed": traffic_seed, "requests": len(lat),
        "failed": int((~win.answered).sum()),
        "p50_ms": latency.percentile(lat, 50),
        "p95_ms": latency.percentile(lat, 95),
        "over_limit": float(np.mean(lat > limit_ms)),
        "unanswered_at_close": win.unanswered_at_close,
        "trail_s": win.trail_s,
        "lateness_p99_ms": latency.lateness_ms(win.due, win.sent)["p99"],
        "compiles": compiles,
    }
    row["passes"] = passes(row, limit_ms)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", action="append", required=True,
                    help="<cell>=<rate>,<rate>,... (requests/s), repeatable")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds-per-rate", type=int, default=2)
    ap.add_argument("--out", default=str(run.BENCH / "cells"))
    args = ap.parse_args(argv)
    if args.seeds_per_rate < 2:
        run.log("the rule needs two traffic seeds per rate or more")
        return 2

    import numpy as np

    import compiles
    import openloop
    import spec
    import system
    import traffic
    from repro.serve import Session
    from repro.serve.sched import QueryRequest

    plan = []
    for arg in args.cell:
        name, rates = arg.split("=")
        plan.append((spec.cell(name), sorted(float(r) for r in rates.split(","))))
    conf = plan[0][0].config
    if any(c.config != conf for c, _ in plan):
        run.log("the swept cells must share one configuration")
        return 2
    if run.chip(max(c.chips for c, _ in plan)) is None:
        run.log("needs a TPU")
        return 2
    run.configure_compile_cache()
    meter = compiles.CompileMeter()
    col = spec.collection(conf, args.seed)
    dfs = np.bincount(col.term_ids, minlength=col.n_terms)
    t0 = time.perf_counter()
    engine, secs, _ = system.build(conf, col, log=run.log)
    run.log(f"set-up {time.perf_counter() - t0:.1f} s: {secs}")
    status = 0
    with Session(engine) as session:
        for cell, rates in plan:
            mix = cell.traffic
            limit = float(mix["latency_limit_ms"])
            warm = traffic.schedule(mix, max(rates), args.seconds, dfs, args.seed,
                                    stream=traffic.WARM_STREAM)
            t0 = time.perf_counter()
            shapes = system.warm(session, engine, warm, traffic.max_terms(mix))
            run.log(f"{cell.name}: warm {time.perf_counter() - t0:.1f} s on {len(warm)} "
                    f"requests and {shapes} fused-kernel shapes")
            rows, stream = [], 10
            for rate in rates:
                if rows and failed_everywhere(rows, rows[-1]["rate_qps"]):
                    break  # past capacity
                for j in range(args.seeds_per_rate):
                    seed = args.seed + j
                    sched = traffic.schedule(mix, rate, args.seconds, dfs, seed,
                                             stream=stream)
                    stream += 1
                    mark = meter.mark()
                    win = openloop.run(session, system.requests(sched, QueryRequest),
                                       sched.due_s, args.seconds)
                    got = meter.since(mark)
                    row = window_row(win, rate, seed, got["lowered"] + got["compiles"], limit)
                    rows.append(row)
                    run.log(f"{cell.name} {json.dumps(row)}"
                            + (f" compiled {got['names'][:3]}" if got["names"] else ""))
            k = knee(rows, limit)
            reached = any(failed_everywhere(rows, r["rate_qps"]) for r in rows)
            out = {"rate_qps": None if k is None else round(RATE_SHARE * k, 3),
                   "knee_qps": k, "knee_reached": reached, "rule": RULE, "window_s": args.seconds,
                   "seed": args.seed, "sweep": rows}
            print(json.dumps({"cell": cell.name, **out}), flush=True)
            if k is None:
                run.log(f"{cell.name}: no swept rate passed; no rate written")
                status = 1
                continue
            path = Path(args.out) / f"{cell.name}.json"
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(json.dumps(out, indent=1) + "\n")
            run.log(f"{cell.name}: knee {k} q/s, rate {out['rate_qps']} q/s -> {path}")
    return status


if __name__ == "__main__":
    sys.exit(main())
