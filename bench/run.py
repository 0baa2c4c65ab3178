"""Run one benchmark cell once: set up, warm, measure an open-loop window, check.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name (``spec.py``).  The run makes its collection and
traffic from ``--seed``, builds the served index through the program's API,
warms every shape the cell's traffic reaches and then a separate stream of
the same mix (set-up, reported as ``setup_s``), sends the cell's requests
open loop for ``--seconds`` at the cell's fixed rate, and then compares every
answer due in the window with the plain reference (``reference.py``).

Standard error reports, before the result: the set-up phases, the
compilations inside the window (there should be none), the collector's
passes and the longest generator stalls with what was running then, and
last each number compared beside its limit.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``, each number compared with its limit.

Needs a TPU with as many chips as the cell asks for: otherwise it exits
non-zero before any work and prints no result.  The configuration's control
is run by ``control.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def chip(chips: int) -> dict | None:
    """The device JAX reports, or None without a TPU holding ``chips`` chips.
    A TPU kind with no published peaks is an error."""
    import jax

    import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        return None
    peaks.peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR if set,
    else the fixed directory ``.jax_cache`` in the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def _profile_dir() -> str:
    return str(ROOT / "artifacts" / "bench" / "profile")


@dataclass
class Inputs:
    """A seed's collection, its df per term, the window's requests and the
    warm-up's (another stream of the same mix)."""

    col: object
    dfs: object
    sched: object
    warm_sched: object


def inputs(cell, seed: int, seconds: float) -> Inputs:
    import numpy as np

    import spec
    import traffic

    col = spec.collection(cell.config, seed)
    dfs = np.bincount(col.term_ids, minlength=col.n_terms)
    mix = cell.traffic
    return Inputs(col, dfs,
                  traffic.schedule(mix, cell.rate_qps, seconds, dfs, seed),
                  traffic.schedule(mix, cell.rate_qps, seconds, dfs, seed,
                                   stream=traffic.WARM_STREAM))


@dataclass
class Served:
    """One window served, with what was observed around it."""

    win: object
    setup_s: float
    warm_s: float
    warm_shapes: int
    compiles: dict
    gc: object
    snapshot: dict
    index_bytes: dict
    memory_peak_bytes: int
    marks: dict
    setup_compiles: tuple  # (backend compiles, cache loads, programs lowered) in set-up


def serve(engine, inp: Inputs, seconds: float, max_terms: int, *, meter,
          tracer=None, profile: bool = False) -> Served:
    """Warm ``engine`` behind a Session, then send the window open loop."""
    import memory
    import openloop
    import stalls
    import system
    from repro.serve import Session
    from repro.serve.sched import QueryRequest

    reqs = system.requests(inp.sched, QueryRequest)
    with Session(engine) as session:
        t0 = time.perf_counter()
        shapes = system.warm(session, engine, inp.warm_sched, max_terms)
        warm_s = time.perf_counter() - t0
        engine.metrics.reset()
        if tracer is not None:
            tracer.reset()
        marks: dict = {}

        def on_open(opened: float) -> None:
            if profile:
                import jax

                with jax.profiler.TraceAnnotation("bench.window_open"):
                    marks["perf_ns"] = time.perf_counter_ns()

        if profile:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(_profile_dir(), profiler_options=opts)
        mark = meter.mark()
        with stalls.GcPauses() as gc_passes:
            win = openloop.run(session, reqs, inp.sched.due_s, seconds, on_open=on_open)
        compiles = meter.since(mark)
        if profile:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"profiler trace written in {time.perf_counter() - t0:.2f} s")
        snapshot = engine.metrics.snapshot()
        index_bytes = memory.index_bytes(engine)
        peak = memory_peak_bytes()
    return Served(win, win.opened - T_START, warm_s, shapes, compiles, gc_passes,
                  snapshot, index_bytes, peak, marks, mark)


def check(cell, inp: Inputs, win):
    """-> (per request: answered right; wrong answers; answers that never came)."""
    import numpy as np

    import reference

    t0 = time.perf_counter()
    sched = inp.sched
    answered = win.answered
    scoring = cell.config["scoring"] if sched.mode == "ranked" else None
    ref = reference.Reference(inp.col, sched.terms, scoring)
    right = np.zeros(len(sched), bool)
    for i, ans in enumerate(win.answers):
        if not answered[i]:
            continue
        if sched.mode == "ranked":
            ids, scores = ref.topk(sched.terms[i], sched.k)
            right[i] = np.array_equal(ans.ids, ids) and np.array_equal(ans.scores, scores)
        else:
            right[i] = np.array_equal(ans.ids, ref.boolean(sched.terms[i]))
    wrong = int((answered & ~right).sum())
    missing = sum(a is None for a in win.answers)
    log(f"reference: {int(answered.sum())} answers compared in "
        f"{time.perf_counter() - t0:.2f} s")
    return right, wrong, missing


def report(sv: Served, inp: Inputs, n_postings: int, spans=()) -> None:
    """The earlier lines of standard error: what happened in the window."""
    import numpy as np

    import latency
    import stalls

    win, sched, dfs = sv.win, inp.sched, inp.dfs
    c = sv.compiles
    log(f"compilations in window: {c['lowered']} programs lowered, {c['compiles']} "
        f"backend compiles, {c['cache_loads']} persistent-cache loads"
        + (f": {c['names'][:5]}" if c["names"] else ""))
    late = latency.lateness_ms(win.due, win.sent)
    refused: dict = {}
    for a in win.answers:
        if a is not None and not a.ok:
            refused[a.reason] = refused.get(a.reason, 0) + 1
    log(f"window: {len(sched)} due, {int(win.answered.sum())} answered, "
        f"{int((~win.answered).sum())} failed ({sum(a is None for a in win.answers)} "
        f"never answered, refused {refused}), {win.unanswered_at_close} unanswered at "
        f"close, last answer "
        f"{win.trail_s:.3f} s after it; generator lateness ms p50 {late['p50']:.3f} "
        f"p99 {late['p99']:.3f} max {late['max']:.3f}")
    end = win.opened + win.seconds
    gcs = sv.gc.summary(win.opened, end)
    log("garbage collector in window: " + ("; ".join(
        f"gen{g} {s['passes']} passes, {s['total_s']:.4f} s, max {s['max_s']:.4f} s"
        for g, s in sorted(gcs.items())) or "no passes"))

    def describe(i: int) -> str:
        t = sched.terms[i][sched.terms[i] >= 0]
        return f"#{i} {len(t)} terms, df sum {int(dfs[t].sum())}"

    found = stalls.send_stalls(win.due, win.sent)
    recs = stalls.attribute(found, opened=win.opened, sent=win.sent, done=win.done,
                            describe=describe, gc_passes=sv.gc.passes, spans=spans)
    log(f"longest generator stalls: {json.dumps(recs)}")
    lat_all = (win.done - win.due) * 1e3
    if np.isfinite(lat_all).any():
        worst = int(np.nanargmax(lat_all))
        log(f"slowest answer {lat_all[worst]:.1f} ms, due {win.due[worst] - win.opened:.2f} s "
            f"into the window: {describe(worst)}")
    parts = ", ".join(f"{k} {v} B ({8 * v / n_postings:.2f} bits/posting)"
                      for k, v in sv.index_bytes.items())
    log(f"index bytes by component: {parts}")


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict, *,
             profile: bool | None = None) -> dict:
    """One run of ``cell`` -> the result object (last key ``checks``).

    ``profile`` (default: ``trace``) turns the device profiler on in a traced
    run; tests on the CPU turn it off, having no device to trace."""
    import compiles
    import devtrace
    import latency
    import openloop
    import spec
    import system
    import traffic
    from repro.obs import Tracer

    profile = trace if profile is None else profile
    meter = compiles.CompileMeter()
    conf, mix = cell.config, cell.traffic
    t0 = time.perf_counter()
    inp = inputs(cell, seed, seconds)
    col, sched = inp.col, inp.sched
    log(f"collection: {col.n_docs} docs, {col.n_terms} terms, {col.n_postings} "
        f"postings in {time.perf_counter() - t0:.2f} s; {len(sched)} {sched.mode} "
        f"requests at {cell.rate_qps} q/s over {seconds} s")
    tracer = Tracer() if trace else None
    engine, secs, _ = system.build(conf, col, tracer=tracer, log=log)
    sv = serve(engine, inp, seconds, traffic.max_terms(mix), meter=meter,
               tracer=tracer, profile=profile)
    del engine
    log("set-up seconds: " + ", ".join(f"{k} {v:.2f}" for k, v in secs.items())
        + f", warm {sv.warm_s:.2f} ({sv.warm_shapes} fused-kernel shapes); total "
        f"{sv.setup_s:.2f}; backend compiles, persistent-cache loads, programs lowered "
        f"{sv.setup_compiles}")
    win = sv.win

    dev, spans = None, []
    if tracer is not None:
        spans = list(tracer.spans)
    if profile:
        t0 = time.perf_counter()
        path = sorted(Path(_profile_dir()).glob("**/*.xplane.pb"), key=os.path.getmtime)[-1]
        tr = devtrace.load(str(path))
        shutil.rmtree(_profile_dir(), ignore_errors=True)
        offset = tr.markers["bench.window_open"] - sv.marks["perf_ns"]
        lo = int(win.opened * 1e9) + offset
        dev = devtrace.reduce(tr, lo, int(win.closed * 1e9) + offset,
                              devtrace.program_spans(tracer, offset))
        log(f"trace read and reduced in {time.perf_counter() - t0:.2f} s")
    host_spans = devtrace.program_spans(tracer, 0) if tracer is not None else []
    report(sv, inp, col.n_postings,
           spans=[(n, s / 1e9, e / 1e9, d) for n, s, e, d in host_spans])

    right, wrong, missing = check(cell, inp, win)
    answered = win.answered
    limit_ms = float(mix["latency_limit_ms"])
    lat = latency.latencies_ms(win.due, win.done, answered)
    cap_ms = 1e3 * (seconds + openloop.WAIT_PAST_CLOSE_S)

    def pct(q):  # a failure is beyond every limit: reported at the wait cap
        v = latency.percentile(lat, q)
        return cap_ms if math.isinf(v) else v

    bits = 8.0 * sum(sv.index_bytes.values()) / col.n_postings
    log(f"p50 {pct(50):.3f} ms, p95 {pct(95):.3f} ms, goodput "
        f"{latency.goodput_qps(lat, right, limit_ms, seconds):.4f} q/s, "
        f"{bits:.4f} bits/posting, setup {sv.setup_s:.2f} s")

    result: dict = {"correct": wrong == 0 and missing == 0, "attempted": len(sched),
                    "failed": int((~answered).sum())}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not trace:
        values = {
            "p50_ms": pct(50),
            "p95_ms": pct(95),
            "goodput_qps": latency.goodput_qps(lat, right, limit_ms, seconds),
            "index_bits_per_posting": bits,
            "setup_s": sv.setup_s,
        }
    else:
        if dev is not None:
            device.update(busy_s=dev["busy_s"], window_s=dev["window_s"])
            result["breakdown"] = {"device_ops": dev["device_ops"],
                                   "idle_gaps": dev["idle_gaps"]}
        n_ok = int(answered.sum())
        ctx = {"mode": sched.mode, "snapshot": sv.snapshot, "spans": spans,
               "device": dev, "p95_ms": pct(95),
               "n_boolean": n_ok if sched.mode == "boolean" else 0,
               "n_ranked": n_ok if sched.mode == "ranked" else 0,
               "index_bytes": sv.index_bytes, "n_postings": col.n_postings}
        values = {m["name"]: spec.reader(m["name"])(ctx) for m in cell.per_layer}
    result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in values.items()
                         if v is not None and k in units}
    result["device"] = dict(device, memory_peak_bytes=sv.memory_peak_bytes)
    result["checks"] = {"wrong_answers": {"value": wrong, "limit": 0},
                        "missing_answers": {"value": missing, "limit": 0}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import spec

    import repro.serve  # noqa: F401  (the system under test: without it, stop here)

    cell = spec.cell(args.workload)
    if cell.rate_qps is None:
        log(f"no fixed rate for {cell.name}: run sweep.py on the chip first")
        return 2
    device = chip(cell.chips)
    if device is None:
        log(f"needs a TPU with {cell.chips} chip(s); JAX found none")
        return 2
    configure_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
