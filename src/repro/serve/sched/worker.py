"""Process shard worker: one ShardEngine served over a multiprocessing pipe.

The GIL is why the serving front-end sheds threads for processes: the
probe/verify phase is many small numpy ops, and the measured convoy made
K=4 thread fan-out ~8x slower than serial.  A process replica owns a full
``ShardEngine`` for one document partition, rebuilt from the persistent
shard-store — which is what makes replicas cheap: streams are ``np.memmap``
arenas, so spawning R replicas of a shard shares one page cache and none of
them re-encode anything (engines reload ~28x faster than re-encoding).

Protocol (request/response over one ``multiprocessing.Pipe``):

  ("ready", {"shard": i, "pid": p}) worker -> parent once the engine is built
  ("bool", q[, ctx])              (B, T) padded int32 -> ("ok", packed bitmap)
  ("topk", [(terms, required, k, floor), ...][, ctx])
                                  -> ("ok", [(ids, scores), ...]) global ids
  ("ping",)                       -> ("ok", "pong") — forces spawn/warm
  ("clock",)                      -> ("ok", perf_counter_ns) — offset sync
  ("stats",)                      -> ("ok", shard metrics snapshot)
  ("caches",)                     -> ("ok", cache_report) — jit-cache sizes,
                                  observed fused shapes and arena counters;
                                  the warm-snapshot tests read this to prove
                                  a respawned worker is re-jit-free
  ("crash",)                      hard-exits the process (crash-path tests)
  ("stop",)                       clean shutdown
  ("err", traceback_str)          any handler failure (worker stays alive)

Workers inherit the parent's environment, so where
``JAX_COMPILATION_CACHE_DIR`` is set every worker of every (re)spawn shares
that persistent compilation cache, and the warm-log replay a fresh process
receives (sched/replica.py) re-traces against executables already on disk
instead of re-invoking XLA.

``ctx`` is an optional ``repro.obs.TraceContext``: when present the reply
grows a third element, ``("ok", payload, {"spans": [...], "probes": [...]})``
— the worker's span buffer (drained per request, absolute worker-clock
nanoseconds, its root span's parent the host span ``ctx`` names) and its
routed-probe records, which the host replica maps onto its own timeline /
probe sink (obs/collate.py).  The worker runs its own
``Tracer`` and an in-memory ``ProbeLog`` either way; with no ctx (or
``ctx.trace`` false) nothing extra is recorded or shipped, keeping the
trace-off wire cost at zero.

Workers plan locally: each carries the *global* document frequencies, so
``plan_batch`` on a worker reproduces the facade plan for its shard exactly
— term order, run masks and guided/decode routes are identical, which is
what keeps the process-parallel path bit-identical to in-process serving.

``execute_bool`` / ``execute_topk`` are shared with ``InlineReplica`` so
the inline (0-replica) scheduler path runs the very same code.
"""
from __future__ import annotations

import os
import time
import traceback

import numpy as np

from repro.obs import trace


def execute_bool(shard, q: np.ndarray, global_dfs: np.ndarray, verified: bool) -> np.ndarray:
    """Plan (global term order) + execute one shard's slice of a batch."""
    from repro.serve.planner import plan_batch

    plan = plan_batch(q, global_dfs, [shard], verified=verified)
    return shard.execute(q, plan.shard_plans[0], plan.qplans)


def execute_topk(shard, items: list) -> list:
    """Serve [(terms, required, k, floor)] -> [(global ids, scores)].

    Applies the ranked run mask locally (skip when no term has local
    postings or a required term is absent — same rule as
    planner.ranked_run_mask), so the session can broadcast one item list to
    every shard group.  Live items go through ``shard.query_topk_batch`` —
    with ``ranked.fused_kernel`` that is one fused Pallas dispatch for the
    whole batch, otherwise a loop over the multi-phase path.
    """
    empty = (np.zeros(0, np.int32), np.zeros(0, np.int64))
    ldfs = shard.local_dfs
    out: list = [empty] * len(items)
    idx, batch = [], []
    for pos, (terms, required, k, floor) in enumerate(items):
        terms = tuple(int(t) for t in terms)
        required = tuple(int(t) for t in required)
        if (
            not terms
            or k <= 0
            or not any(int(ldfs[t]) for t in terms)
            or any(int(ldfs[t]) == 0 for t in required)
        ):
            continue
        idx.append(pos)
        batch.append((terms, int(k), required, int(floor)))
    if batch:
        for pos, r in zip(idx, shard.query_topk_batch(batch)):
            out[pos] = (r.ids, r.scores)
    return out


def cache_report(shard) -> dict:
    """Compiled-executable census for one engine: the warm-restore probe.

    ``dense_cache`` / ``dense_shapes`` cover the fused ranked kernel's jit
    cache in *this* process; ``arena`` is the device-arena residency
    counters (uploads must stay at 1 per process no matter how many
    dispatches ran).  Inline replicas report the same shape.
    """
    from repro.kernels.fused_query import dense

    arena = getattr(getattr(shard, "_ranked", None), "_arena", None) or None
    return {
        "dense_cache": dense.cache_size(),
        "dense_shapes": sorted(dense.observed_shapes()),
        "arena": arena.counters.as_dict() if arena else None,
    }


def _build_shard(spec: dict):
    """Reconstruct the spec'd ShardEngine from the persistent shard-store."""
    from repro.core.learned_bloom import LearnedBloom
    from repro.index.store import load_index
    from repro.serve.config import ServeConfig
    from repro.serve.shard import ShardEngine, slice_bloom

    lb = LearnedBloom(
        params=spec["lb_params"],
        tau=spec["lb_tau"],
        backup_keys=spec["lb_backup_keys"],
        n_docs=int(spec["n_docs"]),
    )
    lo, hi = int(spec["lo"]), int(spec["hi"])
    inv, store = load_index(
        os.path.join(spec["store_dir"], f"shard-{spec['shard_idx']:04d}"), mmap=True
    )
    cfg = ServeConfig(**spec["cfg_kwargs"])
    shard = ShardEngine(
        slice_bloom(lb, lo, hi), inv, spec["li_cfg"], cfg, lo=lo, hi=hi, tier2=store
    )
    shard.shard_id = int(spec["shard_idx"])
    return shard, cfg


def worker_main(conn, spec: dict) -> None:
    """Entry point of a spawned process replica (see module docstring)."""
    from repro.obs.probelog import ProbeLog
    from repro.obs.trace import SpanRef, Tracer

    try:
        shard, cfg = _build_shard(spec)
        # in-memory probe sink, installed before the engine's first probe
        # (GuidedPostings captures the handle lazily); drained per request
        # and shipped back when the ctx asks, discarded otherwise
        plog = ProbeLog()
        cfg.obs.probe_log = plog
        wtracer = Tracer(name=f"shard-worker-{spec['shard_idx']}")
        global_dfs = np.asarray(spec["global_dfs"])
        conn.send(("ready", {"shard": int(spec["shard_idx"]), "pid": os.getpid()}))
    except Exception:
        try:
            conn.send(("err", traceback.format_exc()))
        finally:
            return
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            return
        op = msg[0]
        if op == "stop":
            return
        if op == "crash":  # test hook: die mid-batch, no reply, no cleanup
            os._exit(17)
        try:
            if op == "ping":
                conn.send(("ok", "pong"))
            elif op == "clock":
                conn.send(("ok", time.perf_counter_ns()))
            elif op in ("bool", "topk"):
                ctx = msg[2] if len(msg) > 2 else None
                traced = ctx is not None and ctx.trace
                # the root hangs under the host span that dispatched it
                parent = SpanRef(ctx.parent, ctx.parent_depth) if traced else None
                with trace.activate(wtracer if traced else None, parent), trace.span(
                    f"worker.{op}"
                ), plog.context(query=None, shard=shard.shard_id):
                    if op == "bool":
                        payload = execute_bool(shard, msg[1], global_dfs, cfg.verified)
                    else:
                        payload = execute_topk(shard, msg[1])
                probes = plog.drain()  # drain always: bound worker memory
                if ctx is None:
                    conn.send(("ok", payload))
                else:
                    wire = {"spans": wtracer.drain_wire() if traced else []}
                    if ctx.probe:
                        wire["probes"] = probes
                    conn.send(("ok", payload, wire))
            elif op == "stats":
                conn.send(("ok", shard.metrics.snapshot()))
            elif op == "caches":
                conn.send(("ok", cache_report(shard)))
            else:
                conn.send(("err", f"unknown op {op!r}"))
        except Exception:
            try:
                conn.send(("err", traceback.format_exc()))
            except (BrokenPipeError, OSError):
                return
