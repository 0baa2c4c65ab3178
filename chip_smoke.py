"""Chip smoke: the served path once on one TPU, every answer checked exact.

Builds a seeded collection with the shapes of the ``robust`` preset
(60,000 terms, average document length 230, Zipf-Mandelbrot a=1.2, b=2.7)
at 52,800 documents (Robust04's 528k / 10) in 4 document shards, trains the
learned membership model on the chip with ``repro.launch.serve``'s own
``train_membership``, and serves through ``repro.serve.Session`` (inline,
fused ranked kernel):

  * 64 Boolean conjunctions                      vs brute_force_answers
  * 64 ranked OR queries at k=10                 vs brute_force_topk
  * 32 mixed-required queries at k=10            vs brute_force_topk
  * 16 OR queries at k=100 (above DENSE_MAX_K)   vs brute_force_topk

Ranked answers must match the oracle in ids and scores.  Every query set is
served twice: the first pass compiles what ``Session.warm`` did not, the
second is timed as serving.  The script also checks that the kernels run
compiled (not interpreted) and that the bucketed fused Pallas kernel served
ranked tails, the k=100 queries among them; at this vocabulary the dense
arena's size cap admits no shard.

    python chip_smoke.py

Needs a TPU: on any other platform it exits non-zero before any work.  The
last line of standard output is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``,
printed only when every check passed.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

N_DOCS = 52_800  # Robust04's 528k documents / 10
N_SHARDS = 4
TRAIN_STEPS = 300
SEED = 7


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class _CompileMeter:
    """Backend compiles and persistent-cache hits seen through jax.monitoring."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(name, secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"backend compiles {self.compiles} ({self.compile_s:.2f} s), "
                f"persistent-cache hits {self.cache_hits}")


def _check_ranked(name, got, want) -> None:
    bad = [
        i for i, (g, w) in enumerate(zip(got, want))
        if not (np.array_equal(g.ids, w.ids) and np.array_equal(g.scores, w.scores))
    ]
    if bad or len(got) != len(want):
        raise AssertionError(f"{name}: {len(bad)} of {len(want)} answers differ "
                             f"from brute force (first at query {bad[:1]})")
    _log(f"{name}: ids and scores equal to brute force for {len(want)}/{len(want)}")


def run(n_docs: int = N_DOCS) -> None:
    """Build, train, serve and check (raises on any failure)."""
    from repro.common.compile_cache import configure_compile_cache
    from repro.common.config import PAPER_COLLECTIONS, LearnedIndexConfig
    from repro.core import fit_thresholds
    from repro.data.queries import brute_force_answers, sample_queries, zipf_disjunctions
    from repro.kernels import resolve_interpret
    from repro.kernels.arena import stream_residency_counters
    from repro.kernels.fused_query.dense import DENSE_MAX_K
    from repro.launch.serve import build_collection, train_membership
    from repro.obs import Tracer
    from repro.rank.score import brute_force_topk
    from repro.serve import BooleanEngine, ServeConfig, Session

    cache_dir = configure_compile_cache()
    _log(f"compile cache: {cache_dir}")
    meter = _CompileMeter()

    t0 = time.perf_counter()
    ccfg = dataclasses.replace(PAPER_COLLECTIONS["robust"], n_docs=n_docs, seed=SEED)
    corpus, inv = build_collection(ccfg)
    li_cfg = LearnedIndexConfig(embed_dim=64, truncation_k=64, block_size=128)
    t_collection = time.perf_counter() - t0
    _log(f"collection: {corpus.n_docs} docs, {corpus.n_terms} terms, "
         f"{inv.n_postings} postings (avg len {ccfg.avg_doc_len}, zipf "
         f"a={ccfg.zipf_a} b={ccfg.zipf_b}, seed {SEED}) in {t_collection:.2f} s")

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        train_membership(corpus, inv, li_cfg, steps=TRAIN_STEPS)
    )
    lb = fit_thresholds(params, inv)
    t_train = time.perf_counter() - t0
    _log(f"train: {TRAIN_STEPS} steps + zero-FN thresholds in {t_train:.2f} s")

    t0 = time.perf_counter()
    # the tracer's kernel.fused_query spans tell bucketed Pallas dispatches
    # (kernels.fused_query.ops._dispatch_group) from dense-arena ones
    tracer = Tracer()
    cfg = ServeConfig(n_shards=N_SHARDS, ranked=dict(fused_kernel=True),
                      obs=dict(trace=tracer))
    eng = BooleanEngine(lb, inv, li_cfg, cfg)
    for sh in eng.shards:
        sh.ensure_payloads()  # hybrid codec choice + payload streams, per shard
    t_build = time.perf_counter() - t0
    hist: dict[str, int] = {}
    for sh in eng.shards:
        for c, n in sh.tier2.codec_histogram().items():
            hist[c] = hist.get(c, 0) + n
    _log(f"build: {len(eng.shards)} shards {eng._ranges}, hybrid stores + "
         f"payloads in {t_build:.2f} s (collection + stores "
         f"{t_collection + t_build:.2f} s), codecs {hist}")

    interpret = resolve_interpret()
    if interpret:
        raise AssertionError("Pallas kernels would run interpreted on this backend")

    bq = sample_queries(corpus, 64, seed=3)
    oq, _ = zipf_disjunctions(inv.dfs, 64, seed=7)
    mq, mreq = zipf_disjunctions(inv.dfs, 32, n_required=1, seed=11)
    kq, _ = zipf_disjunctions(inv.dfs, 16, seed=13)
    k_big = 100
    assert k_big > DENSE_MAX_K

    with Session(eng) as session:
        t0 = time.perf_counter()
        session.warm()
        t_warm = time.perf_counter() - t0
        _log(f"warm: {t_warm:.2f} s; {meter.line()}")

        def serve():
            return (
                session.query_batch(bq),
                session.query_topk(oq, 10),
                session.query_topk(mq, 10, required=mreq),
                session.query_topk(kq, k_big),
            )

        t0 = time.perf_counter()
        serve()
        t_first = time.perf_counter() - t0
        _log(f"first pass (compiles the remaining shapes): {t_first:.2f} s; "
             f"{meter.line()}")
        n_compiles = meter.compiles
        t0 = time.perf_counter()
        boolean, ranked, mixed, big = serve()
        t_serve = time.perf_counter() - t0
        _log(f"serve: {len(bq) + len(oq) + len(mq) + len(kq)} queries in "
             f"{t_serve:.2f} s (host clock, second pass), "
             f"{meter.compiles - n_compiles} compiles in the pass")

    exact = brute_force_answers(corpus, bq)
    n_exact = sum(np.array_equal(r, e) for r, e in zip(boolean, exact))
    _log(f"boolean: exact {n_exact}/{len(bq)}")
    if n_exact != len(bq):
        raise AssertionError("boolean answers differ from brute force")
    im = eng.impact_model
    _check_ranked("ranked OR k=10", ranked, brute_force_topk(inv, im, oq, 10))
    _check_ranked("mixed-required k=10", mixed,
                  brute_force_topk(inv, im, mq, 10, required=mreq))
    _check_ranked(f"ranked OR k={k_big}", big, brute_force_topk(inv, im, kq, k_big))

    rs = eng.metrics.snapshot()["ranked"]
    spans = [s for s in tracer.spans if s.name == "kernel.fused_query"]
    bucketed = [s for s in spans if "dense" not in s.attrs]
    _log(f"fused kernel: {rs['fused_queries']} shard-queries, "
         f"{len(bucketed)} bucketed Pallas dispatches (candidate buckets "
         f"{sorted({s.attrs['candidates'] for s in bucketed})}, k "
         f"{sorted({s.attrs['k'] for s in bucketed})}), "
         f"{len(spans) - len(bucketed)} dense-arena dispatches, "
         f"{rs['fused_lanes']} probe lanes, interpret={interpret}")
    if not bucketed or rs["fused_queries"] <= 0:
        raise AssertionError("the bucketed fused Pallas kernel never dispatched")
    if k_big not in {s.attrs["k"] for s in bucketed}:
        raise AssertionError(f"no bucketed dispatch served the k={k_big} queries")
    arenas = [sh.ranked.arena for sh in eng.shards]
    _log(f"uploads: fused-kernel input tiles {rs['fused_device_bytes']} bytes, "
         f"dense-arena tables {sum(a.counters.upload_bytes for a in arenas if a)} "
         f"bytes ({sum(a is not None for a in arenas)}/{len(arenas)} shards "
         f"eligible), resident streams "
         f"{stream_residency_counters()['upload_bytes']} bytes")

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    _log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
         f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    _log(f"seconds: collection {t_collection:.2f}, build {t_build:.2f}, "
         f"train {t_train:.2f}, warm {t_warm:.2f}, first pass {t_first:.2f}, "
         f"serve {t_serve:.2f}; {meter.line()}")


def main() -> int:
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's default platform is {platform!r}",
              file=sys.stderr)
        return 1
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(src))
    run()
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
