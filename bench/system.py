"""The system under test, set up through the program's own API.

From the seeded collection: the program's inverted index and BM25 impact
model, its hybrid-codec stores with payload streams (one per document shard,
each built in its own process so that the shards build side by side while
this process trains the membership model on the chip), its learned-Bloom
thresholds, then ``BooleanEngine`` (``assemble``, which a control calls
again with its overrides over the same parts) and ``Session``.  The build processes run
numpy only and never open the chip.
"""
from __future__ import annotations

import concurrent.futures as cf
import multiprocessing
import os
import sys
import time
from contextlib import redirect_stdout
from dataclasses import dataclass

from collection import Collection


def _worker_init() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"  # a build process must never open the chip


def _build_store(local_inv, impact_model, lo: int):
    """One shard's hybrid store with its payload stream (runs in a worker)."""
    from repro.postings import HybridPostings

    t0 = time.perf_counter()
    store = HybridPostings.from_index(local_inv)
    im = impact_model
    store.attach_payloads(im.quantize_index(local_inv, lo=lo), bits=im.params.bits,
                          scale=im.scale)
    return store, time.perf_counter() - t0


def serve_config(config: dict, overrides: dict | None = None, tracer=None):
    """The configuration's ServeConfig, with a control's overrides on top."""
    from repro.serve import ServeConfig

    block = {k: (dict(v) if isinstance(v, dict) else v) for k, v in config["serve"].items()}
    for key, value in (overrides or {}).items():
        if isinstance(value, dict):
            block.setdefault(key, {}).update(value)
        else:
            block[key] = value
    return ServeConfig(**block, obs=dict(trace=tracer))


@dataclass
class Built:
    """What set-up made before any engine: the program's corpus, global
    inverted index and impact model, shard ranges and slices, learned-Bloom
    model and the shards' stores."""

    corpus: object
    inv: object
    impact_model: object
    ranges: list
    locals_: list
    lb: object
    li_cfg: object
    stores: list


def build(config: dict, col: Collection, *, tracer=None, log=print) -> tuple[object, dict, Built]:
    """-> (BooleanEngine, seconds of each set-up phase, what it was made from)."""
    import dataclasses

    from repro.common.config import CorpusConfig, LearnedIndexConfig
    from repro.core import fit_thresholds
    from repro.data.corpus import Corpus
    from repro.index.build import build_inverted_index, slice_index
    from repro.launch.serve import train_membership
    from repro.rank.score import BM25Params, ImpactModel
    from repro.serve.shard import shard_ranges

    cfg = serve_config(config)
    shape = config["collection"]
    secs: dict[str, float] = {}
    t0 = time.perf_counter()
    corpus = Corpus(
        cfg=CorpusConfig(name=config["name"], n_docs=col.n_docs, n_terms=col.n_terms,
                         avg_doc_len=shape["avg_doc_len"], zipf_a=shape["zipf_a"],
                         zipf_b=shape["zipf_b"]),
        doc_offsets=col.doc_offsets, term_ids=col.term_ids, term_freqs=col.term_freqs,
    )
    inv = build_inverted_index(corpus)
    im = ImpactModel.build(inv, BM25Params(bits=cfg.ranked.payload_bits))
    ranges = shard_ranges(inv.n_docs, cfg.n_shards)
    locals_ = [slice_index(inv, lo, hi) for lo, hi in ranges]
    secs["invert"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    # the build memo holds every global impact; workers quantize their slice
    im_sent = dataclasses.replace(im)
    pool = cf.ProcessPoolExecutor(
        max_workers=len(ranges), mp_context=multiprocessing.get_context("spawn"),
        initializer=_worker_init,
    )
    try:
        futs = [pool.submit(_build_store, loc, im_sent, lo)
                for loc, (lo, _) in zip(locals_, ranges)]
        li = config["learned_index"]
        li_cfg = LearnedIndexConfig(embed_dim=li["embed_dim"],
                                    truncation_k=li["truncation_k"],
                                    block_size=li["block_size"])
        t1 = time.perf_counter()
        with redirect_stdout(sys.stderr):  # the trainer logs its loss to stdout
            params = train_membership(corpus, inv, li_cfg, steps=config["train"]["steps"],
                                      lr=config["train"]["lr"])
        import jax

        params = jax.block_until_ready(params)
        secs["train"] = time.perf_counter() - t1
        t1 = time.perf_counter()
        lb = fit_thresholds(params, inv)
        secs["fit"] = time.perf_counter() - t1
        built = [f.result() for f in futs]
    finally:
        pool.shutdown(wait=True)
    secs["stores_wall"] = time.perf_counter() - t0
    secs["store_build_max"] = max(s for _, s in built)

    parts = Built(corpus, inv, im, ranges, locals_, lb, li_cfg, [st for st, _ in built])
    t0 = time.perf_counter()
    engine = assemble(config, parts, tracer=tracer)
    secs["engine"] = time.perf_counter() - t0
    hist: dict[str, int] = {}
    for sh in engine.shards:
        for c, n in sh.tier2.codec_histogram().items():
            hist[c] = hist.get(c, 0) + n
    log(f"system: {len(ranges)} shards {ranges}, {inv.n_postings} postings, "
        f"codecs {hist}")
    return engine, secs, parts


def assemble(config: dict, parts: Built, *, overrides: dict | None = None, tracer=None):
    """The served ``BooleanEngine`` over what set-up built, with a control's
    overrides on top.  A control that serves other payload widths
    re-attaches the stores' payloads at that width (the stores change)."""
    from repro.rank.score import BM25Params, ImpactModel
    from repro.serve import BooleanEngine
    from repro.serve.shard import ShardEngine, slice_bloom

    cfg = serve_config(config, overrides, tracer)
    im = parts.impact_model
    bits = int(cfg.ranked.payload_bits)
    if bits != im.params.bits:
        im = ImpactModel.build(parts.inv, BM25Params(bits=bits))
        for (lo, _), loc, store in zip(parts.ranges, parts.locals_, parts.stores):
            store.attach_payloads(im.quantize_index(loc, lo=lo), bits=bits, scale=im.scale)
    shards = [
        ((lo, hi), ShardEngine(slice_bloom(parts.lb, lo, hi), loc, parts.li_cfg, cfg,
                               lo=lo, hi=hi, tier2=store, impact_model=im))
        for (lo, hi), loc, store in zip(parts.ranges, parts.locals_, parts.stores)
    ]
    return BooleanEngine(parts.lb, parts.inv, parts.li_cfg, cfg, shards=shards)


LEARNED_CODECS = ("plm", "rmi")  # the codecs whose terms reach the kernel as wide windows


def warm(session, engine, sched, max_terms: int) -> int:
    """Warm-up before a window, never on the window's own requests; returns
    the fused-kernel shapes compiled.

    The program's own warm-up (its batch buckets), then every shape of the
    fused ranked kernel that this traffic can reach (``warm_fused``), then
    ``sched`` -- a separate stream of the same mix -- closed loop in bursts
    of 1, 2, .., max_batch requests, so that every batch size is served and
    the server's decode caches hold what a server that has been serving this
    mix would hold."""
    from repro.serve.sched import QueryRequest

    session.warm()
    shapes = 0
    if sched.mode == "ranked" and engine.cfg.ranked.fused_kernel:
        shapes = warm_fused(engine, max_terms, sched.k)
    reqs = requests(sched, QueryRequest)
    max_batch = engine.cfg.sched.max_batch
    i, b = 0, 1
    while i < len(reqs):
        futs = [session.submit_async(r, block=True) for r in reqs[i:i + b]]
        for f in futs:
            f.result()
        i += b
        b = b % max_batch + 1
    return shapes


def window_widths(engine) -> list[int]:
    """The fused kernel's window buckets these stores can reach: one lane
    for classical codecs; every power of two up to ``W_CAP`` once a learned
    codec holds some term."""
    from repro.kernels.fused_query.ops import W_CAP

    learned = any(c in LEARNED_CODECS for sh in engine.shards
                  for c in sh.tier2.codec_histogram())
    widths, w = [1], 2
    while learned and w <= W_CAP:
        widths.append(w)
        w *= 2
    return widths


def fused_shapes(engine, max_terms: int) -> list[tuple[int, int, int, int]]:
    """Every (rows, tail terms, candidates, window) bucket a ranked query of
    at most ``max_terms`` terms can reach on these shards: rows padded to the
    program's row bucket of one batch, tails of 1..max_terms terms,
    candidates 128 * 2^j up to the largest shard, windows as
    ``window_widths``."""
    from repro.kernels.fused_query.ops import _CANDQ, _ROWQ, _bucket

    widest = _bucket(max(sh.hi - sh.lo for sh in engine.shards), _CANDQ)
    Q = _bucket(engine.cfg.sched.max_batch, _ROWQ)
    out, C = [], _CANDQ
    while C <= widest:
        out += [(Q, T, C, W) for T in range(1, max_terms + 1)
                for W in window_widths(engine)]
        C *= 2
    return out


def warm_fused(engine, max_terms: int, k: int) -> int:
    """Compile every shape of ``fused_shapes``, called as the program's bridge
    calls the kernel.  Returns the number of shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.fused_query.kernel import NEVER, fused_topk

    pbits = int(engine.cfg.ranked.payload_bits)
    shapes = fused_shapes(engine, max_terms)
    for Q, T, C, W in shapes:
        qt, qtc, qtcw = (Q, T), (Q, T, C), (Q, T, C, W)
        arrays = (np.zeros(qt, np.uint32), np.zeros(qt, np.int32),
                  *(np.zeros(qtc, np.int32) for _ in range(4)),
                  np.zeros(qtc, np.float32),
                  *(np.zeros(qtcw, np.uint32) for _ in range(4)),
                  np.full((Q, C), NEVER, np.int32), np.zeros((Q, C), np.int32),
                  np.zeros((Q, 1), np.int32))
        # the keywords as the program passes them: jit keys on them as given
        jax.block_until_ready(fused_topk(*(jnp.asarray(a) for a in arrays),
                                         k=min(k, C), pbits=pbits, interpret=None))
    return len(shapes)


def requests(sched, request_cls) -> list:
    mode, k = sched.mode, sched.k
    return [request_cls(terms=row, mode=mode, k=k) for row in sched.terms]
