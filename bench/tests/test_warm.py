"""The warm-up compiles every shape the served path calls."""
from types import SimpleNamespace

import numpy as np

import system


def _engine(codecs, hi=100):
    store = SimpleNamespace(codec_histogram=lambda: dict(codecs))
    cfg = SimpleNamespace(ranked=SimpleNamespace(payload_bits=8),
                          sched=SimpleNamespace(max_batch=8))
    return SimpleNamespace(cfg=cfg, shards=[SimpleNamespace(lo=0, hi=hi, tier2=store)])


def test_window_widths_follow_the_codecs_the_build_chose():
    assert system.window_widths(_engine({"eliasfano": 3, "optpfd": 1})) == [1]
    assert system.window_widths(_engine({"plm": 1, "varbyte": 2})) == [1, 2, 4, 8, 16, 32]


def test_fused_shapes_cover_tails_and_candidate_buckets():
    shapes = system.fused_shapes(_engine({"varbyte": 1}, hi=300), max_terms=3)
    assert {s[0] for s in shapes} == {8}
    assert {s[1] for s in shapes} == {1, 2, 3} and {s[2] for s in shapes} == {128, 256, 512}


def test_warm_fused_covers_the_program_call():
    import jax.numpy as jnp

    from repro.kernels.fused_query.kernel import NEVER, fused_topk

    assert system.warm_fused(_engine({"varbyte": 1}), max_terms=2, k=10) == 2
    before = fused_topk._cache_size()
    Q, T, C, W = 8, 2, 128, 1
    # the operands and keywords as the program's bridge passes them
    arrays = (np.zeros((Q, T), np.uint32), np.zeros((Q, T), np.int32),
              *(np.zeros((Q, T, C), np.int32) for _ in range(4)),
              np.zeros((Q, T, C), np.float32),
              *(np.zeros((Q, T, C, W), np.uint32) for _ in range(4)),
              np.full((Q, C), NEVER, np.int32), np.zeros((Q, C), np.int32),
              np.zeros((Q, 1), np.int32))
    fused_topk(*(jnp.asarray(a) for a in arrays), k=10, pbits=8, interpret=None)
    assert fused_topk._cache_size() == before


def test_a_boolean_window_after_the_warm_up_compiles_nothing():
    import compiles
    import run
    import spec
    import traffic
    from conftest import tiny_cell

    cell = tiny_cell("boolean-weblog")
    meter = compiles.CompileMeter()
    inp = run.inputs(cell, 2**33 + 9, 1.5)
    engine, _, _ = system.build(cell.config, inp.col, log=lambda *_: None)
    sv = run.serve(engine, inp, 1.5, traffic.max_terms(cell.traffic), meter=meter)
    assert sv.win.answered.all() and sv.warm_shapes == 0
    assert sv.compiles == {"compiles": 0, "cache_loads": 0, "lowered": 0, "names": []}
    assert spec.collection(cell.config, 2**33 + 9).n_postings == inp.col.n_postings
