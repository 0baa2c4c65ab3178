"""End-to-end behaviour tests for the paper's system: train the membership
model, build the learned-Bloom engine, serve queries exactly; checkpoint
resume mid-training; memory report vs Eq.(2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import CorpusConfig, LearnedIndexConfig, OptimizerConfig
from repro.core import (
    estimate_gain,
    false_negative_rate,
    false_positive_rate,
    fit_thresholds,
    init_membership,
    membership_loss,
)
from repro.data.corpus import synthesize_corpus
from repro.data.loader import membership_batches
from repro.data.queries import brute_force_answers, sample_queries
from repro.index.build import build_inverted_index
from repro.serve import BooleanEngine, ServeConfig
from repro.train import init_train_state, make_train_step


@pytest.fixture(scope="module")
def system():
    corpus = synthesize_corpus(CorpusConfig(n_docs=600, n_terms=2500, avg_doc_len=60, seed=5))
    inv = build_inverted_index(corpus)
    li_cfg = LearnedIndexConfig(embed_dim=32, truncation_k=24, block_size=64)
    params, _ = init_membership(jax.random.key(0), li_cfg, corpus.n_terms, corpus.n_docs)
    ocfg = OptimizerConfig(lr=0.05, warmup_steps=10, total_steps=150, weight_decay=0.0)
    step = jax.jit(make_train_step(lambda p, b: membership_loss(p, b), ocfg))
    st = init_train_state(params, ocfg)
    for i, batch in zip(range(150), membership_batches(corpus, batch_size=1024, seed=1)):
        params, st, _ = step(params, st, {k: jnp.asarray(v) for k, v in batch.items()})
    lb = fit_thresholds(params, inv)
    return corpus, inv, li_cfg, lb


def test_trained_model_fpr_beats_random(system):
    corpus, inv, li_cfg, lb = system
    fpr_trained = false_positive_rate(lb, inv, sample=4000)
    p_rand, _ = init_membership(jax.random.key(9), li_cfg, corpus.n_terms, corpus.n_docs)
    lb_rand = fit_thresholds(p_rand, inv)
    fpr_rand = false_positive_rate(lb_rand, inv, sample=4000)
    assert false_negative_rate(lb, inv) == 0.0
    assert fpr_trained < fpr_rand  # training must tighten the filter


@pytest.mark.parametrize("algorithm", ["exhaustive", "two_tier", "block"])
def test_engine_verified_mode_is_exact(system, algorithm):
    corpus, inv, li_cfg, lb = system
    eng = BooleanEngine(lb, inv, li_cfg, ServeConfig(algorithm=algorithm, verified=True))
    q = sample_queries(corpus, 16, seed=2)
    results = eng.query_batch(q)
    exact = brute_force_answers(corpus, q)
    if algorithm == "two_tier":
        # exactness guaranteed only for tier-1-guaranteed queries (paper §3.2)
        from repro.core import two_tier_guaranteed
        guar = np.asarray(two_tier_guaranteed(
            jnp.asarray(inv.dfs.astype(np.int32)), jnp.asarray(q),
            li_cfg.truncation_k, with_model=True))
        pairs = [(r, e) for r, e, g in zip(results, exact, guar) if g]
        assert pairs, "no guaranteed queries sampled"
    else:
        pairs = list(zip(results, exact))
    for r, e in pairs:
        assert np.array_equal(r, e)


def test_engine_kernel_path_matches_jnp(system):
    corpus, inv, li_cfg, lb = system
    q = sample_queries(corpus, 8, seed=4)
    e1 = BooleanEngine(lb, inv, li_cfg,
                       ServeConfig(algorithm="exhaustive", verified=False, use_kernel=True))
    e2 = BooleanEngine(lb, inv, li_cfg,
                       ServeConfig(algorithm="exhaustive", verified=False, use_kernel=False))
    r1 = e1.query_batch(q)
    r2 = e2.query_batch(q)
    for a, b in zip(r1, r2):
        assert np.array_equal(a, b)


def test_memory_report_consistent_with_gain(system):
    corpus, inv, li_cfg, lb = system
    eng = BooleanEngine(lb, inv, li_cfg)
    rep = eng.memory_report()
    assert rep["model_bits"] > 0 and rep["tier1_bits"] > 0
    g = estimate_gain(inv, li_cfg.truncation_k, s_worst_bits=li_cfg.model_bits_per_pair)
    # Eq.(2)'s worst-case model charge must upper-bound the actual model size
    # attributable to replaced terms (the actual model is shared across terms)
    assert g.s_worst_bits * g.n_replaced * inv.n_docs >= rep["model_bits"] or g.n_replaced == 0


def test_checkpoint_resume_training(tmp_path):
    """Kill-and-resume: training continues from the checkpoint exactly."""
    from repro.checkpoint import CheckpointManager

    corpus = synthesize_corpus(CorpusConfig(n_docs=200, n_terms=800, avg_doc_len=40, seed=6))
    li_cfg = LearnedIndexConfig(embed_dim=16)
    params, _ = init_membership(jax.random.key(0), li_cfg, corpus.n_terms, corpus.n_docs)
    ocfg = OptimizerConfig(lr=0.02, warmup_steps=2, total_steps=60, weight_decay=0.0)
    step = jax.jit(make_train_step(lambda p, b: membership_loss(p, b), ocfg))
    st = init_train_state(params, ocfg)
    cm = CheckpointManager(str(tmp_path))
    batches = [
        {k: jnp.asarray(v) for k, v in b.items()}
        for _, b in zip(range(20), membership_batches(corpus, batch_size=256, seed=2))
    ]
    # run 10 steps, checkpoint, continue to 20 (reference trajectory)
    for i in range(10):
        params, st, _ = step(params, st, batches[i])
    cm.save(10, {"params": params, "opt": st})
    ref_p, ref_st = params, st
    for i in range(10, 20):
        ref_p, ref_st, _ = step(ref_p, ref_st, batches[i])
    # resume path must reproduce the reference trajectory bit-for-bit
    s, tree = cm.restore_latest({"params": params, "opt": st})
    assert s == 10
    rp, rst = tree["params"], tree["opt"]
    for i in range(10, 20):
        rp, rst, _ = step(rp, rst, batches[i])
    assert int(rst.step) == 20
    for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(rp)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ compile cache
_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.common.compile_cache import configure_compile_cache
path = configure_compile_cache()
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR set: the cache is written there and nothing
    overrides it; unset: the fixed in-checkout path, never a temp dir."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    from repro.common.compile_cache import DEFAULT_DIR, ENV_VAR

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(Path(__file__).resolve().parents[1] / "src"), env.get("PYTHONPATH", "")]
    )
    env.pop(ENV_VAR, None)
    want = DEFAULT_DIR
    if from_env:
        want = tmp_path / "cache"
        env[ENV_VAR] = str(want)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=from_env)],
        env=env, capture_output=True, text=True, timeout=120, check=True,
    ).stdout.split()
    assert out == [str(want), str(want)]
    if from_env:
        assert any(want.iterdir()), "the compile was not cached where the env says"
