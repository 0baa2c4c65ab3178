"""Continuous-batching scheduler tests (serve/sched).

Covers the acceptance edges of the scheduler subsystem: legacy-wrapper
bit-parity (inline and through real process workers), queue saturation
shedding lowest-priority first, expired deadlines never reaching a worker,
crash retry-once-then-typed-error (fakes and the real process crash hook),
all-pad short-circuits, tenant quotas, same-mode batch coalescing, and the
ServeConfig legacy-kwarg shim.
"""
import threading
import time
import warnings
from concurrent.futures import Future

import jax
import numpy as np
import pytest

from repro.common.config import CorpusConfig, LearnedIndexConfig
from repro.core import fit_thresholds, init_membership
from repro.data.corpus import synthesize_corpus
from repro.data.queries import sample_queries, zipf_conjunctions
from repro.index.build import build_inverted_index
from repro.obs.metrics import Registry
from repro.serve import (
    BooleanEngine,
    QueryRequest,
    QueryResult,
    Rejected,
    ServeConfig,
    Session,
)
from repro.serve.config import ObsConfig, RankedConfig, SchedConfig
from repro.serve.sched import (
    MODE_RANKED,
    REJECT_DEADLINE,
    REJECT_QUEUE_FULL,
    REJECT_SHUTDOWN,
    REJECT_TENANT_QUOTA,
    REJECT_WORKER_FAILED,
    AdmissionQueue,
    Pending,
    ProcessReplica,
    ReplicaGroup,
    WorkerFailure,
)
from repro.serve.sched.replica import ReplicaError


# ------------------------------------------------------------------ fixtures
@pytest.fixture(scope="module")
def system():
    corpus = synthesize_corpus(
        CorpusConfig(n_docs=400, n_terms=1600, avg_doc_len=50, seed=31)
    )
    inv = build_inverted_index(corpus)
    li_cfg = LearnedIndexConfig(embed_dim=16, truncation_k=16, block_size=64)
    params, _ = init_membership(jax.random.key(2), li_cfg, corpus.n_terms, corpus.n_docs)
    lb = fit_thresholds(params, inv)
    return corpus, inv, li_cfg, lb


def _engine(system, **cfg_kwargs):
    corpus, inv, li_cfg, lb = system
    return BooleanEngine(lb, inv, li_cfg, ServeConfig(**cfg_kwargs))


def _queries(system):
    corpus, inv, *_ = system
    q = sample_queries(corpus, 10, max_terms=4, seed=5)
    rq = zipf_conjunctions(inv.dfs, 8, max_terms=4, seed=9)
    return q, rq


# ------------------------------------------------------- wrapper bit-parity
def test_legacy_wrappers_bit_identical_inline(system):
    eng = _engine(system, n_shards=3)
    q, rq = _queries(system)
    want_bool = eng.query_batch(q)
    want_bm = eng.query_batch_bitmap(q)
    want_or = eng.query_topk(rq, k=10, mode="or")
    want_and = eng.query_topk(rq, k=10, mode="and")
    with Session(eng) as s:
        got_bool = s.query_batch(q)
        got_bm = s.query_batch_bitmap(q)
        got_or = s.query_topk(rq, k=10, mode="or")
        got_and = s.query_topk(rq, k=10, mode="and")
    for a, b in zip(want_bool, got_bool):
        assert np.array_equal(a, b)
    assert got_bm.dtype == np.uint32 and np.array_equal(want_bm, got_bm)
    for a, b in zip(want_or + want_and, got_or + got_and):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.scores, b.scores)


def test_submit_matches_wrapper_and_carries_timing(system):
    eng = _engine(system, n_shards=2)
    q, rq = _queries(system)
    with Session(eng) as s:
        r = s.submit(QueryRequest(terms=q[0]))
        assert isinstance(r, QueryResult) and r.ok
        assert np.array_equal(r.ids, eng.query_batch(q[:1])[0])
        assert r.scores is None and r.service_us > 0
        rr = s.submit(QueryRequest(terms=rq[0], mode=MODE_RANKED, k=5))
        want = eng.query_topk(rq[:1], k=5, mode="or")[0]
        assert np.array_equal(rr.ids, want.ids)
        assert np.array_equal(rr.scores, want.scores)


def test_legacy_wrappers_bit_identical_process_workers(system, tmp_path):
    """The acceptance edge: process replicas plan with global dfs, so the
    parallel path is bit-identical to in-process serving."""
    eng = _engine(system, n_shards=2, sched=dict(n_replicas=1))
    q, rq = _queries(system)
    want_bool = eng.query_batch(q)
    want_or = eng.query_topk(rq, k=10, mode="or")
    want_and = eng.query_topk(rq, k=10, mode="and")
    with Session(eng, store_dir=str(tmp_path)) as s:
        s.warm()
        got_bool = s.query_batch(q)
        got_or = s.query_topk(rq, k=10, mode="or")
        got_and = s.query_topk(rq, k=10, mode="and")
    for a, b in zip(want_bool, got_bool):
        assert np.array_equal(a, b)
    for a, b in zip(want_or + want_and, got_or + got_and):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.scores, b.scores)


# --------------------------------------------------------------- fake parts
class RecordingReplica:
    """Answers empty bitmaps / empty heaps; records every dispatch."""

    def __init__(self, n_docs=64):
        self.calls = []
        self.inflight = 0
        self.n_docs = n_docs

    def call(self, msg):
        self.calls.append(msg)
        if msg[0] == "bool":
            words = (self.n_docs + 31) // 32
            return np.zeros((len(msg[1]), words), dtype=np.uint32)
        if msg[0] == "topk":
            return [(np.zeros(0, np.int32), np.zeros(0, np.int64))] * len(msg[1])
        return "pong"

    def close(self):
        pass


class FlakyReplica(RecordingReplica):
    """Raises ReplicaError for the first ``fail_n`` calls, then recovers."""

    def __init__(self, fail_n, **kw):
        super().__init__(**kw)
        self.fail_n = fail_n

    def call(self, msg):
        if len(self.calls) < self.fail_n:
            self.calls.append(msg)
            raise ReplicaError("injected")
        return super().call(msg)


def _fake_session(eng, replica, **sched_kwargs):
    eng.cfg.sched = SchedConfig(**sched_kwargs)
    group = ReplicaGroup(
        0,
        [replica],
        lo=0,
        n_docs=eng.n_docs,
        retries=eng.cfg.sched.worker_retries,
        metrics=eng.metrics,
    )
    return Session(eng, replica_groups=[group], auto_start=False)


# -------------------------------------------------------- admission control
def test_saturation_sheds_lowest_priority_first(system):
    eng = _engine(system, n_shards=1)
    q, _ = _queries(system)
    s = _fake_session(eng, RecordingReplica(), max_queue=2)
    try:
        f_low_old = s.submit_async(QueryRequest(terms=q[0], priority=0, tenant="low"))
        f_low_new = s.submit_async(QueryRequest(terms=q[1], priority=0, tenant="low"))
        # queue full; a higher-priority arrival displaces the YOUNGEST
        # lowest-priority entry, preserving the FIFO head
        f_high = s.submit_async(QueryRequest(terms=q[2], priority=1, tenant="vip"))
        shed = f_low_new.result(timeout=1)
        assert isinstance(shed, Rejected) and shed.reason == REJECT_QUEUE_FULL
        assert shed.tenant == "low"
        assert not f_low_old.done() and not f_high.done()
        # next priority-1 arrival displaces the remaining priority-0 entry
        f_eq = s.submit_async(QueryRequest(terms=q[3], priority=1))
        assert f_low_old.result(timeout=1).reason == REJECT_QUEUE_FULL
        assert not f_eq.done()
        # queue is now all priority 1: an equal-priority arrival is rejected
        # itself — it may not churn the queue
        f_eq2 = s.submit_async(QueryRequest(terms=q[4], priority=1))
        eq2 = f_eq2.result(timeout=1)
        assert isinstance(eq2, Rejected) and eq2.reason == REJECT_QUEUE_FULL
        assert not f_high.done() and not f_eq.done()
        snap = eng.metrics.snapshot()["sched"]
        assert snap["shed"]["queue_full"] == 3
    finally:
        s.close()
    assert f_high.result(timeout=1).reason == REJECT_SHUTDOWN
    assert f_eq.result(timeout=1).reason == REJECT_SHUTDOWN


def test_tenant_quota_caps_queued_requests(system):
    eng = _engine(system, n_shards=1)
    q, _ = _queries(system)
    s = _fake_session(eng, RecordingReplica(), tenant_quota=1, max_queue=16)
    try:
        f1 = s.submit_async(QueryRequest(terms=q[0], tenant="chatty"))
        f2 = s.submit_async(QueryRequest(terms=q[1], tenant="chatty"))
        f3 = s.submit_async(QueryRequest(terms=q[2], tenant="other"))
        over = f2.result(timeout=1)
        assert isinstance(over, Rejected) and over.reason == REJECT_TENANT_QUOTA
        assert over.tenant == "chatty"
        assert not f1.done() and not f3.done()  # quota is per tenant
    finally:
        s.close()


def test_expired_deadline_never_reaches_a_worker(system):
    eng = _engine(system, n_shards=1)
    q, _ = _queries(system)
    replica = RecordingReplica()
    s = _fake_session(eng, replica)
    try:
        f_dead = s.submit_async(QueryRequest(terms=q[0], deadline_ms=1))
        f_live = s.submit_async(QueryRequest(terms=q[1]))
        time.sleep(0.02)  # deadline passes while the scheduler is held
        s._loop_thread.start()
        shed = f_dead.result(timeout=2)
        assert isinstance(shed, Rejected) and shed.reason == REJECT_DEADLINE
        assert f_live.result(timeout=2).ok
        # the expired request was shed at take_batch: no dispatch carried it
        assert all(len(msg[1]) == 1 for msg in replica.calls if msg[0] == "bool")
        assert eng.metrics.snapshot()["sched"]["shed"]["deadline"] == 1
    finally:
        s.close()


def test_default_deadline_from_config(system):
    eng = _engine(system, n_shards=1)
    q, _ = _queries(system)
    s = _fake_session(eng, RecordingReplica(), default_deadline_ms=1)
    try:
        f = s.submit_async(QueryRequest(terms=q[0]))
        time.sleep(0.02)
        s._loop_thread.start()
        assert f.result(timeout=2).reason == REJECT_DEADLINE
    finally:
        s.close()


# ------------------------------------------------------------- crash paths
def test_flaky_replica_retries_once_then_succeeds(system):
    eng = _engine(system, n_shards=1)
    q, _ = _queries(system)
    replica = FlakyReplica(fail_n=1)
    s = _fake_session(eng, replica)
    s._loop_thread.start()
    try:
        assert s.submit(QueryRequest(terms=q[0]), timeout=2).ok
        snap = eng.metrics.snapshot()["sched"]
        assert snap["worker_retries"] == 1
        assert snap["worker_failures"] == 0
    finally:
        s.close()


def test_dead_replica_exhausts_retries_then_typed_rejection(system):
    eng = _engine(system, n_shards=1)
    q, _ = _queries(system)
    s = _fake_session(eng, FlakyReplica(fail_n=10**6))  # never recovers
    s._loop_thread.start()
    try:
        r = s.submit(QueryRequest(terms=q[0]), timeout=2)
        assert isinstance(r, Rejected) and r.reason == REJECT_WORKER_FAILED
        assert eng.metrics.snapshot()["sched"]["worker_failures"] == 1
    finally:
        s.close()


def test_replica_group_prefers_sibling_on_retry():
    bad, good = FlakyReplica(fail_n=10**6), RecordingReplica()
    good.inflight = 5  # least-loaded picks `bad` first...
    group = ReplicaGroup(0, [bad, good], retries=1)
    assert group.call(("ping",)) == "pong"  # ...retry lands on the sibling
    assert len(bad.calls) == 1 and len(good.calls) == 1
    with pytest.raises(WorkerFailure):
        ReplicaGroup(0, [FlakyReplica(fail_n=10**6)], retries=1).call(("ping",))


def test_process_worker_crash_retry_then_typed_failure(system, tmp_path):
    """The real crash hook: ("crash",) hard-exits the worker; the group
    respawns and retries, the retry crashes again, the failure is typed."""
    eng = _engine(system, n_shards=1, sched=dict(n_replicas=1))
    with Session(eng, store_dir=str(tmp_path)) as s:
        s.warm()
        group = s._groups[0]
        with pytest.raises(WorkerFailure) as ei:
            group.call(("crash",))
        assert ei.value.attempts == 2  # retry budget spent
        # the group recovered: next dispatch respawns and serves
        assert group.call(("ping",)) == "pong"
        snap = eng.metrics.snapshot()["sched"]
        assert snap["worker_retries"] == 1 and snap["worker_failures"] == 1


def test_process_replicas_refused_on_tpu(system, tmp_path, monkeypatch):
    """A TPU belongs to one process: asking for process replicas there fails
    at construction, before anything is saved or spawned."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = _engine(system, n_shards=1, sched=dict(n_replicas=1))
    with pytest.raises(RuntimeError, match="one process"):
        Session(eng, store_dir=str(tmp_path))
    assert not (tmp_path / "shards.json").exists()


def test_launcher_refuses_replicas_on_tpu(monkeypatch):
    """launch/serve.py --replicas N>0 stops at argument parsing on a TPU."""
    from repro.launch import serve as launcher

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr("sys.argv", ["serve", "--replicas", "1"])
    with pytest.raises(SystemExit) as ei:
        launcher.main()
    assert ei.value.code == 2


def test_warm_propagates_a_failing_fused_dispatch(system, monkeypatch):
    """A ranked warm-up failure surfaces from warm(), not from the first
    served request."""
    import repro.kernels.fused_query.ops as fused_ops

    def boom(*a, **kw):
        raise RuntimeError("fused kernel failed to compile")

    monkeypatch.setattr(fused_ops, "fused_topk_batch", boom)
    eng = _engine(system, n_shards=1, ranked=dict(fused_kernel=True))
    with Session(eng) as s:
        with pytest.raises(RuntimeError, match="failed to compile"):
            s.warm()


# ---------------------------------------------------------- short-circuits
def test_all_pad_and_k0_short_circuit_without_dispatch(system):
    eng = _engine(system, n_shards=1)
    replica = RecordingReplica()
    s = _fake_session(eng, replica)
    try:
        pad = np.full(4, -1, np.int32)
        r = s.submit_async(QueryRequest(terms=pad)).result(timeout=1)
        assert r.ok and r.ids.size == 0 and r.scores is None
        r = s.submit_async(QueryRequest(terms=pad, mode=MODE_RANKED)).result(timeout=1)
        assert r.ok and r.ids.size == 0 and r.scores is not None and r.scores.size == 0
        r = s.submit_async(
            QueryRequest(terms=np.array([3], np.int32), mode=MODE_RANKED, k=0)
        ).result(timeout=1)
        assert r.ok and r.ids.size == 0
        assert replica.calls == []  # resolved at submit: nothing was enqueued
        snap = eng.metrics.snapshot()["sched"]
        assert snap["short_circuit"] == 3 and snap["enqueued"] == 0
    finally:
        s.close()


# -------------------------------------------------------------- coalescing
def _pending(mode="boolean", tenant="default", priority=0, deadline=None, seq=0):
    req = QueryRequest(terms=np.array([1], np.int32), mode=mode, tenant=tenant,
                       priority=priority)
    return Pending(req=req, future=Future(), row=req.terms,
                   t_submit=time.monotonic(), deadline=deadline, seq=seq)


def test_take_batch_coalesces_head_mode_across_queue():
    queue = AdmissionQueue(SchedConfig(max_batch=16, max_queue=16), Registry())
    for mode in ["boolean", "boolean", "ranked", "boolean"]:
        queue.offer(_pending(mode=mode))
    # the head's mode coalesces past the other mode (FIFO within a mode);
    # the skipped ranked entry is left at the head for the next round
    batch = queue.take_batch(16)
    assert [p.req.mode for p in batch] == ["boolean"] * 3
    assert [p.seq for p in batch] == sorted(p.seq for p in batch)
    assert [p.req.mode for p in queue.take_batch(16)] == ["ranked"]
    # max_batch still caps a same-mode pull mid-queue
    for mode in ["ranked", "boolean", "ranked", "ranked"]:
        queue.offer(_pending(mode=mode))
    assert [p.req.mode for p in queue.take_batch(2)] == ["ranked"] * 2
    # the un-pulled entries keep arrival order: boolean is now the head
    assert [p.req.mode for p in queue.take_batch(16)] == ["boolean"]
    assert [p.req.mode for p in queue.take_batch(16)] == ["ranked"]


def test_take_batch_respects_max_batch_and_arrival_order():
    queue = AdmissionQueue(SchedConfig(max_batch=16, max_queue=64), Registry())
    for _ in range(5):
        queue.offer(_pending())
    batch = queue.take_batch(3)
    assert len(batch) == 3
    assert [p.seq for p in batch] == sorted(p.seq for p in batch)  # FIFO
    assert len(queue.take_batch(16)) == 2


def test_continuous_batching_coalesces_arrivals_while_busy(system):
    """Arrivals during an in-flight dispatch pile up and go out as one batch."""
    eng = _engine(system, n_shards=1)
    q, _ = _queries(system)

    gate = threading.Event()
    class SlowReplica(RecordingReplica):
        def call(self, msg):
            if msg[0] == "bool" and not gate.is_set():
                self.calls.append(msg)
                gate.wait(timeout=5)  # hold the batch in flight
                words = (self.n_docs + 31) // 32
                return np.zeros((len(msg[1]), words), dtype=np.uint32)
            return super().call(msg)

    def _wait(cond, timeout=5.0):
        deadline = time.monotonic() + timeout
        while not cond() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert cond()

    replica = SlowReplica()
    s = _fake_session(eng, replica, max_batch=16)
    s._loop_thread.start()
    try:
        # occupy every runner slot with a gated in-flight batch, one at a
        # time so they cannot coalesce with each other
        n_slots = 2 * max(1, s.sched_cfg.n_replicas)
        first = []
        for i in range(n_slots):
            first.append(s.submit_async(QueryRequest(terms=q[i])))
            _wait(lambda: len(replica.calls) == len(first))
        # all slots busy -> the loop is parked on the slot semaphore and
        # these five arrivals pile up in the admission queue
        rest = [s.submit_async(QueryRequest(terms=q[i]))
                for i in range(n_slots, n_slots + 5)]
        _wait(lambda: len(s._queue._items) == 5)
        gate.set()
        assert all(f.result(timeout=5).ok for f in first)
        assert all(f.result(timeout=5).ok for f in rest)
        sizes = [len(msg[1]) for msg in replica.calls if msg[0] == "bool"]
        # the gated slot-fillers went out alone; the five arrivals went out
        # as ONE coalesced batch (its row matrix padded up to the 8-row
        # power-of-two bucket, so count batches, not rows)
        assert sizes[:n_slots] == [1] * n_slots
        assert len(sizes) == n_slots + 1 and sizes[n_slots] == 8
        snap = eng.metrics.snapshot()["sched"]
        assert snap["batches"] == n_slots + 1
        assert snap["dispatched"] == n_slots + 5
    finally:
        s.close()


# ------------------------------------------------------------- config shim
def test_flat_kwargs_deprecated_but_land_in_subconfigs():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg = ServeConfig(payload_bits=4, topk_exhaustive_cutoff=0)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert cfg.ranked.payload_bits == 4
    assert cfg.ranked.topk_exhaustive_cutoff == 0
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg = ServeConfig(ranked=False)  # old boolean flag
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert cfg.ranked.enabled is False and not cfg.ranked
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ServeConfig(shard_workers=4)  # retired knob: warned, ignored
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    with pytest.raises(TypeError):
        ServeConfig(not_a_knob=1)


def test_flat_kwarg_warning_cached_per_call_site():
    """A hot loop re-building configs warns once per call site, not per call."""
    from repro.serve import config as cfg_mod

    cfg_mod._WARNED_SITES.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for _ in range(3):
            ServeConfig(payload_bits=4)  # one site: exactly one warning
    dep = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(dep) == 1
    # a different call site with the same kwarg still gets its own warning
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ServeConfig(payload_bits=4)
    assert sum(issubclass(x.category, DeprecationWarning) for x in w) == 1


def test_flat_attributes_forward_to_subconfigs():
    cfg = ServeConfig()
    cfg.trace = sentinel = object()
    assert cfg.obs.trace is sentinel and cfg.trace is sentinel
    cfg.payload_bits = 4
    assert cfg.ranked.payload_bits == 4
    cfg.ranked.score_kernel = True
    assert cfg.score_kernel is True
    assert isinstance(cfg.obs, ObsConfig) and isinstance(cfg.ranked, RankedConfig)


def test_subconfigs_accept_dicts():
    cfg = ServeConfig(
        obs=dict(trace=None),
        ranked=dict(payload_bits=4),
        sched=dict(n_replicas=2, max_batch=8),
    )
    assert cfg.ranked.payload_bits == 4
    assert cfg.sched.n_replicas == 2 and cfg.sched.max_batch == 8


def test_worker_spec_round_trips_engine_flags():
    cfg = ServeConfig(
        n_shards=4,
        verified=False,
        ranked=dict(payload_bits=4),
        sched=dict(n_replicas=3),
        obs=dict(trace=object()),  # handles must NOT cross the pipe
    )
    spec = cfg.worker_spec()
    clone = ServeConfig(**spec)
    assert clone.verified is False and clone.n_shards == 4
    assert clone.ranked.payload_bits == 4
    assert clone.obs.trace is None  # worker builds its own obs
    assert clone.sched.n_replicas == 0  # workers execute; the session schedules


def test_coalesce_window_lingers_for_stragglers():
    """coalesce_us holds a non-full batch open so near-simultaneous arrivals
    ride the same dispatch."""
    queue = AdmissionQueue(
        SchedConfig(max_batch=8, max_queue=16, coalesce_us=200_000), Registry()
    )
    queue.offer(_pending())

    def late():
        time.sleep(0.03)
        queue.offer(_pending())
        queue.offer(_pending())

    t = threading.Thread(target=late)
    t.start()
    t0 = time.monotonic()
    batch = queue.take_batch(8)
    t.join()
    assert len(batch) == 3  # the stragglers made it into the lingering batch
    assert time.monotonic() - t0 < 1.0


def test_coalesce_window_anchored_to_head_submit_time():
    """The window is measured from the head's submit, not from take_batch:
    a batch that already aged while runners were busy dispatches at once."""
    queue = AdmissionQueue(
        SchedConfig(max_batch=8, max_queue=16, coalesce_us=150_000), Registry()
    )
    p = _pending()
    p.t_submit = time.monotonic() - 1.0  # aged in queue during a busy spell
    queue.offer(p)
    t0 = time.monotonic()
    assert len(queue.take_batch(8)) == 1
    assert time.monotonic() - t0 < 0.05  # no linger added on top of the age


# ------------------------------------------------------- ranked floor fan-in
def test_ranked_floor_forwarding_bit_identical(system):
    """forward_floor shares the running global kth score across the shard
    fan-in; it must only skip work, never change results."""
    _, rq = _queries(system)
    eng_f = _engine(system, n_shards=3, sched=dict(forward_floor=True))
    eng_0 = _engine(system, n_shards=3, sched=dict(forward_floor=False))
    want = eng_0.query_topk(rq, k=3, mode="or")  # engine facade reference
    with Session(eng_f) as sf, Session(eng_0) as s0:
        floors_sent = []
        for g in sf._groups:
            def wrap(msg, _orig=g.call):
                if msg[0] == "topk":
                    floors_sent.append([it[3] for it in msg[1]])
                return _orig(msg)
            g.call = wrap
        got_f = sf.query_topk(rq, k=3)
        got_0 = s0.query_topk(rq, k=3)
    for a, b, c in zip(got_f, got_0, want):
        assert np.array_equal(a.ids, b.ids) and np.array_equal(a.scores, b.scores)
        assert np.array_equal(a.ids, c.ids) and np.array_equal(a.scores, c.scores)
    # later groups in the sequential fan-in actually saw a raised floor
    assert any(f > 0 for fl in floors_sent for f in fl)


# ------------------------------------------------------------- warm snapshot
def test_warm_snapshot_respawn_bit_identical_and_re_jit_free(system, tmp_path):
    """A crashed worker's replacement replays the recorded warm log against
    the persistent compile cache: same jit cache, same shapes, same bits."""
    eng = _engine(
        system,
        n_shards=1,
        ranked=dict(fused_kernel=True),
        sched=dict(n_replicas=1),
    )
    _, rq = _queries(system)
    with Session(eng, store_dir=str(tmp_path)) as s:
        s.warm()
        want = s.query_topk(rq, k=5)
        rep = s._groups[0].replicas[0]
        before = rep.call(("caches",))
        assert before["dense_cache"] > 0 and before["dense_shapes"]
        assert before["arena"]["uploads"] == 1
        with pytest.raises(ReplicaError):
            rep.call(("crash",))
        after = rep.call(("caches",))  # respawn + warm-log replay first
        assert rep.warm_replays > 0 and rep.clock_syncs == 2
        assert after["dense_cache"] == before["dense_cache"]
        assert after["dense_shapes"] == before["dense_shapes"]
        got = s.query_topk(rq, k=5)
        post = rep.call(("caches",))
        # re-jit-free: serving the same shapes compiled nothing new
        assert post["dense_cache"] == after["dense_cache"]
        assert post["dense_shapes"] == after["dense_shapes"]
        for a, b in zip(want, got):
            assert np.array_equal(a.ids, b.ids)
            assert np.array_equal(a.scores, b.scores)
    assert (tmp_path / "warm_snapshot.json").exists()
    # the compile cache is placed from outside (JAX_COMPILATION_CACHE_DIR),
    # never inside the shard-store
    assert not (tmp_path / "xla-compile-cache").exists()
    # a brand-new session over the same store preloads the snapshot, so its
    # first spawn replays the previous run's whole shape coverage
    eng2 = _engine(
        system,
        n_shards=1,
        ranked=dict(fused_kernel=True),
        sched=dict(n_replicas=1),
    )
    with Session(eng2, store_dir=str(tmp_path)) as s2:
        rep2 = s2._groups[0].replicas[0]
        assert len(rep2._warm_log) > 0  # seeded before the first spawn
        rep2.call(("ping",))
        assert rep2.warm_replays > 0
        got2 = s2.query_topk(rq, k=5)
        for a, b in zip(want, got2):
            assert np.array_equal(a.ids, b.ids)
            assert np.array_equal(a.scores, b.scores)


# ------------------------------------------------------------- causal spans
@pytest.fixture(scope="module")
def traced_session(system):
    """4 inline shards through a traced Session, the served cell's path:
    concurrent Boolean requests, one ranked request, one shed request."""
    from repro.obs import Tracer

    tracer = Tracer()
    eng = _engine(system, n_shards=4, obs=dict(trace=tracer))
    q, rq = _queries(system)
    with Session(eng) as s:
        futs = [s.submit_async(QueryRequest(terms=row)) for row in q]
        futs.append(s.submit_async(QueryRequest(terms=rq[0], mode=MODE_RANKED, k=3)))
        futs.append(s.submit_async(QueryRequest(terms=q[0], deadline_ms=-1.0)))
        results = [f.result(timeout=30) for f in futs]
    return tracer, results


def _chain(by_id, span):
    out = []
    while span.parent:
        span = by_id[span.parent]
        out.append(span.name)
    return out


def test_shard_spans_hang_under_dispatch_and_batch(traced_session):
    tracer, results = traced_session
    assert [r.ok for r in results] == [True] * (len(results) - 1) + [False]
    by_id = {s.id: s for s in tracer.spans}
    assert len(by_id) == len(tracer.spans)  # ids unique per tracer
    shard = [s for s in tracer.spans
             if s.name in ("shard.verify", "shard.candidate_mask")]
    assert {s.name for s in shard} == {"shard.verify", "shard.candidate_mask"}
    dispatch_tids = {s.tid for s in tracer.spans if s.name == "sched.dispatch"}
    # recorded on the fan pool's threads, yet below the runner's dispatch
    assert any(s.tid not in dispatch_tids for s in shard)
    for s in shard:
        chain = _chain(by_id, s)
        assert chain[:2] == ["shard.execute", "sched.dispatch"], chain
        assert chain[-1] == "sched.batch" and s.depth == 3


def test_every_span_is_one_deeper_than_its_parent(traced_session):
    tracer, _ = traced_session
    by_id = {s.id: s for s in tracer.spans}
    for s in tracer.spans:
        if s.parent == 0:
            assert s.depth == 0, s
        else:
            assert s.depth == by_id[s.parent].depth + 1, s
    assert {"serve.plan", "shard.execute", "sched.resolve", "sched.request",
            "decode.postings"} <= {s.name for s in tracer.spans}


def test_one_request_span_per_request_with_its_queue_wait(traced_session):
    tracer, results = traced_session
    reqs = [s for s in tracer.spans if s.name == "sched.request"]
    assert len(reqs) == len(results)  # one each, served or shed
    ids = sorted(s.attrs["request"] for s in reqs)
    assert ids == list(range(1, len(results) + 1))
    by_req = {s.attrs["request"]: s for s in reqs}
    waits = [s for s in tracer.spans if s.name == "sched.queue_wait"]
    for w in waits:
        root = by_req[w.attrs["request"]]
        assert w.parent == root.id and w.depth == 1
        assert root.ts_us <= w.ts_us + 1.0
    served = [s for s in reqs if s.attrs["outcome"] == "ok"]
    assert len(served) == sum(r.ok for r in results)
    assert sorted(w.attrs["request"] for w in waits) == sorted(
        s.attrs["request"] for s in served)
    [shed] = [s for s in reqs if s.attrs["outcome"] != "ok"]
    assert shed.attrs["outcome"] == REJECT_DEADLINE and shed.depth == 0
    # a batch's causal link is the list of requests it serves
    batched = [r for s in tracer.spans if s.name == "sched.batch"
               for r in s.attrs["requests"]]
    assert sorted(batched) == sorted(s.attrs["request"] for s in served)


def test_serve_plan_once_per_plan_batch_call(system, monkeypatch):
    from repro.obs import Tracer
    from repro.serve import boolean, planner

    calls = []

    def counting(*a, _orig=planner.plan_batch, **kw):
        calls.append(1)
        return _orig(*a, **kw)

    monkeypatch.setattr(planner, "plan_batch", counting)  # Session's shards
    monkeypatch.setattr(boolean, "plan_batch", counting)  # the facade
    q, _ = _queries(system)
    tracer = Tracer()
    eng = _engine(system, n_shards=4, obs=dict(trace=tracer))
    eng.query_batch(q[:3])
    assert len(calls) == 1
    with Session(eng) as s:
        s.query_batch(q[3:6])
    plans = [s for s in tracer.spans if s.name == "serve.plan"]
    assert len(plans) == len(calls) > 1
    batches = [s for s in tracer.spans if s.name == "sched.batch"]
    assert len(calls) == 1 + 4 * len(batches)  # each shard plans its batch


def test_trace_off_session_records_no_span_and_draws_no_id(system):
    from repro.obs import Tracer

    tracer = Tracer()
    eng = _engine(system, n_shards=4, obs=dict(trace=tracer))
    eng.cfg.obs.trace = None
    q, _ = _queries(system)
    with Session(eng) as s:
        got = s.query_batch(q[:4])
        shed = s.submit(QueryRequest(terms=q[0], deadline_ms=-1.0))
    assert len(got) == 4 and not shed.ok
    assert tracer.spans == [] and tracer.next_id() == 1
