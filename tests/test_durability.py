"""Crash-safe streaming: journal/checkpoint/recovery invariants.

The contract under test is BIT-IDENTITY: for a seeded stream, an engine
recovered from (last durable checkpoint + journal-tail replay) is
leaf-for-leaf equal to the engine that never crashed — and so are its
subsequent query answers. Pinned here for

  * a crash at an arbitrary batch boundary AND a crash mid-replay
    (recovery of a failed recovery),
  * both store dtypes (fp32 and int8),
  * the single-device ``Engine`` and the 4-device sharded engine
    (subprocess, forced host-device mesh).

Plus the mechanics underneath: torn-tail detection, monotone seqs,
segment truncation only behind a durable checkpoint, delta checkpoints
that restore exactly like fulls, and a failed checkpoint write that
never advances the dirty baseline (nothing is lost, the next save
covers it).
"""
import faulthandler
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import clustering, heavy_hitter, pipeline, prefilter
from repro.data.streams import make_stream
from repro.engine import Engine
from repro.serve.durability import (CheckpointStore, DurabilityConfig,
                                    IngestJournal, classify_error,
                                    replay_journal)
from repro.serve.runtime import AsyncServer, ServerConfig
from repro.testing import faults
from repro.train import checkpoint as ckpt_lib

DIM = 32
WATCHDOG_S = 240.0

pytestmark = pytest.mark.timeout(300)


@pytest.fixture(autouse=True)
def _deadlock_watchdog():
    def _die():
        faulthandler.dump_traceback(file=sys.stderr)
        os._exit(3)

    timer = threading.Timer(WATCHDOG_S, _die)
    timer.daemon = True
    timer.start()
    yield
    timer.cancel()


def small_cfg(**kw):
    return pipeline.PipelineConfig(
        pre=prefilter.PrefilterConfig(num_vectors=3, dim=DIM, alpha=0.0,
                                      basis="fixed"),
        clus=clustering.ClusterConfig(num_clusters=16, dim=DIM),
        hh=heavy_hitter.HHConfig(capacity=8, admit_prob=0.5),
        update_interval=kw.pop("update_interval", 64),
        **kw)


def scfg(**kw):
    return ServerConfig(max_batch=8, topk=5, two_stage=True, nprobe=4, **kw)


def assert_leaves_identical(a, b):
    fa, fb = ckpt_lib.flatten_tree(a), ckpt_lib.flatten_tree(b)
    assert fa.keys() == fb.keys()
    bad = [k for k in fa
           if not np.array_equal(np.asarray(fa[k]), np.asarray(fb[k]))]
    assert not bad, f"leaves differ: {bad}"


# ---------------------------------------------------------------- journal
def test_journal_roundtrip_and_monotone_seqs(tmp_path):
    j = IngestJournal(str(tmp_path), segment_bytes=1 << 12)
    rng = np.random.default_rng(0)
    batches = [(rng.standard_normal((4, DIM)).astype(np.float32),
                np.arange(4, dtype=np.int32) + 4 * i) for i in range(9)]
    for i, (x, ids) in enumerate(batches):
        j.append(i, x, ids)
    assert j.last_seq() == 8
    got = list(j.replay(0))
    assert [s for s, _x, _i in got] == list(range(9))
    for (s, x, ids), (wx, wids) in zip(got, batches):
        np.testing.assert_array_equal(x, wx)
        np.testing.assert_array_equal(ids, wids)
    # a tail replay starts where asked
    assert [s for s, _x, _i in j.replay(6)] == [6, 7, 8]
    # seqs must stay monotone — a skipped seq is a hole replay can't fill
    with pytest.raises(AssertionError, match="monotone"):
        j.append(42, *batches[0])
    # a fresh handle over the same directory resumes the seq chain
    j.close()
    j2 = IngestJournal(str(tmp_path), segment_bytes=1 << 12)
    assert j2.last_seq() == 8
    j2.append(9, *batches[0])
    j2.close()


def test_journal_torn_tail_is_dropped(tmp_path):
    j = IngestJournal(str(tmp_path), segment_bytes=1 << 20)
    x = np.ones((4, DIM), np.float32)
    for i in range(3):
        j.append(i, x * i, np.arange(4, dtype=np.int32))
    j.close()
    seg = [f for f in os.listdir(tmp_path) if f.endswith(".wal")][0]
    path = os.path.join(str(tmp_path), seg)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:  # tear the last record mid-payload
        f.truncate(size - 7)
    j2 = IngestJournal(str(tmp_path))
    assert [s for s, _x, _i in j2.replay(0)] == [0, 1]  # tail dropped
    assert j2.last_seq() == 1
    # ... and the journal keeps appending right after the torn record
    j2.append(2, x, np.arange(4, dtype=np.int32))
    assert [s for s, _x, _i in j2.replay(0)] == [0, 1, 2]
    j2.close()


def test_journal_truncate_only_covered_segments(tmp_path):
    # tiny segments: every append rolls a new one
    j = IngestJournal(str(tmp_path), segment_bytes=1)
    x = np.ones((4, DIM), np.float32)
    for i in range(5):
        j.append(i, x, np.arange(4, dtype=np.int32))
    assert j.stats()["segments"] == 5
    j.truncate(2)  # covers seqs 0..2 -> segments holding them go
    assert [s for s, _x, _i in j.replay(0)] == [3, 4]
    # the active segment survives even when fully covered
    j.truncate(4)
    assert j.stats()["segments"] == 1
    assert [s for s, _x, _i in j.replay(0)] == [4]
    j.close()


# ------------------------------------------------------------- checkpoints
def _ingest_n(eng, stream, n, b=16):
    batches = [stream.next_batch(b) for _ in range(n)]
    for bb in batches:
        eng.ingest(bb["embedding"], bb["doc_id"])
    return batches


@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
def test_delta_checkpoint_restores_exactly(tmp_path, store_dtype):
    cfg = small_cfg(store_depth=4, store_dtype=store_dtype)
    stream = make_stream("iot", dim=DIM)
    eng = Engine(cfg, jax.random.key(0))
    store = CheckpointStore(str(tmp_path), cluster_axis=0)

    _ingest_n(eng, stream, 3)
    out = store.save(2, eng.state, blocking=True)
    assert out["mode"] == "full"
    _ingest_n(eng, stream, 2)
    out = store.save(4, eng.state, blocking=True)
    assert out["mode"] == "delta"
    _ingest_n(eng, stream, 2)
    out = store.save(6, eng.state, blocking=True)
    assert out["mode"] == "delta"  # chains: full + delta + delta

    restored, meta = store.restore(eng.state)
    assert meta["seq"] == 6
    assert_leaves_identical(eng.state, restored)


def test_checkpoint_write_failure_never_advances_baseline(tmp_path):
    cfg = small_cfg(store_depth=4)
    stream = make_stream("iot", dim=DIM)
    eng = Engine(cfg, jax.random.key(0))
    store = CheckpointStore(str(tmp_path), cluster_axis=0)
    _ingest_n(eng, stream, 2)
    store.save(1, eng.state, blocking=True)
    state_at_1 = jax.tree.map(jnp.copy, eng.state)

    _ingest_n(eng, stream, 2)
    with faults.inject("checkpoint.write:raise@1") as plan:
        store.save(3, eng.state)
        store.wait()
    assert plan.fired("checkpoint.write") == 1
    assert store.saves["failed"] == 1
    assert store.poll_error(raise_=False) is not None
    # the failed save changed nothing durable: restore still yields seq 1
    restored, meta = store.restore(eng.state)
    assert meta["seq"] == 1
    assert_leaves_identical(state_at_1, restored)
    # ... and the NEXT save covers everything since seq 1 (baseline did
    # not advance), so restore equals the live state again
    out = store.save(3, eng.state, blocking=True)
    assert out["mode"] == "delta"
    restored, meta = store.restore(eng.state)
    assert meta["seq"] == 3
    assert_leaves_identical(eng.state, restored)


def test_replay_quarantines_poison_batch(tmp_path):
    j = IngestJournal(str(tmp_path))
    x = np.ones((4, DIM), np.float32)
    for i in range(4):
        j.append(i, x * i, np.arange(4, dtype=np.int32))

    applied = []

    def apply(x, ids):
        if int(x[0, 0]) == 2:  # batch seq 2 is poison
            raise faults.InjectedFault("poison")
        applied.append(int(x[0, 0]))

    report = replay_journal(j, 0, apply, quarantine_after=3)
    assert applied == [0, 1, 3]
    assert report.quarantined == [2]   # counted + named, never silent
    assert report.replayed == 3
    # fatal errors are NOT retried or quarantined — they surface
    with pytest.raises(faults.InjectedFatal):
        replay_journal(j, 0,
                       lambda x, ids: (_ for _ in ()).throw(
                           faults.InjectedFatal("bug")),
                       quarantine_after=3)
    j.close()


def test_classify_error():
    assert classify_error(faults.InjectedFault("x")) == "transient"
    assert classify_error(faults.InjectedFatal("x")) == "fatal"
    assert classify_error(TimeoutError()) == "transient"
    assert classify_error(ValueError("shape")) == "fatal"
    assert classify_error(AssertionError()) == "fatal"


# ----------------------------------------------------- recovery bit-identity
def _reference_engine(cfg, batches):
    ref = Engine(cfg, jax.random.key(0))
    for b in batches:
        ref.ingest(b["embedding"], b["doc_id"])
    return ref


@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("crash_at", [3, 7])
def test_crash_recovery_bit_identical(tmp_path, store_dtype, crash_at):
    """Crash at an arbitrary batch boundary; recover; compare leaf-for-
    leaf with the uncrashed run AND the query answers."""
    cfg = small_cfg(store_depth=4, store_dtype=store_dtype)
    stream = make_stream("iot", dim=DIM)
    batches = [stream.next_batch(16) for _ in range(10)]
    ref = _reference_engine(cfg, batches)

    dcfg = DurabilityConfig(checkpoint_dir=str(tmp_path), checkpoint_every=3)
    srv = AsyncServer(cfg, scfg(), engine=Engine(cfg, jax.random.key(0)),
                      publish_every=2, durability=dcfg)
    with faults.inject(f"ingest.admit:crash@{crash_at + 1}"):
        for b in batches:
            try:
                srv.ingest(b["embedding"], b["doc_id"])
            except RuntimeError:
                # the thread already died: the journal append happens
                # BEFORE the enqueue, so this batch is durable regardless
                pass
        # the ingest thread died mid-stream (simulated SIGKILL) — but
        # queries keep answering from the pinned snapshot
        srv._thread.join(30.0)
        assert not srv._thread.is_alive()
    q = stream.queries(4)["embedding"]
    for qv in q:
        srv.submit(qv)
    out = srv.drain()
    assert len(out) == 4 and all(o["doc_ids"].shape == (5,) for o in out)
    srv._durable.close()

    # recovery into a fresh process-equivalent: same cfg, same init key
    srv2 = AsyncServer(cfg, scfg(), engine=Engine(cfg, jax.random.key(0)),
                       publish_every=2, durability=dcfg)
    rep = srv2.recovery_report
    assert rep is not None and rep["quarantined"] == []
    # every journaled batch is applied: checkpointed prefix + replay tail
    assert rep["applied_seq"] == len(batches) - 1
    assert_leaves_identical(ref.state, srv2.engine.state)

    # subsequent answers are identical to the uncrashed engine's
    snap_ref, snap_rec = ref.publish(), srv2.engine.publish()
    want = ref.query_snapshot(snap_ref, jnp.asarray(q), 5, two_stage=True,
                              nprobe=4)
    got = srv2.engine.query_snapshot(snap_rec, jnp.asarray(q), 5,
                                     two_stage=True, nprobe=4)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    srv2.close()


def test_mid_replay_crash_then_second_recovery_bit_identical(tmp_path):
    """A crash DURING recovery replay must leave the durable state intact:
    the next recovery starts over and still lands bit-identical."""
    cfg = small_cfg(store_depth=4)
    stream = make_stream("iot", dim=DIM)
    batches = [stream.next_batch(16) for _ in range(8)]
    ref = _reference_engine(cfg, batches)

    dcfg = DurabilityConfig(checkpoint_dir=str(tmp_path), checkpoint_every=3)
    srv = AsyncServer(cfg, scfg(), engine=Engine(cfg, jax.random.key(0)),
                      publish_every=2, durability=dcfg)
    with faults.inject("ingest.admit:crash@6"):
        for b in batches:
            try:
                srv.ingest(b["embedding"], b["doc_id"])
            except RuntimeError:
                pass  # thread already dead; batch journaled before _put
        srv._thread.join(30.0)
    srv._durable.close()

    # first recovery crashes mid-replay (second replayed batch)
    with faults.inject("replay:crash@2"):
        with pytest.raises(faults.InjectedCrash):
            AsyncServer(cfg, scfg(), engine=Engine(cfg, jax.random.key(0)),
                        publish_every=2, durability=dcfg)
    # the durable state was not touched: a second recovery completes and
    # is bit-identical to the uncrashed run
    srv2 = AsyncServer(cfg, scfg(), engine=Engine(cfg, jax.random.key(0)),
                       publish_every=2, durability=dcfg)
    assert srv2.recovery_report["quarantined"] == []
    assert_leaves_identical(ref.state, srv2.engine.state)
    srv2.close()


def test_recovery_resumes_the_stream_seamlessly(tmp_path):
    """Post-recovery ingest continues the seq chain and stays identical
    to an uncrashed engine that saw the whole stream."""
    cfg = small_cfg(store_depth=4)
    stream = make_stream("iot", dim=DIM)
    batches = [stream.next_batch(16) for _ in range(12)]
    ref = _reference_engine(cfg, batches)

    dcfg = DurabilityConfig(checkpoint_dir=str(tmp_path), checkpoint_every=4)
    srv = AsyncServer(cfg, scfg(), engine=Engine(cfg, jax.random.key(0)),
                      publish_every=2, durability=dcfg)
    with faults.inject("ingest.admit:crash@5"):
        for b in batches[:8]:
            try:
                srv.ingest(b["embedding"], b["doc_id"])
            except RuntimeError:
                pass  # thread already dead; batch journaled before _put
        srv._thread.join(30.0)
    srv._durable.close()

    srv2 = AsyncServer(cfg, scfg(), engine=Engine(cfg, jax.random.key(0)),
                       publish_every=2, durability=dcfg)
    for b in batches[8:]:   # the stream resumes where it left off
        srv2.ingest(b["embedding"], b["doc_id"])
    srv2.sync()
    assert srv2.robustness_stats()["journal_last_seq"] == len(batches) - 1
    assert_leaves_identical(ref.state, srv2.engine.state)
    fresh = srv2.freshness_stats()
    assert fresh["lag_docs"] == 0
    srv2.close()


def test_sharded_crash_recovery_bit_identical():
    """4-device sharded engine: checkpoint the stacked state, crash the
    server mid-stream, recover — leaf-for-leaf equal to the uncrashed
    sharded run, and the recovered snapshot serves identical answers."""
    _run_in_4_device_subprocess("""
        import tempfile
        from repro.configs.streaming_rag import paper_pipeline_config
        from repro.data.streams import make_stream
        from repro.engine.sharded import ShardedEngine
        from repro.serve.durability import DurabilityConfig
        from repro.serve.runtime import AsyncServer, ServerConfig
        from repro.testing import faults
        from repro.train import checkpoint as ckpt_lib

        D, M = 4, 1
        cfg = paper_pipeline_config(dim=32, k=32, capacity=12,
                                    update_interval=48, alpha=-1.0,
                                    store_depth=4)
        scfg = ServerConfig(max_batch=8, topk=5, two_stage=True, nprobe=4)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((D, M), ("data", "model"))
        stream = make_stream("iot", dim=32)
        batches = [stream.next_batch(64) for _ in range(8)]

        ref = ShardedEngine(cfg, mesh, jax.random.key(0),
                            reconcile_every=10**9)
        for b in batches:
            ref.ingest(b["embedding"], b["doc_id"])

        d = tempfile.mkdtemp()
        dcfg = DurabilityConfig(checkpoint_dir=d, checkpoint_every=3)
        eng = ShardedEngine(cfg, mesh, jax.random.key(0),
                            reconcile_every=10**9)
        srv = AsyncServer(cfg, scfg, engine=eng, publish_every=4,
                          durability=dcfg)
        with faults.inject("ingest.admit:crash@6"):
            for b in batches:
                try:
                    srv.ingest(b["embedding"], b["doc_id"])
                except RuntimeError:
                    pass  # thread dead; batch journaled before _put
            srv._thread.join(60.0)
            assert not srv._thread.is_alive()
        srv._durable.close()

        eng2 = ShardedEngine(cfg, mesh, jax.random.key(0),
                             reconcile_every=10**9)
        srv2 = AsyncServer(cfg, scfg, engine=eng2, publish_every=4,
                           durability=dcfg)
        rep = srv2.recovery_report
        assert rep is not None and rep["applied_seq"] == len(batches) - 1

        fa = ckpt_lib.flatten_tree(ref.local)
        fb = ckpt_lib.flatten_tree(eng2.local)
        bad = [k for k in fa
               if not np.array_equal(np.asarray(fa[k]), np.asarray(fb[k]))]
        assert not bad, f"leaves differ: {bad}"

        q = jnp.asarray(stream.queries(8)["embedding"])
        want = ref.query_snapshot(ref.reconcile(), q, 5, two_stage=True,
                                  nprobe=4)
        got = eng2.query_snapshot(eng2.reconcile(), q, 5, two_stage=True,
                                  nprobe=4)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
        srv2.close()
        print("SHARDED-RECOVERY-OK")
    """)


def _run_in_4_device_subprocess(body: str):
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
    """) + textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                          text=True, timeout=600,
                          env={**os.environ, "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout
