"""Async serving runtime invariants.

* threaded stress: concurrent background ingest + foreground query for N
  rounds — every submitted query is answered EXACTLY once (monotone
  tickets, no drops, no duplicates), and every answer is bit-reproducible
  from the fully-published snapshot version it claims to have been served
  from (no torn reads: answers re-computed offline against the recorded
  snapshot must match).
* monotone tickets + drain on the synchronous server: tickets never
  restart after a flush, and ``drain()`` answers everything pending at
  shutdown (a single flush answers at most ``max_batch``).
* dead-row padding: ``doc_id < 0`` rows are inert for every
  retrieval-visible state leaf (the sharded engine pads ragged batches
  with them).

The module is deadlock-paranoid: a watchdog hard-fails the process if a
test wedges (pytest-timeout enforces the same bound in CI, where the
plugin is installed).
"""
import faulthandler
import os
import sys
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import clustering, heavy_hitter, pipeline, prefilter
from repro.data.streams import make_stream
from repro.engine import Engine
from repro.serve.runtime import AsyncServer, ServerConfig

DIM = 32
WATCHDOG_S = 240.0

pytestmark = pytest.mark.timeout(300)  # enforced where pytest-timeout exists


@pytest.fixture(autouse=True)
def _deadlock_watchdog():
    """Fail fast (with tracebacks) if a threaded test wedges, even when
    the pytest-timeout plugin is not installed."""
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


class _RecordingEngine(Engine):
    """Engine that keeps every published snapshot, so answers can be
    re-verified offline against the exact snapshot they were served from."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.published = {}

    def publish(self):
        snap = super().publish()
        self.published[snap.version] = snap
        return snap


def test_async_stress_exactly_once_from_published_snapshots():
    cfg = small_cfg(store_depth=4, update_interval=32)
    stream = make_stream("iot", dim=DIM)
    engine = _RecordingEngine(cfg, jax.random.key(0))
    server = AsyncServer(
        cfg, ServerConfig(max_batch=8, max_wait_ms=0.0, topk=5,
                          two_stage=True, nprobe=4),
        engine=engine, publish_every=2, queue_max=4)

    n_rounds, qps = 12, 6
    queries: dict[int, np.ndarray] = {}
    qlock = threading.Lock()

    def submitter():
        rng = np.random.default_rng(7)
        for _ in range(n_rounds):
            for qv in stream.queries(qps)["embedding"]:
                t = server.submit(qv)
                with qlock:
                    queries[t] = np.asarray(qv)
            rng.random()  # jitter-free but yields the GIL via the loop

    sub = threading.Thread(target=submitter)
    sub.start()
    answers = []
    for _ in range(n_rounds):
        b = stream.next_batch(32)
        answers += server.serve_round(b)   # flush-first, then enqueue
    sub.join()
    server.sync()
    answers += server.drain()
    server.close()

    # exactly once: every ticket answered, none twice, none invented
    tickets = [a["ticket"] for a in answers]
    assert len(tickets) == len(queries) == n_rounds * qps
    assert sorted(tickets) == sorted(queries)

    # every answer claims a version that was actually published, and
    # recomputing the query against that recorded snapshot reproduces the
    # answer bit-for-bit -> served state was a fully-published snapshot
    versions = {a["snapshot_version"] for a in answers}
    assert versions <= set(engine.published)
    assert len(engine.published) >= 2  # background publishes happened
    for a in answers[:: max(1, len(answers) // 16)]:
        snap = engine.published[a["snapshot_version"]]
        want = engine.query_snapshot(snap, queries[a["ticket"]][None], 5,
                                     two_stage=True, nprobe=4)
        np.testing.assert_array_equal(a["doc_ids"], np.asarray(want[2][0]))
        np.testing.assert_array_equal(a["scores"], np.asarray(want[0][0]))

    # freshness accounting is closed out by the final publish
    fresh = server.freshness_stats()
    assert fresh["docs_ingested"] == fresh["docs_published"]
    assert fresh["lag_docs"] == 0


def test_async_adaptive_overload_sheds_exactly_once_with_markers():
    """Threaded overload stress for query-adaptive serving: a flood of
    submissions drives the degradation controller down the ladder to
    shedding, concurrent ingest keeps the priority dispatcher busy, and
    EVERY ticket — shed included — is answered exactly once with honest
    markers (``degraded``/``shed``/``plan``/``snapshot_version``).
    Non-shed answers are bit-reproducible from their recorded snapshot
    under their answered plan; after the flood the controller recovers
    to full effort hysteretically."""
    from repro.engine.plan import QueryPlan

    cfg = small_cfg(store_depth=4, update_interval=32)
    stream = make_stream("iot", dim=DIM)
    engine = _RecordingEngine(cfg, jax.random.key(0))
    scfg = ServerConfig(max_batch=4, max_wait_ms=0.0, topk=5,
                        two_stage=True, nprobe=4, adaptive=True,
                        max_queue_depth=6, low_queue_depth=0,
                        recover_after=2)
    server = AsyncServer(cfg, scfg, engine=engine, publish_every=2,
                         queue_max=4)
    # ladder at (nprobe=4, depth=4, k=5): full -> (4,2) -> shed
    assert len(server.plan_space.ladder) == 3
    full = server.plan_space.full

    for _ in range(4):
        server.ingest(stream.next_batch(32)["embedding"],
                      stream.next_batch(32)["doc_id"])
    server.sync()

    n_burst = 60
    queries: dict[int, np.ndarray] = {}
    qlock = threading.Lock()

    def flooder():
        for qv in stream.queries(n_burst)["embedding"]:
            t = server.submit(qv)
            with qlock:
                queries[t] = np.asarray(qv)

    sub = threading.Thread(target=flooder)
    sub.start()
    # let the backlog actually build before the first flush, so the
    # controller deterministically escalates past the high watermark
    while len(server._pending) < scfg.max_queue_depth + scfg.max_batch:
        time.sleep(0.001)
    answers = []
    while len(answers) < n_burst:
        answers += server.flush()
        if len(answers) % 12 == 0:  # concurrent ingest dispatch pressure
            server.ingest(stream.next_batch(16)["embedding"],
                          stream.next_batch(16)["doc_id"])
    sub.join()
    # recovery trickle: empty-queue flushes accumulate calm and walk the
    # controller back up to full effort (recover_after=2 per level)
    for qv in stream.queries(10)["embedding"]:
        t = server.submit(qv)
        with qlock:
            queries[t] = np.asarray(qv)
        answers += server.flush()
    server.sync()
    answers += server.drain()
    server.close()

    # exactly once, shed included
    tickets = [a["ticket"] for a in answers]
    assert sorted(tickets) == sorted(queries)
    assert len(tickets) == len(set(tickets)) == n_burst + 10

    shed = [a for a in answers if a["shed"]]
    degraded_live = [a for a in answers if a["degraded"] and not a["shed"]]
    full_effort = [a for a in answers if not a["degraded"]]
    assert shed and degraded_live and full_effort  # whole ladder exercised
    assert server.stats["shed"] == len(shed)
    assert answers[-1]["degraded"] is False  # recovered by the tail

    for a in shed:  # explicit overload sentinel, never engine output
        assert a["degraded"] is True
        assert "snapshot_version" in a
        assert np.all(a["doc_ids"] == -1) and np.all(a["clusters"] == -1)
        assert np.all(np.isneginf(a["scores"]))
    for a in degraded_live:
        plan = QueryPlan(a["plan"]["nprobe"], a["plan"]["depth"])
        assert plan != full
    # every live answer is bit-reproducible from its recorded snapshot
    # under the plan it says it was served with
    live = [a for a in answers if not a["shed"]]
    for a in live[:: max(1, len(live) // 12)]:
        snap = engine.published[a["snapshot_version"]]
        plan = QueryPlan(a["plan"]["nprobe"], a["plan"]["depth"])
        want = engine.query_snapshot(snap, queries[a["ticket"]][None], 5,
                                     two_stage=True, plan=plan)
        np.testing.assert_array_equal(a["doc_ids"], np.asarray(want[2][0]))
        np.testing.assert_array_equal(a["scores"], np.asarray(want[0][0]))


def test_async_ingest_thread_error_surfaces():
    cfg = small_cfg(store_depth=4)
    server = AsyncServer(
        cfg, ServerConfig(max_batch=4, topk=5, two_stage=True, nprobe=4),
        key=jax.random.key(1), publish_every=1, queue_max=2)
    server.ingest(np.zeros((8, DIM + 1), np.float32),  # wrong dim -> dies
                  np.arange(8, dtype=np.int32))
    with pytest.raises((RuntimeError, TimeoutError)):
        server.sync(timeout=10.0)
        server.ingest(np.zeros((8, DIM), np.float32),
                      np.arange(8, dtype=np.int32))
        server.sync(timeout=10.0)


def test_tickets_monotone_and_drain_answers_everything():
    cfg = small_cfg(store_depth=4)
    stream = make_stream("iot", dim=DIM)
    server = AsyncServer(cfg, ServerConfig(max_batch=4, max_wait_ms=0.0,
                                           topk=5, two_stage=True,
                                           nprobe=4),
                         key=jax.random.key(2))
    server.ingest(stream.next_batch(64)["embedding"],
                  stream.next_batch(64)["doc_id"])
    server.sync()                              # queries see the batch

    first = [server.submit(q) for q in stream.queries(10)["embedding"]]
    assert first == list(range(10))
    out1 = server.flush()                      # one flush: max_batch only
    assert [o["ticket"] for o in out1] == [0, 1, 2, 3]
    rest = server.drain()                      # shutdown path: the rest
    assert [o["ticket"] for o in rest] == [4, 5, 6, 7, 8, 9]
    assert not server._pending

    # tickets keep increasing after a flush — no restart, no collision
    more = [server.submit(q) for q in stream.queries(3)["embedding"]]
    assert more == [10, 11, 12]
    out2 = server.drain()
    assert [o["ticket"] for o in out2] == [10, 11, 12]
    seen = [o["ticket"] for o in out1 + rest + out2]
    assert len(seen) == len(set(seen)) == 13
    server.close()


def test_dead_rows_are_inert_for_retrieval_state():
    """doc_id < 0 rows (ragged-batch padding) must not touch centroids,
    counts, the doc store, or arrival accounting."""
    cfg = small_cfg(store_depth=4)
    stream = make_stream("iot", dim=DIM)
    b = stream.next_batch(30)
    x = jnp.asarray(b["embedding"])
    ids = jnp.asarray(b["doc_id"], jnp.int32)
    xp = jnp.concatenate([x, jnp.zeros((2, DIM), jnp.float32)])
    idp = jnp.concatenate([ids, jnp.full((2,), -1, jnp.int32)])

    s_plain, _ = pipeline.ingest_batch(
        cfg, pipeline.init(cfg, jax.random.key(3)), x, ids)
    s_pad, info = pipeline.ingest_batch(
        cfg, pipeline.init(cfg, jax.random.key(3)), xp, idp)

    np.testing.assert_array_equal(np.asarray(s_plain.clus.counts),
                                  np.asarray(s_pad.clus.counts))
    np.testing.assert_array_equal(np.asarray(s_plain.clus.centroids),
                                  np.asarray(s_pad.clus.centroids))
    for name in ("ids", "stamps", "ptr", "embs"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_plain.store, name)),
            np.asarray(getattr(s_pad.store, name)))
    assert int(s_pad.arrivals) == int(s_plain.arrivals) == 30
    assert int(s_pad.kept) == int(s_plain.kept)
    assert int(s_pad.hh.total_seen) == int(s_plain.hh.total_seen)
    assert not bool(np.any(np.asarray(info["keep"])[-2:]))


def test_drain_racing_concurrent_submit_answers_exactly_once():
    """The shutdown lifecycle path: ``drain()`` racing concurrent
    ``submit()`` threads. Every ticket that submit() ever returned is
    answered EXACTLY once across the racing drains plus one final sweep
    — no stranded queries, no duplicates, no invented tickets."""
    cfg = small_cfg(store_depth=4)
    stream = make_stream("iot", dim=DIM)
    server = AsyncServer(
        cfg, ServerConfig(max_batch=4, max_wait_ms=0.0, topk=5,
                          two_stage=True, nprobe=4),
        key=jax.random.key(3), publish_every=2, queue_max=4)
    server.ingest(stream.next_batch(64)["embedding"],
                  stream.next_batch(64)["doc_id"])
    server.sync()

    tickets: list[int] = []
    tlock = threading.Lock()

    def submitter(seed: int):
        rng = np.random.default_rng(seed)
        for qv in stream.queries(30)["embedding"]:
            t = server.submit(qv)
            with tlock:
                tickets.append(t)
            if rng.random() < 0.2:
                time.sleep(0.0005)   # jitter the interleaving

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(3)]
    for th in threads:
        th.start()
    answers = []
    while any(th.is_alive() for th in threads):  # drain DURING shutdown
        answers += server.drain()
    for th in threads:
        th.join()
    answers += server.drain()        # final sweep: nothing left stranded

    got = sorted(a["ticket"] for a in answers)
    assert got == sorted(tickets)            # exactly once, none stranded
    assert len(got) == len(set(got)) == 90
    assert not server._pending
    server.close()
