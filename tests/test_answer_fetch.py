"""The flush's answer fetch: a batch's transfers overlap.

``serve.runtime.fetch_answers`` issues the device-to-host transfers of a
batch's device answers (float32 scores, int32 ids and labels) at once and
only then reads them. These tests pin:

* bit-identity, dtypes included, of flush answers against
  ``Engine.query_snapshot`` on the same snapshot — int8 and float32
  stores, batch sizes 1, 7 and 32, a snapshot with dead slots (the
  dead-entry score sentinel and -1 ids), the prototype-only path and the
  cached path; NaN, -inf and -0.0 bit patterns through the fetch itself;
* host arrays (the shed answer, cached hits, host-only engines) pass
  through untouched and count no transfer;
* the ``flush.fetch`` span's ``transfers`` arg, and that an uncached
  flush starts all three transfers before it reads any of them.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs.streaming_rag import paper_pipeline_config
from repro.data.streams import make_stream
from repro.kernels.common import NEG_INF
from repro.engine.engine import ServingSnapshot
from repro.serve.runtime import AsyncServer, ServerConfig, fetch_answers

DIM = 32
TOPK = 5
NPROBE = 4

pytestmark = pytest.mark.timeout(300)  # where pytest-timeout exists


@pytest.fixture(autouse=True)
def _fresh_obs():
    was = obs.enabled()
    obs.disable()
    yield
    obs.disable()
    if was:
        obs.enable()


def _cfg(store_dtype):
    # depth-8 rings fed 8 docs: most slots stay dead, so half the queries'
    # routed candidates run short of k and their answers carry dead entries
    return paper_pipeline_config(dim=DIM, k=24, capacity=24, alpha=-1.0,
                                 admit_prob=1.0, update_interval=8,
                                 store_depth=8, store_dtype=store_dtype)


def _server(store_dtype, **scfg):
    cfg = _cfg(store_dtype)
    stream = make_stream("iot", dim=DIM)
    srv = AsyncServer(
        cfg, ServerConfig(**{"max_batch": 32, "max_wait_ms": 0.0,
                             "topk": TOPK, "nprobe": NPROBE,
                             "two_stage": True, **scfg}),
        key=jax.random.key(0), publish_every=1)
    b = stream.next_batch(8)
    srv.ingest(b["embedding"], b["doc_id"])
    srv.sync()
    return srv, np.asarray(stream.queries(32)["embedding"], np.float32)


@pytest.fixture(scope="module", params=["int8", "fp32"])
def served(request):
    srv, pool = _server(request.param)
    yield srv, pool
    srv.close()


def _flush(srv, qs):
    tickets = [srv.submit(q) for q in qs]
    by_ticket = {o["ticket"]: o for o in srv.flush()}
    assert sorted(by_ticket) == tickets
    return [by_ticket[t] for t in tickets]


def _assert_identical(answers, want):
    scores, _, ids, labels = (np.asarray(a) for a in want)
    for i, o in enumerate(answers):
        for key, ref in (("scores", scores), ("doc_ids", ids),
                         ("clusters", labels)):
            got = o[key]
            assert got.dtype == ref.dtype, key
            np.testing.assert_array_equal(got.view(np.uint32),
                                          ref[i].view(np.uint32))


@pytest.mark.parametrize("Q", [1, 7, 32])
def test_flush_bit_identical_to_query_snapshot(served, Q):
    srv, pool = served
    qs = pool[:Q]
    answers = _flush(srv, qs)
    want = srv.engine.query_snapshot(srv._snapshot, qs, TOPK,
                                     two_stage=True, nprobe=NPROBE)
    _assert_identical(answers, want)
    assert answers[0]["scores"].dtype == np.float32
    assert answers[0]["doc_ids"].dtype == np.int32


def test_dead_slots_reach_the_answers(served):
    """The snapshot's dead slots reach the answers: the kernels' dead
    score (NEG_INF) beside -1 ids, bit-identical to the engine's."""
    srv, pool = served
    answers = _flush(srv, pool)
    scores = np.stack([o["scores"] for o in answers])
    ids = np.stack([o["doc_ids"] for o in answers])
    dead = ids == -1
    assert dead.any() and not dead.all()
    assert (scores[dead] == NEG_INF).all() and (scores[~dead] > -1).all()
    _assert_identical(answers, srv.engine.query_snapshot(
        srv._snapshot, pool, TOPK, two_stage=True, nprobe=NPROBE))


def test_prototype_only_flush_bit_identical():
    srv, pool = _server("int8", two_stage=False)
    try:
        qs = pool[:7]
        answers = _flush(srv, qs)
        _assert_identical(answers, srv.engine.query_snapshot(
            srv._snapshot, qs, TOPK, two_stage=False))
    finally:
        srv.close()


def test_sync_server_flush_bit_identical_to_live_query():
    """After ``sync()`` the published snapshot equals the live state, so
    a flush answers bit-identically to the engine's live ``query``."""
    srv, pool = _server("fp32")
    try:
        qs = pool[:7]
        _assert_identical(_flush(srv, qs), srv.engine.query(
            qs, TOPK, two_stage=True, nprobe=NPROBE))
    finally:
        srv.close()


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_fetch_is_exact_for_every_bit_pattern(dtype):
    """NaN payloads, -0.0, infinities and denormals survive the fetch."""
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2**32, (16, TOPK), dtype=np.uint64)
    bits[:4, 0] = [0x7F800001, 0xFFC00000, 0x80000000, 0x00000001]
    host = bits.astype(np.uint32).view(dtype)
    ids = np.arange(16 * TOPK, dtype=np.int32).reshape(16, TOPK)
    got, transfers = fetch_answers((jnp.asarray(host), jnp.asarray(ids)))
    assert transfers == 2
    assert got[0].dtype == dtype and got[1].dtype == np.int32
    np.testing.assert_array_equal(got[0].view(np.uint32),
                                  host.view(np.uint32))
    np.testing.assert_array_equal(got[1], ids)


def test_host_arrays_pass_through_untouched():
    k = TOPK
    shed = (np.full((3, k), -np.inf, np.float32),
            np.full((3, k), -1, np.int32), np.full((3, k), -1, np.int32))
    got, transfers = fetch_answers(shed)
    assert transfers == 0
    assert all(g is s for g, s in zip(got, shed))


def _spans(name):
    return [e for e in obs.tracer().events() if e["name"] == name]


def test_uncached_flush_overlaps_its_transfers(served, monkeypatch):
    """The flush starts the transfers of scores, ids and labels (never
    rows) before it waits on the first of them."""
    srv, pool = served
    _flush(srv, pool[:3])           # compiled before counting
    obs.enable(metrics=False, trace=True)
    array_type = type(jnp.zeros(1))
    real_copy, real_read = array_type.copy_to_host_async, np.asarray
    log = []

    def copy(a):
        log.append(("copy", id(a), a.shape))
        return real_copy(a)

    def read(a, *args, **kw):
        if isinstance(a, jax.Array):
            log.append(("read", id(a), a.shape))
        return real_read(a, *args, **kw)

    monkeypatch.setattr(array_type, "copy_to_host_async", copy)
    monkeypatch.setattr(np, "asarray", read)
    _flush(srv, pool[:3])
    monkeypatch.undo()
    assert [op for op, _, _ in log] == ["copy"] * 3 + ["read"] * 3
    assert [i for _, i, _ in log[:3]] == [i for _, i, _ in log[3:]]
    assert all(shape == (3, TOPK) for _, _, shape in log)
    (fetch,) = _spans("flush.fetch")
    assert fetch["args"]["transfers"] == 3


class _HostEngine:
    """An engine whose answers are made on the host: nothing to
    transfer."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.version = 0

    def publish(self):
        self.version += 1
        return ServingSnapshot(None, None, None, version=self.version)

    def query_snapshot(self, snap, q, k, *, two_stage, nprobe, plan=None):
        b = q.shape[0]
        ids = np.tile(np.arange(k, dtype=np.int32), (b, 1))
        return np.zeros((b, k), np.float32), ids, ids, ids


def test_host_answers_count_no_transfer():
    cfg = _cfg("int8")
    srv = AsyncServer(cfg, ServerConfig(max_batch=4, topk=TOPK),
                      engine=_HostEngine(cfg))
    try:
        obs.enable(metrics=False, trace=True)
        srv.submit(np.zeros(DIM, np.float32))
        (o,) = srv.flush()
        assert o["doc_ids"].tolist() == list(range(TOPK))
        (fetch,) = _spans("flush.fetch")
        assert fetch["args"]["transfers"] == 0
    finally:
        srv.close()


def test_shed_flush_answers_host_sentinels():
    """A shed flush never touches the device: its sentinel answers pass
    through as host arrays, and it opens no fetch."""
    srv, pool = _server("int8", max_batch=4, adaptive=True,
                        max_queue_depth=1, low_queue_depth=0)
    try:
        assert srv.plan_space.ladder[-1].shed
        for q in pool[:20]:
            srv.submit(q)
        obs.enable(metrics=False, trace=True)
        out = []
        while not out or not out[-1]["shed"]:
            out = srv.flush()
        for o in out:
            assert o["scores"].dtype == np.float32
            assert o["doc_ids"].dtype == np.int32
            assert np.isneginf(o["scores"]).all()
            assert (o["doc_ids"] == -1).all() and (o["clusters"] == -1).all()
        fetches = _spans("flush.fetch")
        assert len(fetches) == len(_spans("flush")) - 1
        assert all(f["args"]["transfers"] == 3 for f in fetches)
        srv.drain()
    finally:
        srv.close()


def test_cached_flush_bit_identical_and_counts_sub_batches():
    """The cached path fetches each served sub-batch (rows too) through
    the same helper; an all-hit flush fetches nothing."""
    srv, pool = _server("fp32", cache_entries=64)
    try:
        qs = pool[:7]
        obs.enable(metrics=False, trace=True)
        first = _flush(srv, qs)
        again = _flush(srv, qs)
        want = srv.engine.query_snapshot(srv._snapshot, qs, TOPK,
                                         two_stage=True, nprobe=NPROBE)
        _assert_identical(first, want)
        _assert_identical(again, want)
        assert [f["args"]["transfers"] for f in _spans("flush.fetch")] \
            == [4, 0]
        assert srv.cache_stats()["hits"] == len(qs)
    finally:
        srv.close()
