"""Hot-set serving cache invariants (the two-level cache behind
``serve.runtime.AsyncServer``).

* result cache exactness gates: a hit requires matching query bytes,
  plan bucket, snapshot version AND recorded routes — plus bounded LRU
  and precise publish invalidation (dirty-routed entries evicted, clean
  survivors re-keyed, no-dirty-info publishes clear).
* hot tier parity: a covered query served through the pinned tier
  (fused dispatcher, ``source="hotset"``) is bit-identical to the
  full-store snapshot oracle after host remap.
* end-to-end bit-identity: a cached+hot AsyncServer and an uncached one
  sharing the SAME engine answer identically across rounds and across a
  dirtying publish; pin bytes are charged in ``state_memory_bytes``.
* constant stats schemas: latency/cache/freshness stats are zero-safe
  before the first flush and after ``close()``.
* 4-device sharded precision: a delta publish dirties a cluster subset
  S; exactly the entries routed clear of S keep hitting (bit-identical),
  the S-touching ones are invalidated.
"""
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.streaming_rag import paper_pipeline_config
from repro.data.streams import make_stream
from repro.engine import Engine, stages
from repro.serve.hotset import HotSet, route_signature
from repro.serve.result_cache import ResultCache
from repro.serve.runtime import AsyncServer, ServerConfig

DIM = 32

pytestmark = pytest.mark.timeout(300)  # where pytest-timeout exists


def _cfg(**kw):
    return paper_pipeline_config(
        dim=DIM, k=kw.pop("k", 24), capacity=kw.pop("capacity", 24),
        alpha=0.1, admit_prob=1.0, update_interval=kw.pop(
            "update_interval", 64),
        store_depth=kw.pop("store_depth", 8), **kw)


# --------------------------------------------------------------- result cache
def test_result_cache_exactness_gates_and_lru():
    rc = ResultCache(2)
    routes = np.array([1, 5, -1], np.int32)
    ans = (np.ones(3, np.float32), np.arange(3, dtype=np.int32),
           np.arange(3, dtype=np.int32), np.zeros(3, np.int32))
    rc.insert(b"q0", "np4xd8", 3, routes, ans)
    assert rc.lookup(b"q0", "np4xd8", 3, routes) is ans
    # every gate misses independently: version, plan bucket, routes
    # (order matters — stage 1 emits an ORDERED route list), query bytes
    assert rc.lookup(b"q0", "np4xd8", 4, routes) is None
    assert rc.lookup(b"q0", "np2xd8", 3, routes) is None
    assert rc.lookup(b"q0", "np4xd8", 3,
                     np.array([5, 1, -1], np.int32)) is None
    assert rc.lookup(b"q1", "np4xd8", 3, routes) is None
    # bounded LRU: third distinct key evicts the oldest
    rc.insert(b"q1", "np4xd8", 3, routes, ans)
    rc.insert(b"q2", "np4xd8", 3, routes, ans)
    assert len(rc) == 2 and rc.evicted_lru == 1
    assert rc.lookup(b"q0", "np4xd8", 3, routes) is None
    s = rc.stats()
    assert s["hits"] == 1 and s["misses"] == 5 and s["entries"] == 2
    assert s["hit_rate"] == pytest.approx(1 / 6)


def test_result_cache_publish_invalidation_is_precise():
    rc = ResultCache(8)
    rc.insert(b"a", "p", 1, np.array([0, 3], np.int32), "A")
    rc.insert(b"b", "p", 1, np.array([4, 7], np.int32), "B")
    rc.insert(b"c", "p", 1, np.array([2, -1], np.int32), "C")
    rc.on_publish(2, np.array([3, 9]))     # dirties clusters {3, 9}
    # exactly the entry routed through 3 is gone; survivors re-keyed to
    # the new version and keep hitting there
    assert rc.invalidated == 1 and rc.rekeyed == 2 and len(rc) == 2
    assert rc.lookup(b"a", "p", 2, np.array([0, 3], np.int32)) is None
    assert rc.lookup(b"b", "p", 2, np.array([4, 7], np.int32)) == "B"
    assert rc.lookup(b"c", "p", 2, np.array([2, -1], np.int32)) == "C"
    # staleness: both hits survived exactly one publish
    assert rc.stats()["hit_staleness"] == pytest.approx(1.0)
    rc.on_publish(3, np.array([], np.int64))   # republish: nothing moved
    assert len(rc) == 2 and rc.rekeyed == 4 and rc.invalidated == 1
    rc.on_publish(4, None)                 # no dirty info -> clear all
    assert len(rc) == 0 and rc.cleared == 2
    assert rc.lookup(b"b", "p", 4, np.array([4, 7], np.int32)) is None


def test_result_cache_exact_peek_skips_route_verification():
    """Within one snapshot version routing is deterministic, so an entry
    verified at the pinned version answers without a route pass; a
    publish forces one verifying lookup before the fast path re-arms."""
    rc = ResultCache(4)
    routes = np.array([1, 2], np.int32)
    rc.insert(b"q", "p", 5, routes, "A")
    assert rc.peek_exact(b"q", "p", 5) == "A"
    assert rc.hits_exact == 1
    assert rc.peek_exact(b"q", "p", 6) is None     # version moved
    assert rc.misses == 0          # peek never counts a miss: the caller
    #                                falls through to the verifying lookup
    rc.on_publish(6, np.array([9]))                # clean -> rekeyed to 6
    assert rc.peek_exact(b"q", "p", 6) is None     # routes unverified at 6
    assert rc.lookup(b"q", "p", 6, routes) == "A"  # verifies routes at 6
    assert rc.peek_exact(b"q", "p", 6) == "A"      # fast path re-armed
    assert rc.stats()["hits_exact"] == 2


def test_route_signature_is_order_invariant_and_pad_inert():
    a = np.array([7, 2, 11, -1], np.int32)
    b = np.array([11, 7, 2, -1, -1, -1], np.int32)
    assert route_signature(a) == route_signature(b) >= 0
    assert route_signature(np.array([-1, -1], np.int32)) == -1
    assert route_signature(a) != route_signature(np.array([7, 2], np.int32))


# ------------------------------------------------------------ hot tier parity
def test_hot_tier_serve_is_bit_identical_to_snapshot_oracle():
    cfg = _cfg(k=16, capacity=16, store_depth=4, update_interval=32)
    eng = Engine(cfg, jax.random.key(1))
    stream = make_stream("iot", dim=DIM)
    for _ in range(6):
        b = stream.next_batch(32)
        eng.ingest(b["embedding"], b["doc_id"])
    snap = eng.publish()

    hs = HotSet(cfg, max_batch=8, pin_budget_bytes=1 << 20, capacity=16,
                refresh_every=1, min_count=1)
    q = np.asarray(stream.queries(8)["embedding"], np.float32)
    routes = np.asarray(stages.route(cfg.index, snap.index,
                                     snap.route_labels, jnp.asarray(q), 4))
    hs.observe(routes)
    hs.sync(snap)
    assert hs.active and hs.pinned_bytes > 0
    cov = hs.covered(routes)
    # budget >> store: every routed cluster of every observed query pins
    assert cov.all()

    out = hs.serve(snap, jnp.asarray(q), 5, 4, cfg.store_depth,
                   cfg.clus.use_pallas)
    scores = np.asarray(out[0])
    doc_ids = np.asarray(out[2])
    rows, clusters = hs.remap(np.asarray(out[1]), np.asarray(out[3]))
    want = eng.query_snapshot(snap, q, 5, two_stage=True, nprobe=4)
    np.testing.assert_array_equal(scores, np.asarray(want[0]))
    np.testing.assert_array_equal(rows, np.asarray(want[1]))
    np.testing.assert_array_equal(doc_ids, np.asarray(want[2]))
    np.testing.assert_array_equal(clusters, np.asarray(want[3]))


# ----------------------------------------------------------------- end to end
def test_cached_server_bit_identical_to_uncached_across_publishes():
    """A cached+hot server and an uncached one over the SAME engine give
    identical answers round after round, including straight through a
    dirtying publish — and the cache actually worked (hits, hot serving,
    tier rebuilds, precise invalidation all observed)."""
    cfg = _cfg()
    stream = make_stream("iot", dim=DIM)
    eng = Engine(cfg, jax.random.key(0))
    srv = AsyncServer(
        cfg, ServerConfig(max_batch=8, max_wait_ms=0.0, topk=5,
                          two_stage=True, nprobe=4, cache_entries=64,
                          hotset=True, pin_budget_mb=1.0, hotset_refresh=2,
                          hotset_min_count=1),
        engine=eng, publish_every=1)
    srv_u = AsyncServer(
        cfg, ServerConfig(max_batch=8, max_wait_ms=0.0, topk=5,
                          two_stage=True, nprobe=4),
        engine=eng, publish_every=10**9)
    for _ in range(4):
        b = stream.next_batch(32)
        srv.ingest(b["embedding"], b["doc_id"])
    srv.sync()
    srv_u.sync()   # both pin snapshots of identical engine content

    pool = np.asarray(stream.queries(12)["embedding"], np.float32)
    rng = np.random.default_rng(3)
    for rnd in range(6):
        if rnd == 3:   # dirtying publish mid-run; re-pin both servers
            b = stream.next_batch(32)
            srv.ingest(b["embedding"], b["doc_id"])
            srv.sync()
            srv_u.sync()
        qs = pool[rng.integers(0, len(pool), 8)]
        tc = [srv.submit(qv) for qv in qs]
        tu = [srv_u.submit(qv) for qv in qs]
        out_c = {o["ticket"]: o for o in srv.flush()}
        out_u = {o["ticket"]: o for o in srv_u.flush()}
        assert len(out_c) == len(out_u) == 8
        for a, b_ in zip(tc, tu):
            np.testing.assert_array_equal(out_c[a]["scores"],
                                          out_u[b_]["scores"])
            np.testing.assert_array_equal(out_c[a]["doc_ids"],
                                          out_u[b_]["doc_ids"])
            np.testing.assert_array_equal(out_c[a]["clusters"],
                                          out_u[b_]["clusters"])

    cs = srv.cache_stats()
    assert cs["enabled"]
    assert cs["hits"] > 0 and 0.0 < cs["hit_rate"] < 1.0
    assert cs["hot_served"] > 0 and cs["tier_rebuilds"] > 0
    # the mid-run publish actually exercised invalidation
    assert cs["invalidated"] + cs["cleared"] > 0
    assert cs["hit_staleness"] >= 0.0
    # pin accounting: resident tier bytes charged on top of engine state
    assert cs["pinned_bytes"] > 0
    assert srv.state_memory_bytes() == \
        eng.state_memory_bytes() + cs["pinned_bytes"]
    ls = srv.latency_stats()
    assert ls["pinned_bytes"] == cs["pinned_bytes"]
    assert ls["cache_hit_rate"] == pytest.approx(cs["hit_rate"])
    srv.close()
    srv_u.close()


# -------------------------------------------------------------- stats schemas
def test_stats_schemas_constant_before_first_flush_and_after_close():
    cfg = _cfg()
    srv = AsyncServer(
        cfg, ServerConfig(max_batch=4, max_wait_ms=0.0, topk=5,
                          two_stage=True, nprobe=4, cache_entries=8,
                          hotset=True, pin_budget_mb=0.25),
        key=jax.random.key(2))
    cache_keys = {"enabled", "hits", "misses", "hit_rate", "entries",
                  "invalidated", "cleared", "rekeyed", "evicted_lru",
                  "hit_staleness", "pinned_bytes", "pinned_clusters",
                  "hot_served", "tier_rebuilds"}

    def check(server):
        ls = server.latency_stats()
        assert ls["cache_hit_rate"] == 0.0 and ls["pinned_bytes"] == 0
        assert ls["batches"] == 0 and ls["p50_ms"] == 0.0
        cs = server.cache_stats()
        assert set(cs) == cache_keys and cs["enabled"]
        assert cs["hits"] == cs["misses"] == 0 and cs["hit_rate"] == 0.0
        fr = server.freshness_stats()
        assert {"snapshot_version", "published_at", "snapshot_age_s",
                "docs_enqueued", "docs_ingested", "docs_published",
                "lag_docs"} <= set(fr)
        assert fr["lag_docs"] == 0

    check(srv)           # before any flush or publish-cadence tick
    srv.close()
    check(srv)           # after close: same schema, still zero-safe
    # caching disabled -> same cache_stats schema, enabled=False
    plain = AsyncServer(
        cfg, ServerConfig(max_batch=4, max_wait_ms=0.0, topk=5,
                          two_stage=True, nprobe=4),
        key=jax.random.key(2))
    cs = plain.cache_stats()
    assert set(cs) == cache_keys and not cs["enabled"]
    plain.close()


def test_cache_config_guardrails():
    cfg = _cfg()
    # caching requires two_stage (answers must record routed clusters)
    with pytest.raises(AssertionError, match="two_stage"):
        AsyncServer(cfg, ServerConfig(max_batch=4, topk=5,
                                      cache_entries=8),
                    key=jax.random.key(0))
    with pytest.raises(AssertionError, match="two_stage"):
        AsyncServer(cfg, ServerConfig(max_batch=4, topk=5, hotset=True),
                    key=jax.random.key(0))


# ------------------------------------------------------------------- sharded
def _run_in_4_device_subprocess(body: str):
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np
        import jax, jax.numpy as jnp
    """) + textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                          text=True, timeout=600,
                          env={**__import__("os").environ,
                               "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_sharded_delta_publish_invalidates_precisely_4dev():
    """4-device ShardedEngine, delta reconcile: a small publish dirties a
    cluster subset S. Entries routed clear of S keep serving — counted as
    hits AND bit-identical to the fresh snapshot oracle — while exactly
    the S-touching entries are invalidated."""
    out = _run_in_4_device_subprocess("""
        from repro.configs.streaming_rag import paper_pipeline_config
        from repro.data.streams import make_stream
        from repro.engine import stages
        from repro.engine.sharded import ShardedEngine
        from repro.serve.runtime import AsyncServer, ServerConfig

        cfg = paper_pipeline_config(dim=32, k=24, capacity=24, alpha=0.1,
                                    admit_prob=1.0, update_interval=10**9,
                                    store_depth=4)
        stream = make_stream("iot", dim=32)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        eng = ShardedEngine(cfg, mesh, jax.random.key(0),
                            reconcile_every=10**9, reconcile_mode="delta")
        srv = AsyncServer(cfg, ServerConfig(max_batch=8, max_wait_ms=0.0,
                                            topk=5, two_stage=True,
                                            nprobe=2, cache_entries=64),
                          engine=eng, publish_every=10**9)
        for _ in range(6):
            b = stream.next_batch(64)
            srv.ingest(b["embedding"], b["doc_id"])
        srv.sync()
        pool = np.asarray(stream.queries(16)["embedding"], np.float32)

        def ask(qs):
            ts = [srv.submit(qv) for qv in qs]
            outs = []
            while len(outs) < len(ts):
                outs += srv.flush()
            return {o["ticket"]: o for o in outs}, ts

        a1, t1 = ask(pool)
        cache = srv._result_cache
        assert len(cache) == 16, len(cache)
        hits0 = cache.hits
        snap_old = srv._snapshot
        old_routes = np.asarray(stages.route(
            cfg.index, snap_old.index, snap_old.route_labels,
            jnp.asarray(pool), 2))

        # small targeted ingests dirty only a cluster subset; a tiny
        # batch can be fully prefiltered (republish, nothing moved), so
        # keep going until the accumulated dirty set splits the pool:
        # some entries routed through it, some routed clear of it
        def hits_route(dirty_set):
            if not dirty_set.size:
                return np.zeros((len(pool),), bool)
            return np.array([np.isin(
                old_routes[i][old_routes[i] >= 0], dirty_set).any()
                for i in range(len(pool))])

        dirty = np.array([], np.int32)
        for _ in range(20):
            b = stream.next_batch(8)
            srv.ingest(b["embedding"], b["doc_id"])
            srv.sync()
            info = eng.last_publish_info
            assert info["mode"] in ("delta", "republish"), info
            dirty = np.union1d(dirty, np.asarray(info["dirty"]).ravel())
            touched = hits_route(dirty)
            if touched.any() and not touched.all():
                break
        assert 0 < dirty.size < cfg.clus.num_clusters, dirty

        a2, t2 = ask(pool)
        snap = srv._snapshot
        new_routes = np.asarray(stages.route(
            cfg.index, snap.index, snap.route_labels,
            jnp.asarray(pool), 2))
        clean = np.array([
            np.array_equal(old_routes[i], new_routes[i]) and
            not np.isin(old_routes[i][old_routes[i] >= 0], dirty).any()
            for i in range(len(pool))])
        assert clean.any(), "no entry routed clear of the dirty set"
        assert not clean.all(), "no entry touched the dirty set"
        # precision: EXACTLY the clean-routed entries hit...
        assert cache.hits - hits0 == int(clean.sum()), (
            cache.hits - hits0, int(clean.sum()))
        assert cache.invalidated > 0 and cache.rekeyed > 0
        # ...their served answers are the recorded ones...
        for i, (to, tn) in enumerate(zip(t1, t2)):
            if clean[i]:
                np.testing.assert_array_equal(a1[to]["doc_ids"],
                                              a2[tn]["doc_ids"])
                np.testing.assert_array_equal(a1[to]["scores"],
                                              a2[tn]["scores"])
        # ...and EVERY answer (hit or recompute) matches the fresh oracle
        for i, tn in enumerate(t2):
            want = eng.query_snapshot(snap, pool[i][None], 5,
                                      two_stage=True, nprobe=2)
            np.testing.assert_array_equal(a2[tn]["doc_ids"],
                                          np.asarray(want[2][0]))
            np.testing.assert_array_equal(a2[tn]["scores"],
                                          np.asarray(want[0][0]))
        srv.close()
        print("clean", int(clean.sum()), "dirty_clusters", dirty.size,
              "invalidated", cache.invalidated)
        print("SHARDED-CACHE-OK")
    """)
    assert "SHARDED-CACHE-OK" in out
