"""Engine-layer invariants: the stage decomposition is behavior-preserving
(single-device outputs bit-identical through the public pipeline API), the
stage functions compose to the fused step, and full PipelineState
checkpoints round-trip to identical query results."""
import tempfile

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import clustering, heavy_hitter, pipeline, prefilter
from repro.data.streams import make_stream
from repro.engine import Engine, stages
from repro.engine.engine import ingest_impl, snapshot_query_impl
from repro.kernels.common import l2_normalize
from repro.serve.runtime import AsyncServer, ServerConfig
from repro.train.checkpoint import CheckpointManager

DIM = 32


def small_cfg(**kw):
    return pipeline.PipelineConfig(
        pre=prefilter.PrefilterConfig(num_vectors=3, dim=DIM, alpha=0.0,
                                      basis="fixed"),
        clus=clustering.ClusterConfig(num_clusters=16, dim=DIM),
        hh=heavy_hitter.HHConfig(capacity=8, admit_prob=0.5),
        update_interval=kw.pop("update_interval", 64),
        **kw)


def _ingest_n(cfg, state, batches):
    for b in batches:
        state, _ = pipeline.ingest_batch(
            cfg, state, jnp.asarray(b["embedding"]),
            jnp.asarray(b["doc_id"]))
    return state


def _leaves_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if jnp.issubdtype(la.dtype, jax.dtypes.prng_key):
            la, lb = jax.random.key_data(la), jax.random.key_data(lb)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_engine_object_matches_pipeline_api_bitwise():
    """Engine.ingest/query and the pipeline entry points are the same
    implementation — states and query outputs must agree bit-for-bit."""
    cfg = small_cfg(store_depth=4)
    s = make_stream("iot", dim=DIM)
    batches = [s.next_batch(32) for _ in range(5)]

    state = _ingest_n(cfg, pipeline.init(cfg, jax.random.key(0)), batches)
    eng = Engine(cfg, jax.random.key(0))
    for b in batches:
        eng.ingest(b["embedding"], b["doc_id"])
    _leaves_equal(state, eng.state)

    q = jnp.asarray(s.queries(6)["embedding"])
    for kwargs in ({}, {"two_stage": True, "nprobe": 4}):
        out_p = pipeline.query(cfg, state, q, 5, **kwargs)
        out_e = eng.query(q, 5, **kwargs)
        for a, b in zip(out_p, out_e):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unjitted_stage_composition_equals_jitted_pipeline():
    """ingest_impl and snapshot_query_impl, run un-jitted (the raw stage
    compositions), produce the same results as the jit-compiled public
    wrappers."""
    cfg = small_cfg(store_depth=4)
    s = make_stream("iot", dim=DIM)
    batches = [s.next_batch(32) for _ in range(3)]

    s_jit = pipeline.init(cfg, jax.random.key(1))
    s_raw = pipeline.init(cfg, jax.random.key(1))
    for b in batches:
        x = jnp.asarray(b["embedding"])
        ids = jnp.asarray(b["doc_id"])
        s_jit, _ = pipeline.ingest_batch(cfg, s_jit, x, ids)
        s_raw, _ = ingest_impl(cfg, s_raw, x, ids)
    for la, lb in zip(jax.tree.leaves(s_jit), jax.tree.leaves(s_raw)):
        if jnp.issubdtype(la.dtype, jax.dtypes.prng_key):
            la, lb = jax.random.key_data(la), jax.random.key_data(lb)
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6, atol=1e-7)

    q = jnp.asarray(s.queries(4)["embedding"])
    out_j = pipeline.query(cfg, s_jit, q, 5, two_stage=True, nprobe=4)
    with jax.disable_jit():
        out_r = snapshot_query_impl(cfg, s_raw.index, s_raw.route_labels,
                                    s_raw.store, q, 5, two_stage=True,
                                    nprobe=4)
    np.testing.assert_array_equal(np.asarray(out_j[2]), np.asarray(out_r[2]))
    np.testing.assert_allclose(np.asarray(out_j[0]), np.asarray(out_r[0]),
                               rtol=1e-6, atol=1e-7)


def test_route_and_rerank_stages_compose_to_two_stage_query():
    """pipeline.query(two_stage=True) == route -> rerank -> decode, run
    stage by stage — pins the decomposition the sharded path relies on."""
    cfg = small_cfg(store_depth=4, update_interval=32)
    s = make_stream("iot", dim=DIM)
    state = _ingest_n(cfg, pipeline.init(cfg, jax.random.key(2)),
                      [s.next_batch(32) for _ in range(4)])
    q = jnp.asarray(s.queries(6)["embedding"])
    k, nprobe = 5, 4

    routes = stages.route(cfg.index, state.index, state.route_labels, q,
                          nprobe)
    qn = l2_normalize(q)
    sc, pos = stages.rerank(state.store, qn, routes, k, cfg.clus.use_pallas)
    staged = stages.decode_rerank(state.store.ids, routes, sc, pos,
                                  cfg.store_depth, nprobe)
    fused = pipeline.query(cfg, state, q, k, two_stage=True, nprobe=nprobe)
    # rows, doc ids and clusters exact; scores to 1e-6, because the fused
    # query and the staged stages are different programs whose fp32 dots
    # XLA may fuse and accumulate differently (a 1-ulp difference)
    for a, b in zip(fused[1:], staged[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(staged[0]),
                               rtol=1e-6, atol=1e-6)


def test_upsert_snapshot_invariants_after_refresh():
    """After an index refresh the routing snapshot mirrors the live
    counter, and index rows hold the (normalized) centroids of their
    snapshot labels."""
    cfg = small_cfg(store_depth=2, update_interval=16)
    s = make_stream("iot", dim=DIM)
    state = _ingest_n(cfg, pipeline.init(cfg, jax.random.key(3)),
                      [s.next_batch(32) for _ in range(4)])
    assert int(state.upserts) > 0
    live = np.asarray(heavy_hitter.active_mask(state.hh))
    rl = np.asarray(state.route_labels)
    np.testing.assert_array_equal(rl >= 0, live)
    np.testing.assert_array_equal(
        rl[live], np.asarray(state.hh.labels)[live])
    want = np.asarray(l2_normalize(
        state.clus.centroids[np.maximum(rl, 0)]))
    got = np.asarray(state.index.vectors)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-6)


def test_checkpoint_roundtrip_preserves_query_results():
    """Full PipelineState (doc store + route-label snapshot + typed rng
    key included) through CheckpointManager save/restore -> identical
    proto-only AND two-stage query results."""
    cfg = small_cfg(store_depth=4, update_interval=32)
    s = make_stream("iot", dim=DIM)
    state = _ingest_n(cfg, pipeline.init(cfg, jax.random.key(4)),
                      [s.next_batch(32) for _ in range(4)])
    q = jnp.asarray(s.queries(6)["embedding"])

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(7, state, metadata={"arrivals": int(state.arrivals)})
        restored, meta = mgr.restore(jax.eval_shape(lambda: state))
    assert meta["step"] == 7 and meta["arrivals"] == int(state.arrivals)
    _leaves_equal(state, restored)

    restored = jax.tree.map(jnp.asarray, restored)
    for kwargs in ({}, {"two_stage": True, "nprobe": 4}):
        out_a = pipeline.query(cfg, state, q, 5, **kwargs)
        out_b = pipeline.query(cfg, restored, q, 5, **kwargs)
        for a, b in zip(out_a, out_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # and ingest continues identically from the restored state
    nb = s.next_batch(32)
    x, ids = jnp.asarray(nb["embedding"]), jnp.asarray(nb["doc_id"])
    s1, _ = pipeline.ingest_batch(cfg, state, x, ids)
    s2, _ = pipeline.ingest_batch(cfg, restored, x, ids)
    _leaves_equal(s1, s2)


def test_server_runs_on_explicit_engine():
    """AsyncServer accepts a pre-built engine (the protocol the sharded
    engine plugs into) and serves two-stage answers from it."""
    cfg = small_cfg(store_depth=4, update_interval=32)
    s = make_stream("iot", dim=DIM)
    eng = Engine(cfg, jax.random.key(5))
    server = AsyncServer(cfg, ServerConfig(max_batch=4, max_wait_ms=0.0,
                                           topk=5, two_stage=True,
                                           nprobe=4),
                         engine=eng)
    # before any batch was answered, stats must not crash (launch/serve.py
    # reports through latency_stats for exactly this reason)
    empty = server.latency_stats()
    assert empty == {"batches": 0, "mean_ms": 0.0, "p50_ms": 0.0,
                     "p90_ms": 0.0, "p99_ms": 0.0, "window": 0,
                     "answer_p50_ms": 0.0, "answer_p90_ms": 0.0,
                     "answer_p99_ms": 0.0, "answer_window": 0,
                     # serving-cache keys are part of the constant schema,
                     # zero-safe when caching is disabled
                     "cache_hit_rate": 0.0, "pinned_bytes": 0}
    answered = []
    for _ in range(4):
        b = s.next_batch(32)
        for qv in s.queries(2)["embedding"]:
            server.submit(qv)
        answered += server.serve_round(b)
        server.sync()   # the next round's queries see this round's docs
    answered += server.flush()
    server.close()
    assert len(answered) == 8
    assert server.engine is eng
    stats = server.latency_stats()
    assert stats["batches"] > 0 and stats["p99_ms"] >= stats["p50_ms"] >= 0
    for a in answered:
        assert a["scores"].shape == (5,)
