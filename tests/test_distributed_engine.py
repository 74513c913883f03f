"""Distributed streaming-engine semantics on a forced 4-host-device CPU
mesh (subprocess, like test_distributed.py, so the main session keeps the
real single-device view):

  * data-sharded ingest == per-shard single-device replay (same stages)
  * gather-based reconciliation == the host-side oracle merge
  * distributed two-stage retrieval (replicated routing + per-shard rerank
    + global top-k merge) == single-device retrieval over the published
    snapshot — doc ids/rows exact, scores to float tolerance — including
    after heavy-hitter evictions (routing snapshot semantics)
  * cluster sharding divides per-device serving-store bytes by the model
    axis
  * the extended make_distributed_merge carries ring-buffer state
  * delta snapshot publication == full reconciliation, leaf-for-leaf
    bit-identical at every publish (including ragged tail batches and
    snapshot version numbering)
  * ragged batches: `ingest` pads with dead doc_id=-1 rows; the padded
    engine equals the per-shard single-device replay of the same padded
    sub-batches, and padding never reaches query results
"""
import subprocess
import sys
import textwrap


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
                          env={**__import__("os").environ,
                               "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_sharded_engine_matches_single_device_oracle():
    out = _run_in_4_device_subprocess("""
        from repro.configs.streaming_rag import paper_pipeline_config
        from repro.core import pipeline
        from repro.data.streams import make_stream
        from repro.engine.sharded import (ShardedEngine,
                                          reconcile_stacked_states)
        from repro.store import docstore

        D, M = 2, 2
        cfg = paper_pipeline_config(dim=32, k=32, capacity=12,
                                    update_interval=48, alpha=-1.0,
                                    store_depth=4)
        stream = make_stream("iot", dim=32)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((D, M), ("data", "model"))
        eng = ShardedEngine(cfg, mesh, jax.random.key(0),
                            reconcile_every=100)
        batches = [stream.next_batch(64) for _ in range(8)]
        for b in batches:
            eng.ingest(b["embedding"], b["doc_id"])
        snap = eng.reconcile()

        # ---- per-shard replay on the plain single-device path ----
        states = []
        for s in range(D):
            st = ShardedEngine.shard_init_state(cfg, jax.random.key(0), s, D)
            for b in batches:
                x = jnp.asarray(b["embedding"]).reshape(D, -1, 32)[s]
                ids = jnp.asarray(b["doc_id"], jnp.int32).reshape(D, -1)[s]
                st, _ = pipeline.ingest_batch(cfg, st, x, ids)
            states.append(st)

        # evictions DID happen -> the routing snapshot is post-eviction
        assert sum(int(s.hh.total_evictions) for s in states) > 0

        # sharded ingest == replay, shard by shard
        local = jax.device_get(eng.local)
        for s in range(D):
            for la, lb in zip(jax.tree.leaves(
                    jax.tree.map(lambda a: a[s], local)),
                    jax.tree.leaves(states[s])):
                if jnp.issubdtype(jnp.asarray(lb).dtype,
                                  jax.dtypes.prng_key):
                    la = np.asarray(jax.random.key_data(jnp.asarray(la)))
                    lb = np.asarray(jax.random.key_data(lb))
                la, lb = np.asarray(la), np.asarray(lb)
                if np.issubdtype(lb.dtype, np.floating):
                    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
                else:
                    np.testing.assert_array_equal(la, lb)
        print("INGEST-PARITY-OK")

        # ---- reconciliation == host oracle ----
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        oracle = reconcile_stacked_states(cfg, stacked)
        np.testing.assert_array_equal(np.asarray(snap.route_labels),
                                      np.asarray(oracle.route_labels))
        np.testing.assert_array_equal(np.asarray(snap.index.ids),
                                      np.asarray(oracle.index.ids))
        np.testing.assert_array_equal(np.asarray(snap.index.valid),
                                      np.asarray(oracle.index.valid))
        np.testing.assert_allclose(np.asarray(snap.index.vectors),
                                   np.asarray(oracle.index.vectors),
                                   rtol=1e-5, atol=1e-6)
        for name in ("ids", "stamps", "ptr"):
            np.testing.assert_array_equal(
                np.asarray(getattr(snap.store, name)),
                np.asarray(getattr(oracle.store, name)))
        # ring embeddings are pure gathers of shard values -> bit-exact
        np.testing.assert_array_equal(np.asarray(snap.store.embs),
                                      np.asarray(oracle.store.embs))
        print("RECONCILE-OK")

        # ---- distributed two-stage query == single device on the SAME
        # snapshot (isolates the retrieval path from merge float noise) ----
        host_state = states[0]._replace(
            index=jax.tree.map(jnp.asarray, jax.device_get(snap.index)),
            route_labels=jnp.asarray(np.asarray(snap.route_labels)),
            store=jax.tree.map(lambda a: jnp.asarray(np.asarray(a)),
                               jax.device_get(snap.store)))
        q = jnp.asarray(stream.queries(16)["embedding"])
        for kwargs in ({}, {"two_stage": True, "nprobe": 6}):
            got = eng.query(q, 5, **kwargs)
            want = pipeline.query(cfg, host_state, q, 5, **kwargs)
            np.testing.assert_array_equal(np.asarray(got[2]),
                                          np.asarray(want[2]))  # doc ids
            np.testing.assert_array_equal(np.asarray(got[1]),
                                          np.asarray(want[1]))  # rows
            np.testing.assert_array_equal(np.asarray(got[3]),
                                          np.asarray(want[3]))  # clusters
            np.testing.assert_allclose(np.asarray(got[0]),
                                       np.asarray(want[0]),
                                       rtol=1e-5, atol=1e-6)
        print("QUERY-PARITY-OK")

        # ---- cluster sharding divides serving-store bytes by M ----
        full = docstore.memory_bytes(cfg.store)
        per_dev = eng.store_bytes_per_device()
        assert per_dev * M == full, (per_dev, full)
        print("STORE-SHARDING-OK")
    """)
    for tag in ("INGEST-PARITY-OK", "RECONCILE-OK", "QUERY-PARITY-OK",
                "STORE-SHARDING-OK"):
        assert tag in out


def test_delta_reconcile_bit_identical_to_full():
    """Two ShardedEngines fed the identical stream — one publishing full
    rebuilds, one delta publications — must publish leaf-for-leaf
    bit-identical snapshots at every reconcile, through heavy-hitter
    evictions and a ragged final batch. Also smoke-serves the async
    runtime over the delta engine and pins its answers to
    query_snapshot on the published snapshot."""
    out = _run_in_4_device_subprocess("""
        from repro.configs.streaming_rag import paper_pipeline_config
        from repro.data.streams import make_stream
        from repro.engine.sharded import ShardedEngine
        from repro.serve.runtime import AsyncServer, ServerConfig

        D, M = 2, 2
        cfg = paper_pipeline_config(dim=32, k=32, capacity=12,
                                    update_interval=48, alpha=-1.0,
                                    store_depth=4)
        stream = make_stream("iot", dim=32)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((D, M), ("data", "model"))
        full = ShardedEngine(cfg, mesh, jax.random.key(0),
                             reconcile_every=10**9)
        delta = ShardedEngine(cfg, mesh, jax.random.key(0),
                              reconcile_every=10**9,
                              reconcile_mode="delta", delta_max_frac=1.0,
                              delta_bucket_min=8)
        sizes = [64] * 7 + [37]          # ragged tail batch
        for i, bsz in enumerate(sizes):
            b = stream.next_batch(bsz)
            for eng in (full, delta):
                eng.ingest(b["embedding"], b["doc_id"])
            sf, sd = full.reconcile(), delta.reconcile()
            assert sf.version == sd.version == i + 1
            assert sf.published_at > 0 and sd.published_at > 0
            # published_at is wall-clock (necessarily differs); device
            # leaves must be bit-identical
            for a, c in zip(jax.tree.leaves(sf._replace(published_at=0.0)),
                            jax.tree.leaves(sd._replace(published_at=0.0))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        assert len(delta._delta_fns) > 0, "delta path never exercised"
        assert int(jax.device_get(
            jax.tree.map(lambda a: a[0], full.local).hh.total_evictions
        )) >= 0
        print("DELTA-IDENTITY-OK")

        # async runtime over the delta engine: answers == query_snapshot
        scfg = ServerConfig(max_batch=8, max_wait_ms=0.0, topk=5,
                            two_stage=True, nprobe=6)
        srv = AsyncServer(cfg, scfg, engine=delta, publish_every=1,
                          queue_max=8)
        qs = stream.queries(8)["embedding"]
        tickets = [srv.submit(q) for q in qs]
        srv.ingest(stream.next_batch(64)["embedding"],
                   stream.next_batch(64)["doc_id"])
        srv.sync()
        outs = srv.drain()
        srv.close()
        assert sorted(o["ticket"] for o in outs) == sorted(tickets)
        for o in outs:
            v = o["snapshot_version"]
            assert v >= len(sizes) + 1  # published by the runtime
        snap = srv._snapshot
        want = delta.query_snapshot(snap, jnp.asarray(qs), 5,
                                    two_stage=True, nprobe=6)
        got = srv.engine.query_snapshot(snap, jnp.asarray(qs), 5,
                                        two_stage=True, nprobe=6)
        np.testing.assert_array_equal(np.asarray(want[2]),
                                      np.asarray(got[2]))
        print("ASYNC-SHARDED-OK")
    """)
    for tag in ("DELTA-IDENTITY-OK", "ASYNC-SHARDED-OK"):
        assert tag in out


def test_ragged_batch_pads_match_padded_replay():
    """A ragged global batch must not crash data-sharded ingest: the
    engine pads with dead doc_id=-1 rows, the result equals the padded
    per-shard single-device replay, and no padding reaches the store or
    query results."""
    out = _run_in_4_device_subprocess("""
        from repro.configs.streaming_rag import paper_pipeline_config
        from repro.core import pipeline
        from repro.data.streams import make_stream
        from repro.engine.sharded import ShardedEngine
        from repro.store import docstore

        D, M = 4, 1
        cfg = paper_pipeline_config(dim=32, k=32, capacity=12,
                                    update_interval=48, alpha=-1.0,
                                    store_depth=4)
        stream = make_stream("iot", dim=32)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((D, M), ("data", "model"))
        eng = ShardedEngine(cfg, mesh, jax.random.key(0),
                            reconcile_every=100)
        sizes = [64, 61, 64, 39]              # two ragged batches
        batches = [stream.next_batch(s) for s in sizes]
        for b in batches:
            eng.ingest(b["embedding"], b["doc_id"])   # must not crash
        snap = eng.reconcile()

        # oracle: replay the SAME deterministic padding per shard
        states = [ShardedEngine.shard_init_state(cfg, jax.random.key(0),
                                                 s, D) for s in range(D)]
        for b, bsz in zip(batches, sizes):
            pad = -bsz % D
            x = np.concatenate([np.asarray(b["embedding"], np.float32),
                                np.zeros((pad, 32), np.float32)])
            ids = np.concatenate([np.asarray(b["doc_id"], np.int32),
                                  np.full((pad,), -1, np.int32)])
            xs = x.reshape(D, -1, 32)
            idss = ids.reshape(D, -1)
            for s in range(D):
                states[s], _ = pipeline.ingest_batch(
                    cfg, states[s], jnp.asarray(xs[s]),
                    jnp.asarray(idss[s]))
        local = jax.device_get(eng.local)
        for s in range(D):
            for la, lb in zip(jax.tree.leaves(
                    jax.tree.map(lambda a: a[s], local)),
                    jax.tree.leaves(states[s])):
                if jnp.issubdtype(jnp.asarray(lb).dtype,
                                  jax.dtypes.prng_key):
                    la = np.asarray(jax.random.key_data(jnp.asarray(la)))
                    lb = np.asarray(jax.random.key_data(lb))
                la, lb = np.asarray(la), np.asarray(lb)
                if np.issubdtype(lb.dtype, np.floating):
                    np.testing.assert_allclose(la, lb, rtol=1e-5,
                                               atol=1e-6)
                else:
                    np.testing.assert_array_equal(la, lb)
        print("RAGGED-PARITY-OK")

        # padding is dead everywhere: the merged store has no sentinel
        # stamps for live slots and queries never surface pad rows
        ids = np.asarray(snap.store.ids)
        stamps = np.asarray(snap.store.stamps)
        assert np.all(stamps[ids >= 0] >= 0)
        q = jnp.asarray(stream.queries(8)["embedding"])
        scores, rows, doc_ids, labels = eng.query(q, 5, two_stage=True,
                                                  nprobe=6)
        doc_ids = np.asarray(doc_ids)
        assert np.all((doc_ids >= 0) | (doc_ids == -1))
        assert (doc_ids >= 0).sum() > 0
        print("RAGGED-DEAD-OK")
    """)
    for tag in ("RAGGED-PARITY-OK", "RAGGED-DEAD-OK"):
        assert tag in out


def test_distributed_merge_carries_ring_buffers():
    """make_distributed_merge (the legacy data-axis reconciliation) now
    merges the doc store exactly instead of silently dropping it."""
    out = _run_in_4_device_subprocess("""
        from repro.configs.streaming_rag import paper_pipeline_config
        from repro.core import pipeline
        from repro.data.streams import make_stream
        from repro.distributed.collectives import make_distributed_merge
        from repro.store import docstore

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4,), ("data",))
        cfg = paper_pipeline_config(dim=32, k=32, capacity=16,
                                    update_interval=64, alpha=-1.0,
                                    store_depth=4)
        stream = make_stream("iot", dim=32)
        states = []
        for shard in range(4):
            st = pipeline.init(cfg, jax.random.key(shard))
            for _ in range(3):
                b = stream.next_batch(64)
                st, _ = pipeline.ingest_batch(
                    cfg, st, jnp.asarray(b["embedding"]),
                    jnp.asarray(b["doc_id"]))
            states.append(st)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)

        merged = make_distributed_merge(cfg, mesh, ("data",))(stacked)
        want = docstore.merge_stacked(cfg.store, stacked.store)
        for i in range(4):  # every shard holds the exact global union
            np.testing.assert_array_equal(np.asarray(merged.store.ids[i]),
                                          np.asarray(want.ids))
            np.testing.assert_array_equal(np.asarray(merged.store.stamps[i]),
                                          np.asarray(want.stamps))
            np.testing.assert_array_equal(np.asarray(merged.store.ptr[i]),
                                          np.asarray(want.ptr))
            np.testing.assert_array_equal(np.asarray(merged.store.embs[i]),
                                          np.asarray(want.embs))
        assert int(docstore.size(jax.tree.map(lambda a: a[0],
                                              merged.store))) > 0
        print("MERGE-STORE-OK")
    """)
    assert "MERGE-STORE-OK" in out
