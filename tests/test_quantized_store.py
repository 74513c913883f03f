"""Quantized tiered store: int8 ring buffers with per-slot fp32 scales.

Covers the whole vertical slice: the shared quantization convention
(``store.quant``, also re-exported by ``distributed.compression``),
quantize-on-admit ring writes, the int8 dequant-rerank kernel vs a
dequantized jnp oracle, exact merges of quantized stores, dtype-aware
memory accounting + budget splits, checkpoint round-trips, and (in a
forced-4-device subprocess) delta reconciliation bit-identity and
distributed query parity on quantized leaves.
"""
import dataclasses
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import clustering, heavy_hitter, pipeline, prefilter
from repro.data.streams import make_stream
from repro.kernels.rerank.ref import rerank_topk_ref
from repro.kernels.rerank.rerank import rerank_topk_pallas
from repro.store import docstore, quant

RNG = np.random.default_rng(7)


def small_cfg(**kw):
    d = kw.pop("dim", 32)
    return pipeline.PipelineConfig(
        pre=prefilter.PrefilterConfig(num_vectors=3, dim=d, alpha=0.0,
                                      basis="fixed"),
        clus=clustering.ClusterConfig(num_clusters=16, dim=d),
        hh=heavy_hitter.HHConfig(capacity=8, admit_prob=0.5),
        update_interval=kw.pop("update_interval", 32),
        store_depth=kw.pop("store_depth", 4),
        store_dtype=kw.pop("store_dtype", "int8"),
        **kw)


# ------------------------------------------------------------------ quant
def test_quantize_roundtrip_error_bound():
    """|x - dequant(quantize(x))| <= scale/2 elementwise, per-row and
    per-tensor; scales are max|x|/127 and q never exceeds [-127, 127]."""
    x = jnp.asarray(RNG.normal(size=(64, 48)) * RNG.uniform(0.01, 3.0),
                    jnp.float32)
    for axis in (None, -1):
        q, s = quant.quantize_int8(x, axis=axis)
        assert q.dtype == jnp.int8
        assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= 127
        s_b = s if axis is None else s[:, None]
        xhat = quant.dequantize_int8(q, s_b)
        err = np.abs(np.asarray(x) - np.asarray(xhat))
        np.testing.assert_array_less(err, np.asarray(s_b) * 0.5 + 1e-7
                                     + np.zeros_like(err))
    # scale rule
    np.testing.assert_allclose(
        np.asarray(quant.int8_scale(x, axis=-1)),
        np.maximum(np.abs(np.asarray(x)).max(axis=-1), 1e-12) / 127.0,
        rtol=1e-6)
    # all-zero input quantizes to zeros (no division blowup)
    q0, s0 = quant.quantize_int8(jnp.zeros((4, 8)), axis=-1)
    assert (np.asarray(q0) == 0).all() and (np.asarray(s0) > 0).all()


def test_compression_rebased_on_shared_convention():
    """distributed.compression's int8 helpers ARE the shared store.quant
    functions — one rounding/scale convention everywhere."""
    from repro.distributed import compression

    assert compression.quantize_int8 is quant.quantize_int8
    assert compression.dequantize_int8 is quant.dequantize_int8
    x = jnp.asarray(RNG.normal(size=(33, 17)), jnp.float32)
    q, s = compression.quantize_int8(x)   # per-tensor (legacy call shape)
    assert q.shape == x.shape and np.ndim(s) == 0
    np.testing.assert_allclose(
        np.asarray(compression.dequantize_int8(q, s)), np.asarray(x),
        atol=float(s) * 0.5 + 1e-7)


# --------------------------------------------------------------- ring write
def test_int8_ring_write_matches_quantized_sequential_oracle():
    """Quantize-on-admit: the int8 ring equals a per-arrival oracle that
    quantizes each admitted row with the shared convention."""
    cfg = docstore.StoreConfig(num_clusters=4, depth=3, dim=8,
                               normalize=False, store_dtype="int8")
    B = 14
    x = jnp.asarray(RNG.normal(size=(B, 8)), jnp.float32)
    labels = jnp.asarray(RNG.integers(0, 4, B), jnp.int32)
    admit = jnp.asarray(RNG.random(B) > 0.3)
    ids = jnp.arange(B, dtype=jnp.int32)

    got = docstore.add_batch(cfg, docstore.init(cfg), x, labels, admit, ids,
                             ids)
    qx, sx = quant.quantize_int8(x, axis=-1)  # same jnp rounding as the store

    embs = np.zeros((4, 3, 8), np.int8)
    scales = np.zeros((4, 3), np.float32)
    sids = -np.ones((4, 3), np.int32)
    ptr = np.zeros(4, np.int32)
    for i in range(B):
        if not bool(admit[i]):
            continue
        l, s = int(labels[i]), int(ptr[int(labels[i])]) % 3
        embs[l, s] = np.asarray(qx[i])
        scales[l, s] = float(sx[i])
        sids[l, s] = i
        ptr[l] += 1
    assert got.embs.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got.embs), embs)
    np.testing.assert_allclose(np.asarray(got.scales), scales, rtol=1e-7)
    np.testing.assert_array_equal(np.asarray(got.ids), sids)
    # dequantized store approximates the raw rows within the quant bound
    deq = np.asarray(docstore.dequantize(cfg, got))
    for i in range(B):
        if not bool(admit[i]):
            continue
        l = int(labels[i])
        match = (sids[l] == i)
        if match.any():
            s = int(np.nonzero(match)[0][0])
            assert np.abs(deq[l, s] - np.asarray(x[i])).max() \
                <= scales[l, s] * 0.5 + 1e-7


def test_int8_split_batches_equal_one_batch():
    cfg = docstore.StoreConfig(num_clusters=3, depth=2, dim=4,
                               store_dtype="int8")
    B = 20
    x = jnp.asarray(RNG.normal(size=(B, 4)), jnp.float32)
    labels = jnp.asarray(RNG.integers(0, 3, B), jnp.int32)
    admit = jnp.ones(B, bool)
    ids = jnp.arange(B, dtype=jnp.int32)
    whole = docstore.add_batch(cfg, docstore.init(cfg), x, labels, admit,
                               ids, ids)
    split = docstore.init(cfg)
    for lo, hi in [(0, 7), (7, 8), (8, 20)]:
        split = docstore.add_batch(cfg, split, x[lo:hi], labels[lo:hi],
                                   admit[lo:hi], ids[lo:hi], ids[lo:hi])
    for a, b in zip(whole, split):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------- rerank
def _int8_store_arrays(C, depth, d, live_frac):
    v = RNG.normal(size=(C, depth, d)).astype(np.float32)
    q, s = quant.quantize_int8(jnp.asarray(v), axis=-1)
    live = jnp.asarray(RNG.random((C, depth)) < live_frac)
    return q, s, live


def test_int8_rerank_kernel_parity_vs_dequantized_oracle():
    """The int8 kernel vs a plain fp32 oracle over the DEQUANTIZED tensor:
    ids exact, scores within float tolerance (the kernel applies the scale
    to the score row instead of the embedding tile). Sweeps depths that do
    and don't hit the int8 sublane pad (32)."""
    for (Q, C, depth, P, k, live_frac) in [(4, 10, 8, 3, 5, 0.7),
                                           (2, 6, 5, 4, 12, 0.5),
                                           (3, 8, 32, 4, 10, 0.9),
                                           (1, 3, 4, 2, 8, 0.25),
                                           (3, 5, 8, 2, 1, 0.0)]:
        d = 32
        q = jnp.asarray(RNG.normal(size=(Q, d)), jnp.float32)
        embs, scales, live = _int8_store_arrays(C, depth, d, live_frac)
        routes = jnp.asarray(RNG.integers(-1, C, (Q, P)).astype(np.int32))
        deq = quant.dequantize_int8(embs, scales[..., None])

        sc_p, id_p = rerank_topk_pallas(q, embs, live, routes, k, scales)
        sc_o, id_o = rerank_topk_ref(q, deq, live, routes, k)
        np.testing.assert_array_equal(np.asarray(id_p), np.asarray(id_o))
        live_rows = np.asarray(sc_o) > -1e29
        np.testing.assert_allclose(np.asarray(sc_p)[live_rows],
                                   np.asarray(sc_o)[live_rows],
                                   rtol=1e-5, atol=1e-5)

        # and the int8 ref path (same operation order) is bit-compatible
        sc_r, id_r = rerank_topk_ref(q, embs, live, routes, k, scales)
        np.testing.assert_array_equal(np.asarray(id_p), np.asarray(id_r))
        np.testing.assert_allclose(np.asarray(sc_p)[live_rows],
                                   np.asarray(sc_r)[live_rows],
                                   rtol=1e-6, atol=1e-6)


def test_int8_rerank_tie_break_lowest_position():
    C, depth, d = 4, 4, 8
    embs = jnp.full((C, depth, d), 127, jnp.int8).at[:, :, 1:].set(0)
    scales = jnp.full((C, depth), 1.0 / 127.0, jnp.float32)
    q = jnp.ones((2, d), jnp.float32)
    live = jnp.ones((C, depth), bool)
    routes = jnp.asarray([[0, 1], [2, 2]], jnp.int32)
    sc_p, id_p = rerank_topk_pallas(q, embs, live, routes, 5, scales)
    sc_r, id_r = rerank_topk_ref(q, embs, live, routes, 5, scales)
    np.testing.assert_array_equal(np.asarray(id_p), np.asarray(id_r))
    np.testing.assert_array_equal(np.asarray(id_p),
                                  [[0, 1, 2, 3, 4], [0, 1, 2, 3, 4]])
    np.testing.assert_allclose(np.asarray(sc_p), np.asarray(sc_r))


# -------------------------------------------------------------------- merge
def test_merge_stacked_quantized_is_pure_gather():
    """Merging S quantized shard stores == quantizing the merge of the
    fp32 twin stores: the merge gathers int8 rows + scales, it never
    re-quantizes (ids/stamps/ptr identical to the fp32 merge)."""
    d, S, k, depth = 16, 3, 5, 4
    cfg32 = docstore.StoreConfig(num_clusters=k, depth=depth, dim=d)
    cfg8 = dataclasses.replace(cfg32, store_dtype="int8")
    stores32, stores8 = [], []
    for sh in range(S):
        B = 30
        x = jnp.asarray(RNG.normal(size=(B, d)), jnp.float32)
        labels = jnp.asarray(RNG.integers(0, k, B), jnp.int32)
        admit = jnp.asarray(RNG.random(B) > 0.4)
        ids = jnp.asarray(sh * B + np.arange(B), jnp.int32)
        stamps = ids * 3 + 1
        stores32.append(docstore.add_batch(cfg32, docstore.init(cfg32), x,
                                           labels, admit, ids, stamps))
        stores8.append(docstore.add_batch(cfg8, docstore.init(cfg8), x,
                                          labels, admit, ids, stamps))
    m32 = docstore.merge_stacked(
        cfg32, jax.tree.map(lambda *xs: jnp.stack(xs), *stores32))
    m8 = docstore.merge_stacked(
        cfg8, jax.tree.map(lambda *xs: jnp.stack(xs), *stores8))
    for name in ("ids", "stamps", "ptr"):
        np.testing.assert_array_equal(np.asarray(getattr(m8, name)),
                                      np.asarray(getattr(m32, name)))
    # per-slot: quantizing the fp32 merged rows reproduces the int8 merge
    qm, sm = quant.quantize_int8(m32.embs, axis=-1)
    live = np.asarray(docstore.live_mask(m32))
    np.testing.assert_array_equal(np.asarray(m8.embs)[live],
                                  np.asarray(qm)[live])
    np.testing.assert_allclose(np.asarray(m8.scales)[live],
                               np.asarray(sm)[live], rtol=1e-7)
    assert not np.asarray(m8.scales)[~live].any()  # dead slots zeroed


# ------------------------------------------------------------ end-to-end
def _ingest(cfg, state, stream, n_batches=6, batch=64):
    for _ in range(n_batches):
        b = stream.next_batch(batch)
        state, _ = pipeline.ingest_batch(
            cfg, state, jnp.asarray(b["embedding"]), jnp.asarray(b["doc_id"]))
    return state


def test_two_stage_int8_query_end_to_end():
    """Routed two-stage retrieval over an int8 store: results are real
    stored docs, and self-retrieval recovers a stored doc at cosine ~1
    (within the quantization error bound)."""
    cfg = small_cfg()
    state = pipeline.init(cfg, jax.random.key(0))
    stream = make_stream("synthetic", dim=32)
    state = _ingest(cfg, state, stream)
    assert state.store.embs.dtype == jnp.int8

    q = jnp.asarray(stream.queries(8)["embedding"])
    sc, rows, ids, clusters = pipeline.query(cfg, state, q, 6,
                                             two_stage=True, nprobe=4)
    sc, rows, ids, clusters = map(np.asarray, (sc, rows, ids, clusters))
    live = sc > -1e29
    assert live.any()
    store_ids = np.asarray(state.store.ids)
    depth = cfg.store_depth
    for i in range(q.shape[0]):
        for r, d_, c in zip(rows[i][live[i]], ids[i][live[i]],
                            clusters[i][live[i]]):
            assert c >= 0 and r // depth == c
            assert store_ids[c, r % depth] == d_
    assert (np.diff(sc, axis=1) <= 1e-6).all()

    # self-retrieval on the dequantized stored vectors
    routable = set(np.asarray(state.hh.labels)[np.asarray(state.index.valid)])
    deq = np.asarray(docstore.dequantize(cfg.store, state.store))
    picks = [(c, s) for c in range(cfg.clus.num_clusters)
             for s in range(cfg.store_depth)
             if store_ids[c, s] >= 0 and c in routable][:8]
    assert picks
    q2 = jnp.asarray(np.stack([deq[c, s] for c, s in picks]))
    sc2, _r, ids2, _c = pipeline.query(cfg, state, q2, 4, two_stage=True,
                                       nprobe=cfg.hh.capacity)
    for i, (c, s) in enumerate(picks):
        assert int(store_ids[c, s]) in np.asarray(ids2[i]).tolist()
        assert float(sc2[i, 0]) > 0.98


def test_equal_state_int8_vs_fp32_rings_share_everything_but_the_store():
    """store_dtype is a storage-precision knob ONLY: ids/stamps/ptr of the
    rings and every non-store leaf evolve identically; the int8 embs are
    the per-slot quantization of the fp32 embs."""
    cfg32 = small_cfg(store_dtype="fp32")
    cfg8 = small_cfg(store_dtype="int8")
    stream32 = make_stream("synthetic", dim=32)
    stream8 = make_stream("synthetic", dim=32)
    s32 = _ingest(cfg32, pipeline.init(cfg32, jax.random.key(0)), stream32, 4)
    s8 = _ingest(cfg8, pipeline.init(cfg8, jax.random.key(0)), stream8, 4)
    for name in ("ids", "stamps", "ptr"):
        np.testing.assert_array_equal(np.asarray(getattr(s8.store, name)),
                                      np.asarray(getattr(s32.store, name)))
    np.testing.assert_array_equal(np.asarray(s8.route_labels),
                                  np.asarray(s32.route_labels))
    qm, sm = quant.quantize_int8(s32.store.embs, axis=-1)
    live = np.asarray(docstore.live_mask(s32.store))
    np.testing.assert_array_equal(np.asarray(s8.store.embs)[live],
                                  np.asarray(qm)[live])
    np.testing.assert_allclose(np.asarray(s8.store.scales)[live],
                               np.asarray(sm)[live], rtol=1e-7)


# ------------------------------------------------- accounting + checkpoint
def test_memory_accounting_dtype_aware():
    for dtype in ("fp32", "int8"):
        cfg = docstore.StoreConfig(num_clusters=7, depth=5, dim=24,
                                   store_dtype=dtype)
        actual = sum(l.size * l.dtype.itemsize
                     for l in jax.tree.leaves(docstore.init(cfg)))
        assert docstore.memory_bytes(cfg) == actual
    c32 = docstore.StoreConfig(num_clusters=10, depth=8, dim=128)
    c8 = dataclasses.replace(c32, store_dtype="int8")
    # int8 rings fit ~4x the depth in the same embedding bytes
    assert docstore.memory_bytes(c8) < docstore.memory_bytes(c32)
    assert docstore.memory_bytes(dataclasses.replace(c8, depth=32)) \
        <= docstore.memory_bytes(c32) + 10 * 32 * 12  # slot overhead only
    # pipeline-level accounting follows the store dtype
    assert pipeline.state_memory_bytes(small_cfg(store_dtype="int8")) < \
        pipeline.state_memory_bytes(small_cfg(store_dtype="fp32"))


def test_budget_to_config_folds_store_bytes():
    """Deep rings now cost clusters: at one budget, a deep-ring base gets
    fewer clusters than a storeless base, and an int8 base more than an
    fp32 base at equal depth — and the realized state stays within
    budget-scale of the target for ring-heavy configs."""
    base0 = pipeline.PipelineConfig()
    base32 = dataclasses.replace(base0, store_depth=32)
    base8 = dataclasses.replace(base0, store_depth=32, store_dtype="int8")
    k0 = pipeline.budget_to_config(2.0, base=base0).clus.num_clusters
    k32 = pipeline.budget_to_config(2.0, base=base32).clus.num_clusters
    k8 = pipeline.budget_to_config(2.0, base=base8).clus.num_clusters
    assert k32 < k8 < k0
    cfg = pipeline.budget_to_config(2.0, base=base32)
    assert pipeline.state_memory_bytes(cfg) < 1.25 * 2e6


def test_checkpoint_roundtrip_int8_state(tmp_path):
    from repro.train.checkpoint import CheckpointManager

    cfg = small_cfg()
    state = pipeline.init(cfg, jax.random.key(3))
    state = _ingest(cfg, state, make_stream("synthetic", dim=32), 3)
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    mgr.save(1, state, metadata={"arrivals": int(state.arrivals)})
    restored, meta = mgr.restore(state)
    assert meta["arrivals"] == int(state.arrivals)
    assert restored.store.embs.dtype == jnp.int8
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        if jnp.issubdtype(jnp.asarray(a).dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------- 4-device mesh
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


def test_sharded_quantized_store_delta_identity_and_query_parity():
    """On a 4-device mesh with int8 stores: (a) reconciliation equals the
    host-side oracle merge leaf-for-leaf (int8 leaves gather bit-exactly),
    (b) delta publications are bit-identical to full rebuilds at every
    publish, (c) distributed two-stage retrieval over the cluster-sharded
    int8 store matches single-device retrieval on the same snapshot, and
    (d) per-device serving bytes report the int8 itemsize."""
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
                                    store_depth=4, store_dtype="int8")
        stream = make_stream("iot", dim=32)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((D, M), ("data", "model"))
        full = ShardedEngine(cfg, mesh, jax.random.key(0),
                             reconcile_every=10**9)
        delta = ShardedEngine(cfg, mesh, jax.random.key(0),
                              reconcile_every=10**9,
                              reconcile_mode="delta", delta_max_frac=1.0,
                              delta_bucket_min=8)
        sizes = [64] * 5 + [37]                 # ragged tail batch
        batches = [stream.next_batch(s) for s in sizes]
        for i, b in enumerate(batches):
            for eng in (full, delta):
                eng.ingest(b["embedding"], b["doc_id"])
            sf, sd = full.reconcile(), delta.reconcile()
            assert sf.version == sd.version == i + 1
            # published_at is wall-clock (necessarily differs); device
            # leaves must be bit-identical
            for a, c in zip(jax.tree.leaves(sf._replace(published_at=0.0)),
                            jax.tree.leaves(sd._replace(published_at=0.0))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        assert len(delta._delta_fns) > 0, "delta path never exercised"
        assert sf.store.embs.dtype == jnp.int8
        print("DELTA-IDENTITY-INT8-OK")

        # ---- reconcile == host oracle on quantized leaves ----
        states = []
        for s in range(D):
            st = ShardedEngine.shard_init_state(cfg, jax.random.key(0), s, D)
            for b, bsz in zip(batches, sizes):
                pad = -bsz % D
                x = np.concatenate([np.asarray(b["embedding"], np.float32),
                                    np.zeros((pad, 32), np.float32)])
                ids = np.concatenate([np.asarray(b["doc_id"], np.int32),
                                      np.full((pad,), -1, np.int32)])
                st, _ = pipeline.ingest_batch(
                    cfg, st, jnp.asarray(x.reshape(D, -1, 32)[s]),
                    jnp.asarray(ids.reshape(D, -1)[s]))
            states.append(st)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        oracle = reconcile_stacked_states(cfg, stacked)
        snap = full.serving
        for name in ("embs", "ids", "stamps", "ptr", "scales"):
            np.testing.assert_array_equal(
                np.asarray(getattr(snap.store, name)),
                np.asarray(getattr(oracle.store, name)))
        print("RECONCILE-INT8-OK")

        # ---- distributed rerank over int8 shards == single device ----
        host_state = states[0]._replace(
            index=jax.tree.map(jnp.asarray, jax.device_get(snap.index)),
            route_labels=jnp.asarray(np.asarray(snap.route_labels)),
            store=jax.tree.map(lambda a: jnp.asarray(np.asarray(a)),
                               jax.device_get(snap.store)))
        q = jnp.asarray(stream.queries(16)["embedding"])
        got = full.query(q, 5, two_stage=True, nprobe=6)
        want = pipeline.query(cfg, host_state, q, 5, two_stage=True,
                              nprobe=6)
        np.testing.assert_array_equal(np.asarray(got[2]),
                                      np.asarray(want[2]))  # doc ids
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))  # rows
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-5, atol=1e-6)
        assert (np.asarray(got[2]) >= 0).any()
        print("QUERY-PARITY-INT8-OK")

        # ---- per-device serving bytes reflect the int8 itemsize ----
        full_bytes = docstore.memory_bytes(cfg.store)
        per_dev = full.store_bytes_per_device()
        assert per_dev * M == full_bytes, (per_dev, full_bytes)
        cfg32 = paper_pipeline_config(dim=32, k=32, capacity=12,
                                      update_interval=48, alpha=-1.0,
                                      store_depth=4)
        assert full_bytes < docstore.memory_bytes(cfg32.store)
        print("STORE-BYTES-INT8-OK")
    """)
    for tag in ("DELTA-IDENTITY-INT8-OK", "RECONCILE-INT8-OK",
                "QUERY-PARITY-INT8-OK", "STORE-BYTES-INT8-OK"):
        assert tag in out
