"""Fused serve-path kernel parity: the single-program route + gather +
dequant-rerank + top-k (``kernels.serve``) vs the staged composition it
replaced.

Contract (the repo-wide kernel parity contract): ids/pos/routes are
asserted BIT-EXACT against the staged reference — including the dead -> -1
semantics and the lowest-index tie-break — while scores are allclose
(fp32 matmul accumulation order differs between the fused per-row dots
and the staged full-matrix products, exactly as for mips/rerank).

Sweeps: fp32/int8 rings, ragged/dead slots (invalid index rows, -1 route
labels, partially-filled rings), non-default autotune tiles, snapshot vs
live Engine state, and (subprocess, forced 4-device CPU mesh) the
cluster-sharded fused path vs the single-device fused path — ids exact.
"""
import dataclasses
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.engine import stages
from repro.kernels.serve.ref import serve_topk_ref
from repro.kernels.serve.serve import (ideal_serve_bytes, modeled_dma_bytes,
                                       serve_topk_pallas)

RNG = np.random.default_rng(7)


def _problem(Q, d, cap, C, depth, *, quantized, dead_frac=0.2,
             label_dead_frac=0.1, live_frac=0.85):
    qr = jnp.asarray(RNG.normal(size=(Q, d)), jnp.float32)
    qn = jnp.asarray(RNG.normal(size=(Q, d)), jnp.float32)
    vectors = jnp.asarray(RNG.normal(size=(cap, d)), jnp.float32)
    valid = jnp.asarray(RNG.random(cap) >= dead_frac)
    labels = jnp.where(jnp.asarray(RNG.random(cap) >= label_dead_frac),
                       jnp.asarray(RNG.integers(0, C, cap), jnp.int32), -1)
    live = jnp.asarray(RNG.random((C, depth)) < live_frac)
    if quantized:
        embs = jnp.asarray(RNG.integers(-127, 128, (C, depth, d)), jnp.int8)
        scales = jnp.asarray(RNG.random((C, depth)) * 0.02 + 1e-4,
                             jnp.float32)
    else:
        embs = jnp.asarray(RNG.normal(size=(C, depth, d)), jnp.float32)
        scales = None
    return qr, qn, vectors, valid, labels, embs, live, scales


def _assert_parity(got, want):
    (sc, pos, rt), (esc, epos, ert) = got, want
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(ert))
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(epos))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(esc), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("Q,d,cap,C,depth,P,k", [
    (10, 128, 64, 16, 8, 4, 3),
    (7, 256, 100, 24, 16, 8, 10),
    (50, 384, 100, 100, 16, 8, 10),   # paper defaults
    (1, 128, 5, 3, 4, 2, 1),
    (33, 64, 200, 40, 8, 6, 48),      # k == P * depth (full extraction)
])
@pytest.mark.parametrize("quantized", [False, True])
def test_serve_fused_matches_staged_reference(Q, d, cap, C, depth, P, k,
                                              quantized):
    args = _problem(Q, d, cap, C, depth, quantized=quantized)
    scales = args[-1]
    _assert_parity(serve_topk_pallas(*args[:-1], k, P, scales),
                   serve_topk_ref(*args[:-1], k, P, scales))


@pytest.mark.parametrize("quantized", [False, True])
def test_serve_all_dead_and_empty_rings(quantized):
    """Fully-dead corners: no valid index slot routes anywhere; empty
    rings yield all -1/-NEG_INF results, never garbage positions."""
    args = _problem(6, 64, 32, 8, 8, quantized=quantized, dead_frac=1.0)
    scales = args[-1]
    sc, pos, rt = serve_topk_pallas(*args[:-1], 4, 3, scales)
    _assert_parity((sc, pos, rt), serve_topk_ref(*args[:-1], 4, 3, scales))
    assert np.all(np.asarray(rt) == -1) and np.all(np.asarray(pos) == -1)

    args = _problem(6, 64, 32, 8, 8, quantized=quantized, live_frac=0.0)
    scales = args[-1]
    sc, pos, rt = serve_topk_pallas(*args[:-1], 4, 3, scales)
    _assert_parity((sc, pos, rt), serve_topk_ref(*args[:-1], 4, 3, scales))
    assert np.all(np.asarray(pos) == -1)


@pytest.mark.parametrize("tile", [dict(bq=16, bk=256, bd=8),
                                  dict(bq=8, bk=128, bd=4),
                                  dict(bq=32, bk=512, bd=16)])
def test_serve_tiles_do_not_change_results(tile):
    """Every autotune tile point returns identical ids — tiling is a pure
    performance knob, so a cache winner can never change results."""
    args = _problem(20, 128, 100, 30, 16, quantized=True)
    scales = args[-1]
    want = serve_topk_ref(*args[:-1], 10, 8, scales)
    _assert_parity(serve_topk_pallas(*args[:-1], 10, 8, scales, **tile),
                   want)


def test_serve_dispatcher_consumes_tune_cache(tmp_path, monkeypatch):
    """A persisted autotune winner is loaded at trace time and recorded in
    ``tuning.applied`` — and does not change the returned ids."""
    from repro.kernels import tuning
    from repro.kernels.serve.ops import serve_topk

    cache = tmp_path / "tune_cache.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
    tuning.reload()
    tuning.applied.clear()
    tuning.record("serve", "int8", {"bq": 16, "bk": 256, "bd": 8},
                  {"us_per_call": 1.0})

    args = _problem(12, 64, 64, 16, 16, quantized=True)
    scales = args[-1]
    got = serve_topk(*args[:-1], 5, 4, scales=scales, use_pallas=True)
    key = f"{tuning.platform()}/serve/int8"
    assert tuning.applied.get(key) == {"bq": 16, "bk": 256, "bd": 8}
    _assert_parity(got, serve_topk_ref(*args[:-1], 5, 4, scales))
    tuning.reload()
    tuning.applied.clear()


@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
def test_engine_fused_query_matches_staged_live_and_snapshot(store_dtype):
    """End-to-end through the engine: a real ingested state queried with
    the fused path (use_pallas=True, interpret) equals the staged path
    (use_pallas=False) — live state and published snapshot, ids exact."""
    from repro.configs.streaming_rag import paper_pipeline_config
    from repro.engine.engine import Engine

    cfg = paper_pipeline_config(dim=32, k=16, capacity=12,
                                update_interval=32, alpha=-1.0,
                                store_depth=4, store_dtype=store_dtype)
    eng = Engine(cfg, jax.random.key(0))
    rng = np.random.default_rng(3)
    for b in range(4):
        x = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
        eng.ingest(x, jnp.arange(24, dtype=jnp.int32) + 24 * b)
    q = jnp.asarray(rng.normal(size=(9, 32)), jnp.float32)

    def run(use_pallas, via_snapshot):
        c = dataclasses.replace(
            cfg, clus=dataclasses.replace(cfg.clus, use_pallas=use_pallas))
        e = Engine(c, jax.random.key(0), state=eng.state)
        if via_snapshot:
            return e.query_snapshot(e.publish(), q, k=6, two_stage=True,
                                    nprobe=4)
        return e.query(q, k=6, two_stage=True, nprobe=4)

    for via_snapshot in (False, True):
        sc_f, rows_f, ids_f, cl_f = run(True, via_snapshot)
        sc_s, rows_s, ids_s, cl_s = run(False, via_snapshot)
        np.testing.assert_array_equal(np.asarray(rows_f), np.asarray(rows_s))
        np.testing.assert_array_equal(np.asarray(ids_f), np.asarray(ids_s))
        np.testing.assert_array_equal(np.asarray(cl_f), np.asarray(cl_s))
        np.testing.assert_allclose(np.asarray(sc_f), np.asarray(sc_s),
                                   rtol=2e-5, atol=2e-5)


def test_serve_stage_matches_staged_stage_composition():
    """``stages.serve_topk`` (fused) == ``stages.route`` + ``stages.rerank``
    (staged) over the same snapshot leaves — the engine-level contract."""
    from repro.core import index as index_lib
    from repro.store import docstore

    d, cap, C, depth = 48, 40, 12, 8
    icfg = index_lib.IndexConfig(capacity=cap, dim=d)
    scfg = docstore.StoreConfig(num_clusters=C, depth=depth, dim=d,
                                store_dtype="int8")
    index = index_lib.init(icfg)
    rows = jnp.arange(cap, dtype=jnp.int32)
    index = index_lib.upsert(
        icfg, index, rows, jnp.asarray(RNG.normal(size=(cap, d)),
                                       jnp.float32),
        rows, jnp.asarray(RNG.random(cap) < 0.8))
    labels = jnp.where(jnp.asarray(RNG.random(cap) < 0.9),
                       jnp.asarray(RNG.integers(0, C, cap), jnp.int32), -1)
    store = docstore.init(scfg)
    x = jnp.asarray(RNG.normal(size=(40, d)), jnp.float32)
    store = docstore.add_batch(scfg, store, x,
                               jnp.asarray(RNG.integers(0, C, 40), jnp.int32),
                               jnp.ones(40, bool),
                               jnp.arange(40, dtype=jnp.int32),
                               jnp.arange(40, dtype=jnp.int32))
    q = jnp.asarray(RNG.normal(size=(7, d)), jnp.float32)

    sc_f, pos_f, rt_f = stages.serve_topk(icfg, index, labels, store, q, 5,
                                          4, True)
    rt_s = stages.route(icfg, index, labels, q, 4)
    from repro.kernels.common import l2_normalize
    sc_s, pos_s = stages.rerank(store, l2_normalize(q), rt_s, 5, False)
    np.testing.assert_array_equal(np.asarray(rt_f), np.asarray(rt_s))
    np.testing.assert_array_equal(np.asarray(pos_f), np.asarray(pos_s))
    np.testing.assert_allclose(np.asarray(sc_f), np.asarray(sc_s),
                               rtol=2e-5, atol=2e-5)


def test_modeled_bytes_within_budget_at_paper_defaults():
    """The analytic DMA ledger of one fused call stays within 1.25x the
    roofline ideal (one pass over the routed rings + the query block) at
    paper serving defaults — the ISSUE's serve-side HBM budget."""
    for quantized in (False, True):
        got = modeled_dma_bytes(Q=50, d=384, cap=100, C=100, depth=16,
                                nprobe=8, k=10, quantized=quantized)
        ideal = ideal_serve_bytes(Q=50, d=384, depth=16, nprobe=8,
                                  quantized=quantized)
        assert got <= 1.25 * ideal, (got, ideal, quantized)


def test_sharded_fused_serve_matches_single_device():
    """4-device cluster-sharded fused serve == single-device fused serve,
    ids/rows exact (subprocess: forced 4-device CPU mesh)."""
    body = """
        import dataclasses
        from repro.configs.streaming_rag import paper_pipeline_config
        from repro.engine.engine import Engine
        from repro.engine.sharded import ShardedEngine

        for store_dtype in ("fp32", "int8"):
            cfg = paper_pipeline_config(dim=32, k=16, capacity=12,
                                        update_interval=32, alpha=-1.0,
                                        store_depth=4,
                                        store_dtype=store_dtype)
            cfg = dataclasses.replace(
                cfg, clus=dataclasses.replace(cfg.clus, use_pallas=True))
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 2), ("data", "model"))
            eng = ShardedEngine(cfg, mesh, jax.random.key(0),
                                reconcile_every=100)
            rng = np.random.default_rng(3)
            for b in range(4):
                x = jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)
                eng.ingest(x, jnp.arange(32, dtype=jnp.int32) + 32 * b)
            snap = eng.reconcile()
            q = jnp.asarray(rng.normal(size=(9, 32)), jnp.float32)

            sc_d, rows_d, ids_d, cl_d = eng.query_snapshot(
                snap, q, k=6, two_stage=True, nprobe=4)
            sc_s, rows_s, ids_s, cl_s = eng.query_snapshot(
                snap, q, k=6, two_stage=True, nprobe=4, staged=True)
            # fused sharded == staged sharded (ids exact)
            np.testing.assert_array_equal(np.asarray(ids_d),
                                          np.asarray(ids_s))
            np.testing.assert_array_equal(np.asarray(cl_d), np.asarray(cl_s))

            # == single-device fused over the gathered snapshot
            single = Engine(cfg, jax.random.key(0))
            full_store = jax.tree.map(
                lambda a: jnp.asarray(np.asarray(a)), snap.store)
            from repro.engine.engine import snapshot_query_impl
            sc_1, rows_1, ids_1, cl_1 = snapshot_query_impl(
                cfg, jax.tree.map(jnp.asarray, snap.index),
                jnp.asarray(snap.route_labels), full_store, q, 6,
                two_stage=True, nprobe=4)
            np.testing.assert_array_equal(np.asarray(ids_d),
                                          np.asarray(ids_1))
            np.testing.assert_array_equal(np.asarray(cl_d),
                                          np.asarray(cl_1))
            np.testing.assert_allclose(np.asarray(sc_d), np.asarray(sc_1),
                                       rtol=2e-5, atol=2e-5)
        print("SHARDED-SERVE-OK")
    """
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
    assert "SHARDED-SERVE-OK" in proc.stdout
