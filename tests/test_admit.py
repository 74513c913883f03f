"""Fused ingest-admission parity: the admit megakernel (interpret mode on
CPU) against the staged reference composition it replaces.

The contract the engine relies on (and the reason admission can be fused
at all): keep masks, labels, and int8 rows/scales are BIT-IDENTICAL
between the fused kernel and the staged prefilter -> assign ->
quantize-on-admit path — for fp32 and int8 stores, for ragged/padded
batches (dead doc_id=-1 rows), single-device and on the forced 4-device
mesh. Scores (r, sims) are float-tolerance (different reduction shapes).
"""
import dataclasses
import functools
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import clustering, pipeline, prefilter
from repro.data.streams import make_stream
from repro.engine import stages
from repro.kernels.admit.admit import admit_pallas
from repro.kernels.admit.ref import admit_ref
from repro.kernels.assign.ref import assign_ref
from repro.kernels.common import l2_normalize
from repro.kernels.prefilter.ref import prefilter_scores_ref
from repro.store import docstore, quant

RNG = np.random.default_rng(0)


def _arr(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype=dtype)


def _check_parity(out_p, out_r, *, exact_ids=True):
    """Scores allclose; keep/labels/rows/scales bit-for-bit."""
    r_p, keep_p, lbl_p, sim_p, v_p, s_p = out_p
    r_r, keep_r, lbl_r, sim_r, v_r, s_r = out_r
    np.testing.assert_allclose(np.asarray(r_p), np.asarray(r_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sim_p), np.asarray(sim_r),
                               rtol=1e-5, atol=1e-6)
    if exact_ids:
        np.testing.assert_array_equal(np.asarray(keep_p), np.asarray(keep_r))
        np.testing.assert_array_equal(np.asarray(lbl_p), np.asarray(lbl_r))
    if v_r is None:
        assert v_p is None and s_p is None and s_r is None
        return
    if exact_ids:
        np.testing.assert_array_equal(np.asarray(v_p), np.asarray(v_r))
        np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_r))
    else:
        np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("B,K,d,n", [(64, 32, 48, 5), (300, 150, 96, 3),
                                     (17, 700, 256, 5), (1, 5, 64, 1),
                                     (513, 100, 384, 5)])
@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
def test_admit_matches_staged_reference(B, K, d, n, store_dtype):
    """Fused kernel vs the jitted staged reference across shapes: keep,
    labels, and the ring-write-ready rows/scales bit-for-bit (the jit
    context is how both paths execute inside the engine)."""
    x, basis, cent = _arr((B, d)), _arr((n, d)), _arr((K, d))
    alpha = 0.05
    ref = jax.jit(functools.partial(admit_ref, alpha=alpha,
                                    store_dtype=store_dtype))
    out_p = admit_pallas(x, basis, cent, alpha, store_dtype=store_dtype)
    out_r = ref(x, basis, cent)
    _check_parity(out_p, out_r)


@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
def test_admit_bf16_inputs(store_dtype):
    """bf16 microbatches widen to fp32 inside both paths (scores to
    tolerance; ids can tie under bf16, as in the other kernel sweeps)."""
    x, basis, cent = (_arr((96, 64), jnp.bfloat16), _arr((4, 64),
                      jnp.bfloat16), _arr((24, 64), jnp.bfloat16))
    out_p = admit_pallas(x, basis, cent, 0.1, store_dtype=store_dtype)
    out_r = jax.jit(functools.partial(admit_ref, alpha=0.1,
                                      store_dtype=store_dtype))(x, basis, cent)
    _check_parity(out_p, out_r, exact_ids=False)


def test_admit_ref_is_the_staged_composition():
    """The oracle is literally the staged path: prefilter ref -> assign
    ref -> the store's quantize convention, bit-for-bit."""
    x, basis, cent = _arr((80, 48)), _arr((5, 48)), _arr((20, 48))
    alpha = 0.1
    live = jnp.asarray(RNG.random(80) > 0.3)
    r, keep, labels, sims, v, vscale = admit_ref(
        x, basis, cent, alpha, live, store_dtype="int8")

    r_s = prefilter_scores_ref(x, basis)
    lbl_s, sim_s = assign_ref(x, cent)
    v_s, s_s = quant.quantize_int8(l2_normalize(x), axis=-1)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r_s))
    np.testing.assert_array_equal(np.asarray(keep),
                                  np.asarray((r_s >= alpha) & live))
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(lbl_s))
    np.testing.assert_array_equal(np.asarray(sims), np.asarray(sim_s))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_s))
    np.testing.assert_array_equal(np.asarray(vscale), np.asarray(s_s))


def test_admit_emit_rows_disabled():
    """Store-disabled configs (depth 0) skip the row outputs entirely."""
    x, basis, cent = _arr((32, 48)), _arr((3, 48)), _arr((8, 48))
    for fn in (admit_pallas,
               jax.jit(functools.partial(admit_ref, alpha=0.0,
                                         emit_rows=False))):
        if fn is admit_pallas:
            out = fn(x, basis, cent, 0.0, emit_rows=False)
        else:
            out = fn(x, basis, cent)
        assert out[4] is None and out[5] is None
    out_p = admit_pallas(x, basis, cent, 0.0, emit_rows=False)
    out_r = jax.jit(functools.partial(admit_ref, alpha=0.0,
                                      emit_rows=False))(x, basis, cent)
    _check_parity(out_p, out_r)


@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
def test_admit_ragged_dead_rows_inert(store_dtype):
    """Ragged-batch padding (zero rows, live=False — exactly what
    ShardedEngine.ingest pads with) through the fused kernel: dead rows
    are inert in score, keep, label, and quantized output, bit-identical
    to the staged reference's treatment of the same rows."""
    B, d, K, n = 70, 96, 30, 5
    x, basis, cent = _arr((B, d)), _arr((n, d)), _arr((K, d))
    live = jnp.arange(B) < 50
    x = jnp.where(live[:, None], x, 0.0)  # engine pads with zero rows
    alpha = 0.05
    out_p = admit_pallas(x, basis, cent, alpha, live,
                         store_dtype=store_dtype)
    out_r = jax.jit(functools.partial(admit_ref, alpha=alpha,
                                      store_dtype=store_dtype))(
        x, basis, cent, live=live)
    _check_parity(out_p, out_r)

    r, keep, labels, _, v, vscale = out_p
    dead = ~np.asarray(live)
    # dead rows: never kept, deterministic zero score / cluster-0 label
    assert not np.asarray(keep)[dead].any()
    np.testing.assert_array_equal(np.asarray(r)[dead], 0.0)
    np.testing.assert_array_equal(np.asarray(labels)[dead], 0)
    # quantized output of a zero row is all-zero with the clamp scale, so
    # even a buggy downstream write could only scatter zeros
    np.testing.assert_array_equal(np.asarray(v)[dead], 0)
    if store_dtype == "int8":
        np.testing.assert_allclose(np.asarray(vscale)[dead], 1e-12 / 127.0)


def _small_cfg(store_dtype="fp32", use_pallas=None, **kw):
    cfg = pipeline.PipelineConfig(
        pre=prefilter.PrefilterConfig(num_vectors=3, dim=32, alpha=0.05,
                                      basis="fixed", use_pallas=use_pallas),
        clus=clustering.ClusterConfig(num_clusters=16, dim=32,
                                      use_pallas=use_pallas),
        update_interval=64, store_depth=4, store_dtype=store_dtype, **kw)
    return cfg


def test_stages_admit_equals_screen_assign_quantize():
    """stages.admit (the one admission implementation every engine
    composition picks up) == stages.screen -> stages.assign_update -> the
    store-side quantize. Pinned on the reference dispatch explicitly
    (use_pallas=False): this test defines the staged-decomposition
    semantics, which must hold bit-for-bit in every environment —
    kernel-vs-reference parity is pinned by the sweeps above."""
    cfg = _small_cfg(store_dtype="int8", use_pallas=False)
    s = make_stream("iot", dim=32)
    state = pipeline.init(cfg, jax.random.key(0))
    b = s.next_batch(40)
    x = jnp.asarray(b["embedding"])
    ids = jnp.asarray(b["doc_id"]).at[-7:].set(-1)  # ragged tail
    live = ids >= 0
    x = jnp.where(live[:, None], x, 0.0)

    pre_f, r_f, keep_f, clus_f, lbl_f, sim_f, v_f, s_f = stages.admit(
        cfg.pre, cfg.clus, cfg.store, state.pre, state.clus, x, live)
    pre_s, r_s, keep_s = stages.screen(cfg.pre, state.pre, x, live)
    clus_s, lbl_s, sim_s = stages.assign_update(cfg.clus, state.clus, x,
                                                keep_s)
    for a, b_ in ((r_f, r_s), (keep_f, keep_s), (lbl_f, lbl_s),
                  (sim_f, sim_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    for a, b_ in zip(jax.tree.leaves((pre_f, clus_f)),
                     jax.tree.leaves((pre_s, clus_s))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    # ring write with the pre-quantized rows == store-side quantization
    stamps = jnp.arange(40, dtype=jnp.int32)
    st_pre = docstore.add_batch(cfg.store, state.store, x, lbl_f, keep_f,
                                ids, stamps, v=v_f, vscale=s_f)
    st_own = docstore.add_batch(cfg.store, state.store, x, lbl_s, keep_s,
                                ids, stamps)
    for a, b_ in zip(jax.tree.leaves(st_pre), jax.tree.leaves(st_own)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
def test_engine_fused_kernel_matches_reference_engine(store_dtype):
    """Full single-device ingest with the fused Pallas admission
    (use_pallas=True, interpret mode) vs the staged reference engine:
    every integer and boolean PipelineState leaf — counters, labels, ids,
    masks, int8 ring rows — identical across a stream with a ragged batch,
    every float leaf to 1e-6, and two-stage query doc ids identical."""
    cfg_r = _small_cfg(store_dtype=store_dtype, use_pallas=False)
    cfg_p = _small_cfg(store_dtype=store_dtype, use_pallas=True)
    s = make_stream("iot", dim=32)
    st_r = pipeline.init(cfg_r, jax.random.key(0))
    st_p = pipeline.init(cfg_p, jax.random.key(0))
    for i in range(5):
        b = s.next_batch(32)
        ids = jnp.asarray(b["doc_id"])
        if i == 3:
            ids = ids.at[-5:].set(-1)
        x = jnp.asarray(b["embedding"])
        x = jnp.where((ids >= 0)[:, None], x, 0.0)
        st_r, _ = pipeline.ingest_batch(cfg_r, st_r, x, ids)
        st_p, _ = pipeline.ingest_batch(cfg_p, st_p, x, ids)
    for (path, a), (_, b_) in zip(
            jax.tree_util.tree_flatten_with_path(st_r)[0],
            jax.tree_util.tree_flatten_with_path(st_p)[0]):
        if jnp.issubdtype(jnp.asarray(a).dtype, jax.dtypes.prng_key):
            a, b_ = jax.random.key_data(a), jax.random.key_data(b_)
        a, b_ = np.asarray(a), np.asarray(b_)
        if np.issubdtype(a.dtype, np.floating):
            # the kernel and the staged composition are different programs:
            # their fp32 similarities (e.g. rep_sims) may differ by an ulp
            np.testing.assert_allclose(a, b_, rtol=1e-6, atol=1e-6,
                                       err_msg=jax.tree_util.keystr(path))
        else:
            np.testing.assert_array_equal(
                a, b_, err_msg=jax.tree_util.keystr(path))

    q = jnp.asarray(s.queries(6)["embedding"])
    out_r = pipeline.query(cfg_r, st_r, q, 5, two_stage=True, nprobe=4)
    out_p = pipeline.query(cfg_p, st_p, q, 5, two_stage=True, nprobe=4)
    np.testing.assert_array_equal(np.asarray(out_r[2]), np.asarray(out_p[2]))


def test_sharded_engine_fused_kernel_parity_4dev():
    """ShardedEngine ingest with the fused Pallas admission vs the staged
    reference on a forced 4-device (2 data x 2 model) mesh: shard-local
    states and the published snapshot match — integer and boolean leaves
    exactly, float leaves to 1e-6 (different programs: fp32 similarities
    may differ by an ulp) — ragged global batches included."""
    body = """
        import dataclasses

        def same(a, c, msg=""):
            a, c = np.asarray(a), np.asarray(c)
            if np.issubdtype(a.dtype, np.floating):
                np.testing.assert_allclose(a, c, rtol=1e-6, atol=1e-6,
                                           err_msg=msg)
            else:
                np.testing.assert_array_equal(a, c, err_msg=msg)

        from repro.configs.streaming_rag import paper_pipeline_config
        from repro.data.streams import make_stream
        from repro.engine.sharded import ShardedEngine

        cfg_r = paper_pipeline_config(dim=32, k=32, capacity=12,
                                      update_interval=48, alpha=0.05,
                                      store_depth=4, store_dtype="int8")
        cfg_r = dataclasses.replace(
            cfg_r, clus=dataclasses.replace(cfg_r.clus, use_pallas=False))
        cfg_p = dataclasses.replace(
            cfg_r, clus=dataclasses.replace(cfg_r.clus, use_pallas=True))
        stream = make_stream("iot", dim=32)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        eng_r = ShardedEngine(cfg_r, mesh, jax.random.key(0),
                              reconcile_every=100)
        eng_p = ShardedEngine(cfg_p, mesh, jax.random.key(0),
                              reconcile_every=100)
        for i in range(6):
            b = stream.next_batch(61 if i == 4 else 64)  # ragged batch 4
            eng_r.ingest(b["embedding"], b["doc_id"])
            eng_p.ingest(b["embedding"], b["doc_id"])
        for (path, a), (_, c) in zip(
                jax.tree_util.tree_flatten_with_path(
                    jax.device_get(eng_r.local))[0],
                jax.tree_util.tree_flatten_with_path(
                    jax.device_get(eng_p.local))[0]):
            if jnp.issubdtype(jnp.asarray(a).dtype, jax.dtypes.prng_key):
                a = jax.random.key_data(jnp.asarray(a))
                c = jax.random.key_data(jnp.asarray(c))
            same(a, c, jax.tree_util.keystr(path))
        print("LOCAL-PARITY-OK")

        snap_r, snap_p = eng_r.reconcile(), eng_p.reconcile()
        for a, c in zip(jax.tree.leaves((snap_r.index, snap_r.route_labels,
                                         snap_r.store)),
                        jax.tree.leaves((snap_p.index, snap_p.route_labels,
                                         snap_p.store))):
            same(a, c)
        print("SNAPSHOT-PARITY-OK")
    """
    out = _run_in_4_device_subprocess(body)
    assert "LOCAL-PARITY-OK" in out and "SNAPSHOT-PARITY-OK" in out


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
