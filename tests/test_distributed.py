"""Distributed semantics: shard merges, compressed collectives, distributed
MIPS. Runs on an 8-device host-platform mesh in a SUBPROCESS so the main
test session keeps the real single-device view (the 512-device override is
dry-run-only)."""
import subprocess
import sys
import textwrap


def _run_in_multi_device_subprocess(body: str):
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
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


def test_counter_merge_across_shards_matches_union():
    out = _run_in_multi_device_subprocess("""
        from repro.core import heavy_hitter as hh
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        cfg = hh.HHConfig(capacity=32, admit_prob=1.0)
        rng = np.random.default_rng(0)
        streams = rng.integers(0, 12, (8, 64)).astype(np.int32)

        def shard_fn(labels):
            s = hh.init(cfg)
            s, _ = hh.update_batch(cfg, s, labels[0], jax.random.key(0))
            from repro.distributed.collectives import merge_counters
            m = merge_counters(cfg, s, "data")
            return jax.tree.map(lambda x: x[None], m)

        with jax.set_mesh(mesh):
            fn = shard_map(shard_fn, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False)
            merged = fn(jnp.asarray(streams))
        # every shard holds the same global union counts
        got = {int(l): int(c) for l, c in
               zip(np.asarray(merged.labels[0]), np.asarray(merged.counts[0]))
               if l >= 0}
        true = {int(v): int(n) for v, n in
                zip(*np.unique(streams, return_counts=True))}
        assert got == true, (got, true)
        for i in range(1, 8):
            assert np.array_equal(np.asarray(merged.counts[i]),
                                  np.asarray(merged.counts[0]))
        print("COUNTER-MERGE-OK")
    """)
    assert "COUNTER-MERGE-OK" in out


def test_weighted_centroid_merge_and_compressed_psum():
    out = _run_in_multi_device_subprocess("""
        from repro.core import clustering as C
        from repro.distributed.collectives import merge_clusters
        from repro.distributed.compression import compressed_psum
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(1)
        cents = rng.normal(size=(8, 4, 16)).astype(np.float32)
        counts = rng.integers(0, 10, (8, 4)).astype(np.float32)

        def shard_fn(c, n):
            s = C.ClusterState(c[0], n[0])
            m = merge_clusters(s, "data")
            return m.centroids[None], m.counts[None]

        fn = shard_map(shard_fn, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")), check_vma=False)
        mc, mn = fn(jnp.asarray(cents), jnp.asarray(counts))
        want_n = counts.sum(0)
        want_c = (cents * counts[..., None]).sum(0) / np.maximum(
            want_n, 1.0)[:, None]
        ok = want_n > 0
        np.testing.assert_allclose(np.asarray(mn[0]), want_n, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(mc[0])[ok], want_c[ok],
                                   rtol=1e-4, atol=1e-5)

        # --- compressed psum: error feedback keeps cumulative sums honest ---
        g = rng.normal(size=(8, 64)).astype(np.float32)

        def cp(x, e):
            tot, ne = compressed_psum(x[0], "data", e[0])
            return tot[None], ne[None]

        fn2 = shard_map(cp, mesh=mesh, in_specs=(P("data"), P("data")),
                        out_specs=(P("data"), P("data")), check_vma=False)
        err = jnp.zeros((8, 64))
        acc = np.zeros(64)
        for step in range(8):
            tot, err = fn2(jnp.asarray(g), err)
            acc += np.asarray(tot[0])
        true_acc = g.sum(0) * 8
        rel = np.abs(acc - true_acc) / (np.abs(true_acc) + 1e-6)
        assert np.median(rel) < 0.05, np.median(rel)
        print("CENTROID-AND-PSUM-OK")
    """)
    assert "CENTROID-AND-PSUM-OK" in out


def test_distributed_mips_matches_exact():
    out = _run_in_multi_device_subprocess("""
        from repro.distributed.collectives import distributed_mips_topk
        from repro.kernels.mips.ref import mips_topk_ref
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        rng = np.random.default_rng(2)
        N, d, k = 512, 16, 10
        X = rng.normal(size=(N, d)).astype(np.float32)
        q = rng.normal(size=(3, d)).astype(np.float32)
        valid = np.ones(N, bool)

        def fn(qq, xx, vv):
            return distributed_mips_topk(qq, xx, vv, k, "model")

        sm = shard_map(fn, mesh=mesh,
                       in_specs=(P(), P("model"), P("model")),
                       out_specs=(P(), P()), check_vma=False)
        sc, ids = sm(jnp.asarray(q), jnp.asarray(X), jnp.asarray(valid))
        sc_ref, ids_ref = mips_topk_ref(jnp.asarray(q), jnp.asarray(X),
                                        jnp.asarray(valid), k)
        np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_ref),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))
        print("DIST-MIPS-OK")
    """)
    assert "DIST-MIPS-OK" in out


def test_elastic_checkpoint_restore_onto_mesh():
    """Save on 1 device, restore sharded onto an 8-device mesh."""
    out = _run_in_multi_device_subprocess("""
        from repro.train.checkpoint import CheckpointManager
        from jax.sharding import NamedSharding
        import tempfile
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        tree = {"w": jnp.arange(64.0).reshape(8, 8)}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, tree)
            sh = {"w": NamedSharding(mesh, P("data"))}
            restored, meta = mgr.restore(jax.eval_shape(lambda: tree),
                                         shardings=sh)
            assert len(restored["w"].sharding.device_set) == 8
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.arange(64.0).reshape(8, 8))
        print("ELASTIC-OK")
    """)
    assert "ELASTIC-OK" in out


def test_distributed_pipeline_merge_end_to_end():
    """Full distributed ingest: 8 data shards each run the local pipeline on
    disjoint sub-streams; make_distributed_merge reconciles counters,
    centroids and the index into one consistent global view."""
    out = _run_in_multi_device_subprocess("""
        from repro.configs.streaming_rag import paper_pipeline_config
        from repro.core import heavy_hitter, pipeline
        from repro.data.streams import make_stream
        from repro.distributed.collectives import make_distributed_merge

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("data",))
        cfg = paper_pipeline_config(dim=32, k=32, capacity=16,
                                    update_interval=64, alpha=-1.0)
        stream = make_stream("iot", dim=32)

        # 8 shard-local states over disjoint stream slices
        states = []
        for shard in range(8):
            st = pipeline.init(cfg, jax.random.key(shard))
            for _ in range(3):
                b = stream.next_batch(64)
                st, _ = pipeline.ingest_batch(
                    cfg, st, jnp.asarray(b["embedding"]),
                    jnp.asarray(b["doc_id"]))
            states.append(st)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)

        merge = make_distributed_merge(cfg, mesh, ("data",))
        merged = merge(stacked)

        # all shards converge to the same counter/centroid state
        for leaf in (merged.hh.counts, merged.clus.counts,
                     merged.index.valid):
            arr = np.asarray(leaf)
            for i in range(1, 8):
                assert np.array_equal(arr[i], arr[0])
        # merged counts cover every shard's arrivals that were kept
        total_kept = sum(int(s.kept) for s in states)
        merged_counted = int(np.asarray(merged.hh.counts[0]).sum())
        assert merged_counted <= total_kept
        assert merged_counted > 0
        # merged cluster counts equal the sum of shard counts
        want = np.asarray(stacked.clus.counts).sum(0)
        np.testing.assert_allclose(np.asarray(merged.clus.counts[0]), want,
                                   rtol=1e-4)
        print("PIPELINE-MERGE-OK")
    """)
    assert "PIPELINE-MERGE-OK" in out
