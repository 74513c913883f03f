"""Table 19 — fused single-program serve path: route + gather +
dequant-rerank + top-k in ONE device program, vs the staged two-program
composition.

The paper's serving claim (end-to-end latency < 15 ms at > 900 docs/s)
lives on the query hot path. The fused ``serve`` kernel collapses the
two-stage query into one program: route scores stay in VMEM (no [Q, cap]
matrix in HBM), only the routed ring tiles are DMAd (int8 tiles ride
with their scale rows and widen on-chip), and the final top-k comes out
directly — where the staged path runs a route program and a rerank
program with routes materializing in HBM between them.

What the staged baseline is (same caveat as table 18): on TPU the two
programs are two kernel launches with an HBM round-trip between them;
this CPU bench reifies that structure as two jitted device programs
composed on the host. Both latency variants run the reference dispatch
(XLA-CPU) so the comparison isolates program structure; the Pallas
kernel's correctness rides along as an UNTIMED in-bench parity assert
(interpret mode) — fused ids == staged ids, fp32 and int8, single-device
and 4-device cluster-sharded. Run on a TPU backend for real kernel
latencies.

Measured, at the paper serving configuration (query batch 50, dim 384,
k=100 clusters, ring depth 16, nprobe 8, top-10; fp32 and int8 rings):

  * staged     — p_route (index MIPS + label map) then p_rerank
                 (gather + dequant-rerank + decode), two device programs.
  * fused      — the shipped ``snapshot_query_impl``: one device program.
  * sharded_*  — the same comparison over a forced 4-device
                 cluster-sharded snapshot store (``ShardedEngine``,
                 model axis 4): staged = route program + shard_map rerank
                 program; fused = the shard_map'd single-program serve.

Each row reports p50/p99 per-query-batch latency and the modeled
serve-side HBM bytes per query: the fused rows carry the kernel's
analytic DMA ledger (one pass over the routed rings + the query block +
the VMEM-resident index) and its ratio to the roofline ideal — asserted
<= 1.25x at paper defaults, the ISSUE 7 budget — while the staged rows
carry the HLO-modeled boundary bytes of their two programs.

Needs ``--xla_force_host_platform_device_count=4`` before jax init, so
``run()`` re-execs itself as a child process and parses JSON rows (same
pattern as tables 15-18).
"""
from __future__ import annotations

import sys

from benchmarks.common import emit_row, run_multi_device

Q = 50             # paper serving microbatch
DIM = 384
K_CLUSTERS = 100   # paper Table 2 k
DEPTH = 16
NPROBE = 8
TOPK = 10
ALPHA = 0.1
N_MODEL = 4        # forced CPU cluster shards for the sharded rows


def _paper_cfg(store_dtype: str):
    from repro.configs.streaming_rag import paper_pipeline_config

    return paper_pipeline_config(dim=DIM, k=K_CLUSTERS, capacity=100,
                                 update_interval=200, alpha=ALPHA,
                                 store_depth=DEPTH, store_dtype=store_dtype)


def _latency(fn, *, reps: int):
    """Per-call wall-clock sample -> (p50_ms, p99_ms). First call
    (compile) excluded."""
    import time

    import jax
    import numpy as np

    jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    ts = np.asarray(times) * 1e3
    return float(np.percentile(ts, 50)), float(np.percentile(ts, 99))


def _ingested_engine(cfg, seed: int, n_batches: int = 8):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import Engine

    rng = np.random.default_rng(seed)
    eng = Engine(cfg, jax.random.key(seed))
    for b in range(n_batches):
        x = jnp.asarray(rng.normal(size=(50, DIM)), jnp.float32)
        eng.ingest(x, jnp.arange(50, dtype=jnp.int32) + 50 * b)
    return eng, jnp.asarray(rng.normal(size=(Q, DIM)), jnp.float32)


def _staged_programs(cfg):
    """The pre-fusion two-stage query as two jitted device programs with
    the route table crossing HBM between them — the structure the fused
    kernel removes."""
    import functools

    import jax

    from repro.engine import stages
    from repro.kernels.common import l2_normalize

    @functools.partial(jax.jit, static_argnames=("nprobe",))
    def p_route(index, route_labels, q, nprobe):
        return stages.route(cfg.index, index, route_labels, q, nprobe)

    @functools.partial(jax.jit, static_argnames=("k", "nprobe"))
    def p_rerank(store, q, routes, k, nprobe):
        qn = l2_normalize(q)
        scores, pos = stages.rerank(store, qn, routes, k, False)
        return stages.decode_rerank(store.ids, routes, scores, pos,
                                    cfg.store_depth, nprobe)

    def query(snap, q):
        routes = p_route(snap.index, snap.route_labels, q, NPROBE)
        return p_rerank(snap.store, q, routes, TOPK, NPROBE)

    def modeled_bytes(snap, q):
        """Sum of the TWO programs' HLO boundary bytes (jitting the
        composite would fuse them — exactly what the fused path does)."""
        from repro.obs import kern

        routes = p_route(snap.index, snap.route_labels, q, NPROBE)
        b1 = kern.modeled_cost(
            lambda: p_route(snap.index, snap.route_labels, q, NPROBE))
        b2 = kern.modeled_cost(
            lambda: p_rerank(snap.store, q, routes, TOPK, NPROBE))
        return int(b1["modeled_hbm_bytes"] + b2["modeled_hbm_bytes"])

    return query, modeled_bytes


def _assert_ids_equal(a, b, label):
    import numpy as np

    (_, rows_a, ids_a, cl_a), (_, rows_b, ids_b, cl_b) = a, b
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b),
                                  err_msg=label)
    np.testing.assert_array_equal(np.asarray(rows_a), np.asarray(rows_b),
                                  err_msg=label)
    np.testing.assert_array_equal(np.asarray(cl_a), np.asarray(cl_b),
                                  err_msg=label)


def _serve_ledger(store_dtype: str):
    from repro.kernels.serve.serve import (ideal_serve_bytes,
                                           modeled_dma_bytes)

    quantized = store_dtype == "int8"
    got = modeled_dma_bytes(Q=Q, d=DIM, cap=100, C=K_CLUSTERS, depth=DEPTH,
                            nprobe=NPROBE, k=TOPK, quantized=quantized)
    ideal = ideal_serve_bytes(Q=Q, d=DIM, depth=DEPTH, nprobe=NPROBE,
                              quantized=quantized)
    assert got <= 1.25 * ideal, (store_dtype, got, ideal)
    return got, ideal


def _single_device_rows(reps: int, seed: int):
    import dataclasses

    from repro.engine.engine import snapshot_query_impl

    rows = []
    for store_dtype in ("fp32", "int8"):
        cfg = _paper_cfg(store_dtype)
        eng, q = _ingested_engine(cfg, seed)
        snap = eng.publish()
        staged, staged_modeled_bytes = _staged_programs(cfg)

        fused = lambda: snapshot_query_impl(
            cfg, snap.index, snap.route_labels, snap.store, q, TOPK,
            two_stage=True, nprobe=NPROBE)
        ref_out = staged(snap, q)
        _assert_ids_equal(fused(), ref_out, f"single/{store_dtype}/ref")

        # untimed Pallas parity: the fused KERNEL (interpret on CPU) must
        # return the exact staged ids at the paper serving shape
        cfg_pal = dataclasses.replace(
            cfg, clus=dataclasses.replace(cfg.clus, use_pallas=True))
        pal_out = snapshot_query_impl(
            cfg_pal, snap.index, snap.route_labels, snap.store, q, TOPK,
            two_stage=True, nprobe=NPROBE)
        _assert_ids_equal(pal_out, ref_out, f"single/{store_dtype}/pallas")

        dma, ideal = _serve_ledger(store_dtype)
        staged_bytes = staged_modeled_bytes(snap, q)
        for variant, fn, mb in (("staged", lambda: staged(snap, q),
                                 staged_bytes),
                                ("fused", fused, dma)):
            p50, p99 = _latency(fn, reps=reps)
            rows.append({
                "table": "table19", "variant": variant,
                "store_dtype": store_dtype, "devices": 1, "q_batch": Q,
                "p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
                "modeled_hbm_bytes_per_query": mb // Q,
                "serve_ideal_bytes_per_query": ideal // Q,
                "bytes_vs_ideal":
                    round(mb / ideal, 3) if variant == "fused" else None})
    return rows


def _sharded_rows(reps: int, seed: int):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.engine.sharded import ShardedEngine

    rng = np.random.default_rng(seed)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((N_MODEL,), ("model",))
    rows = []
    for store_dtype in ("fp32", "int8"):
        cfg = _paper_cfg(store_dtype)
        eng = ShardedEngine(cfg, mesh, jax.random.key(seed),
                            reconcile_every=10**9)
        for b in range(8):
            x = jnp.asarray(rng.normal(size=(50, DIM)), jnp.float32)
            eng.ingest(x, jnp.arange(50, dtype=jnp.int32) + 50 * b)
        snap = eng.reconcile()
        q = jnp.asarray(rng.normal(size=(Q, DIM)), jnp.float32)

        fused = lambda: eng.query_snapshot(snap, q, TOPK, two_stage=True,
                                           nprobe=NPROBE)
        staged = lambda: eng.query_snapshot(snap, q, TOPK, two_stage=True,
                                            nprobe=NPROBE, staged=True)
        _assert_ids_equal(fused(), staged(), f"sharded/{store_dtype}/ref")

        # untimed Pallas parity on the sharded fused path
        cfg_pal = dataclasses.replace(
            cfg, clus=dataclasses.replace(cfg.clus, use_pallas=True))
        eng_pal = ShardedEngine(cfg_pal, mesh, jax.random.key(seed),
                                reconcile_every=10**9)
        eng_pal.serving = snap
        _assert_ids_equal(
            eng_pal.query_snapshot(snap, q, TOPK, two_stage=True,
                                   nprobe=NPROBE),
            staged(), f"sharded/{store_dtype}/pallas")

        dma, ideal = _serve_ledger(store_dtype)
        for variant, fn in (("sharded_staged", staged),
                            ("sharded_fused", fused)):
            p50, p99 = _latency(fn, reps=reps)
            rows.append({
                "table": "table19", "variant": variant,
                "store_dtype": store_dtype, "devices": N_MODEL,
                "q_batch": Q,
                "p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
                "modeled_hbm_bytes_per_query":
                    dma // Q if variant == "sharded_fused" else None,
                "serve_ideal_bytes_per_query": ideal // Q,
                "bytes_vs_ideal": (round(dma / ideal, 3)
                                   if variant == "sharded_fused" else None)})
    return rows


def _child(reps: int, seed: int):
    rows = _single_device_rows(reps, seed) + _sharded_rows(reps, seed)
    for row in rows:
        emit_row(row)


def run(reps: int = 40, seed: int = 0) -> list[dict]:
    return run_multi_device(
        "benchmarks.table19_serve_fusion", ["--child", reps, seed],
        lambda: _child(reps, seed))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(int(sys.argv[2]), int(sys.argv[3]))
    else:
        for r in run():
            print(r)
