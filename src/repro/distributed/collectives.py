"""Distributed streaming-RAG state maintenance (DESIGN.md §5).

The stream is sharded over the data axes; every shard runs the full local
pipeline (prefilter -> cluster -> counter). Periodically the shards
reconcile:

  * centroids : count-weighted mean  — psum(n_j·μ_j) / psum(n_j)
  * counters  : label-union merge    — all_gather(states) + fold of
                heavy_hitter.merge (exact count-sum semantics)
  * index     : rebuilt from the merged prototypes (a B×d broadcast)

These run inside shard_map over the data axes; the model axis holds the
sharded retrieval index (distributed MIPS: local top-k + global merge).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import clustering, heavy_hitter
from repro.kernels.common import NEG_INF


def merge_clusters(state: clustering.ClusterState, axis) -> clustering.ClusterState:
    """Count-weighted centroid merge across ``axis`` (inside shard_map)."""
    wsum = jax.lax.psum(state.centroids * state.counts[:, None], axis)
    n = jax.lax.psum(state.counts, axis)
    c = jnp.where((n > 0)[:, None], wsum / jnp.maximum(n, 1.0)[:, None],
                  state.centroids)
    return clustering.ClusterState(centroids=c, counts=n)


def merge_counters(cfg: heavy_hitter.HHConfig, state: heavy_hitter.HHState,
                   axis) -> heavy_hitter.HHState:
    """All-gather shard counters and fold pairwise merges (inside shard_map)."""
    gathered = jax.lax.all_gather(state, axis)  # leading axis = shards
    n = jax.tree.leaves(gathered)[0].shape[0]
    merged = jax.tree.map(lambda x: x[0], gathered)
    for i in range(1, n):
        merged = heavy_hitter.merge(
            cfg, merged, jax.tree.map(lambda x: x[i], gathered))
    return merged


def make_distributed_merge(cfg, mesh, data_axis_names: tuple[str, ...]):
    """shard_map-wrapped reconciliation of per-shard pipeline states.

    Takes the data-sharded PipelineState pytree (counters/centroids differ
    per shard) and returns one where cluster, counter, representative-doc
    AND document-store state are globally consistent (replicated across
    data shards). The index rebuild + routing snapshot goes through the
    shared ``engine.stages.upsert_snapshot`` — the same code the
    single-device ingest step runs.
    """
    from repro.core import pipeline
    from repro.engine import stages
    from repro.store import docstore

    axis = data_axis_names

    def local_merge(state: pipeline.PipelineState) -> pipeline.PipelineState:
        clus = merge_clusters(state.clus, axis)
        hh = merge_counters(cfg.hh, state.hh, axis)
        rep = jax.lax.pmax(state.rep_ids, axis)
        rep_sims = jax.lax.pmax(state.rep_sims, axis)
        # exact ring-buffer union (newest `depth` per cluster survive)
        gathered_store = jax.lax.all_gather(state.store, axis)
        store = docstore.merge_stacked(cfg.store, gathered_store)
        idx, route_labels = stages.upsert_snapshot(
            cfg.index, state.index, hh, clus.centroids, rep)
        return state._replace(clus=clus, hh=hh, index=idx,
                              route_labels=route_labels, store=store,
                              rep_ids=rep, rep_sims=rep_sims)

    def shard_fn(stacked_slice):
        # per-shard slice keeps a leading dim of 1 under shard_map
        state = jax.tree.map(lambda x: x[0], stacked_slice)
        merged = local_merge(state)
        return jax.tree.map(lambda x: x[None], merged)

    def merge_stacked(stacked_states):
        """stacked_states: pytree with leading dim = #data shards."""
        # jitted: JAX's eager shard_map path re-shards each output in its
        # own program and trips over outputs XLA proves replicated
        fn = jax.jit(jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(axis), stacked_states),),
            out_specs=jax.tree.map(lambda _: P(axis), stacked_states),
            check_vma=False))
        return fn(stacked_states)

    return merge_stacked


# ---------------------------------------------------------------------------
# Distributed MIPS: index rows sharded over the model axis
# ---------------------------------------------------------------------------
def distributed_mips_topk(q, index_rows, valid, k: int, axis: str = "model"):
    """Local exact top-k per shard + all_gather merge (inside shard_map).

    q replicated [Q, d]; index_rows/valid sharded on rows.
    Returns globally-consistent (scores [Q,k], global row ids [Q,k]).
    """
    n_local = index_rows.shape[0]
    s = q.astype(jnp.float32) @ index_rows.astype(jnp.float32).T
    s = jnp.where(valid[None, :], s, NEG_INF)
    loc_sc, loc_id = jax.lax.top_k(s, min(k, n_local))
    shard = jax.lax.axis_index(axis)
    glob_id = loc_id + shard * n_local
    all_sc = jax.lax.all_gather(loc_sc, axis, axis=1, tiled=True)  # [Q, n*k]
    all_id = jax.lax.all_gather(glob_id, axis, axis=1, tiled=True)
    sc, pos = jax.lax.top_k(all_sc, k)
    return sc, jnp.take_along_axis(all_id, pos, axis=1)


def distributed_rerank_topk(qn, embs, live, ids, routes, k: int,
                            axis: str = "model", use_pallas: bool | None = None,
                            scales=None, depth: int | None = None):
    """Distributed two-stage rerank: doc-store rings cluster-sharded over
    ``axis`` (inside shard_map). Generalizes ``distributed_mips_topk`` to
    routed ring gathers.

    qn replicated [Q, d] (pre-normalized); embs [kl, depth, d] / live
    [kl, depth] / ids [kl, depth] are this shard's cluster slice (global
    clusters [off, off+kl), off = axis_index * kl); routes [Q, P]
    replicated global cluster ids (-1 = no route). ``scales`` [kl, depth]
    f32 carries the per-slot dequantization scales when the rings are
    int8 (the quantized store layout) — each shard dequantizes inside its
    local rerank kernel, so the wire and HBM only ever hold int8 rings.

    Each shard masks the route list to its own clusters, reranks its rings
    locally (same kernel as single-device stage 2), then the per-shard
    top-k merge globally. Because the masked route list keeps the GLOBAL
    route positions, the merged (score, pos) order — including the
    lowest-position tie-break — is bit-identical to a single device
    reranking the full store.

    ``depth`` (a QueryPlan's rerank depth) clips each shard's rings to
    their first ``depth`` slots before the local kernel — the same
    prefix slice as ``stages.rerank``, so the merged order still equals
    the single-device plan query. None = full rings.

    Returns (scores [Q,k] desc, pos [Q,k] = j*depth+slot into the route
    list, doc_ids [Q,k]); dead entries are -1.
    """
    from repro.engine.stages import slice_rings
    from repro.kernels.rerank.ops import rerank_topk

    embs, live, scales = slice_rings(embs, live, scales, depth)
    kl, depth = embs.shape[0], embs.shape[1]
    P = routes.shape[1]
    off = jax.lax.axis_index(axis) * kl
    local_routes = jnp.where((routes >= off) & (routes < off + kl),
                             routes - off, -1)
    scores, pos = rerank_topk(qn, embs, live, local_routes, k,
                              scales=scales, use_pallas=use_pallas)
    return _merge_local_rerank(scores, pos, local_routes, ids, k, P, depth,
                               axis)


def _merge_local_rerank(scores, pos, local_routes, ids, k: int, P: int,
                        depth: int, axis):
    """Shared tail of the distributed serve/rerank paths: resolve each live
    local candidate's doc id while its ring is still addressable, then
    all_gather the per-shard top-k and merge with the lowest-position
    tie-break — bit-identical to single-device ``lax.top_k`` over the flat
    [Q, P*depth] score table (stable sort by position, then stable sort by
    descending score)."""
    dead = pos < 0
    j = jnp.clip(pos // depth, 0, P - 1)
    slot = jnp.clip(pos % depth, 0, depth - 1)
    lcl = jnp.take_along_axis(local_routes, j, axis=1)
    doc = jnp.where(dead, -1, ids[jnp.clip(lcl, 0), slot])
    pos_key = jnp.where(dead, P * depth, pos)  # dead entries sort last

    all_sc = jax.lax.all_gather(scores, axis, axis=1, tiled=True)   # [Q,S*k]
    all_pos = jax.lax.all_gather(pos_key, axis, axis=1, tiled=True)
    all_doc = jax.lax.all_gather(doc, axis, axis=1, tiled=True)

    o2 = jnp.argsort(all_pos, axis=1)
    sc2 = jnp.take_along_axis(all_sc, o2, axis=1)
    pos2 = jnp.take_along_axis(all_pos, o2, axis=1)
    doc2 = jnp.take_along_axis(all_doc, o2, axis=1)
    o1 = jnp.argsort(-sc2, axis=1)[:, :k]
    sc = jnp.take_along_axis(sc2, o1, axis=1)
    posk = jnp.take_along_axis(pos2, o1, axis=1)
    dock = jnp.take_along_axis(doc2, o1, axis=1)
    alive = sc > NEG_INF / 2
    return (sc, jnp.where(alive, posk, -1).astype(jnp.int32),
            jnp.where(alive, dock, -1).astype(jnp.int32))


def distributed_serve_topk(qr, qn, vectors, valid, route_labels, embs, live,
                           ids, k: int, nprobe: int, axis: str = "model",
                           use_pallas: bool | None = None, scales=None,
                           depth: int | None = None):
    """Distributed FUSED serve path (inside shard_map): every shard runs
    the one-program route + gather + dequant-rerank + top-k kernel over
    its cluster slice, then the shards merge exactly like
    ``distributed_rerank_topk``.

    qr/qn replicated [Q, d] (stage-1/stage-2 query vectors, caller-side
    normalization policy as in ``stages.serve_topk``); vectors [cap, d] /
    valid [cap] / route_labels [cap] the REPLICATED prototype index +
    slot -> global-cluster snapshot; embs/live/ids/scales this shard's
    cluster slice (global clusters [off, off+kl)).

    Localizing the label table BEFORE the fused kernel — out-of-shard
    slots become -1 — is exactly the staged global-route-then-mask: the
    prototype index is replicated, so every shard extracts the same
    top-``nprobe`` slots in the same order, and each route position j
    holds either the localized cluster or -1. The globally-consistent
    route list is recovered with a ``pmax`` over the per-shard partials
    (each position is live on exactly the owning shard).

    ``depth`` (a QueryPlan's rerank depth) clips each shard's rings to
    their first ``depth`` slots before the fused kernel (None = full) —
    parity with the single-device plan query is preserved because every
    shard applies the same prefix slice.

    Returns (scores [Q,k] desc, pos [Q,k], doc_ids [Q,k],
    routes [Q,nprobe] GLOBAL cluster ids); dead entries are -1.
    """
    from repro.engine.stages import slice_rings
    from repro.kernels.serve.ops import serve_topk

    embs, live, scales = slice_rings(embs, live, scales, depth)
    kl, depth = embs.shape[0], embs.shape[1]
    off = jax.lax.axis_index(axis) * kl
    local_labels = jnp.where((route_labels >= off) & (route_labels < off + kl),
                             route_labels - off, -1)
    scores, pos, local_rt = serve_topk(qr, qn, vectors, valid, local_labels,
                                       embs, live, k, nprobe, scales=scales,
                                       use_pallas=use_pallas)
    routes = jax.lax.pmax(jnp.where(local_rt >= 0, local_rt + off, -1), axis)
    sc, posk, dock = _merge_local_rerank(scores, pos, local_rt, ids, k,
                                         nprobe, depth, axis)
    return sc, posk, dock, routes


def hierarchical_psum(x, pod_axis: str | None, data_axis: str):
    """Explicit hierarchical all-reduce: reduce-scatter intra-pod, psum over
    the (slow) pod axis on the scattered shard, all-gather intra-pod.
    Matches what XLA derives from mesh order; exposed for the compression
    path which needs to quantize only the inter-pod hop."""
    if pod_axis is None:
        return jax.lax.psum(x, data_axis)
    shard = jax.lax.psum_scatter(x, data_axis, scatter_dimension=0,
                                 tiled=True)
    shard = jax.lax.psum(shard, pod_axis)
    return jax.lax.all_gather(shard, data_axis, axis=0, tiled=True)
