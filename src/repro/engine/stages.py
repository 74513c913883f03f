"""The composable stages of the streaming engine.

Each stage is a pure function over plain pytrees — no mesh, no jit, no
device placement — extracted from the fused per-microbatch pipeline step
(paper Algorithm 1) plus the two retrieval stages of routed two-stage
retrieval. ``engine.engine`` composes them into the single-device step;
``engine.sharded`` and ``distributed/collectives.py`` compose the same
functions inside ``shard_map``, so there is exactly one implementation of
each piece of pipeline semantics (the upsert/route-label snapshot logic in
particular used to be forked between ``pipeline.do_upsert`` and
``collectives.local_merge``).

Stage map (ingest):

    admit (fused screen + assign + quantize-on-admit, one device program)
      │        ──► count ──► update_representatives
      │                        │
      │                        ├──► store_write   (admitted docs, rows
      │                        │                   pre-quantized by admit)
      │                        └──► upsert_snapshot (every T arrivals)
      └── staged reference: screen ──► assign_update (+ store-side
          quantize), the decomposition ``admit`` runs with
          use_pallas=False — bit-identical keep/labels/rows/scales

Stage map (two-stage query):

    serve_topk (fused route + gather + dequant-rerank + top-k,
      │         one device program)     ──► decode_rerank
      └── staged reference: route (prototype index, replicated)
          ──► rerank (ring buffers, shardable), the decomposition
          ``serve_topk`` runs with use_pallas=False — identical
          routes/pos (scores to fp32 accumulation order)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import clustering, heavy_hitter, index as index_lib, prefilter
from repro.kernels.admit.ops import admit as admit_op
from repro.kernels.common import NEG_INF, l2_normalize
from repro.kernels.rerank.ops import rerank_topk
from repro.kernels.serve.ops import serve_topk as serve_topk_op
from repro.store import docstore


# --------------------------------------------------------------------- ingest
def screen(pre_cfg: prefilter.PrefilterConfig, pre_state, x: jnp.ndarray,
           live: jnp.ndarray | None = None):
    """(1) adaptive-basis window ingest + (2) relevance screening.

    ``live`` ([B] bool, optional) marks real rows; dead rows (ragged-batch
    padding, doc_id < 0) are kept out of the PCA window and forced to
    keep=False so every downstream stage treats them as inert.

    Staged reference form of the admission decision — the ingest hot path
    composes over the fused ``admit`` stage instead, which produces
    bit-identical keep masks.
    """
    pre = prefilter.ingest(pre_cfg, pre_state, x, mask=live)
    r, keep = prefilter.score(pre_cfg, pre, x)
    if live is not None:
        keep = keep & live
    return pre, r, keep


def assign_update(clus_cfg: clustering.ClusterConfig, clus_state,
                  x: jnp.ndarray, keep: jnp.ndarray):
    """(3) cluster assignment + centroid update (only retained items).

    Staged reference form — the ingest hot path gets labels/sims from the
    fused ``admit`` stage and applies the same ``clustering.update``."""
    labels, sims = clustering.assign(clus_cfg, clus_state, x)
    clus = clustering.update(clus_cfg, clus_state, x, labels, keep)
    return clus, labels, sims


def admit(pre_cfg: prefilter.PrefilterConfig,
          clus_cfg: clustering.ClusterConfig,
          store_cfg: docstore.StoreConfig,
          pre_state, clus_state, x: jnp.ndarray,
          live: jnp.ndarray | None = None):
    """(1)+(2)+(3) fused: window ingest, then ONE admission device program
    (``kernels.admit``) that streams x once and emits the prefilter score,
    the keep mask (threshold + live mask fused in), the cluster label +
    similarity, and the ring-write-ready store row — already quantized for
    int8 stores — followed by the same centroid update as the staged path.

    This is the one implementation of admission semantics: the
    single-device engine, the shard_map ingest and ``pipeline.ingest_batch``
    all compose over it. With ``use_pallas=False`` (the CPU default) it
    dispatches to the staged prefilter -> assign -> quantize reference
    composition, so screen/assign_update stay the pinned oracle.

    Returns (pre, r, keep, clus, labels, sims, v, vscale); v/vscale are
    None when the store is disabled (depth 0).
    """
    pre = prefilter.ingest(pre_cfg, pre_state, x, mask=live)
    use_pallas = (clus_cfg.use_pallas if clus_cfg.use_pallas is not None
                  else pre_cfg.use_pallas)
    r, keep, labels, sims, v, vscale = admit_op(
        x, pre.basis, clus_state.centroids, pre_cfg.alpha, live,
        store_dtype=store_cfg.store_dtype, normalize=store_cfg.normalize,
        emit_rows=store_cfg.depth > 0, use_pallas=use_pallas)
    clus = clustering.update(clus_cfg, clus_state, x, labels, keep)
    return pre, r, keep, clus, labels, sims, v, vscale


def count(hh_cfg: heavy_hitter.HHConfig, hh_state, labels: jnp.ndarray,
          keep: jnp.ndarray, key: jax.Array):
    """(4) heavy-hitter counting over retained labels (per-arrival
    semantics; ``heavy_hitter.update_batch`` picks the scan or the event
    loop from ``hh_cfg``)."""
    masked_labels = jnp.where(keep, labels, -1).astype(jnp.int32)
    hh, hh_info = heavy_hitter.update_batch(hh_cfg, hh_state, masked_labels, key)
    return hh, masked_labels, hh_info


def update_representatives(rep_ids, rep_sims, labels, sims, doc_ids, keep,
                           k: int):
    """Track the *freshest* member doc per cluster (recency scatter-max).

    Doc ids are monotone in arrival time, so the max id is the newest
    member — retrieval then surfaces current facts, which is the entire
    point of a streaming index (the paper's time-sensitive QA case study).
    rep_sims tracks that member's similarity for diagnostics.
    """
    seg = jnp.where(keep, labels, k)
    newest = jax.ops.segment_max(
        jnp.where(keep, doc_ids, -1), seg, num_segments=k + 1)[:k]
    new_ids = jnp.maximum(rep_ids, newest.astype(jnp.int32))
    wins = keep & (doc_ids >= new_ids[jnp.minimum(labels, k - 1)])
    new_sims = rep_sims
    new_sims = new_sims.at[jnp.where(wins, labels, k)].set(
        jnp.where(wins, sims, 0.0), mode="drop")
    return new_ids, new_sims


def store_write(store_cfg: docstore.StoreConfig, store, x, labels, stored,
                doc_ids, stamps, v=None, vscale=None):
    """Tiered document store: ring-write docs that survived BOTH filters
    (pre-filter relevance + a heavy-hitter-tracked cluster at arrival).

    ``v``/``vscale`` are the ring-write-ready rows the fused ``admit``
    stage emits (already normalized, already quantized for int8 stores);
    without them the store normalizes/quantizes ``x`` itself — identical
    results either way."""
    return docstore.add_batch(store_cfg, store, x, labels, stored, doc_ids,
                              stamps, v=v, vscale=vscale)


def upsert_snapshot(index_cfg: index_lib.IndexConfig, index, hh_state,
                    centroids, rep_ids):
    """(5) rebuild the prototype index from the live counter slots and
    snapshot the slot->label routing table at the same instant.

    Routing must read THIS snapshot, not the live hh labels: the counter
    rewrites its slots on eviction immediately, while index vectors only
    refresh on upsert — a live lookup would score a slot against one
    cluster's centroid and rerank a different cluster's ring.

    Returns (new_index, route_labels [bmax] i32 with -1 for dead slots).
    """
    bmax = hh_state.labels.shape[0]
    slots = jnp.arange(bmax, dtype=jnp.int32)
    lbl = hh_state.labels
    vecs = centroids[jnp.maximum(lbl, 0)]
    ids = rep_ids[jnp.maximum(lbl, 0)]
    valid = heavy_hitter.active_mask(hh_state)
    new_index = index_lib.upsert(index_cfg, index, slots, vecs, ids, valid)
    return new_index, jnp.where(valid, lbl, -1)


def delta_upsert_snapshot(index_cfg: index_lib.IndexConfig, prev_index,
                          prev_slot_labels, hh_state, centroids, rep_ids,
                          cluster_dirty):
    """Delta form of ``upsert_snapshot``: re-upsert only the slots whose
    content can have changed since the previous publish, reusing every
    other row of ``prev_index`` untouched.

    A slot's index row is a pure function of (its counter label, the merged
    centroid of that label's cluster, the cluster's representative id, its
    validity), so it is stale iff its raw counter label changed
    (``prev_slot_labels`` is the raw ``hh.labels`` snapshot from the last
    publish — raw, not route labels, because the full rebuild writes
    vectors even for invalid slots), its validity flipped, or its cluster
    is dirty (centroid/rep-id moved). Rows outside that mask are
    bit-identical to what a full rebuild would write, which is what makes
    delta publications exactly equal full reconciliation.

    Returns (new_index, route_labels, slot_labels) — ``slot_labels`` is the
    raw label snapshot the NEXT delta publish compares against.
    """
    lbl = hh_state.labels
    valid = heavy_hitter.active_mask(hh_state)
    lbl_c = jnp.maximum(lbl, 0)
    stale = ((lbl != prev_slot_labels) | (valid != prev_index.valid)
             | cluster_dirty[lbl_c])
    vecs = (l2_normalize(centroids[lbl_c]) if index_cfg.normalize
            else centroids[lbl_c].astype(jnp.float32))
    new_index = index_lib.FlatIndex(
        vectors=jnp.where(stale[:, None], vecs, prev_index.vectors),
        ids=jnp.where(stale, jnp.where(valid, rep_ids[lbl_c], -1),
                      prev_index.ids),
        valid=valid,
        version=prev_index.version,  # full rebuilds always publish 1
    )
    return new_index, jnp.where(valid, lbl, -1), lbl


# -------------------------------------------------------------- observability
# One in-graph reduction of the full pipeline state into a small i32
# vector — the device side of the telemetry subsystem (``repro.obs``).
# The engines evaluate it ONCE PER PUBLISH and fetch it as one tiny host
# transfer; nothing on the per-batch ingest or per-query path ever reads
# it, so enabling metrics adds zero device syncs to serving.
PIPELINE_COUNTER_NAMES = (
    "arrivals",        # docs seen (live rows only)
    "admitted",        # passed the prefilter (admission accept numerator)
    "hh_seen",         # arrivals reaching the heavy-hitter counter
    "hh_evictions",    # counter slot evictions
    "hh_writes",       # counter slot writes (state changes)
    "hh_occupied",     # occupied active counter slots
    "hh_capacity",     # active capacity B_t
    "hh_max_count",    # largest per-slot count (saturation headroom)
    "store_live",      # live ring slots across all clusters
    "store_slots",     # total ring slots (k * depth)
    "store_min_fill",  # least-filled cluster ring
    "store_max_fill",  # most-filled cluster ring
    "index_valid",     # valid prototype index slots
    "upserts",         # index refresh batches
)

# How shard-local counter vectors aggregate into one engine-level view
# (aligned with PIPELINE_COUNTER_NAMES): extensive quantities sum across
# data shards; per-shard extrema take min/max. The local prototype index
# is per-shard (the serving index is rebuilt at reconcile), so its slot
# count reports the shard max rather than a double-counting sum.
PIPELINE_COUNTER_COMBINE = (
    "sum", "sum", "sum", "sum", "sum",
    "sum", "sum", "max",
    "sum", "sum", "min", "max",
    "max", "sum",
)
assert len(PIPELINE_COUNTER_NAMES) == len(PIPELINE_COUNTER_COMBINE)


def pipeline_counters(cfg, state) -> jnp.ndarray:
    """Reduce a ``PipelineState`` to the ``[len(PIPELINE_COUNTER_NAMES)]``
    i32 device counter vector. Pure and jit-safe: composed under jit by
    ``Engine.device_counters`` and under ``vmap`` over the stacked shard
    states by ``ShardedEngine.device_counters``."""
    hh = state.hh
    occ = heavy_hitter.active_mask(hh)
    hh_occupied = jnp.sum(occ.astype(jnp.int32))
    hh_max = jnp.max(jnp.where(occ, hh.counts, 0))
    k, depth = state.store.ids.shape
    if depth > 0:
        fill = jnp.sum((state.store.ids >= 0).astype(jnp.int32), axis=1)
        store_live = jnp.sum(fill)
        store_min, store_max = jnp.min(fill), jnp.max(fill)
    else:  # store disabled: all-zero occupancy (static config branch)
        store_live = store_min = store_max = jnp.int32(0)
    return jnp.stack([
        state.arrivals,
        state.kept,
        hh.total_seen,
        hh.total_evictions,
        hh.total_writes,
        hh_occupied,
        hh.active_capacity,
        hh_max,
        store_live,
        jnp.int32(k * depth),
        store_min,
        store_max,
        jnp.sum(state.index.valid.astype(jnp.int32)),
        state.upserts,
    ]).astype(jnp.int32)


def decode_pipeline_counters(stacked) -> dict:
    """Host-side decode of fetched counter vectors ``[S, N]`` (S=1 for the
    single-device engine): aggregate across shards per
    ``PIPELINE_COUNTER_COMBINE`` and derive the rates the paper's
    operational claims are stated in (admission accept rate, ring
    occupancy, counter saturation, label-change rate). Pure numpy — runs
    on the host after the one publish-time transfer."""
    import numpy as np

    arr = np.asarray(stacked, dtype=np.int64)
    assert arr.ndim == 2 and arr.shape[1] == len(PIPELINE_COUNTER_NAMES), \
        arr.shape
    out: dict = {}
    for i, (name, comb) in enumerate(zip(PIPELINE_COUNTER_NAMES,
                                         PIPELINE_COUNTER_COMBINE)):
        col = arr[:, i]
        out[name] = int({"sum": np.sum, "max": np.max,
                         "min": np.min}[comb](col))
    out["admit_rate"] = out["admitted"] / max(out["arrivals"], 1)
    out["store_fill"] = out["store_live"] / max(out["store_slots"], 1)
    out["hh_occupancy"] = out["hh_occupied"] / max(out["hh_capacity"], 1)
    # arrivals that changed the counter's labels: evictions, plus inserts,
    # which equal the occupied slots where no slot is ever emptied (the
    # counters that heavy_hitter.update_batch runs as an event loop)
    out["hh_label_change_rate"] = ((out["hh_evictions"] + out["hh_occupied"])
                                   / max(out["hh_seen"], 1))
    return out


def store_occupancy(store) -> jnp.ndarray:
    """[3] i32 (live, min-fill, max-fill) of a (possibly cluster-sharded)
    serving-snapshot store — the published-store half of the per-cluster
    ring occupancy counters. jit-safe; evaluated only at publish."""
    if store.ids.shape[1] == 0:
        z = jnp.int32(0)
        return jnp.stack([z, z, z])
    fill = jnp.sum((store.ids >= 0).astype(jnp.int32), axis=1)
    return jnp.stack([jnp.sum(fill), jnp.min(fill), jnp.max(fill)])


# ---------------------------------------------------------------------- query
def route(index_cfg: index_lib.IndexConfig, index, route_labels,
          q: jnp.ndarray, nprobe: int) -> jnp.ndarray:
    """Stage 1: the prototype index routes each query to its top-``nprobe``
    clusters. Returns routes [Q, nprobe] i32 cluster ids (-1 = no route)."""
    sc1, slots, _ = index_lib.search(index_cfg, index, q, nprobe)
    labels = route_labels[slots]
    return jnp.where((sc1 > NEG_INF / 2) & (labels >= 0), labels, -1)


def slice_rings(embs, live, scales, depth: int | None):
    """Clip ring buffers to a plan's rerank ``depth``: the kernel gathers
    only the first ``depth`` slots of each routed ring, cutting the
    dominant stage-2 HBM bytes proportionally. Rings wrap (slot =
    ptr % depth), so the prefix is an age-uniform subset of each
    cluster's docs — the recall cost is graceful, not systematically
    stale (and a per-cluster newest-k gather would itself cost the full
    HBM pass the shrunken plan exists to avoid).

    ``depth >= store depth`` (or None) is the full-effort identity — the
    arrays pass through untouched, so a full-effort plan compiles and
    executes the exact pre-plan program."""
    if depth is None or depth >= embs.shape[1]:
        return embs, live, scales
    return (embs[:, :depth], live[:, :depth],
            None if scales is None else scales[:, :depth])


def rerank(store, qn: jnp.ndarray, routes: jnp.ndarray, k: int,
           use_pallas: bool | None, depth: int | None = None):
    """Stage 2: gather the routed ring buffers, exact cosine rerank.

    int8 stores hand the kernel their per-slot scales; dequantization
    happens inside the kernel with fp32 accumulation (the store's leaf
    dtype is the single source of truth, so every composition of this
    stage — single-device, snapshot, sharded — picks the right path).

    ``depth`` (a QueryPlan's rerank depth) clips each routed ring to its
    first ``depth`` slots before the kernel; None = full ring.

    Returns (scores [Q,k] desc, pos [Q,k] = j*depth+slot into the route
    list, -1 for dead entries)."""
    scales = store.scales if store.embs.dtype == jnp.int8 else None
    embs, live, scales = slice_rings(store.embs, docstore.live_mask(store),
                                      scales, depth)
    return rerank_topk(qn, embs, live, routes, k,
                       scales=scales, use_pallas=use_pallas)


def serve_topk(index_cfg: index_lib.IndexConfig, index, route_labels, store,
               q: jnp.ndarray, k: int, nprobe: int,
               use_pallas: bool | None, depth: int | None = None,
               source: str = "store"):
    """Stages 1+2 fused: ONE device program routes each query through the
    prototype index (running top-``nprobe``, no [Q, cap] score matrix in
    HBM), DMAs only the routed ring tiles, dequant-reranks them with fp32
    accumulation, and emits the final top-``k`` — the single two-stage
    query implementation every engine composes over (``Engine.query``,
    ``Engine.query_snapshot``, the sharded per-shard rerank, the async
    serving runtime).

    Query normalization policy matches the staged path exactly: the
    stage-1 vector follows the index config (unit prototypes -> unit
    queries), the stage-2 vector is always unit-norm for cosine. With
    ``use_pallas=False`` (the CPU default) the dispatcher runs the staged
    mips -> label-map -> rerank reference composition, so ``route`` +
    ``rerank`` stay the pinned oracle.

    ``depth`` (a QueryPlan's rerank depth) clips each routed ring to its
    first ``depth`` slots before the kernel; None = full ring. The
    (nprobe, depth) pair is the plan bucket the dispatcher keys its tune
    cache and trace counters by; ``source`` tags an alternate ring block
    (the pinned hot tier passes ``"hotset"`` with a tier-slot-remapped
    ``route_labels``) so its compiled variants get their own identity.

    Returns (scores [Q,k] desc, pos [Q,k] = j*depth+slot into the route
    list, routes [Q,nprobe] cluster ids; -1 for dead entries everywhere).
    """
    qn = l2_normalize(q)
    qr = qn if index_cfg.normalize else q.astype(jnp.float32)
    scales = store.scales if store.embs.dtype == jnp.int8 else None
    embs, live, scales = slice_rings(store.embs, docstore.live_mask(store),
                                      scales, depth)
    return serve_topk_op(qr, qn, index.vectors, index.valid, route_labels,
                         embs, live, k, nprobe,
                         scales=scales, use_pallas=use_pallas, source=source)


def gather_rings(store, clusters: jnp.ndarray, valid: jnp.ndarray):
    """Gather a row-subset of a (possibly cluster-sharded) doc store into
    a compact contiguous block — the hot-set serving tier's pin step.

    ``clusters`` [H] i32 store rows to pin (padding rows may repeat a real
    cluster); ``valid`` [H] bool marks real entries. The gathered rows are
    exact copies of the source rings (same dtype, same scales), so a
    rerank over the tier is bit-identical to one over the full store;
    padded rows get all-dead ids so they can never surface a document.

    Returns a ``DocStore`` of shape ``[H, depth, ...]`` addressed by tier
    slot — callers route into it with a remapped ``route_labels`` (true
    cluster id -> tier slot, -1 for unpinned).
    """
    tier = jax.tree.map(lambda a: a[clusters], store)
    return tier._replace(ids=jnp.where(valid[:, None], tier.ids, -1))


def decode_rerank(store_ids, routes, scores, pos, depth: int, nprobe: int,
                  doc_ids=None, store_depth: int | None = None):
    """Resolve rerank positions into (scores, rows, doc_ids, clusters).

    ``depth`` is the rerank depth ``pos`` was encoded with (a QueryPlan
    may clip it below the store's ring depth); ``store_depth`` is the
    full ring depth flat store rows are addressed in (defaults to
    ``depth`` — the full-effort case). rows are flat store positions
    cluster*store_depth + slot; dead entries -1. ``doc_ids`` may be
    passed pre-resolved (the distributed rerank looks them up
    shard-locally before the gather, when the rings are still
    addressable); otherwise they are read from ``store_ids``."""
    if store_depth is None:
        store_depth = depth
    dead = pos < 0
    j = jnp.clip(pos // depth, 0, nprobe - 1)
    slot = jnp.clip(pos % depth, 0, depth - 1)
    cluster = jnp.take_along_axis(routes, j, axis=1)
    cluster = jnp.where(dead, -1, cluster)
    if doc_ids is None:
        doc_ids = jnp.where(dead, -1, store_ids[jnp.clip(cluster, 0), slot])
    rows = jnp.where(dead, -1, jnp.clip(cluster, 0) * store_depth + slot)
    return scores, rows, doc_ids, cluster
