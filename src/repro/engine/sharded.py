"""ShardedEngine: the multi-device composition of the engine stages.

Execution model (DESIGN.md §5, extended):

  * ingest  — the stream is data-sharded over the mesh's ``data`` axis:
              every data shard runs the full single-device ingest step
              (``engine.engine.ingest_impl`` — the SAME code, inside
              shard_map) on its sub-stream. Shard-local states never get
              overwritten by reconciliation, so repeated merges stay exact
              (no double counting of a shared prefix).
  * reconcile — periodically (every ``reconcile_every`` ingested batches)
              the shards publish a globally-consistent serving snapshot:
              counters label-union-merged, centroids count-weighted-merged,
              rep-ids recency-merged, and the doc-store rings exactly
              merged (newest ``depth`` per cluster across shards). The
              prototype index + routing table are rebuilt through the
              shared ``stages.upsert_snapshot``. The merge is gather-based
              and bit-deterministic, so every device publishes the same
              snapshot — this is the "exact reconciliation" the counters'
              merge semantics make possible (counts merge exactly,
              centroids merge count-weighted).
  * serve   — the snapshot's doc store is cluster-sharded over the mesh's
              ``model`` axis (shard m owns clusters [m·k/M, (m+1)·k/M)),
              dropping per-device store bytes by M. Two-stage queries run
              stage-1 routing replicated against the (small) prototype
              index, stage-2 rerank locally per shard, then a global top-k
              merge (``collectives.distributed_rerank_topk``) whose
              tie-breaking is bit-identical to the single-device path.

Reconciliation has two publication modes:

  * ``full``  — rebuild the snapshot from scratch (all-gather every shard
              sub-state, merge everything). Always exact; O(full state)
              gather + merge per publish.
  * ``delta`` — per-cluster dirty tracking: a cluster is dirty iff some
              shard processed a kept document for it since the last
              publish (cluster counts are monotone per kept assignment,
              so comparing (counts, store ptr, rep ids) signatures is an
              exact change detector). Only the dirty clusters' centroids,
              rep-ids and ring buffers are gathered, re-merged, and
              scattered into the *previous* snapshot; the counter merge +
              routing snapshot stay full (they are O(Bmax), tiny). Dirty
              counts are bucketed to powers of two so the jitted delta
              step compiles O(log k) times. Delta publications are
              bit-identical to full rebuilds (pinned by test) because the
              merges are independent per cluster row and clean clusters'
              merged values cannot have changed.

The host-side ``reconcile_states`` is the single source of truth for
merge semantics: the distributed path all-gathers shard states and runs
the very same merge composition, so the mesh execution equals the host
oracle leaf-for-leaf.
"""
from __future__ import annotations

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import clustering, heavy_hitter, index as index_lib, pipeline
from repro.distributed import sharding as shard_rules
from repro.distributed.collectives import (distributed_rerank_topk,
                                           distributed_serve_topk)
from repro.engine import stages
from repro.engine.engine import (ServingSnapshot, _resolve_plan,
                                 ingest_impl, snapshot_query_impl)
from repro.kernels.common import l2_normalize
from repro.store import docstore

__all__ = ["ServingSnapshot", "ShardedEngine", "reconcile_states",
           "reconcile_stacked_states"]


# ---------------------------------------------------------------- pure merges
def _merge_clusters_stacked(stacked: clustering.ClusterState
                            ) -> clustering.ClusterState:
    """Count-weighted centroid merge over the leading shard axis. Clusters
    unseen by every shard keep shard 0's centroid (shards start from one
    shared init, so those are identical across shards by construction)."""
    n = jnp.sum(stacked.counts, axis=0)
    wsum = jnp.sum(stacked.centroids * stacked.counts[..., None], axis=0)
    c = jnp.where((n > 0)[:, None], wsum / jnp.maximum(n, 1.0)[:, None],
                  stacked.centroids[0])
    return clustering.ClusterState(centroids=c, counts=n)


def _merge_counters_stacked(hh_cfg: heavy_hitter.HHConfig, stacked
                            ) -> heavy_hitter.HHState:
    """Fold pairwise exact label-union merges from shard 0 upward — the
    same fold order as ``collectives.merge_counters``."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    merged = jax.tree.map(lambda x: x[0], stacked)
    for i in range(1, n):
        merged = heavy_hitter.merge(
            hh_cfg, merged, jax.tree.map(lambda x: x[i], stacked))
    return merged


def _merge_shard_states(cfg: pipeline.PipelineConfig, clus, hh, rep_ids,
                        store):
    """The four shard-state merges behind reconciliation, in one place:
    (merged ClusterState, merged HHState, merged rep_ids, merged store)."""
    return (_merge_clusters_stacked(clus),
            _merge_counters_stacked(cfg.hh, hh),
            jnp.max(rep_ids, axis=0),
            docstore.merge_stacked(cfg.store, store))


def reconcile_states(cfg: pipeline.PipelineConfig, clus, hh, rep_ids,
                     store) -> ServingSnapshot:
    """Merge S shard-local pipeline sub-states (cluster, counter, rep-id
    and store leaves stacked on a leading shard axis) into one
    globally-consistent serving snapshot with the FULL (unsharded) doc
    store. Pure and deterministic — the shard_map reconcile path
    all-gathers and runs exactly this merge composition, so distributed
    reconciliation equals this host-side oracle leaf-for-leaf."""
    m_clus, m_hh, m_rep, m_store = _merge_shard_states(cfg, clus, hh,
                                                       rep_ids, store)
    index, route_labels = stages.upsert_snapshot(
        cfg.index, index_lib.init(cfg.index), m_hh, m_clus.centroids, m_rep)
    return ServingSnapshot(index=index, route_labels=route_labels,
                           store=m_store)


def reconcile_stacked_states(cfg: pipeline.PipelineConfig,
                             stacked: pipeline.PipelineState
                             ) -> ServingSnapshot:
    """Host-side oracle entry: reconcile full stacked PipelineStates."""
    return reconcile_states(cfg, stacked.clus, stacked.hh, stacked.rep_ids,
                            stacked.store)


# ------------------------------------------------------------------- engine
class ShardedEngine:
    """Data-sharded streaming ingest + cluster-sharded serving over a mesh.

    Implements the same serving protocol as ``engine.Engine`` —
    ``ingest`` / ``query`` / ``publish`` / ``query_snapshot`` /
    ``index_size`` — so ``AsyncServer`` can hold either. ``mesh`` may
    carry a ``data`` axis (ingest sharding), a ``model`` axis (doc-store
    cluster sharding), or both; a missing axis degrades to that dimension
    running unsharded.
    """

    def __init__(self, cfg: pipeline.PipelineConfig, mesh, key: jax.Array,
                 *, warmup: jnp.ndarray | None = None,
                 data_axis: str = "data", model_axis: str = "model",
                 reconcile_every: int = 1, reconcile_mode: str = "full",
                 delta_max_frac: float = 0.5, delta_bucket_min: int = 32):
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.cfg = cfg
        self.mesh = mesh
        self.data_axis = data_axis if data_axis in sizes else None
        self.model_axis = model_axis if model_axis in sizes else None
        self.n_data = sizes.get(data_axis, 1)
        self.n_model = sizes.get(model_axis, 1)
        assert cfg.clus.num_clusters % self.n_model == 0, \
            "num_clusters must divide the model axis for cluster sharding"
        assert reconcile_mode in ("full", "delta"), reconcile_mode
        self.reconcile_every = max(1, reconcile_every)
        self.reconcile_mode = reconcile_mode
        # delta publishes fall back to a full rebuild above this dirty frac
        # (the gather-the-dirty-rows plan stops paying once most rows move);
        # dirty buckets are floored so sparse publishes share one compile
        self.delta_max_frac = delta_max_frac
        self.delta_bucket_min = delta_bucket_min
        self._batches_since_reconcile = 0
        self.serving: ServingSnapshot | None = None
        self._publish_version = 0
        # delta-publication state: merged (centroids, rep_ids, raw counter
        # slot labels) from the last publish + the host-side per-shard
        # (counts, store ptr, rep_ids) signature the dirty mask diffs.
        self._pub_cache = None
        self._pub_sig = None
        self._delta_fns: dict = {}
        # host-side record of the last publication for observability and
        # precise cache invalidation:
        # {"mode": "full"|"delta"|"republish", "dirty_clusters": int,
        #  "dirty_frac": float, "dirty": np.ndarray|None}. ``dirty`` is
        # the exact dirty-cluster index array whenever the signature was
        # diffed, None when there was no baseline (consumers must assume
        # everything changed). Set by every reconcile() path.
        self.last_publish_info: dict | None = None
        self._counters_fn = None

        # All shards start from ONE shared init (identical centroids /
        # prefilter basis / counters) and diverge only through their
        # sub-streams + admission rng — required for exact reconciliation
        # of never-updated clusters.
        base = pipeline.init(cfg, key, warmup)
        rngs = jax.random.split(jax.random.fold_in(key, 0x5A), self.n_data)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (self.n_data,) + a.shape),
            base._replace(rng=jnp.zeros(())))  # rng stacked separately below
        stacked = stacked._replace(rng=rngs)
        self._data_spec = P(self.data_axis) if self.data_axis else P()
        self.local = jax.device_put(
            stacked,
            shard_rules.engine_state_shardings(mesh, stacked, self.data_axis))
        self._ingest_fn = self._build_ingest()
        self._reconcile_fn = self._build_reconcile()
        self._rerank_fns: dict = {}
        self._serve_fns: dict = {}

    @staticmethod
    def shard_init_state(cfg, key, shard: int, n_data: int,
                         warmup=None) -> pipeline.PipelineState:
        """The exact state data shard ``shard`` starts from — exposed so
        single-device oracles can replay a shard's sub-stream."""
        base = pipeline.init(cfg, key, warmup)
        rngs = jax.random.split(jax.random.fold_in(key, 0x5A), n_data)
        return base._replace(rng=rngs[shard])

    # ------------------------------------------------------------ shard_map
    def _build_ingest(self):
        cfg, axis, data_axis = self.cfg, self._data_spec, self.data_axis

        def shard_fn(stacked, x, ids):
            state = jax.tree.map(lambda a: a[0], stacked)
            new_state, _ = ingest_impl(cfg, state, x[0], ids[0])
            return jax.tree.map(lambda a: a[None], new_state)

        def run(stacked, x, ids):
            fn = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(shard_rules.leading_axis_pspecs(stacked, data_axis),
                          axis, axis),
                out_specs=shard_rules.leading_axis_pspecs(stacked, data_axis),
                check_vma=False)
            return fn(stacked, x, ids)

        # donate the stacked state like the single-device jit wrapper does —
        # without it every microbatch copies the full [n_data, ...] pytree
        return jax.jit(run, donate_argnums=(0,))

    def _build_reconcile(self):
        """Full snapshot rebuild. Besides the snapshot parts it returns the
        merged (centroids, rep_ids, raw counter labels) that seed the
        delta-publication cache."""
        cfg = self.cfg
        data_axis, model_axis = self.data_axis, self.model_axis
        n_model = self.n_model

        def shard_fn(stacked):
            state = jax.tree.map(lambda a: a[0], stacked)
            sub = (state.clus, state.hh, state.rep_ids, state.store)
            if data_axis is not None:
                sub = jax.lax.all_gather(sub, data_axis)
            else:
                sub = jax.tree.map(lambda a: a[None], sub)
            m_clus, m_hh, m_rep, m_store = _merge_shard_states(cfg, *sub)
            index, route_labels = stages.upsert_snapshot(
                cfg.index, index_lib.init(cfg.index), m_hh,
                m_clus.centroids, m_rep)
            shard = (jax.lax.axis_index(model_axis)
                     if model_axis else jnp.int32(0))
            store = docstore.shard_slice(cfg.store, m_store, shard, n_model)
            return (index, route_labels, store, m_clus.centroids, m_rep,
                    m_hh.labels)

        def run(stacked):
            out_specs = (
                shard_rules.leading_axis_pspecs(self._abstract_index(), None),
                P(),
                shard_rules.leading_axis_pspecs(docstore.init(cfg.store),
                                                model_axis),
                P(), P(), P())
            fn = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(shard_rules.leading_axis_pspecs(
                    stacked, data_axis),),
                out_specs=out_specs, check_vma=False)
            return fn(stacked)

        return jax.jit(run)

    def _build_delta_reconcile(self, n_dirty: int):
        """Delta publication for a (static) dirty bucket of ``n_dirty``
        clusters: gather ONLY the dirty clusters' shard rows, re-merge
        them, and scatter into the previous snapshot. ``dirty`` entries
        equal to k are padding and drop out of every scatter."""
        cfg = self.cfg
        data_axis, model_axis = self.data_axis, self.model_axis
        k = cfg.clus.num_clusters
        kl = k // self.n_model

        def shard_fn(stacked, dirty, prev_index, prev_slots,
                     prev_store, pub_cent, pub_rep):
            state = jax.tree.map(lambda a: a[0], stacked)
            dc = jnp.minimum(dirty, k - 1)  # clipped gather (pads re-merge
            #                                 row k-1 and are then dropped)
            sub = ((state.clus.centroids[dc], state.clus.counts[dc],
                    state.rep_ids[dc]),
                   jax.tree.map(lambda a: a[dc], state.store),
                   state.hh)
            if data_axis is not None:
                sub = jax.lax.all_gather(sub, data_axis)
            else:
                sub = jax.tree.map(lambda a: a[None], sub)
            (s_cent, s_cnt, s_rep), s_store, s_hh = sub

            # counter merge stays full — O(S * Bmax), tiny
            m_hh = _merge_counters_stacked(cfg.hh, s_hh)
            # dirty-row cluster merge (the same math as
            # _merge_clusters_stacked, on the gathered row subset)
            n = jnp.sum(s_cnt, axis=0)
            wsum = jnp.sum(s_cent * s_cnt[..., None], axis=0)
            m_cent = jnp.where((n > 0)[:, None],
                               wsum / jnp.maximum(n, 1.0)[:, None], s_cent[0])
            m_rep = jnp.max(s_rep, axis=0)

            row = jnp.where(dirty >= k, k, dirty)  # k -> scatter-dropped
            new_cent = pub_cent.at[row].set(m_cent, mode="drop")
            new_rep = pub_rep.at[row].set(m_rep, mode="drop")
            cluster_dirty = jnp.zeros((k,), bool).at[row].set(True,
                                                              mode="drop")
            index, route_labels, slot_labels = stages.delta_upsert_snapshot(
                cfg.index, prev_index, prev_slots, m_hh, new_cent, new_rep,
                cluster_dirty)

            # dirty-row ring merge, scattered into the local store shard
            m_rows = docstore.merge_stacked(cfg.store, s_store)
            shard = (jax.lax.axis_index(model_axis)
                     if model_axis else jnp.int32(0))
            lrow = row - shard * kl
            lrow = jnp.where((row >= k) | (lrow < 0) | (lrow >= kl), kl,
                             lrow)
            store = docstore.scatter_rows(prev_store, m_rows, lrow)
            return index, route_labels, store, new_cent, new_rep, slot_labels

        def run(stacked, dirty, prev_index, prev_slots, prev_store,
                pub_cent, pub_rep):
            index_specs = shard_rules.leading_axis_pspecs(
                self._abstract_index(), None)
            store_specs = shard_rules.leading_axis_pspecs(
                docstore.init(cfg.store), model_axis)
            fn = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(shard_rules.leading_axis_pspecs(stacked, data_axis),
                          P(), index_specs, P(), store_specs, P(), P()),
                out_specs=(index_specs, P(), store_specs, P(), P(), P()),
                check_vma=False)
            return fn(stacked, dirty, prev_index, prev_slots,
                      prev_store, pub_cent, pub_rep)

        return jax.jit(run)

    def _abstract_index(self):
        return index_lib.init(self.cfg.index)

    def _build_rerank(self, k: int, nprobe: int, depth: int | None):
        cfg = self.cfg
        model_axis = self.model_axis
        use_pallas = cfg.clus.use_pallas

        def shard_fn(qn, routes, store):
            scales = (store.scales if store.embs.dtype == jnp.int8
                      else None)
            return distributed_rerank_topk(
                qn, store.embs, docstore.live_mask(store), store.ids,
                routes, k, model_axis, use_pallas=use_pallas,
                scales=scales, depth=depth)

        def run(qn, routes, store):
            fn = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(), P(),
                          shard_rules.leading_axis_pspecs(store, model_axis)),
                out_specs=(P(), P(), P()), check_vma=False)
            return fn(qn, routes, store)

        return jax.jit(run)

    def _build_serve(self, k: int, nprobe: int, depth: int | None):
        """Fused serve path over the cluster-sharded snapshot store: the
        (small) prototype index rides in replicated, every shard runs the
        one-program route + gather + dequant-rerank + top-k over its
        cluster slice, and the shards merge exactly like the staged
        ``_build_rerank`` (which stays as the pinned staged reference).
        ``depth`` is the (bucketed) QueryPlan rerank depth; one compiled
        program per (k, nprobe, depth)."""
        cfg = self.cfg
        model_axis = self.model_axis
        use_pallas = cfg.clus.use_pallas

        def shard_fn(qr, qn, vectors, valid, route_labels, store):
            scales = (store.scales if store.embs.dtype == jnp.int8
                      else None)
            return distributed_serve_topk(
                qr, qn, vectors, valid, route_labels, store.embs,
                docstore.live_mask(store), store.ids, k, nprobe,
                model_axis, use_pallas=use_pallas, scales=scales,
                depth=depth)

        def run(qr, qn, vectors, valid, route_labels, store):
            fn = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(), P(), P(), P(), P(),
                          shard_rules.leading_axis_pspecs(store, model_axis)),
                out_specs=(P(), P(), P(), P()), check_vma=False)
            return fn(qr, qn, vectors, valid, route_labels, store)

        return jax.jit(run)

    # -------------------------------------------------------------- protocol
    def ingest(self, x, doc_ids):
        """Ingest one global microbatch [B, d]: split contiguously into
        ``n_data`` shard sub-batches and advance every shard's local
        pipeline in parallel. Ragged batches (B not a multiple of the data
        axis) are padded with dead ``doc_id = -1`` sentinel rows — inert in
        every ingest stage and tombstoned by the store/rerank semantics —
        so a stream's final partial batch serves like any other. Returns
        None (per-shard infos stay local)."""
        x = jnp.asarray(x)
        ids = jnp.asarray(doc_ids, jnp.int32)
        B = x.shape[0]
        pad = -B % self.n_data
        if pad:  # device-resident inputs stay on device when unragged
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
            ids = jnp.concatenate(
                [ids, jnp.full((pad,), -1, jnp.int32)])
            B += pad
        xs = x.reshape(self.n_data, B // self.n_data, *x.shape[1:])
        idss = ids.reshape(self.n_data, B // self.n_data)
        self.ingest_sharded(xs, idss)

    def ingest_sharded(self, xs, idss):
        """Ingest pre-split sub-streams: xs [n_data, b, d], idss [n_data, b]."""
        sh = NamedSharding(self.mesh, self._data_spec)
        self.local = self._ingest_fn(
            self.local, jax.device_put(jnp.asarray(xs), sh),
            jax.device_put(jnp.asarray(idss, jnp.int32), sh))
        self._batches_since_reconcile += 1
        if self._batches_since_reconcile >= self.reconcile_every:
            self.reconcile()

    def _host_signature(self):
        """Per-shard (cluster counts, store ptrs, rep ids) — the exact
        change detector behind the per-cluster dirty mask. All three are
        monotone under kept assignments, and every snapshot-visible
        cluster mutation (centroid, ring write, representative) implies a
        kept assignment to that cluster."""
        return (np.asarray(self.local.clus.counts),
                np.asarray(self.local.store.ptr),
                np.asarray(self.local.rep_ids))

    def _publish(self, index, route_labels, store) -> ServingSnapshot:
        self._publish_version += 1
        self.serving = ServingSnapshot(index=index,
                                       route_labels=route_labels,
                                       store=store,
                                       version=self._publish_version,
                                       published_at=time.time())
        self._batches_since_reconcile = 0
        return self.serving

    def reconcile(self) -> ServingSnapshot:
        """Publish a fresh globally-consistent serving snapshot.

        ``reconcile_mode="delta"``: after the first (necessarily full)
        publish, diff the host signature to find dirty clusters and only
        re-merge those into the previous snapshot. The dirty count is
        bucketed to the next power of two (bounded compilations); above
        ``delta_max_frac`` of all clusters the full rebuild is cheaper and
        is used instead. Publications are bit-identical either way.
        """
        k = self.cfg.clus.num_clusters
        dirty_idx = sig = idx = None
        if self.reconcile_mode == "delta" and self._pub_cache is not None:
            sig = self._host_signature()
            dirty = np.zeros((k,), bool)
            for new, old in zip(sig, self._pub_sig):
                dirty |= np.any(new != old, axis=0)
            idx = np.nonzero(dirty)[0].astype(np.int32)
            if idx.size == 0:
                # no shard saw a kept doc since the last publish: the
                # counters are untouched too, so the snapshot is already
                # exact — republish it under a fresh version.
                self._pub_sig = sig
                self.last_publish_info = {"mode": "republish",
                                          "dirty_clusters": 0,
                                          "dirty_frac": 0.0,
                                          "dirty": idx}
                return self._publish(self.serving.index,
                                     self.serving.route_labels,
                                     self.serving.store)
            if idx.size <= self.delta_max_frac * k:
                dirty_idx = idx

        if dirty_idx is None:  # full rebuild (also seeds the delta cache)
            out = self._reconcile_fn(self.local)
            index, route_labels, store, m_cent, m_rep, slot_labels = out
            self._pub_cache = (m_cent, m_rep, slot_labels)
            if self.reconcile_mode == "delta":
                self._pub_sig = sig if sig is not None \
                    else self._host_signature()
            # ``dirty`` stays the EXACT change set when the signature was
            # diffed (a wide delta that fell back to the cheaper full
            # rebuild); None when there was no baseline to diff against —
            # consumers (the serving result cache) must then assume
            # everything changed.
            self.last_publish_info = {"mode": "full", "dirty_clusters": k,
                                      "dirty_frac": 1.0, "dirty": idx}
            return self._publish(index, route_labels, store)

        n_bucket = min(k, max(self.delta_bucket_min,
                              1 << (int(dirty_idx.size) - 1).bit_length()))
        fn = self._delta_fns.get(n_bucket)
        if fn is None:
            fn = self._delta_fns[n_bucket] = \
                self._build_delta_reconcile(n_bucket)
        padded = np.full((n_bucket,), k, np.int32)
        padded[:dirty_idx.size] = dirty_idx
        m_cent, m_rep, slot_labels = self._pub_cache
        index, route_labels, store, m_cent, m_rep, slot_labels = fn(
            self.local, jnp.asarray(padded), self.serving.index,
            slot_labels, self.serving.store, m_cent, m_rep)
        self._pub_cache = (m_cent, m_rep, slot_labels)
        self._pub_sig = sig
        self.last_publish_info = {"mode": "delta",
                                  "dirty_clusters": int(dirty_idx.size),
                                  "dirty_frac": float(dirty_idx.size) / k,
                                  "dirty": dirty_idx}
        return self._publish(index, route_labels, store)

    def prepare_publish(self):
        """Host-blocking publish prep: wait for the in-flight ingest
        execution the dirty signature reads. The async runtime calls this
        OUTSIDE its dispatch lock so concurrent queries never stall
        behind ingest execution during a publish."""
        if self.reconcile_mode == "delta":
            jax.block_until_ready((self.local.clus.counts,
                                   self.local.store.ptr, self.local.rep_ids))

    def publish(self) -> ServingSnapshot:
        """Serving-protocol alias: reconcile and return the snapshot."""
        return self.reconcile()

    # ------------------------------------------------------------ durability
    # the stacked [S, ...] engine state indexes clusters on axis 1 — the
    # axis ``serve.durability`` slices dirty-cluster delta checkpoints on
    ckpt_cluster_axis = 1

    def checkpoint_state(self):
        """The stacked shard-local pytree the durability layer
        checkpoints; doubles as the abstract tree recovery restores into
        (checkpoints are mesh-elastic: restore re-shards onto the current
        mesh, like ``train.checkpoint``)."""
        return self.local

    def restore_state(self, stacked) -> None:
        """Adopt a recovered stacked state onto this engine's mesh. Every
        publication baseline drops so the next reconcile is a full
        rebuild publishing ``dirty=None`` — the clear-everything event
        the serving caches key on, i.e. cache coherence after recovery."""
        self.local = jax.device_put(
            stacked,
            shard_rules.engine_state_shardings(self.mesh, stacked,
                                               self.data_axis))
        self.serving = None
        self._pub_cache = None
        self._pub_sig = None
        self.last_publish_info = None
        self._batches_since_reconcile = 0

    def query(self, q, k: int = 10, *, two_stage: bool = False,
              nprobe: int = 8, plan=None):
        """Same contract as ``pipeline.query`` over the latest snapshot."""
        if self.serving is None:
            self.reconcile()
        return self.query_snapshot(self.serving, q, k, two_stage=two_stage,
                                   nprobe=nprobe, plan=plan)

    def query_snapshot(self, snap: ServingSnapshot, q, k: int = 10, *,
                       two_stage: bool = False, nprobe: int = 8,
                       plan=None, staged: bool = False):
        """Answer from an explicitly published snapshot (the async runtime
        pins the snapshot it hands out per batch, so in-flight queries are
        isolated from concurrent reconciles).

        Two-stage queries run the FUSED serve path; ``staged=True`` forces
        the original route-program + rerank-program composition — kept as
        the pinned reference the fused path is ids-identical to (parity
        tests and the staged-vs-fused benchmark drive it). ``plan`` (an
        ``engine.plan.QueryPlan``, pre-bucketed) overrides (nprobe, rerank
        depth) for this call on both paths; shards all apply the same
        ring-prefix clip, so plan queries stay parity with single-device.
        """
        q = jnp.asarray(q, jnp.float32)
        cfg = self.cfg
        if not two_stage:
            scores, rows, ids = index_lib.search(cfg.index, snap.index, q, k)
            return scores, rows, ids, snap.route_labels[rows]

        nprobe, depth = _resolve_plan(plan, nprobe)
        store_depth = cfg.store_depth
        depth_eff = (store_depth if depth is None
                     else min(depth, store_depth))
        assert store_depth > 0, "two_stage requires store_depth > 0"
        assert k <= nprobe * depth_eff, "k must be <= nprobe * plan depth"
        if staged:
            routes = stages.route(cfg.index, snap.index, snap.route_labels,
                                  q, nprobe)
            qn = l2_normalize(q)
            if self.model_axis is None:
                scores, pos = stages.rerank(snap.store, qn, routes, k,
                                            cfg.clus.use_pallas,
                                            depth=depth_eff)
                return stages.decode_rerank(snap.store.ids, routes, scores,
                                            pos, depth_eff, nprobe,
                                            store_depth=store_depth)
            key = (k, nprobe, depth_eff)
            if key not in self._rerank_fns:
                self._rerank_fns[key] = self._build_rerank(k, nprobe,
                                                           depth_eff)
            scores, pos, doc_ids = self._rerank_fns[key](qn, routes,
                                                         snap.store)
            return stages.decode_rerank(None, routes, scores, pos, depth_eff,
                                        nprobe, doc_ids=doc_ids,
                                        store_depth=store_depth)
        if self.model_axis is None:
            return snapshot_query_impl(
                cfg, snap.index, snap.route_labels, snap.store, q, k,
                two_stage=True, nprobe=nprobe, depth=depth)
        qn = l2_normalize(q)
        qr = qn if cfg.index.normalize else q
        key = (k, nprobe, depth_eff)
        if key not in self._serve_fns:
            self._serve_fns[key] = self._build_serve(k, nprobe, depth_eff)
        scores, pos, doc_ids, routes = self._serve_fns[key](
            qr, qn, snap.index.vectors, snap.index.valid, snap.route_labels,
            snap.store)
        return stages.decode_rerank(None, routes, scores, pos, depth_eff,
                                    nprobe, doc_ids=doc_ids,
                                    store_depth=store_depth)

    # ------------------------------------------------------------ accounting
    def device_counters(self) -> dict:
        """Fetch the in-graph pipeline counters across every data shard as
        ONE small host transfer (a [S, N] i32 matrix), decoded with the
        per-counter combine rules (arrivals sum, fill levels min/max, ...).
        Called by the serving runtime at publish time only — never on the
        query or per-batch ingest path. Delta-publication accounting
        (``last_publish_info``) rides along as plain host numbers."""
        if self._counters_fn is None:
            self._counters_fn = jax.jit(jax.vmap(
                functools.partial(stages.pipeline_counters, self.cfg)))
        stacked = np.asarray(self._counters_fn(self.local))
        out = stages.decode_pipeline_counters(stacked)
        if self.last_publish_info is not None:
            out["publish_dirty_clusters"] = \
                self.last_publish_info["dirty_clusters"]
            out["publish_dirty_frac"] = self.last_publish_info["dirty_frac"]
        return out

    def index_size(self) -> int:
        if self.serving is None:
            self.reconcile()
        return int(index_lib.size(self.serving.index))

    def state_memory_bytes(self) -> int:
        return pipeline.state_memory_bytes(self.cfg)

    def store_bytes_per_device(self) -> int:
        """Resident serving-store bytes on ONE device (cluster sharding
        divides the ring buffers across the model axis)."""
        if self.serving is None:
            self.reconcile()
        total = 0
        for leaf in jax.tree.leaves(self.serving.store):
            shard_shape = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shard_shape)) * leaf.dtype.itemsize
        return total
