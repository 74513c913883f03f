"""Single-device composition of the engine stages.

``ingest_impl`` is the un-jitted ingest composition — ``core.pipeline``
exposes it behind its jit-compiled public API (``ingest_batch``), and
``engine.sharded`` calls the very same function inside ``shard_map``, so
single- and multi-device ingest share one implementation.
``snapshot_query_impl`` is the one single-device query composition: the
served path runs it over a published snapshot's leaves, ``pipeline.query``
over live state, and ``ShardedEngine`` over a snapshot on a mesh with no
model axis.

``Engine`` wraps (cfg, state) behind the small serving protocol
(``ingest``/``query``/``publish``/``query_snapshot``/``index_size``) that
``serve.runtime.AsyncServer`` is built on; ``sharded.ShardedEngine``
implements the same protocol over a device mesh.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import index as index_lib, pipeline
from repro.engine import stages
from repro.store import docstore


class ServingSnapshot(NamedTuple):
    """The immutable queryable state a streaming engine publishes.

    Queries read ONLY published snapshots (atomic reference swap on the
    host), never the live ingest state — the async runtime's "index
    refresh without interrupting queries". ``version`` is a host-side
    publish sequence number and ``published_at`` the wall-clock publish
    timestamp (``time.time()``; 0.0 = never published, e.g. host-oracle
    snapshots) — both plain host scalars that never enter jit; snapshot
    age in ``freshness_stats()`` is ``now - published_at``.
    """

    index: index_lib.FlatIndex   # replicated across devices
    route_labels: jnp.ndarray    # [bmax] i32 slot -> cluster (-1 dead)
    store: docstore.DocStore     # full, or cluster-sharded over "model"
    version: int = 0
    published_at: float = 0.0


def ingest_impl(cfg: "pipeline.PipelineConfig", state: "pipeline.PipelineState",
                x: jnp.ndarray, doc_ids: jnp.ndarray):
    """Process one microbatch of embeddings [B, d] with external ids [B] i32.

    Rows with ``doc_ids < 0`` are *dead* (ragged-batch padding): they never
    touch the prefilter window, centroids, counters, representatives, or
    the doc store, and they don't count as arrivals — only the per-item
    counter rng stream still advances (it is split per batch slot). Live
    batches (all ids >= 0) behave exactly as before.

    Returns (new_state, info dict of per-batch diagnostics).
    """
    B = x.shape[0]
    k = cfg.clus.num_clusters
    rng, k_hh = jax.random.split(state.rng)

    live = doc_ids >= 0
    n_live = jnp.sum(live.astype(jnp.int32))
    # fused admission: screen + assign + quantize-on-admit in ONE device
    # program (stages.admit -> kernels.admit); the store rows arrive at
    # the ring write below already in the store dtype
    # each stage runs under a named scope, so a profiler trace names the
    # device ops of each (the ``op_name`` of their HLO instructions)
    with jax.named_scope("admit"):
        pre, r, keep, clus, labels, sims, v, vscale = stages.admit(
            cfg.pre, cfg.clus, cfg.store, state.pre, state.clus, x, live)
    with jax.named_scope("counter_scan"):
        hh, masked_labels, hh_info = stages.count(cfg.hh, state.hh, labels,
                                                  keep, k_hh)
    with jax.named_scope("representatives"):
        rep_ids, rep_sims = stages.update_representatives(
            state.rep_ids, state.rep_sims, labels, sims, doc_ids, keep, k)

    stored = keep & (hh_info["admitted"] | hh_info["hit"])
    # arrival index among live rows (== arange(B) for an unpadded batch)
    stamps = state.arrivals + jnp.cumsum(live.astype(jnp.int32)) - 1
    with jax.named_scope("ring_write"):
        store = stages.store_write(cfg.store, state.store, x, labels, stored,
                                   doc_ids, stamps, v=v, vscale=vscale)

    since = state.since_upsert + n_live
    refresh = since >= cfg.update_interval
    with jax.named_scope("upsert"):
        new_index, route_labels = jax.lax.cond(
            refresh,
            lambda args: stages.upsert_snapshot(cfg.index, args[0], hh,
                                                clus.centroids, rep_ids),
            lambda args: args,
            (state.index, state.route_labels))

    new_state = pipeline.PipelineState(
        pre=pre, clus=clus, hh=hh, index=new_index, store=store,
        route_labels=route_labels,
        rep_ids=rep_ids, rep_sims=rep_sims,
        arrivals=state.arrivals + n_live,
        since_upsert=jnp.where(refresh, 0, since),
        kept=state.kept + jnp.sum(keep.astype(jnp.int32)),
        upserts=state.upserts + refresh.astype(jnp.int32),
        rng=rng,
    )
    info = {
        "relevance": r,
        "keep": keep,
        "labels": masked_labels,
        "sims": sims,
        "admitted": hh_info["admitted"],
        "evicted_label": hh_info["evicted_label"],
        "stored": stored,
        "refreshed": refresh,
    }
    return new_state, info


@functools.partial(jax.jit,
                   static_argnames=("cfg", "k", "two_stage", "nprobe",
                                    "depth"))
def snapshot_query_impl(cfg: "pipeline.PipelineConfig", index, route_labels,
                        store, q: jnp.ndarray, k: int, *, two_stage: bool,
                        nprobe: int, depth: int | None = None):
    """Retrieve top-k from (index, route_labels, store) — a published
    ServingSnapshot's leaves, or live state's: (scores [Q,k], rows [Q,k],
    doc_ids [Q,k], clusters [Q,k]).

    ``depth`` is a QueryPlan's rerank depth (ring slots read per routed
    cluster); None or >= store_depth is full effort. Callers pass
    *bucketed* plans (``engine.plan``) — each distinct (nprobe, depth) is
    one compiled variant."""
    if not two_stage:
        scores, rows, ids = index_lib.search(cfg.index, index, q, k)
        return scores, rows, ids, route_labels[rows]
    store_depth = cfg.store_depth
    depth_eff = store_depth if depth is None else min(depth, store_depth)
    assert store_depth > 0, "two_stage requires store_depth > 0"
    assert k <= nprobe * depth_eff, "k must be <= nprobe * plan depth"
    # the ONE two-stage query implementation: fused route + gather +
    # dequant-rerank + top-k (staged route -> rerank when use_pallas=False)
    scores, pos, routes = stages.serve_topk(
        cfg.index, index, route_labels, store, q, k, nprobe,
        cfg.clus.use_pallas, depth=depth_eff)
    return stages.decode_rerank(store.ids, routes, scores, pos, depth_eff,
                                nprobe, store_depth=store_depth)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _pipeline_counters_jit(cfg: "pipeline.PipelineConfig",
                           state: "pipeline.PipelineState"):
    return stages.pipeline_counters(cfg, state)


def _resolve_plan(plan, nprobe: int) -> tuple[int, int | None]:
    """Unpack a QueryPlan into the engine's static (nprobe, depth) args.
    A shed plan must never reach an engine — the serving layer answers
    shed flushes directly with an explicit marker."""
    if plan is None:
        return nprobe, None
    assert not plan.shed, "shed plans are answered by the serving layer"
    return plan.nprobe, plan.depth


class Engine:
    """Single-device streaming engine: (cfg, PipelineState) behind the
    serving protocol. ``ShardedEngine`` implements the same protocol over
    a mesh — the server never branches on which one it holds."""

    def __init__(self, cfg: "pipeline.PipelineConfig", key: jax.Array,
                 warmup: jnp.ndarray | None = None,
                 state: "pipeline.PipelineState | None" = None):
        self.cfg = cfg
        self.state = (pipeline.init(cfg, key, warmup)
                      if state is None else state)
        self._version = 0
        # per-publish dirty-cluster accounting, same contract as
        # ``ShardedEngine.last_publish_info``: {"mode", "dirty_clusters",
        # "dirty_frac", "dirty"} where ``dirty`` is the exact np index
        # array of clusters whose snapshot-visible state can have changed
        # since the previous publish (None on the first publish — no
        # baseline). The serving result cache invalidates against it.
        self._pub_sig = None
        self.last_publish_info: dict | None = None

    def ingest(self, x: jnp.ndarray, doc_ids: jnp.ndarray) -> dict:
        self.state, info = pipeline.ingest_batch(
            self.cfg, self.state, jnp.asarray(x),
            jnp.asarray(doc_ids, jnp.int32))
        return info

    def query(self, q: jnp.ndarray, k: int = 10, *, two_stage: bool = False,
              nprobe: int = 8, plan=None):
        """Retrieve top-k. ``plan`` (an ``engine.plan.QueryPlan``)
        overrides (nprobe, rerank depth) for this call; callers hand in
        *bucketed* plans so the compiled-variant count stays bounded.
        Shed plans never reach the engine (the serving layer answers
        them directly)."""
        nprobe, depth = _resolve_plan(plan, nprobe)
        return pipeline.query(self.cfg, self.state, jnp.asarray(q),
                              k, two_stage=two_stage, nprobe=nprobe,
                              depth=depth)

    def publish(self) -> ServingSnapshot:
        """Copy the queryable sub-state into an immutable serving snapshot.

        The copy decouples the snapshot from ingest buffer donation:
        ``pipeline.ingest_batch`` donates the previous state, so a snapshot
        that aliased it would be invalidated by the very next ingest step —
        exactly the torn read the async runtime must never produce.
        """
        st = self.state
        self._version += 1
        tr = obs.tracer()
        with (tr.span("publish.signature", cat="ingest")
              if tr is not None else obs.NO_SPAN):
            sig = self._host_signature()
        self._update_publish_info(sig)
        with (tr.span("publish.copy", cat="ingest")
              if tr is not None else obs.NO_SPAN):
            return ServingSnapshot(
                index=jax.tree.map(jnp.copy, st.index),
                route_labels=jnp.copy(st.route_labels),
                store=jax.tree.map(jnp.copy, st.store),
                version=self._version,
                published_at=time.time(),
            )

    def _host_signature(self):
        """(cluster counts, ring write ptrs, rep ids) — the exact change
        detector ``engine.sharded`` uses per shard: all three are monotone
        under kept assignments and every snapshot-visible cluster mutation
        (centroid, ring write, representative) implies one."""
        st = self.state
        return (np.asarray(st.clus.counts), np.asarray(st.store.ptr),
                np.asarray(st.rep_ids))

    def prepare_publish(self):
        """Host-blocking publish prep (serving-runtime hook): wait for
        in-flight ingest execution OUTSIDE the runtime's dispatch lock so
        the signature fetch in ``publish`` never stalls a query."""
        st = self.state
        jax.block_until_ready((st.clus.counts, st.store.ptr, st.rep_ids))

    def _update_publish_info(self, sig):
        """Diff the host signature ``sig`` against the previous publish's
        to name the exact dirty-cluster set this publication can have
        changed."""
        k = self.cfg.clus.num_clusters
        if self._pub_sig is None:
            self.last_publish_info = {"mode": "full", "dirty_clusters": k,
                                      "dirty_frac": 1.0, "dirty": None}
        else:
            dirty = np.zeros((k,), bool)
            for new, old in zip(sig, self._pub_sig):
                dirty |= new != old
            idx = np.nonzero(dirty)[0].astype(np.int32)
            self.last_publish_info = {
                "mode": "delta" if idx.size else "republish",
                "dirty_clusters": int(idx.size),
                "dirty_frac": float(idx.size) / k,
                "dirty": idx,
            }
        self._pub_sig = sig

    # ------------------------------------------------------------ durability
    # PipelineState leaves index clusters on their leading axis — the axis
    # ``serve.durability`` slices dirty-cluster delta checkpoints on.
    ckpt_cluster_axis = 0

    def checkpoint_state(self):
        """The pytree the durability layer checkpoints; doubles as the
        abstract tree (shapes/dtypes/structure) recovery restores into."""
        return self.state

    def restore_state(self, state) -> None:
        """Adopt a recovered state. The publish baseline resets so the
        next publication reports mode "full" with ``dirty=None`` — the
        event the serving caches treat as clear-everything, which is the
        cache-coherence contract after recovery."""
        self.state = jax.device_put(state)
        self._pub_sig = None
        self.last_publish_info = None

    def query_snapshot(self, snap: ServingSnapshot, q: jnp.ndarray,
                       k: int = 10, *, two_stage: bool = False,
                       nprobe: int = 8, plan=None):
        """Same contract as ``query``, answered from a published snapshot."""
        nprobe, depth = _resolve_plan(plan, nprobe)
        return snapshot_query_impl(
            self.cfg, snap.index, snap.route_labels, snap.store,
            jnp.asarray(q, jnp.float32), k, two_stage=two_stage,
            nprobe=nprobe, depth=depth)

    def index_size(self) -> int:
        return int(index_lib.size(self.state.index))

    def device_counters(self) -> dict:
        """Fetch the in-graph pipeline counters as ONE small host
        transfer (a [1, N] i32 vector). Called by the serving runtime at
        publish time only — never on the query or per-batch ingest path —
        so metrics-enabled serving adds zero device syncs to queries."""
        vec = np.asarray(_pipeline_counters_jit(self.cfg, self.state))
        return stages.decode_pipeline_counters(vec[None])

    def state_memory_bytes(self) -> int:
        return pipeline.state_memory_bytes(self.cfg)

    def store_bytes_per_device(self) -> int:
        return docstore.memory_bytes(self.cfg.store)
