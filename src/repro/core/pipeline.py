"""The Streaming RAG pipeline (paper Algorithm 1), fused per-microbatch.

    x_t --Pre-filter--> x̃_t --Cluster--> μ_j* --Heavy-Hitter--> C_t
        --Index-Update--> I_t

State is a single pytree: jit-compiled ingest steps, `lax.scan`-able over
stream chunks (throughput benches), checkpointable (fault tolerance), and
shard-mergeable (distributed ingest). Per-arrival semantics inside a
microbatch are preserved by scanning the counter update item-by-item.

Each cluster also tracks a *representative document* (the best-similarity
member seen so far) so retrieval can surface concrete documents for the
downstream QA/summarization benches, not just prototype vectors.

The per-stage implementation lives in ``repro.engine`` (stages.py composed
by engine.py); this module keeps the public config/state types and the
jit-compiled single-device entry points, which stay bit-identical to the
pre-engine fused step. ``repro.engine.sharded`` composes the same stages
under ``shard_map`` for multi-device ingest/serving.

On top of the prototype index sits a tiered document store
(``repro.store``): per cluster, a ring buffer of the ``store_depth`` most
recently *admitted* documents. ``query(..., two_stage=True)`` then runs
routed two-stage retrieval — the prototype index routes each query to its
top-``nprobe`` clusters and the routed ring buffers are exact-reranked
(``repro.kernels.rerank``), so retrieval covers many real documents per
relevant cluster instead of one representative.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import clustering, heavy_hitter, index as index_lib, prefilter
from repro.store import docstore


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Defaults follow paper Table 2."""

    pre: prefilter.PrefilterConfig = prefilter.PrefilterConfig()
    clus: clustering.ClusterConfig = clustering.ClusterConfig()
    hh: heavy_hitter.HHConfig = heavy_hitter.HHConfig()
    update_interval: int = 1000   # index upsert every N arrivals
    # Docs kept per cluster for two-stage retrieval. 0 (default) disables
    # the doc store so prototype-only configs keep the paper's memory
    # footprint; two-stage configs opt in explicitly.
    store_depth: int = 0
    # Ring embedding precision: "fp32", or "int8" (quantize-on-admit with
    # per-slot fp32 scales — ~4x deeper rings at the same store budget;
    # the rerank kernel dequantizes in VMEM with fp32 accumulation).
    store_dtype: str = "fp32"

    @property
    def index(self) -> index_lib.IndexConfig:
        return index_lib.IndexConfig(
            capacity=self.hh.bmax(), dim=self.clus.dim,
            normalize=True, use_pallas=self.clus.use_pallas)

    @property
    def store(self) -> docstore.StoreConfig:
        return docstore.StoreConfig(
            num_clusters=self.clus.num_clusters, depth=self.store_depth,
            dim=self.clus.dim, normalize=True,
            store_dtype=self.store_dtype)

    def __post_init__(self):
        assert self.pre.dim == self.clus.dim, "prefilter/cluster dim mismatch"
        assert self.store_depth >= 0
        assert self.store_dtype in docstore.STORE_DTYPES, self.store_dtype


class PipelineState(NamedTuple):
    pre: prefilter.PrefilterState
    clus: clustering.ClusterState
    hh: heavy_hitter.HHState
    index: index_lib.FlatIndex
    store: docstore.DocStore  # per-cluster ring buffers of admitted docs
    # [bmax] i32 — cluster label per index slot, snapshotted at upsert time.
    # Routing must read THIS, not the live hh labels: the counter rewrites
    # its slots on eviction immediately, while index vectors only refresh
    # every update_interval arrivals — a live lookup would score a slot
    # against one cluster's centroid and rerank a different cluster's ring.
    route_labels: jnp.ndarray
    rep_ids: jnp.ndarray      # [k] i32 best-similarity doc id per cluster
    rep_sims: jnp.ndarray     # [k] f32
    arrivals: jnp.ndarray     # i32 — total docs seen (stream offset)
    since_upsert: jnp.ndarray  # i32
    kept: jnp.ndarray         # i32 — passed the pre-filter
    upserts: jnp.ndarray      # i32 — index refresh batches
    rng: jax.Array


def init(cfg: PipelineConfig, key: jax.Array,
         warmup: jnp.ndarray | None = None) -> PipelineState:
    k1, k2, k3 = jax.random.split(key, 3)
    clus = (clustering.init_from_buffer(cfg.clus, k2, warmup)
            if warmup is not None else clustering.init(cfg.clus, k2))
    k_clusters = cfg.clus.num_clusters
    return PipelineState(
        pre=prefilter.init(cfg.pre, k1, warmup),
        clus=clus,
        hh=heavy_hitter.init(cfg.hh),
        index=index_lib.init(cfg.index),
        store=docstore.init(cfg.store),
        route_labels=jnp.full((cfg.hh.bmax(),), -1, jnp.int32),
        rep_ids=jnp.full((k_clusters,), -1, jnp.int32),
        rep_sims=jnp.full((k_clusters,), -jnp.inf, jnp.float32),
        arrivals=jnp.int32(0),
        since_upsert=jnp.int32(0),
        kept=jnp.int32(0),
        upserts=jnp.int32(0),
        rng=k3,
    )


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def ingest_batch(cfg: PipelineConfig, state: PipelineState,
                 x: jnp.ndarray, doc_ids: jnp.ndarray):
    """Process one microbatch of embeddings [B, d] with external ids [B] i32.

    Returns (new_state, info dict of per-batch diagnostics). The
    implementation lives in ``repro.engine`` as a composition of the
    engine stages (fused admit — screen + assign + quantize-on-admit in
    one device program — then count, store-write, upsert-snapshot, route,
    rerank) shared with the ``shard_map`` multi-device path; this wrapper
    only adds jit + buffer donation.
    """
    from repro.engine.engine import ingest_impl

    return ingest_impl(cfg, state, x, doc_ids)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def ingest_stream(cfg: PipelineConfig, state: PipelineState,
                  chunks: jnp.ndarray, chunk_ids: jnp.ndarray) -> PipelineState:
    """lax.scan ingest over [n_batches, B, d] (+ ids [n_batches, B]).

    This is the throughput-bench entry point: one device dispatch for the
    whole stream chunk.
    """

    def step(s, xs):
        xb, ib = xs
        s2, _ = ingest_batch(cfg, s, xb, ib)
        return s2, None

    out, _ = jax.lax.scan(step, state, (chunks, chunk_ids))
    return out


@functools.partial(jax.jit,
                   static_argnames=("cfg", "k", "two_stage", "nprobe",
                                    "depth"))
def query(cfg: PipelineConfig, state: PipelineState, q: jnp.ndarray,
          k: int = 10, *, two_stage: bool = False, nprobe: int = 8,
          depth: int | None = None):
    """Retrieve top-k: (scores [Q,k], rows [Q,k], doc_ids [Q,k], clusters [Q,k]).

    two_stage=False — prototype-only: top-k over the prototype index; rows
    are index slots, doc_ids the per-cluster representative docs.

    two_stage=True — routed exact retrieval: the prototype index routes
    each query to its top-``nprobe`` clusters (stage 1), whose document
    ring buffers are gathered and exact-reranked by the fused Pallas
    kernel (stage 2). rows are flat store positions
    cluster*store_depth + slot, doc_ids real stored documents; dead
    entries are -1. ``depth`` clips the rerank to the first ``depth``
    ring slots per routed cluster (a QueryPlan's effort; None = full
    ring). (nprobe, depth) are jit-static — pass bucketed plans
    (``engine.plan.PlanSpace``) to bound the compiled-variant count.
    """
    from repro.engine.engine import snapshot_query_impl

    return snapshot_query_impl(cfg, state.index, state.route_labels,
                               state.store, q, k, two_stage=two_stage,
                               nprobe=nprobe, depth=depth)


def state_memory_bytes(cfg: PipelineConfig) -> int:
    """Peak resident bytes of the pipeline state (paper's memory metric)."""
    d = cfg.clus.dim
    k = cfg.clus.num_clusters
    bmax = cfg.hh.bmax()
    pre_w = cfg.pre.window if cfg.pre.basis == "adaptive" else 1
    n = cfg.pre.num_vectors
    cms = cfg.hh.cms_depth * cfg.hh.cms_width * 4
    pre_b = (n * d + pre_w * d) * 4
    clus_b = (k * d + k) * 4
    hh_b = bmax * 8 + cms
    idx_b = index_lib.memory_bytes(cfg.index) + bmax * 4  # + route labels
    rep_b = k * 8
    store_b = docstore.memory_bytes(cfg.store)
    return pre_b + clus_b + hh_b + idx_b + rep_b + store_b


def budget_to_config(memory_mb: float, dim: int = 384,
                     base: PipelineConfig | None = None) -> PipelineConfig:
    """Map a memory budget to (k, B) the way the paper's sweep does (Table 6):
    split the budget ~80/20 between cluster prototypes and index+window.

    Doc-store bytes are folded into the prototype side of the split via
    ``docstore.memory_bytes`` — each cluster pays for its full ring
    (dtype-aware: int8 rings cost ~4x less per slot than fp32), so Table 6
    sweeps stay honest for deep and/or quantized ring configs instead of
    silently blowing the budget on unaccounted store bytes."""
    base = base or PipelineConfig()
    budget = memory_mb * 1e6
    per_proto = dim * 4 * 2 + 24          # centroid + index row + bookkeeping
    # doc rings hang off clusters only — index/counter slots carry no ring.
    # One cluster's ring cost comes from the SAME accounting the state
    # reports (emb dtype + id/stamp/scale overhead + write counter).
    per_cluster = per_proto + docstore.memory_bytes(docstore.StoreConfig(
        num_clusters=1, depth=base.store_depth, dim=dim,
        store_dtype=base.store_dtype))
    k = max(16, int(budget * 0.8 / per_cluster))
    b = max(16, min(k, int(budget * 0.2 / per_proto)))
    return dataclasses.replace(
        base,
        pre=dataclasses.replace(base.pre, dim=dim),
        clus=dataclasses.replace(base.clus, num_clusters=k, dim=dim),
        hh=dataclasses.replace(base.hh, capacity=b, max_capacity=None),
    )
