"""The serving runtime: background ingest with snapshot swaps, and the
micro-batching query front end that answers from the snapshots.

``AsyncServer`` decouples the ingest and query paths — the paper's
"index refresh without interrupting queries" as an actual server shape:

  * a background **ingest thread** drains a bounded stream queue into the
    engine (single-device ``Engine`` or mesh-backed ``ShardedEngine``)
    and every ``publish_every`` batches publishes an immutable
    ``ServingSnapshot`` through an atomic reference swap;
  * the caller-facing **query front end** (micro-batching, monotone
    tickets, bounded latency window) answers every batch from the one
    snapshot reference it read at flush time — queries never block on
    ingest or reconcile, and never observe a half-published state
    (snapshots are functionally constructed; the swap is a single Python
    reference assignment).

Freshness is explicit, not accidental: ``freshness_stats()`` reports the
doc lag between what was ingested and what the published snapshot
serves, and every answer carries the ``snapshot_version`` it was served
from.

Retrieval effort is a per-flush :class:`~repro.engine.plan.QueryPlan`
(two-stage serving): every flush picks (nprobe, rerank depth, shed)
from a fixed :class:`~repro.engine.plan.PlanSpace` bucket ladder.
``ServerConfig.adaptive`` arms the hysteretic degradation controller —
under queue pressure it shrinks depth, then nprobe, then sheds, and
every degraded answer says so explicitly (``degraded``/``shed`` keys +
the plan served). The overload behavior is measured by
``benchmarks/table20_overload``.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import queue
import random
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import pipeline
from repro.engine import stages
from repro.engine.engine import Engine, _resolve_plan
from repro.engine.plan import PlanSpace
from repro.serve.durability import (DurabilityConfig, DurableIngest,
                                    classify_error)
from repro.serve.executor import DegradationController, PriorityDispatcher
from repro.serve.hotset import HotSet
from repro.serve.result_cache import ResultCache
from repro.testing import faults


@dataclasses.dataclass
class ServerConfig:
    max_batch: int = 64
    max_wait_ms: float = 2.0
    topk: int = 10
    two_stage: bool = False    # routed two-stage retrieval (document store)
    nprobe: int = 8            # clusters routed per query when two_stage
    latency_window: int = 1024  # per-batch latencies kept for p50/p99
    # ---- query-adaptive serving (two_stage only) ----
    # adaptive=True arms the degradation controller: under queue pressure
    # each flush walks the PlanSpace ladder (full -> shrink depth ->
    # shrink nprobe -> shed) and answers carry an explicit ``degraded``/
    # ``shed`` marker. adaptive=False always serves the full-effort plan
    # (bit-identical to pre-plan serving).
    adaptive: bool = False
    max_queue_depth: int = 256  # pending queries (post-flush) that escalate
    low_queue_depth: int | None = None  # recovery watermark (None = high//4)
    recover_after: int = 4      # calm flushes required to step back up
    min_depth: int = 1          # floor of the depth ladder
    min_nprobe: int = 1         # floor of the nprobe ladder
    # ---- hot-set serving cache (two_stage only) ----
    # cache_entries > 0 arms the snapshot-versioned exact result cache
    # (``serve.result_cache``): repeat queries answer from recorded exact
    # results, delta publications invalidate only entries routed through
    # dirty clusters. hotset=True arms the query-side heavy-hitter hot
    # set (``serve.hotset``): the hot route sets' clusters pin into a
    # compact fast tier served through the fused kernel dispatcher.
    # Both are bit-identical to uncached serving whenever they answer.
    cache_entries: int = 0      # result-cache capacity (0 = disabled)
    hotset: bool = False        # pinned hot-tier serving
    pin_budget_mb: float = 8.0  # hot-tier budget, charged against
    #                             state_memory_bytes (pow2-floored rows)
    hotset_capacity: int = 32   # HH tracker slots (route-set signatures)
    hotset_refresh: int = 16    # flushes between hot-set reselections
    hotset_min_count: int = 2   # min tracked count before a set pins


def _pad_pow2(q: np.ndarray) -> np.ndarray:
    """Zero-pad a query batch to the next power-of-two row count. Every
    serve program is row-independent, so padding can never change a real
    row's answer — it only bounds the compiled shape count (one variant
    per pow2 bucket instead of one per sub-batch size)."""
    b = q.shape[0]
    n = 1 << (b - 1).bit_length()
    if n == b:
        return q
    return np.concatenate([q, np.zeros((n - b, q.shape[1]), q.dtype)])


@functools.partial(jax.jit, static_argnames=("index_cfg", "nprobe"))
def _route_batch(index_cfg, index, route_labels, q, nprobe):
    """The staged stage-1 route pass (``stages.route`` — the reference
    the fused serve kernel's routes are pinned bit-identical to), as one
    small jitted program: the cached serving path runs it once per flush
    to witness cache exactness, hot-tier coverage, and hot-set tracking."""
    return stages.route(index_cfg, index, route_labels, q, nprobe)


def fetch_answers(arrays):
    """Host copies of a batch's answer arrays, and the number of
    device-to-host transfers that made them.

    Every transfer has a fixed cost far above the bytes of a top-k
    answer. Waited on one after another, a batch pays it once per array;
    so the transfers of all device arrays (``jax.Array``) are issued at
    once and only then read, and they overlap. The arrays come back as
    they are, dtypes included; host arrays, such as shed or cached
    answers, pass through untouched."""
    device = [a for a in arrays if isinstance(a, jax.Array)]
    for a in device:
        a.copy_to_host_async()
    return tuple(np.asarray(a) for a in arrays), len(device)


class _Transfers(threading.local):
    """Device-to-host transfers made by the flush running on this
    thread (a flush runs on one thread; several may run at once)."""
    n = 0


class AsyncServer:
    """Background-ingest serving runtime over any engine.

    ``ingest`` enqueues a stream batch and returns immediately (bounded
    queue — a full queue applies backpressure by blocking the producer,
    never the query path). The ingest thread drains the queue into the
    engine and publishes a snapshot every ``publish_every`` batches; the
    final publish on ``close``/``sync`` covers the tail. ``flush``
    answers from the snapshot reference it reads once per batch, so a
    concurrent publish can never tear an in-flight answer.

    For a ``ShardedEngine``, construct it with a huge ``reconcile_every``
    and let the runtime's publish cadence drive reconciliation (pass
    ``reconcile_mode="delta"`` to amortize frequent publishes).

    Tickets are monotone for the life of the server — they never
    restart after a flush — and each answer dict carries its
    ``ticket`` so callers can join answers back to submissions.
    """

    _STOP = object()

    def __init__(self, cfg: pipeline.PipelineConfig,
                 server_cfg: ServerConfig, key: jax.Array | None = None,
                 warmup=None,
                 embed_fn: Callable[[np.ndarray], np.ndarray] | None = None,
                 engine=None, publish_every: int = 4, queue_max: int = 64,
                 durability: DurabilityConfig | None = None,
                 max_restarts: int = 8, backoff_base_s: float = 0.01,
                 backoff_max_s: float = 1.0, supervise_seed: int = 0):
        if server_cfg.two_stage:  # fail at construction, not first flush
            assert cfg.store_depth > 0, \
                "two_stage serving needs a PipelineConfig with store_depth > 0"
            assert server_cfg.topk <= server_cfg.nprobe * cfg.store_depth, \
                "topk must be <= nprobe * store_depth"
            assert server_cfg.nprobe <= cfg.hh.bmax(), \
                "nprobe must be <= the prototype index capacity"
        assert not (server_cfg.cache_entries or server_cfg.hotset) \
            or server_cfg.two_stage, \
            "the hot-set serving cache requires two_stage=True (cached " \
            "answers record routed clusters)"
        self.cfg = cfg
        self.scfg = server_cfg
        self.embed_fn = embed_fn
        # retrieval-effort plan machinery (two_stage only): the plan
        # space's fixed bucket ladder bounds the compiled serve variants;
        # adaptive serving walks it under queue pressure
        self.plan_space: PlanSpace | None = None
        self._full_plan = None
        self._controller: DegradationController | None = None
        if server_cfg.two_stage:
            self.plan_space = PlanSpace(
                nprobe=server_cfg.nprobe, depth=cfg.store_depth,
                k=server_cfg.topk, min_depth=server_cfg.min_depth,
                min_nprobe=server_cfg.min_nprobe)
            self._full_plan = self.plan_space.full
            if server_cfg.adaptive:
                self._controller = DegradationController(
                    self.plan_space, high=server_cfg.max_queue_depth,
                    low=server_cfg.low_queue_depth,
                    recover_after=server_cfg.recover_after)
        else:
            assert not server_cfg.adaptive, \
                "adaptive serving requires two_stage=True"
        self._pending: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._next_ticket = 0
        self._lat_sum = 0.0
        self._last_snapshot = None
        self._transfers = _Transfers()
        self.stats = {
            "queries": 0, "docs": 0, "batches": 0, "shed": 0,
            "query_latency_ms":
                collections.deque(maxlen=server_cfg.latency_window),
            # per-QUERY enqueue->answer latencies (vs per-batch dispatch
            # above) — the number a caller actually waits
            "answer_latency_ms":
                collections.deque(maxlen=server_cfg.latency_window),
        }
        if engine is not None:
            assert engine.cfg == cfg, "engine.cfg disagrees with cfg"
        else:
            assert key is not None, "either an engine or an init key"
            engine = Engine(cfg, key, warmup)
        self.engine = engine
        self.publish_every = max(1, publish_every)
        # ---- supervision + durability (crash-safe streaming) ----
        self.max_restarts = max_restarts
        self._backoff = (backoff_base_s, backoff_max_s)
        self._jitter = random.Random(supervise_seed)
        self.restarts = 0
        self.quarantined: list[int] = []   # poison-batch seqs (never silent)
        self._attempts: dict[int, int] = {}
        self._quarantine_after = (durability.quarantine_after
                                  if durability is not None else 3)
        self._error_seq: int | None = None
        self._inflight = None              # ingest-thread resume state
        self._inflight_stage = "done"
        self._next_seq = 0                 # non-durable seq counter
        self._ingest_lock = threading.Lock()  # journal order == queue order
        self.recovery_report: dict | None = None
        self._docs_ingested = 0             # ingest-thread private
        self._durable = (DurableIngest(
            durability, cluster_axis=getattr(engine, "ckpt_cluster_axis", 0))
            if durability is not None else None)
        if self._durable is not None and self._durable.needs_recovery():
            self._recover()  # before the first publish: the initial
            #                  snapshot already serves the recovered stream
        # ---- hot-set serving cache (built BEFORE the first publish so
        # no publication can ever race their creation) ----
        self._result_cache = (ResultCache(server_cfg.cache_entries)
                              if server_cfg.cache_entries > 0 else None)
        self._hotset = (HotSet(
            cfg, max_batch=server_cfg.max_batch,
            pin_budget_bytes=int(server_cfg.pin_budget_mb * 2**20),
            capacity=server_cfg.hotset_capacity,
            refresh_every=server_cfg.hotset_refresh,
            min_count=server_cfg.hotset_min_count)
            if server_cfg.hotset else None)
        # publish events (version, dirty-cluster array) cross from the
        # ingest thread to the query path through this deque (GIL-atomic
        # append/popleft); the query path applies them IN ORDER up to the
        # snapshot version it pinned, so invalidation can neither run
        # ahead of the snapshot a flush serves from nor miss a publish.
        self._pub_events: collections.deque = collections.deque()
        self._snapshot = engine.publish()   # queries never see None
        self._published_docs = self._docs_ingested  # recovery is published
        self._since_publish = 0
        self._error: BaseException | None = None
        self._queue: queue.Queue = queue.Queue(maxsize=max(1, queue_max))
        # Serializes DISPATCH (not execution) between the ingest thread
        # and the query path: concurrently enqueueing two multi-device
        # programs from two threads can interleave their per-device
        # enqueue order and stall a collective behind the other program
        # on some devices. Dispatch is asynchronous, so the section is
        # held only for enqueue time; execution still overlaps, and the
        # query path never waits for ingest to *finish* — only for its
        # enqueue. The two-queue priority executor replaces the old
        # plain lock: a queued query flush always dispatches before a
        # queued ingest/publish dispatch, so under load queries never
        # wait behind a backlog of ingest enqueues.
        self._dispatch = PriorityDispatcher()
        self._closed = False
        self._stop_sent = False
        self._thread = threading.Thread(
            target=self._ingest_loop, name="rag-ingest", daemon=True)
        self._thread.start()

    # ----------------------------------------------------------------- query
    def submit(self, query) -> int:
        """Queue one query (text if embed_fn is set, else an embedding).
        Returns a monotonically increasing ticket id. Raises eagerly — a
        clear RuntimeError after ``close()`` (a post-close submission
        could never be answered) and the stored ingest-thread error
        (with its batch seq) instead of letting a doomed ticket queue up."""
        if self._closed:
            raise RuntimeError(
                "server is closed: submit() after close() would never "
                "be answered")
        self._check()
        with self._lock:
            ticket = self._next_ticket
            self._next_ticket += 1
            self._pending.append(
                {"q": query, "t": time.perf_counter(), "ticket": ticket})
        return ticket

    def _flush_due(self) -> bool:
        with self._lock:
            if not self._pending:
                return False
            if len(self._pending) >= self.scfg.max_batch:
                return True
            age_ms = (time.perf_counter() - self._pending[0]["t"]) * 1e3
        return age_ms >= self.scfg.max_wait_ms

    def _choose_plan(self, queue_depth: int):
        """Per-flush effort policy: the degradation controller (adaptive)
        or the fixed full-effort plan; None when plans don't apply
        (prototype-only serving)."""
        if self._controller is not None:
            return self._controller.observe(queue_depth)
        return self._full_plan

    def flush(self) -> list[dict]:
        """Answer up to ``max_batch`` queued queries as one batch.

        The flush's :class:`~repro.engine.plan.QueryPlan` is chosen here
        from the post-batch queue depth; a shed plan answers the whole
        batch immediately with sentinel results and an explicit ``shed``
        marker — every ticket is still answered exactly once. Answers
        carry ``degraded`` (effort below full, including shed) and
        ``plan`` so callers can audit what they got.
        """
        # surface a dead ingest thread on the next flush too — not just
        # lazily from sync()/close() — so callers polling the query path
        # learn about the failed batch immediately
        self._check()
        with self._lock:
            if not self._pending:
                return []
            batch = [self._pending.popleft()
                     for _ in range(min(len(self._pending),
                                        self.scfg.max_batch))]
            depth = len(self._pending)
        plan = self._choose_plan(depth)
        degraded = plan is not None and (plan.shed
                                         or plan != self._full_plan)
        # telemetry is fetched ONCE per batch; both are None when disabled
        # and every obs branch below is skipped — the hot path stays free
        reg, tr = obs.metrics(), obs.tracer()
        plan_args = ({} if plan is None else
                     {"plan_nprobe": plan.nprobe, "plan_depth": plan.depth,
                      "degraded": degraded, "shed": plan.shed})
        fspan = None
        t0 = time.perf_counter()
        if tr is not None:
            # the age of the popped queries at pop time, from the ``t``
            # that submit stored
            waits = [(t0 - b["t"]) * 1e3 for b in batch]
            fspan = tr.span("flush", batch=len(batch), queue_depth=depth,
                            ticket_first=batch[0]["ticket"],
                            ticket_last=batch[-1]["ticket"],
                            wait_ms_mean=sum(waits) / len(waits),
                            wait_ms_max=max(waits), **plan_args)
        if plan is not None and plan.shed:
            # shed: never touches the embedder or the engine — the
            # explicit overload answer, cheap by construction
            k = self.scfg.topk
            scores = np.full((len(batch), k), -np.inf, np.float32)
            ids = np.full((len(batch), k), -1, np.int32)
            labels = np.full((len(batch), k), -1, np.int32)
        else:
            raw = [b["q"] for b in batch]
            if self.embed_fn is not None:
                if tr is not None:
                    with tr.span("embed", batch=len(batch)):
                        q = self.embed_fn(raw)
                else:
                    q = self.embed_fn(raw)
            else:
                q = np.stack(raw)
            self._transfers.n = 0
            with (tr.span("flush.dispatch") if tr is not None
                  else obs.NO_SPAN):
                scores, rows, ids, labels = self._query_batch(
                    np.asarray(q, np.float32), plan)
            # the three answers' transfers overlap (a per-row np.asarray
            # in the loop below would dispatch a multi-device slice per
            # query); ``rows`` is never fetched
            with (tr.span("flush.fetch") if tr is not None
                  else obs.NO_SPAN) as fetch:
                scores, ids, labels = self._fetch((scores, ids, labels))
                if fetch is not None:
                    fetch.args["transfers"] = self._transfers.n
        lat = (time.perf_counter() - t0) * 1e3
        meta = self._batch_meta()
        if plan is not None:
            meta = {**meta, "degraded": degraded, "shed": plan.shed,
                    "plan": {"nprobe": plan.nprobe, "depth": plan.depth}}
        out = []
        for i in range(len(batch)):
            out.append({
                "ticket": batch[i]["ticket"],
                "scores": np.asarray(scores[i]),
                "doc_ids": np.asarray(ids[i]),
                "clusters": np.asarray(labels[i]),
                "enqueue_to_answer_ms":
                    (time.perf_counter() - batch[i]["t"]) * 1e3,
                **meta,
            })
        # stats mutate under the same lock submit/latency_stats take —
        # concurrent flushes must not lose increments or tear the windows
        with self._lock:
            self.stats["queries"] += len(batch)
            self.stats["batches"] += 1
            if plan is not None and plan.shed:
                self.stats["shed"] += len(batch)
            self.stats["query_latency_ms"].append(lat)
            for o in out:
                self.stats["answer_latency_ms"].append(
                    o["enqueue_to_answer_ms"])
            self._lat_sum += lat
        if reg is not None:
            reg.counter("serve_queries_total").inc(len(batch))
            reg.counter("serve_batches_total").inc()
            reg.gauge("serve_queue_depth").set(depth)
            reg.gauge("serve_batch_fill").set(
                len(batch) / self.scfg.max_batch)
            reg.histogram("serve_batch_latency_ms", unit="ms").observe(lat)
            h = reg.histogram("serve_query_e2e_ms", unit="ms")
            for o in out:
                h.observe(o["enqueue_to_answer_ms"])
            if plan is not None:
                # serve.plan telemetry: what effort was actually chosen
                reg.histogram("serve_plan_nprobe", lo=0.5,
                              hi=2048.0).observe(float(plan.nprobe))
                reg.histogram("serve_plan_depth", lo=0.5,
                              hi=2048.0).observe(float(plan.depth))
                reg.gauge("serve_degradation_level").set(
                    self._controller.level
                    if self._controller is not None else 0)
                if plan.shed:
                    reg.counter("serve_shed_total").inc(len(batch))
        if fspan is not None:
            # the answered ticket range and the snapshot it was answered
            # from correlate the flush with the ingest.publish spans
            fspan.args.update(meta if plan is None else
                              {k: v for k, v in meta.items() if k != "plan"})
            fspan.end()
        return out

    def drain(self) -> list[dict]:
        """Flush until no query is left pending — the shutdown path.
        A single ``flush()`` answers at most ``max_batch``; this loops so
        no submitted query is ever silently dropped. ``flush`` checks the
        pending deque under the lock itself, so drain never reads shared
        state unlocked."""
        out: list[dict] = []
        while True:
            got = self.flush()
            if not got:
                return out
            out.extend(got)

    def latency_stats(self) -> dict:
        """Running mean over all batches; percentiles over the bounded
        windows — per-batch dispatch latency (``p*_ms``) and per-query
        enqueue→answer latency (``answer_p*_ms``).

        The schema is CONSTANT for the life of the server: every key is
        present (zero-safe) before the first flush, before the first
        publish, and after ``close()`` — including the serving-cache
        keys (``cache_hit_rate``/``pinned_bytes``), which report 0 when
        caching is disabled or nothing has been served yet."""
        with self._lock:
            window = np.asarray(self.stats["query_latency_ms"],
                                dtype=np.float64)
            answers = np.asarray(self.stats["answer_latency_ms"],
                                 dtype=np.float64)
            n = self.stats["batches"]
            lat_sum = self._lat_sum

        def pct(a, q):
            return float(np.percentile(a, q)) if a.size else 0.0

        cache, hotset = self._result_cache, self._hotset
        return {
            "batches": n,
            "mean_ms": lat_sum / n if n else 0.0,
            "p50_ms": pct(window, 50),
            "p90_ms": pct(window, 90),
            "p99_ms": pct(window, 99),
            "window": int(window.size),
            "answer_p50_ms": pct(answers, 50),
            "answer_p90_ms": pct(answers, 90),
            "answer_p99_ms": pct(answers, 99),
            "answer_window": int(answers.size),
            "cache_hit_rate": (cache.stats()["hit_rate"]
                               if cache is not None else 0.0),
            "pinned_bytes": (hotset.pinned_bytes
                             if hotset is not None else 0),
        }

    def cache_stats(self) -> dict:
        """Serving-cache observability with a consistent zero-safe schema
        whether or not either cache level is enabled (and at any point in
        the server lifecycle — empty windows report zeros, never raise)."""
        cache, hotset = self._result_cache, self._hotset
        out = {
            "enabled": cache is not None or hotset is not None,
            "hits": 0, "misses": 0, "hit_rate": 0.0, "entries": 0,
            "invalidated": 0, "cleared": 0, "rekeyed": 0,
            "evicted_lru": 0, "hit_staleness": 0.0,
            "pinned_bytes": 0, "pinned_clusters": 0, "hot_served": 0,
            "tier_rebuilds": 0,
        }
        if cache is not None:
            s = cache.stats()
            for key in ("hits", "misses", "hit_rate", "entries",
                        "invalidated", "cleared", "rekeyed", "evicted_lru",
                        "hit_staleness"):
                out[key] = s[key]
        if hotset is not None:
            h = hotset.stats()
            out["pinned_bytes"] = h["pinned_bytes"]
            out["pinned_clusters"] = h["pinned_clusters"]
            out["hot_served"] = h["hot_served"]
            out["tier_rebuilds"] = h["rebuilds"]
        return out

    def _fetch(self, arrays) -> tuple:
        """Host copies of answer arrays (:func:`fetch_answers`), counted
        against the flush running on this thread."""
        host, n = fetch_answers(arrays)
        self._transfers.n += n
        return host

    # ---------------------------------------------------------- ingest thread
    def _ingest_loop(self):
        """Supervisor: runs the ingest loop, classifies failures, and
        restarts it with exponential backoff + seeded jitter within a
        bounded budget. Fatal errors (and an exhausted budget) surface on
        the caller thread with the failing batch's sequence number; an
        :class:`~repro.testing.faults.InjectedCrash` escapes supervision
        entirely — the thread dies like a SIGKILL'd process, with no
        final publish/checkpoint/truncation, and only recovery from the
        durable state brings the stream back."""
        while True:
            try:
                self._ingest_run()
                return
            except faults.InjectedCrash:
                return  # simulated process death: no finalization at all
            except BaseException as e:
                seq = (self._inflight[0]
                       if isinstance(self._inflight, tuple) else None)
                if (classify_error(e) == "fatal"
                        or self.restarts >= self.max_restarts):
                    self._error_seq = seq
                    self._error = e  # set LAST: _check reads seq after it
                    return
                self.restarts += 1
                reg = obs.metrics()
                if reg is not None:
                    reg.counter("ingest_restarts_total").inc()
                base, cap = self._backoff
                delay = min(cap, base * (2 ** (self.restarts - 1)))
                time.sleep(delay * (1.0 + 0.25 * self._jitter.random()))
                self._on_restart(seq)

    def _ingest_run(self):
        """One supervised incarnation of the ingest loop. Per-batch work
        is a resumable stage machine (admit -> publish -> checkpoint):
        after a mid-batch failure the restart resumes at the FAILING
        stage, so an already-applied batch is never double-ingested and a
        failed cadence publish/checkpoint is retried immediately."""
        while True:
            item = self._inflight
            if item is None:
                item = self._queue.get()
                self._inflight = item
                self._inflight_stage = "admit"
            if item is self._STOP:
                self._publish()
                if self._durable is not None:  # tail checkpoint + truncate
                    self._checkpoint(blocking=True)
                self._inflight = None
                return
            if isinstance(item, threading.Event):  # sync barrier
                self._publish()
                item.set()
                self._inflight = None
                continue
            seq, x, ids, t_put = item
            if self._inflight_stage == "admit":
                faults.fault_point("ingest.admit", seq=seq)
                tr = obs.tracer()
                # dispatch time (execution is async); ``wait_ms`` is the
                # batch's time from its enqueue to here
                with (tr.span("ingest.admit", cat="ingest", seq=seq,
                              batch=int(np.asarray(ids).size),
                              wait_ms=(time.perf_counter() - t_put) * 1e3)
                      if tr is not None else obs.NO_SPAN):
                    with self._dispatch.ingest():
                        self.engine.ingest(x, ids)
                self._docs_ingested += int(np.sum(np.asarray(ids) >= 0))
                self._since_publish += 1
                if self._durable is not None:
                    self._durable.batch_applied(seq)
                self._attempts.pop(seq, None)
                self._inflight_stage = "publish"
            if self._inflight_stage == "publish":
                if self._since_publish >= self.publish_every:
                    self._publish()
                self._inflight_stage = "checkpoint"
            if self._inflight_stage == "checkpoint":
                if (self._durable is not None
                        and self._durable.should_checkpoint()):
                    self._checkpoint()
                self._inflight = None
                self._inflight_stage = "done"

    def _on_restart(self, seq: int | None):
        """Post-backoff restart hygiene: poison-batch quarantine and
        serving-cache coherence."""
        # a batch that burned its whole per-batch retry budget at the
        # admit stage is quarantined: dropped from the retry loop ONLY —
        # counted, logged, and remembered so recovery replay skips it too
        if seq is not None and self._inflight_stage == "admit":
            n = self._attempts.get(seq, 0) + 1
            self._attempts[seq] = n
            if n >= self._quarantine_after:
                self.quarantined.append(seq)
                if self._durable is not None:
                    self._durable.quarantined.append(seq)
                self._attempts.pop(seq, None)
                self._inflight = None
                self._inflight_stage = "done"
                reg = obs.metrics()
                if reg is not None:
                    reg.counter("ingest_quarantined_total").inc()
        # cache coherence: clear the result cache at the pinned version
        # and mark the hot tier stale — nothing a failed attempt might
        # have half-published can survive the restart
        if self._result_cache is not None or self._hotset is not None:
            self._pub_events.append((self._snapshot.version, None))

    def _checkpoint(self, blocking: bool = False):
        """Cadence checkpoint off the ingest thread (async write; the
        journal truncates from the writer's durable callback). A prior
        write failure was counted by the store and left the dirty
        baseline untouched — this save simply covers it too."""
        self._durable.ckpt.poll_error()  # counted; cleared for the retry
        self._durable.checkpoint(
            self.engine.checkpoint_state(),
            metadata={"docs_ingested": self._docs_ingested},
            blocking=blocking)

    def _recover(self):
        """Constructor-time recovery: restore the newest checkpoint chain
        and replay the journal tail through the normal ingest path —
        bit-identical to the engine that never crashed (determinism of
        ingest + batch-boundary checkpoints). Runs before the first
        publish, so the initial snapshot already serves the recovered
        stream and every cache starts coherent."""
        eng = self.engine
        report = self._durable.recover(
            eng.checkpoint_state(),
            lambda x, ids: eng.ingest(x, ids),
            lambda tree, meta: eng.restore_state(tree))
        self.recovery_report = report
        self.quarantined = list(report["quarantined"])
        docs = report["docs_checkpointed"] + report["docs_replayed"]
        self._docs_ingested = docs
        with self._lock:
            self.stats["docs"] = docs

    def _publish(self):
        faults.fault_point("publish")
        # capture the doc watermark BEFORE publishing: the snapshot holds
        # at least everything ingested up to here
        docs = self._docs_ingested
        reg, tr = obs.metrics(), obs.tracer()
        span = (tr.span("ingest.publish", cat="ingest", docs=docs)
                if tr is not None else None)
        # host-blocking publish prep (e.g. the sharded engine's dirty
        # signature waits on ingest execution) runs OUTSIDE the dispatch
        # lock so a concurrent flush never stalls behind it
        prepare = getattr(self.engine, "prepare_publish", None)
        if prepare is not None:
            with (tr.span("publish.prepare", cat="ingest")
                  if tr is not None else obs.NO_SPAN):
                prepare()
        t0 = time.perf_counter()
        with self._dispatch.ingest():  # publish defers to queued flushes
            snap = self.engine.publish()
        info = getattr(self.engine, "last_publish_info", None)
        # the invalidation event is visible BEFORE the snapshot swap: any
        # flush that pins the new version is guaranteed to find its dirty
        # set queued (a flush still on the old version leaves it queued —
        # version-gated application keeps ordering exact either way)
        if self._result_cache is not None or self._hotset is not None:
            self._pub_events.append(
                (snap.version, info.get("dirty") if info else None))
        self._snapshot = snap        # atomic swap (single ref assignment)
        self._published_docs = docs
        self._since_publish = 0
        if reg is None and tr is None:
            return
        # publish-time telemetry ONLY: the device-counter fetch below is
        # the one host transfer metrics add, and it runs here on the
        # ingest thread — never on the query path
        pub_ms = (time.perf_counter() - t0) * 1e3
        lag = self.stats["docs"] - docs
        if span is not None:
            span.args["version"] = snap.version
            if info is not None:   # scalars only: the dirty index array
                #                    is not JSON-exportable span material
                span.args.update({key: v for key, v in info.items()
                                  if key != "dirty"})
            span.end()
            tr.counter("freshness", {"lag_docs": lag,
                                     "snapshot_version": snap.version})
        if reg is not None:
            reg.counter("publish_total").inc()
            if info is not None:
                reg.counter(f"publish_{info['mode']}_total").inc()
            reg.histogram("publish_latency_ms", unit="ms").observe(pub_ms)
            reg.gauge("publish_lag_docs").set(lag)
            reg.gauge("snapshot_version").set(snap.version)
            counters = getattr(self.engine, "device_counters", None)
            if counters is not None:
                reg.set_many("pipeline_", counters(),
                             help="device pipeline counters (publish fetch)")

    def _check(self):
        if self._error is not None:
            seq = self._error_seq
            raise RuntimeError(
                "async ingest thread died"
                + (f" (batch seq {seq})" if seq is not None else "")
            ) from self._error

    def _put(self, item, timeout: float):
        """Queue.put that can never deadlock on a dead ingest thread: a
        plain blocking put on a full queue would hang forever once the
        consumer has exited (e.g. after an ingest error)."""
        deadline = time.monotonic() + timeout
        while True:
            self._check()
            if not self._thread.is_alive():
                raise RuntimeError("ingest thread is not running")
            try:
                self._queue.put(item, timeout=0.1)
                return
            except queue.Full:
                if time.monotonic() >= deadline:
                    raise TimeoutError("ingest queue stayed full") from None

    # -------------------------------------------------------------- protocol
    def ingest(self, embeddings: np.ndarray, doc_ids: np.ndarray,
               timeout: float = 120.0):
        """Enqueue one stream batch for background ingestion (bounded
        queue: blocks the producer — never the query path — when full).

        With durability armed the batch is journaled (appended + fsync'd)
        BEFORE it is enqueued, under one producer lock, so journal
        sequence order IS queue order — the property replay bit-identity
        rests on. The ``ingest.enqueue`` fault point fires before the
        journal append: a producer-side failure means the batch was never
        acknowledged durable, so nothing is ever silently lost."""
        if self._closed:
            raise RuntimeError(
                "server is closed: ingest() after close() would never "
                "be applied")
        self._check()
        x = np.asarray(embeddings)
        ids = np.asarray(doc_ids)
        tr = obs.tracer()
        span = (tr.span("ingest.enqueue", cat="ingest", batch=int(ids.size))
                if tr is not None else None)
        with self._ingest_lock:
            faults.fault_point("ingest.enqueue")
            if self._durable is not None:
                seq = self._durable.record(x, ids)
            else:
                seq = self._next_seq
                self._next_seq += 1
            self._put((seq, x, ids, time.perf_counter()), timeout)
        if span is not None:
            span.args["seq"] = seq
            span.end()
        # count live rows only (doc_id < 0 is the dead/padding sentinel),
        # mirroring _docs_ingested so freshness lag can actually reach 0
        live = int(np.sum(ids >= 0))
        with self._lock:
            self.stats["docs"] += live
        reg = obs.metrics()
        if reg is not None:
            reg.counter("ingest_docs_enqueued_total").inc(live)
            reg.gauge("ingest_queue_depth").set(self._queue.qsize())

    def _query_batch(self, q: np.ndarray, plan=None):
        self._check()
        snap = self._snapshot        # pin ONE snapshot for the whole batch
        self._last_snapshot = snap
        if self._result_cache is None and self._hotset is None:
            with self._dispatch.query():  # enqueue-only, preempts ingest
                return self.engine.query_snapshot(
                    snap, q, self.scfg.topk, two_stage=self.scfg.two_stage,
                    nprobe=self.scfg.nprobe, plan=plan)
        return self._query_batch_cached(snap, q, plan)

    def _query_batch_cached(self, snap, q: np.ndarray, plan=None):
        """Two-level cached serving for one flush, pinned to ``snap``.

        1. apply queued publications up to the pinned version (precise
           result-cache invalidation + hot-tier staleness);
        2. route-free exact hits: entries whose routes were verified at
           the pinned version answer immediately (stage-1 routing is a
           pure function of the query within one snapshot version, so
           re-deriving their routes is a no-op by determinism) — an
           all-hit flush never touches the device;
        3. ONE batched route pass over the *pending* sub-batch (the
           staged stage-1 the fused kernel is pinned bit-identical to)
           yields ordered routes — the exactness witness for entries that
           survived a publish, the hot-tier coverage test, and the
           heavy-hitter observation in a single small program;
        4. remaining misses split into hot-covered (fused serve over the
           pinned tier) and cold (the unchanged full-store fused path),
           both padded to power-of-two buckets (row-independent math —
           padding can never change a real row's answer) and inserted
           back into the cache.

        Every answer is bit-identical to what the uncached path would
        return for the same snapshot, by construction at each step.
        """
        cache, hotset = self._result_cache, self._hotset
        k = self.scfg.topk
        nprobe_eff, depth = _resolve_plan(plan, self.scfg.nprobe)
        store_depth = self.cfg.store_depth
        depth_eff = store_depth if depth is None else min(depth, store_depth)
        plan_key = (plan.key if plan is not None
                    else f"np{nprobe_eff}xd{depth_eff}")
        while self._pub_events and self._pub_events[0][0] <= snap.version:
            version, dirty = self._pub_events.popleft()
            if cache is not None:
                cache.on_publish(version, dirty)
            if hotset is not None:
                hotset.note_publish(version, dirty)
        B = q.shape[0]
        scores = np.full((B, k), -np.inf, np.float32)
        rows = np.full((B, k), -1, np.int32)
        ids = np.full((B, k), -1, np.int32)
        labels = np.full((B, k), -1, np.int32)
        qbytes = [q[i].tobytes() for i in range(B)]
        pend = []   # needs routing: unverified survivor or absent entry
        for i in range(B):
            ans = (cache.peek_exact(qbytes[i], plan_key, snap.version)
                   if cache is not None else None)
            if ans is not None:
                scores[i], rows[i], ids[i], labels[i] = ans
            else:
                pend.append(i)
        n_miss = 0
        hot_served = 0
        if pend:
            pidx = np.asarray(pend)
            with self._dispatch.query():
                if hotset is not None:
                    hotset.sync(snap)
                routes = np.asarray(_route_batch(
                    self.cfg.index, snap.index, snap.route_labels,
                    jnp.asarray(_pad_pow2(q[pidx])),
                    nprobe_eff))[:pidx.size]
            miss_pos = []
            for j, i in enumerate(pend):
                ans = (cache.lookup(qbytes[i], plan_key, snap.version,
                                    routes[j])
                       if cache is not None else None)
                if ans is not None:
                    scores[i], rows[i], ids[i], labels[i] = ans
                else:
                    miss_pos.append(j)
            # hot-set tracking observes the routed sub-batch only: the
            # route-free hits above are exactly the queries that don't
            # need the tier, so the counter keeps seeing the traffic the
            # tier exists for
            if hotset is not None:
                hotset.observe(routes)
            n_miss = len(miss_pos)
        if n_miss:
            mpos = np.asarray(miss_pos)
            midx = pidx[mpos]
            hot_mask = (hotset.covered(routes[mpos]) if hotset is not None
                        else np.zeros((mpos.size,), bool))
            hot_served = int(np.sum(hot_mask))
            hot_sel, cold_sel = midx[hot_mask], midx[~hot_mask]
            out_c = out_h = None
            with self._dispatch.query():
                if cold_sel.size:
                    out_c = self.engine.query_snapshot(
                        snap, _pad_pow2(q[cold_sel]), k, two_stage=True,
                        nprobe=self.scfg.nprobe, plan=plan)
                if hot_sel.size:
                    out_h = hotset.serve(
                        snap, jnp.asarray(_pad_pow2(q[hot_sel])), k,
                        nprobe_eff, depth_eff, self.cfg.clus.use_pallas)
            if out_c is not None:
                n = cold_sel.size
                sc, rw, di, cl = (a[:n] for a in self._fetch(out_c))
                scores[cold_sel], rows[cold_sel] = sc, rw
                ids[cold_sel], labels[cold_sel] = di, cl
            if out_h is not None:
                n = hot_sel.size
                sc, rw_t, di, cl_t = (a[:n] for a in self._fetch(out_h))
                rw, cl = hotset.remap(rw_t, cl_t)
                scores[hot_sel], rows[hot_sel] = sc, rw
                ids[hot_sel], labels[hot_sel] = di, cl
            if cache is not None:
                for j, i in zip(mpos, midx):
                    cache.insert(qbytes[i], plan_key, snap.version,
                                 routes[j], (scores[i].copy(),
                                             rows[i].copy(), ids[i].copy(),
                                             labels[i].copy()))
        reg = obs.metrics()
        if reg is not None:
            reg.counter("cache_hits_total").inc(B - n_miss)
            reg.counter("cache_misses_total").inc(n_miss)
            if cache is not None:
                reg.gauge("cache_entries").set(len(cache))
            if hotset is not None:
                reg.counter("hotset_served_total").inc(hot_served)
                reg.gauge("hotset_pinned_bytes").set(hotset.pinned_bytes)
                reg.gauge("hotset_pinned_clusters").set(
                    hotset.stats()["pinned_clusters"])
        return scores, rows, ids, labels

    def _batch_meta(self) -> dict:
        # shed flushes never call _query_batch, so fall back to the
        # current snapshot: shed answers still carry the version they
        # *would* have been served from
        snap = (self._last_snapshot if self._last_snapshot is not None
                else self._snapshot)
        return {"snapshot_version": snap.version}

    def serve_round(self, stream_batch=None) -> list[dict]:
        """One event-loop turn: answer due queries FIRST (from the
        published snapshot — the devices are not yet busy with this
        round's ingest), then enqueue the stream batch for background
        ingestion, so a query never waits behind its round's ingest."""
        outs = self.flush() if self._flush_due() else []
        if stream_batch is not None:
            self.ingest(stream_batch["embedding"], stream_batch["doc_id"])
        return outs

    # ------------------------------------------------------------- lifecycle
    def sync(self, timeout: float = 120.0):
        """Block until everything enqueued so far is ingested AND
        published. Queries issued after ``sync`` see all prior docs."""
        ev = threading.Event()
        self._put(ev, timeout)
        if not ev.wait(timeout):
            self._check()
            raise TimeoutError("ingest thread did not sync in time")

    def close(self, timeout: float = 120.0):
        """Stop the ingest thread after a final publish; idempotent once
        the thread has actually stopped (a timed-out close can be
        retried — ``_closed`` only flips after a successful join)."""
        if self._closed:
            return
        if not self._stop_sent and self._thread.is_alive():
            self._put(self._STOP, timeout)
            self._stop_sent = True
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("ingest thread did not stop in time")
        self._closed = True
        if self._durable is not None:
            self._durable.close()
        self._check()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------ accounting
    def state_memory_bytes(self) -> int:
        """Engine state bytes PLUS the hot tier's resident pin bytes —
        the serving-side number charged against the paper's 150 MB
        envelope (the pinned block is real accelerator memory the cache
        holds on top of the engine state)."""
        base = self.engine.state_memory_bytes()
        return base + (self._hotset.pinned_bytes
                       if self._hotset is not None else 0)

    def robustness_stats(self) -> dict:
        """Supervision + durability accounting. The schema is CONSTANT
        whether or not durability is armed (zeros / None / empty when
        disabled) and at every point of the server lifecycle."""
        out = {
            "restarts": self.restarts,
            "max_restarts": self.max_restarts,
            "quarantined": list(self.quarantined),
            "error_seq": self._error_seq,
            "durable": self._durable is not None,
            "recovery": self.recovery_report,
            "journal_last_seq": -1,
            "journal_segments": 0,
            "journal_disk_bytes": 0,
            "journal_lag_batches": 0,
            "checkpoint_seq": None,
            "checkpoint_age_batches": 0,
            "checkpoint_saves": {"full": 0, "delta": 0, "failed": 0},
            "checkpoint_bytes": {"full": 0, "delta": 0},
        }
        if self._durable is not None:
            s = self._durable.stats()
            for key in ("journal_last_seq", "journal_segments",
                        "journal_disk_bytes", "journal_lag_batches",
                        "checkpoint_seq", "checkpoint_age_batches",
                        "checkpoint_saves", "checkpoint_bytes"):
                out[key] = s[key]
        return out

    def freshness_stats(self) -> dict:
        """How far the published snapshot trails the ingested stream —
        in docs (lag) and in wall-clock seconds (snapshot age). Age is
        ``None`` when the pinned snapshot was never actually published
        (``published_at == 0.0``, e.g. a host-oracle snapshot injected in
        tests), so a bogus 55-years age can never be reported. The schema
        is constant for the life of the server — before the first
        publish-cadence tick and after ``close()`` alike."""
        snap = self._snapshot
        published_at = snap.published_at if snap.published_at > 0 else None
        return {
            "snapshot_version": snap.version,
            "published_at": published_at,
            "snapshot_age_s": (time.time() - published_at
                               if published_at is not None else None),
            "docs_enqueued": self.stats["docs"],
            "docs_ingested": self._docs_ingested,
            "docs_published": self._published_docs,
            "lag_docs": self.stats["docs"] - self._published_docs,
        }
