"""Observability subsystem invariants.

* registry semantics: idempotent instruments, exact counters under
  threads, log-bucket histogram percentiles, JSON + Prometheus export;
* the on/off contract: with observability disabled the serving path
  creates no instruments and never touches the device-counter fetch;
  enabled, the device counters are fetched at publish time ONLY — never
  from the query path (the "zero device syncs on queries" property);
* trace export: a threaded async serving run produces a structurally
  valid Chrome trace-event JSON whose flush spans carry the ticket range
  they answered and the snapshot version they answered from (correlated
  against actual publishes);
* satellite fixes: per-query latency window (p90 + window sizes in
  ``latency_stats``), wall-clock snapshot age with the never-published
  guard, and stat exactness under concurrent submit/flush.
"""
import faulthandler
import json
import os
import sys
import threading

import numpy as np
import jax
import pytest

from repro import obs
from repro.core import clustering, heavy_hitter, pipeline, prefilter
from repro.data.streams import make_stream
from repro.engine import Engine
from repro.obs.metrics import Registry
from repro.obs.trace import Tracer, validate_chrome_trace
from repro.engine.engine import ServingSnapshot
from repro.serve.runtime import AsyncServer, ServerConfig

DIM = 32
WATCHDOG_S = 240.0

pytestmark = pytest.mark.timeout(300)


@pytest.fixture(autouse=True)
def _deadlock_watchdog():
    def _die():
        faulthandler.dump_traceback(file=sys.stderr)
        os._exit(3)

    timer = threading.Timer(WATCHDOG_S, _die)
    timer.daemon = True
    timer.start()
    yield
    timer.cancel()


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Each test starts disabled with no inherited instruments (CI runs
    this module under REPRO_OBS=1, which enables at import time)."""
    was = obs.enabled()
    obs.disable()
    yield
    obs.disable()
    if was:
        obs.enable()


def small_cfg(**kw):
    return pipeline.PipelineConfig(
        pre=prefilter.PrefilterConfig(num_vectors=3, dim=DIM, alpha=0.0,
                                      basis="fixed"),
        clus=clustering.ClusterConfig(num_clusters=16, dim=DIM),
        hh=heavy_hitter.HHConfig(capacity=8, admit_prob=0.5),
        update_interval=kw.pop("update_interval", 64),
        **kw)


# ------------------------------------------------------------------ registry
def test_registry_instruments_are_idempotent_and_typed():
    reg = Registry()
    c = reg.counter("a_total")
    assert reg.counter("a_total") is c
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = reg.gauge("depth")
    g.set(7)
    g.add(-2)
    assert g.value == 5.0
    with pytest.raises(AssertionError):
        reg.gauge("a_total")  # kind mismatch must not silently alias


def test_histogram_percentiles_bracket_the_data():
    reg = Registry()
    h = reg.histogram("lat_ms", unit="ms", lo=0.01, hi=1e4, nbuckets=96)
    vals = np.concatenate([np.full(90, 1.0), np.full(9, 50.0),
                           np.full(1, 900.0)])
    for v in vals:
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["min"] == 1.0 and snap["max"] == 900.0
    assert abs(snap["mean"] - float(np.mean(vals))) < 1e-9
    # bucket-resolution percentiles: upper bound of the right bucket,
    # within one geometric step of the true value
    growth = (1e4 / 0.01) ** (1 / 95)
    # nearest-rank semantics: 90% of observations are <= 1.0
    assert 1.0 <= snap["p50"] <= 1.0 * growth
    assert 1.0 <= snap["p90"] <= 1.0 * growth
    assert 50.0 <= snap["p99"] <= 50.0 * growth
    assert 900.0 <= h.percentile(99.5) <= 900.0
    # exact ends via tracked min/max
    assert h.percentile(0) == 1.0 and h.percentile(100) == 900.0


def test_counter_exact_under_concurrent_increments():
    reg = Registry()
    c = reg.counter("hits_total")
    n_threads, per_thread = 8, 2000

    def work():
        for _ in range(per_thread):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread  # no lost += interleavings


def test_json_and_prometheus_export():
    reg = Registry()
    reg.counter("q_total", help="queries").inc(5)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat", unit="ms")
    for v in (0.5, 2.0, 80.0):
        h.observe(v)
    reg.set_many("pipeline_", {"arrivals": 10, "admit_rate": 0.4})

    out = json.loads(reg.to_json())
    assert out["counters"]["q_total"] == 5.0
    assert out["gauges"]["pipeline_arrivals"] == 10.0
    assert out["gauges"]["pipeline_admit_rate"] == 0.4
    assert out["histograms"]["lat"]["count"] == 3

    prom = reg.to_prometheus()
    assert "# TYPE q_total counter" in prom
    assert "q_total 5" in prom
    assert "# TYPE lat histogram" in prom
    assert 'lat_bucket{le="+Inf"} 3' in prom
    assert "lat_count 3" in prom
    # _bucket lines are cumulative and non-decreasing
    runs = [int(line.rsplit(" ", 1)[1]) for line in prom.splitlines()
            if line.startswith("lat_bucket")]
    assert runs == sorted(runs) and runs[-1] == 3


# -------------------------------------------------------------------- tracer
def test_tracer_chrome_export_is_valid_and_bounded():
    tr = Tracer(max_events=4)
    with tr.span("outer", cat="t", a=1) as sp:
        sp.args["b"] = 2          # mid-span correlation fill-in
        with tr.span("inner", cat="t"):
            pass
    tr.counter("depth", {"q": 3})
    for _ in range(4):            # overflow the bounded buffer
        tr.counter("spam", {"q": 1})
    assert len(tr) == 4
    obj = tr.to_chrome()
    assert validate_chrome_trace(obj) == []
    assert obj["otherData"]["dropped_events"] > 0
    names = [e["name"] for e in obj["traceEvents"]]
    assert "process_name" in names  # metadata event survives overflow


def test_full_collections_are_traced_while_tracing_is_enabled():
    import gc

    _, tr = obs.enable(metrics=False, trace=True)
    assert tr.on_gc in gc.callbacks
    gc.collect(0)                 # a young collection is not traced
    gc.collect()
    spans = [e for e in tr.events() if e["name"] == "gc.collect"]
    assert len(spans) >= 1
    assert all(e["args"] == {"generation": 2} and e["dur"] >= 0.0
               for e in spans)
    obs.disable()
    assert tr.on_gc not in gc.callbacks
    gc.collect()
    assert len([e for e in tr.events() if e["name"] == "gc.collect"]) \
        == len(spans)


def test_validate_chrome_trace_flags_malformed_events():
    assert validate_chrome_trace({}) != []
    assert validate_chrome_trace({"traceEvents": []}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "ts": 0.0}]}
    assert any("dur" in p for p in validate_chrome_trace(bad))


# --------------------------------------------------------- frontend threading
class _HostEngine:
    """Engine with host-only answers — isolates the threading behavior
    of the server's submit/flush/drain from any device work."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.version = 0

    def publish(self):
        self.version += 1
        return ServingSnapshot(None, None, None, version=self.version)

    def query_snapshot(self, snap, q, k, *, two_stage, nprobe, plan=None):
        b = q.shape[0]
        ids = np.tile(np.arange(k, dtype=np.int32), (b, 1))
        return (np.zeros((b, k), np.float32), ids, ids,
                np.zeros((b, k), np.int32))


def _host_server(scfg):
    cfg = small_cfg()
    return AsyncServer(cfg, scfg, engine=_HostEngine(cfg))


def test_frontend_totals_exact_under_concurrent_submit_flush():
    obs.enable()  # metrics recording must not perturb exactness
    fe = _host_server(ServerConfig(max_batch=8, max_wait_ms=0.0, topk=4,
                                   latency_window=64))
    n_submitters, per_thread = 4, 200
    answered: list[dict] = []
    alock = threading.Lock()
    stop = threading.Event()

    def submitter(seed):
        rng = np.random.default_rng(seed)
        for _ in range(per_thread):
            fe.submit(rng.normal(size=DIM).astype(np.float32))

    def flusher():
        while not stop.is_set():
            outs = fe.flush()
            if outs:
                with alock:
                    answered.extend(outs)

    flushers = [threading.Thread(target=flusher) for _ in range(2)]
    subs = [threading.Thread(target=submitter, args=(s,))
            for s in range(n_submitters)]
    for t in flushers + subs:
        t.start()
    for t in subs:
        t.join()
    stop.set()
    for t in flushers:
        t.join()
    answered.extend(fe.drain())
    fe.close()

    total = n_submitters * per_thread
    tickets = sorted(a["ticket"] for a in answered)
    assert tickets == list(range(total))       # exactly once, no drops
    assert fe.stats["queries"] == total        # no lost increments
    assert sum(1 for _ in answered) == total
    lat = fe.latency_stats()
    assert lat["batches"] == fe.stats["batches"]
    assert lat["answer_window"] == min(total, 64)
    assert lat["window"] == min(lat["batches"], 64)
    assert lat["answer_p99_ms"] >= lat["answer_p90_ms"] >= \
        lat["answer_p50_ms"] >= 0.0
    reg = obs.metrics()
    assert reg.counter("serve_queries_total").value == total


def test_latency_stats_has_per_query_window_keys_when_empty():
    fe = _host_server(ServerConfig(max_batch=4, topk=2))
    lat = fe.latency_stats()
    fe.close()
    for key in ("p90_ms", "window", "answer_p50_ms", "answer_p90_ms",
                "answer_p99_ms", "answer_window"):
        assert key in lat
    assert lat["answer_window"] == 0 and lat["answer_p90_ms"] == 0.0


# ------------------------------------------------------- serving integration
class _CountingEngine(Engine):
    """Engine counting device_counters fetches — the probe behind the
    "device counters at publish only, never per query" property."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.counter_fetches = 0

    def device_counters(self):
        self.counter_fetches += 1
        return super().device_counters()


def _drive_async(server, stream, rounds=6, qps=4):
    for _ in range(rounds):
        b = stream.next_batch(16)
        for q in stream.queries(qps)["embedding"]:
            server.submit(q)
        server.serve_round(b)
    server.sync()
    server.drain()


def test_device_counters_fetched_at_publish_only():
    cfg = small_cfg(store_depth=4, update_interval=32)
    stream = make_stream("iot", dim=DIM)
    engine = _CountingEngine(cfg, jax.random.key(0))
    scfg = ServerConfig(max_batch=8, max_wait_ms=0.0, topk=5,
                        two_stage=True, nprobe=4)

    # disabled: the query path AND the publish path never fetch
    server = AsyncServer(cfg, scfg, engine=engine, publish_every=2)
    _drive_async(server, stream)
    server.close()
    assert engine.counter_fetches == 0
    assert obs.metrics() is None and obs.tracer() is None

    # enabled: fetched once per publish, still never per query batch
    obs.enable()
    engine2 = _CountingEngine(cfg, jax.random.key(1))
    server2 = AsyncServer(cfg, scfg, engine=engine2, publish_every=2)
    publishes_before = engine2.counter_fetches
    n_flushes = 0
    for _ in range(8):
        for q in stream.queries(4)["embedding"]:
            server2.submit(q)
        n_flushes += 1
        server2.flush()          # query path: must not fetch counters
    assert engine2.counter_fetches == publishes_before
    server2.ingest(stream.next_batch(16)["embedding"],
                   stream.next_batch(16)["doc_id"])
    server2.sync()               # forces a publish -> exactly one fetch
    assert engine2.counter_fetches > publishes_before
    server2.close()
    reg = obs.metrics()
    snap = reg.snapshot()
    assert snap["gauges"]["pipeline_arrivals"] > 0
    assert 0.0 <= snap["gauges"]["pipeline_admit_rate"] <= 1.0
    assert snap["counters"]["publish_total"] >= 1


def test_engine_device_counters_are_consistent():
    cfg = small_cfg(store_depth=4)
    eng = Engine(cfg, jax.random.key(0))
    stream = make_stream("iot", dim=DIM)
    b = stream.next_batch(48)
    eng.ingest(b["embedding"], b["doc_id"])
    c = eng.device_counters()
    assert c["arrivals"] == 48
    assert 0 <= c["admitted"] <= c["arrivals"]
    assert c["store_live"] <= c["store_slots"]
    assert 0.0 <= c["admit_rate"] <= 1.0
    assert 0.0 <= c["store_fill"] <= 1.0
    assert c["store_min_fill"] <= c["store_max_fill"] <= cfg.store_depth
    assert c["hh_occupied"] <= c["hh_capacity"]


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs a forced 4-device CPU mesh")
def test_sharded_device_counters_aggregate_across_shards():
    from repro.engine.sharded import ShardedEngine

    cfg = small_cfg(store_depth=4)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    eng = ShardedEngine(cfg, mesh, jax.random.key(0),
                        reconcile_every=10**9, reconcile_mode="delta")
    stream = make_stream("iot", dim=DIM)
    b = stream.next_batch(64)
    eng.ingest(b["embedding"], b["doc_id"])
    eng.reconcile()
    b = stream.next_batch(64)
    eng.ingest(b["embedding"], b["doc_id"])
    eng.reconcile()
    c = eng.device_counters()
    assert c["arrivals"] == 128          # summed over both data shards
    assert c["store_slots"] == \
        2 * cfg.clus.num_clusters * cfg.store_depth
    assert eng.last_publish_info["mode"] in ("delta", "republish", "full")
    assert c["publish_dirty_frac"] <= 1.0


def test_freshness_stats_snapshot_age_and_guard():
    cfg = small_cfg(store_depth=4)
    stream = make_stream("iot", dim=DIM)
    server = AsyncServer(cfg, ServerConfig(max_batch=4, topk=5,
                                           two_stage=True, nprobe=4),
                         key=jax.random.key(0), publish_every=1)
    server.ingest(stream.next_batch(16)["embedding"],
                  stream.next_batch(16)["doc_id"])
    server.sync()
    fresh = server.freshness_stats()
    assert fresh["published_at"] is not None
    assert 0.0 <= fresh["snapshot_age_s"] < 300.0  # sane wall-clock age
    # never-published snapshots (published_at == 0.0) report None, not a
    # bogus huge age
    server._snapshot = server._snapshot._replace(published_at=0.0)
    fresh = server.freshness_stats()
    assert fresh["snapshot_age_s"] is None
    assert fresh["published_at"] is None
    server.close()


def test_async_trace_spans_correlate_with_published_versions():
    obs.enable()
    cfg = small_cfg(store_depth=4, update_interval=32)
    stream = make_stream("iot", dim=DIM)
    server = AsyncServer(cfg, ServerConfig(max_batch=8, max_wait_ms=0.0,
                                           topk=5, two_stage=True, nprobe=4),
                         key=jax.random.key(0), publish_every=2)
    _drive_async(server, stream, rounds=8, qps=4)
    server.close()

    tr = obs.tracer()
    obj = tr.to_chrome()
    assert validate_chrome_trace(obj) == []
    events = tr.events()
    published = {e["args"]["version"] for e in events
                 if e["name"] == "ingest.publish"}
    assert not [e for e in events if e["name"] == "query"], \
        "per-query events are gone: flushes name their ticket ranges"
    flushes = [e for e in events if e["name"] == "flush"]
    assert flushes
    answered = []
    for f in flushes:
        assert f["ph"] == "X" and f["dur"] >= 0.0
        a = f["args"]
        assert a["ticket_last"] - a["ticket_first"] + 1 == a["batch"]
        assert 0.0 <= a["wait_ms_mean"] <= a["wait_ms_max"]
        # every flush answered from a snapshot that was either the
        # constructor's initial publish (v1) or traced as published
        assert a["snapshot_version"] in published | {1}
        answered += range(a["ticket_first"], a["ticket_last"] + 1)
    # the ticket ranges cover every answered query exactly once
    assert sorted(answered) == list(range(len(answered)))


def test_disabled_obs_records_nothing_and_answers_identically():
    cfg = small_cfg(store_depth=4, update_interval=32)
    scfg = ServerConfig(max_batch=8, max_wait_ms=0.0, topk=5,
                        two_stage=True, nprobe=4)

    def run():
        stream = make_stream("iot", dim=DIM)
        server = AsyncServer(cfg, scfg, key=jax.random.key(0),
                             publish_every=10**9)  # no mid-run publishes
        outs = []
        for _ in range(4):
            b = stream.next_batch(16)
            for q in stream.queries(4)["embedding"]:
                server.submit(q)
            outs += server.serve_round(b)
        server.sync()
        outs += server.drain()
        server.close()
        return sorted(outs, key=lambda o: o["ticket"])

    off = run()
    obs.enable()
    on = run()
    assert obs.metrics() is not None and len(obs.tracer()) > 0
    assert len(on) == len(off)
    for a, b in zip(on, off):               # retrieval gap exactly zero
        assert a["ticket"] == b["ticket"]
        np.testing.assert_array_equal(a["doc_ids"], b["doc_ids"])
        np.testing.assert_array_equal(a["scores"], b["scores"])


def test_kernel_trace_counting_is_trace_time_only():
    import jax.numpy as jnp

    from repro.kernels.rerank.ops import rerank_topk

    obs.enable(trace=False)
    reg = obs.metrics()
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(4, DIM)), jnp.float32)
    embs = jnp.asarray(rng.normal(size=(8, 4, DIM)), jnp.float32)
    live = jnp.ones((8, 4), bool)
    routes = jnp.zeros((4, 2), jnp.int32)
    fn = jax.jit(lambda a, b, c, d: rerank_topk(a, b, c, d, 3,
                                                use_pallas=False))
    for _ in range(5):
        fn(q, embs, live, routes)  # one trace, five executions
    name = "kernel_traces_total_rerank_ref"
    assert reg.counter(name).value == 1


@pytest.mark.parametrize("use_pallas", [False, True])
def test_serve_kernel_compiles_once_across_steady_state_queries(use_pallas):
    """The fused serve kernel must compile exactly once for a steady-state
    query workload (fixed Q/k/nprobe, snapshot leaves changing values but
    not shapes) — a silent re-trace per query would erase the single-
    program latency win. Five query batches against five distinct
    published snapshots -> one jit trace on the serve dispatch path."""
    import jax.numpy as jnp

    from repro.configs.streaming_rag import paper_pipeline_config
    import dataclasses

    obs.enable(trace=False)
    reg = obs.metrics()
    cfg = paper_pipeline_config(dim=DIM, k=16, capacity=12,
                                update_interval=24, alpha=-1.0,
                                store_depth=4)
    cfg = dataclasses.replace(
        cfg, clus=dataclasses.replace(cfg.clus, use_pallas=use_pallas))
    eng = Engine(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    path = "pallas" if use_pallas else "ref"
    name = f"kernel_traces_total_serve_{path}"
    for step in range(5):
        x = jnp.asarray(rng.normal(size=(24, DIM)), jnp.float32)
        eng.ingest(x, jnp.arange(24, dtype=jnp.int32) + 24 * step)
        snap = eng.publish()  # fresh leaves every iteration, same shapes
        q = jnp.asarray(rng.normal(size=(6, DIM)), jnp.float32)
        eng.query_snapshot(snap, q, k=4, two_stage=True, nprobe=3)
    assert reg.counter(name).value == 1
