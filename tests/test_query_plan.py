"""Query-adaptive serving contracts: QueryPlan / PlanSpace / degradation.

Pins the refactor's load-bearing invariants:

  * a FULL-EFFORT plan is bit-identical to a plan-free query — ids,
    rows, clusters AND scores — live state, published snapshot, and
    (subprocess, forced 4-device CPU mesh) the cluster-sharded engine,
    fp32 and int8 rings;
  * a DEGRADED plan equals the oracle of an engine whose store was
    physically clipped to the plan depth (the slice is semantics, not an
    approximation);
  * steady-state compile count equals the number of plan BUCKETS, never
    the number of distinct requested plans (trace counters +
    ``tuning.applied`` variant keys);
  * the PlanSpace ladder/bucketing algebra, the degradation
    controller's hysteresis, and the priority dispatcher's
    queries-before-ingest ordering.
"""
import dataclasses
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.streaming_rag import paper_pipeline_config
from repro.engine.engine import Engine, snapshot_query_impl
from repro.engine.plan import PlanSpace, QueryPlan
from repro.serve.executor import DegradationController, PriorityDispatcher

RNG = np.random.default_rng(11)


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


def _ingested_engine(store_dtype="fp32", *, store_depth=8, dim=32,
                     batches=6):
    cfg = paper_pipeline_config(dim=dim, k=16, capacity=12,
                                update_interval=32, alpha=-1.0,
                                store_depth=store_depth,
                                store_dtype=store_dtype)
    eng = Engine(cfg, jax.random.key(0))
    rng = np.random.default_rng(3)
    for b in range(batches):
        x = jnp.asarray(rng.normal(size=(24, dim)), jnp.float32)
        eng.ingest(x, jnp.arange(24, dtype=jnp.int32) + 24 * b)
    q = jnp.asarray(rng.normal(size=(9, dim)), jnp.float32)
    return cfg, eng, q


# ---------------------------------------------------------------- plan space
def test_plan_space_ladder_shape_and_validity():
    sp = PlanSpace(nprobe=8, depth=16, k=10)
    assert sp.full == QueryPlan(8, 16)
    assert sp.ladder[-1].shed and not any(p.shed for p in sp.buckets)
    # depth halves first (while k fits), then nprobe; every non-shed
    # level is a valid engine call
    assert sp.ladder == (QueryPlan(8, 16), QueryPlan(8, 8), QueryPlan(8, 4),
                         QueryPlan(8, 2), QueryPlan(8, 2, shed=True))
    for p in sp.buckets:
        assert sp.k <= p.nprobe * p.depth
    # a smaller k lets the ladder reach the nprobe halvings
    sp2 = PlanSpace(nprobe=8, depth=16, k=4)
    assert QueryPlan(4, 1) in sp2.buckets
    assert sp2.ladder[-2] == QueryPlan(4, 1)


def test_plan_space_bucket_rounds_effort_up():
    sp = PlanSpace(nprobe=8, depth=16, k=10)
    # exact ladder levels map to themselves
    for p in sp.buckets:
        assert sp.bucket(p) == p
    # arbitrary requests take the LOWEST-effort dominating bucket
    assert sp.bucket(QueryPlan(5, 3)) == QueryPlan(8, 4)
    assert sp.bucket(QueryPlan(1, 1)) == QueryPlan(8, 2)
    # above-full clamps to full; shed maps to the shed level
    assert sp.bucket(QueryPlan(9, 64)) == sp.full
    assert sp.bucket(QueryPlan(2, 2, shed=True)) == sp.ladder[-1]
    # bucketing never reduces either effort dimension below the request
    # (unless the request exceeds full effort)
    for np_, d_ in [(1, 16), (8, 1), (3, 5), (7, 9)]:
        b = sp.bucket(QueryPlan(np_, d_))
        assert b.nprobe >= min(np_, sp.full.nprobe)
        assert b.depth >= min(d_, sp.full.depth)
    assert sp.level(sp.full) == 0
    assert sp.level(sp.ladder[-1]) == len(sp.ladder) - 1


# ------------------------------------------------------ degradation controller
def test_degradation_controller_hysteresis():
    sp = PlanSpace(nprobe=8, depth=8, k=10)
    # ladder: (8,8) (8,4) (8,2) shed
    assert len(sp.ladder) == 4
    c = DegradationController(sp, high=10, low=2, recover_after=3)
    assert c.observe(0) == sp.full
    # escalation: one level per overloaded flush, clamped at shed
    assert c.observe(11) == sp.ladder[1]
    assert c.observe(50) == sp.ladder[2]
    assert c.observe(50) == sp.ladder[3] and sp.ladder[3].shed
    assert c.observe(999) == sp.ladder[3]
    # a mid reading holds the level
    assert c.observe(5) == sp.ladder[3]
    # recovery requires recover_after CONSECUTIVE calm flushes
    assert c.observe(0) == sp.ladder[3]
    assert c.observe(1) == sp.ladder[3]
    assert c.observe(2) == sp.ladder[2]
    # ... and a mid reading resets the calm streak
    assert c.observe(0) == sp.ladder[2]
    assert c.observe(0) == sp.ladder[2]
    assert c.observe(5) == sp.ladder[2]
    assert c.observe(0) == sp.ladder[2]
    assert c.observe(0) == sp.ladder[2]
    assert c.observe(0) == sp.ladder[1]


# --------------------------------------------------------- priority dispatcher
def test_priority_dispatcher_queued_queries_preempt_ingest():
    d = PriorityDispatcher()
    order = []
    inside = threading.Event()
    release = threading.Event()

    def holder():
        with d.query():
            inside.set()
            release.wait(10)

    def ingester(i):
        with d.ingest():
            order.append(("ingest", i))

    def querier(i):
        with d.query():
            order.append(("query", i))

    t0 = threading.Thread(target=holder)
    t0.start()
    assert inside.wait(10)
    threads = [threading.Thread(target=ingester, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    qs = [threading.Thread(target=querier, args=(i,)) for i in range(3)]
    for t in qs:
        t.start()
    # queries register as waiting BEFORE the holder releases, so the
    # ordering assertion below is deterministic, not a race
    deadline = time.monotonic() + 10
    while d._queries_waiting < 3:
        assert time.monotonic() < deadline, "queriers never queued"
        time.sleep(0.001)
    release.set()
    for t in threads + qs + [t0]:
        t.join(10)
        assert not t.is_alive()
    assert [kind for kind, _ in order[:3]] == ["query"] * 3
    assert sorted(order[3:]) == [("ingest", i) for i in range(3)]


# ------------------------------------------------------- full-effort parity
@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
def test_full_effort_plan_bit_identical_live_and_snapshot(store_dtype):
    """plan=QueryPlan(nprobe, store_depth) runs the exact pre-plan
    program: every output — scores included — is bit-identical."""
    cfg, eng, q = _ingested_engine(store_dtype)
    full = QueryPlan(nprobe=4, depth=cfg.store_depth)

    base = eng.query(q, k=6, two_stage=True, nprobe=4)
    planned = eng.query(q, k=6, two_stage=True, plan=full)
    for a, b in zip(base, planned):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    snap = eng.publish()
    base = eng.query_snapshot(snap, q, k=6, two_stage=True, nprobe=4)
    planned = eng.query_snapshot(snap, q, k=6, two_stage=True, plan=full)
    for a, b in zip(base, planned):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
def test_degraded_plan_matches_sliced_store_oracle(store_dtype):
    """A depth-clipped plan answers exactly like an engine whose store
    was PHYSICALLY built at that depth (same index, rings prefix-cut):
    ids/clusters bit-equal, rows equal after re-addressing the oracle's
    flat rows into full-store coordinates."""
    cfg, eng, q = _ingested_engine(store_dtype, batches=8)
    dp = 4
    snap = eng.publish()
    sc, rows, ids, cl = eng.query_snapshot(
        snap, q, k=6, two_stage=True, plan=QueryPlan(nprobe=4, depth=dp))

    cfg_dp = dataclasses.replace(cfg, store_depth=dp)
    store = snap.store
    sliced = store._replace(
        embs=store.embs[:, :dp], ids=store.ids[:, :dp],
        stamps=store.stamps[:, :dp], scales=store.scales[:, :dp])
    sc_o, rows_o, ids_o, cl_o = snapshot_query_impl(
        cfg_dp, snap.index, snap.route_labels, sliced, q, 6,
        two_stage=True, nprobe=4)

    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_o))
    np.testing.assert_array_equal(np.asarray(cl), np.asarray(cl_o))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_o),
                               rtol=2e-5, atol=2e-5)
    rows_o = np.asarray(rows_o)
    expect = np.where(rows_o < 0, -1,
                      (rows_o // dp) * cfg.store_depth + rows_o % dp)
    np.testing.assert_array_equal(np.asarray(rows), expect)
    # the clip is real: at least one answer differs from full effort
    full = eng.query_snapshot(snap, q, k=6, two_stage=True, nprobe=4)
    assert not np.array_equal(np.asarray(full[2]), np.asarray(ids))


# --------------------------------------------------- compile-count regression
def test_steady_state_compiles_equal_plan_buckets_not_plans():
    """Many distinct requested plans, few buckets: the per-variant trace
    counters show exactly ONE jit trace per bucket — compile count is
    bounded by the PlanSpace, not by request diversity."""
    # dim=48 keeps this cfg's jit cache entries disjoint from every other
    # test in the process (trace counters only tick on a fresh trace)
    cfg, eng, q = _ingested_engine("int8", dim=48)
    sp = PlanSpace(nprobe=4, depth=8, k=6, min_depth=2)
    assert [p.key for p in sp.buckets] == ["np4xd8", "np4xd4", "np4xd2"]

    obs.enable(metrics=True, trace=False)
    reg = obs.metrics()
    snap = eng.publish()
    requested = [QueryPlan(4, 8), QueryPlan(3, 8), QueryPlan(2, 7),
                 QueryPlan(4, 5), QueryPlan(3, 3), QueryPlan(1, 8),
                 QueryPlan(2, 2)]
    used = set()
    for pl in requested * 2:  # steady state: repeats must not re-trace
        b = sp.bucket(pl)
        used.add(b)
        eng.query_snapshot(snap, q, k=6, two_stage=True, plan=b)
    assert len(used) == 3 < len(set(requested))

    def traces(name):
        return (reg.counter(f"kernel_traces_total_serve_ref{name}").value
                + reg.counter(
                    f"kernel_traces_total_serve_pallas{name}").value)

    assert traces("") == len(used)
    for b in used:
        assert traces(f"_{b.key}") == 1


def test_tune_cache_variant_entry_wins_over_base(tmp_path, monkeypatch):
    """A plan-bucket tune entry (serve/int8/np4xd8) beats the shared
    serve/int8 fallback for that bucket only; ``tuning.applied`` records
    each lookup under the key that actually matched."""
    from repro.kernels import tuning
    from repro.kernels.serve.ops import serve_topk

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tc.json"))
    tuning.reload()
    tuning.applied.clear()
    base_tile = {"bq": 16, "bk": 256, "bd": 8}
    var_tile = {"bq": 8, "bk": 128, "bd": 4}
    tuning.record("serve", "int8", base_tile)
    tuning.record("serve", "int8", var_tile, variant="np4xd8")

    C, depth, d, cap = 12, 8, 64, 32
    qr = jnp.asarray(RNG.normal(size=(6, d)), jnp.float32)
    qn = jnp.asarray(RNG.normal(size=(6, d)), jnp.float32)
    vectors = jnp.asarray(RNG.normal(size=(cap, d)), jnp.float32)
    valid = jnp.ones(cap, bool)
    labels = jnp.asarray(RNG.integers(0, C, cap), jnp.int32)
    embs = jnp.asarray(RNG.integers(-127, 128, (C, depth, d)), jnp.int8)
    live = jnp.ones((C, depth), bool)
    scales = jnp.asarray(RNG.random((C, depth)) * 0.02 + 1e-4, jnp.float32)

    plat = tuning.platform()
    # bucket np4xd8: the variant entry wins
    a = serve_topk(qr, qn, vectors, valid, labels, embs, live, 5, 4,
                   scales=scales, use_pallas=True)
    assert tuning.applied.get(f"{plat}/serve/int8/np4xd8") == var_tile
    # bucket np2xd8 has no variant entry: base fallback, recorded as such
    b = serve_topk(qr, qn, vectors, valid, labels, embs, live, 5, 2,
                   scales=scales, use_pallas=True)
    assert tuning.applied.get(f"{plat}/serve/int8") == base_tile
    # tiles are pure perf knobs — both calls agree with the reference
    from repro.kernels.serve.ref import serve_topk_ref
    for got, P in ((a, 4), (b, 2)):
        want = serve_topk_ref(qr, qn, vectors, valid, labels, embs, live,
                              5, P, scales)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
    tuning.reload()
    tuning.applied.clear()


# ----------------------------------------------------- 4-device sharded parity
def test_sharded_plan_parity_four_device():
    """Full-effort plan == plan-free on the 4-device cluster-sharded
    engine (all outputs bit-equal), and a degraded plan matches the
    single-device program over the gathered snapshot — fp32 and int8
    (subprocess: forced 4-device CPU mesh)."""
    body = """
        from repro.configs.streaming_rag import paper_pipeline_config
        from repro.engine.engine import snapshot_query_impl
        from repro.engine.plan import QueryPlan
        from repro.engine.sharded import ShardedEngine

        for store_dtype in ("fp32", "int8"):
            cfg = paper_pipeline_config(dim=32, k=16, capacity=12,
                                        update_interval=32, alpha=-1.0,
                                        store_depth=8,
                                        store_dtype=store_dtype)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 2), ("data", "model"))
            eng = ShardedEngine(cfg, mesh, jax.random.key(0),
                                reconcile_every=100)
            rng = np.random.default_rng(3)
            for b in range(4):
                x = jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)
                eng.ingest(x, jnp.arange(32, dtype=jnp.int32) + 32 * b)
            snap = eng.reconcile()
            q = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)

            # full-effort plan == plan-free, every output bit-equal
            base = eng.query_snapshot(snap, q, k=6, two_stage=True,
                                      nprobe=4)
            plan = eng.query_snapshot(snap, q, k=6, two_stage=True,
                                      plan=QueryPlan(nprobe=4, depth=8))
            for a, b2 in zip(base, plan):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b2))

            # degraded plan: sharded == single-device over the gathered
            # snapshot with the same depth clip (ids/clusters exact)
            deg = QueryPlan(nprobe=4, depth=4)
            sc_d, _, ids_d, cl_d = eng.query_snapshot(
                snap, q, k=6, two_stage=True, plan=deg)
            full_store = jax.tree.map(
                lambda a: jnp.asarray(np.asarray(a)), snap.store)
            sc_1, _, ids_1, cl_1 = snapshot_query_impl(
                cfg, jax.tree.map(jnp.asarray, snap.index),
                jnp.asarray(snap.route_labels), full_store, q, 6,
                two_stage=True, nprobe=4, depth=4)
            np.testing.assert_array_equal(np.asarray(ids_d),
                                          np.asarray(ids_1))
            np.testing.assert_array_equal(np.asarray(cl_d),
                                          np.asarray(cl_1))
            np.testing.assert_allclose(np.asarray(sc_d), np.asarray(sc_1),
                                       rtol=2e-5, atol=2e-5)
        print("PLAN-PARITY-OK")
    """
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np
        import jax, jax.numpy as jnp
    """) + textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                          text=True, timeout=600,
                          env={**__import__("os").environ,
                               "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PLAN-PARITY-OK" in proc.stdout
