"""Table 21 — hot-set serving cache under Zipfian query traffic.

Closed-loop Zipf-α sweep over a fixed query pool: a cached server (the
two-level hot-set cache — snapshot-versioned exact result cache +
heavy-hitter pinned fast tier) and an uncached server answer IDENTICAL
draw sequences from the SAME pre-ingested engine, and per-flush latency
is compared.

The pool is larger than the result-cache capacity, so skew is the whole
story: at α=0 (uniform) the LRU churns and most flushes contain misses —
cached p50 ≈ uncached p50 plus bookkeeping; as α grows the Zipf head
stays resident, all-hit flushes dominate, and cached p50 collapses to
host-side lookup time (the route-free exact path never touches the
device). Misses ride the pinned hot tier when covered.

Asserted in-bench:

  * answers are BIT-IDENTICAL to the uncached server on every draw —
    and therefore Recall@10 gap is exactly 0.000 (both recalls are still
    computed independently against the archive oracle and the gap is
    asserted, per α);
  * at α=1.1 the cached p50 is >= 1.5x better than uncached (the smoke
    gate is the weaker strict inequality);
  * the cache actually worked at α=1.1: nonzero hit rate, and after the
    post-sweep staleness probe (a small delta publish + replay) nonzero
    rekeyed entries with precise invalidation accounting.

Reported per α: p50/p90 per-flush latency for both servers, speedup,
hit rate, exact-hit fraction, hot-tier serves, pinned KiB, recall pair,
and the staleness-probe counters (invalidated / rekeyed / hit
staleness).

``--smoke`` runs {0, 1.1} with fewer timed flushes — the CI Zipf gate.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.common import emit_row

DIM = 64
TOPK = 10
NPROBE = 8
DEPTH = 16
MAX_BATCH = 4
POOL = 192           # distinct pool queries ...
CACHE_ENTRIES = 160  # ... deliberately > cache capacity: skew must win
N_INGEST_BATCHES = 12
INGEST_BATCH = 256
ALPHAS = (0.0, 0.8, 1.1, 1.4)
GATE_ALPHA = 1.1
GATE_SPEEDUP = 1.5


def _stream(seed: int = 0):
    from repro.data.streams import StreamConfig, TopicStream

    return TopicStream(StreamConfig(
        "synthetic-drift", dim=DIM, n_topics=96, zipf_s=1.05, drift=0.03,
        burstiness=0.05, noise=0.45, background_frac=0.10, seed=2100 + seed))


def _build(seed: int):
    """One pre-ingested engine + host archive shared by every α cell:
    the sweep varies only the draw distribution over the query pool."""
    import jax

    from benchmarks.common import DocArchive
    from repro.configs.streaming_rag import paper_pipeline_config
    from repro.engine.engine import Engine

    cfg = paper_pipeline_config(dim=DIM, k=96, capacity=64,
                                update_interval=256, alpha=0.1,
                                store_depth=DEPTH)
    stream = _stream(seed)
    archive = DocArchive(DIM)
    warm = [stream.next_batch(INGEST_BATCH) for _ in range(2)]
    for b in warm:
        archive.add(b)
    engine = Engine(cfg, jax.random.key(seed),
                    np.concatenate([b["embedding"] for b in warm]))
    for b in warm:
        engine.ingest(b["embedding"], b["doc_id"])
    for _ in range(N_INGEST_BATCHES):
        b = stream.next_batch(INGEST_BATCH)
        archive.add(b)
        engine.ingest(b["embedding"], b["doc_id"])
    return cfg, engine, archive, stream


def _server(cfg, engine, *, cached: bool):
    from repro.serve.runtime import AsyncServer, ServerConfig

    scfg = ServerConfig(
        max_batch=MAX_BATCH, max_wait_ms=0.0, topk=TOPK, two_stage=True,
        nprobe=NPROBE,
        cache_entries=CACHE_ENTRIES if cached else 0, hotset=cached,
        pin_budget_mb=0.25, hotset_capacity=64, hotset_refresh=8,
        hotset_min_count=2)
    # publishes are driven manually (sync) so the timed loop is clean
    return AsyncServer(cfg, scfg, engine=engine, publish_every=10**9)


def _warm_shapes(server):
    """Compile every pow2 sub-batch shape of the full-effort plan before
    timing: cached flushes serve their cold misses as padded pow2
    sub-batches, and a first-touch compile inside the measured window
    would charge XLA to whichever α first saw that shape."""
    b = 1
    while b <= MAX_BATCH:
        server.engine.query_snapshot(
            server._snapshot, np.zeros((b, DIM), np.float32), TOPK,
            two_stage=True, plan=server._full_plan)
        b *= 2


def _zipf_draws(rng, alpha: float, n: int) -> np.ndarray:
    """n i.i.d. pool indices, P(rank r) ∝ 1/(r+1)^alpha (alpha=0 is
    uniform). Rank == pool index: the head is the low indices."""
    p = 1.0 / np.power(np.arange(1, POOL + 1, dtype=np.float64), alpha)
    return rng.choice(POOL, size=n, p=p / p.sum())


def _answer_rounds(server, pool, draws):
    """Drive ``draws`` (shape [rounds, MAX_BATCH]) closed-loop; returns
    per-flush wall latencies and the answers in draw order."""
    lat_ms = np.zeros(len(draws))
    answers = []
    for r, idx in enumerate(draws):
        for i in idx:
            server.submit(pool[i])
        t0 = time.perf_counter()
        out = server.flush()
        lat_ms[r] = (time.perf_counter() - t0) * 1e3
        assert len(out) == len(idx)
        answers.extend(out)
    return lat_ms, answers


def _recall10(archive, qs: np.ndarray, answers: list[dict]) -> float:
    """Topic-coverage Recall@10 vs the exact archive oracle (the
    benchmarks/common convention, as in tables 14/20)."""
    arc = archive.materialize()
    oracle_ids, _ = arc.oracle_topk(qs, TOPK)
    recalls = []
    for i, a in enumerate(answers):
        o_topics = {t for t in arc.T[oracle_ids[i]] if t >= 0}
        got = [int(d) for d in a["doc_ids"] if 0 <= d < len(arc.T)]
        r_topics = {arc.T[d] for d in got if arc.T[d] >= 0}
        recalls.append(len(o_topics & r_topics) / max(len(o_topics), 1))
    return float(np.mean(recalls))


def _cell(cfg, engine, archive, stream, pool, *, alpha: float,
          n_timed: int, n_warm: int, seed: int) -> dict:
    srv_c = _server(cfg, engine, cached=True)
    srv_u = _server(cfg, engine, cached=False)
    try:
        _warm_shapes(srv_c)
        _warm_shapes(srv_u)
        rng = np.random.default_rng(100 + seed)
        # untimed: LRU/hot-set reach steady state under this α, and the
        # hot-tier program compiles outside the measured window
        warm_draws = _zipf_draws(rng, alpha, n_warm * MAX_BATCH) \
            .reshape(n_warm, MAX_BATCH)
        _answer_rounds(srv_c, pool, warm_draws)
        timed = _zipf_draws(rng, alpha, n_timed * MAX_BATCH) \
            .reshape(n_timed, MAX_BATCH)
        lat_c, ans_c = _answer_rounds(srv_c, pool, timed)
        lat_u, ans_u = _answer_rounds(srv_u, pool, timed)
        # bit-identity on every draw — the cache's core contract
        for a, b in zip(ans_c, ans_u):
            np.testing.assert_array_equal(a["doc_ids"], b["doc_ids"])
            np.testing.assert_array_equal(a["scores"], b["scores"])
        qs = pool[timed.ravel()]
        rec_c = _recall10(archive, qs, ans_c)
        rec_u = _recall10(archive, qs, ans_u)
        assert rec_c == rec_u, (rec_c, rec_u)   # gap exactly 0.000

        # staleness probe (untimed): a small delta publish invalidates
        # precisely, survivors re-key, and head replays hit with
        # staleness >= 1
        b = stream.next_batch(MAX_BATCH)
        srv_c.ingest(b["embedding"], b["doc_id"])
        srv_c.sync()
        srv_u.sync()
        probe = np.tile(np.arange(MAX_BATCH), 2).reshape(2, MAX_BATCH)
        _, pa = _answer_rounds(srv_c, pool, probe)
        _, pb = _answer_rounds(srv_u, pool, probe)
        for a, b_ in zip(pa, pb):
            np.testing.assert_array_equal(a["doc_ids"], b_["doc_ids"])

        cs = srv_c.cache_stats()
        return {
            "table": "table21",
            "alpha": alpha,
            "flushes": n_timed,
            "p50_cached_ms": round(float(np.percentile(lat_c, 50)), 3),
            "p50_uncached_ms": round(float(np.percentile(lat_u, 50)), 3),
            "p90_cached_ms": round(float(np.percentile(lat_c, 90)), 3),
            "p90_uncached_ms": round(float(np.percentile(lat_u, 90)), 3),
            "p50_speedup": round(float(np.percentile(lat_u, 50))
                                 / max(float(np.percentile(lat_c, 50)),
                                       1e-9), 3),
            "hit_rate": round(cs["hit_rate"], 4),
            "exact_hit_frac": round(
                srv_c._result_cache.stats()["hits_exact"]
                / max(cs["hits"], 1), 4),
            "hot_served": cs["hot_served"],
            "pinned_kib": round(cs["pinned_bytes"] / 1024, 1),
            "recall10_cached": round(rec_c, 4),
            "recall10_uncached": round(rec_u, 4),
            "recall_gap": round(rec_c - rec_u, 4),
            "invalidated": cs["invalidated"],
            "rekeyed": cs["rekeyed"],
            "hit_staleness": round(cs["hit_staleness"], 4),
        }
    finally:
        srv_c.close()
        srv_u.close()


def run(n_timed: int = 48, seed: int = 0, smoke: bool = False) -> list[dict]:
    alphas = (0.0, GATE_ALPHA) if smoke else ALPHAS
    n_timed = max(8, n_timed if not smoke else min(n_timed, 24))
    # warm covers the pool ~1.5x so the LRU reaches its α-stationary
    # occupancy before timing starts
    n_warm = max(6, 3 * POOL // MAX_BATCH // 2)
    cfg, engine, archive, stream = _build(seed)
    pool = np.asarray(_stream(seed + 7).queries(POOL)["embedding"],
                      np.float32)

    rows = [_cell(cfg, engine, archive, stream, pool, alpha=a,
                  n_timed=n_timed, n_warm=n_warm, seed=seed)
            for a in alphas]

    # acceptance: at the gate skew the cache pays for itself on p50 —
    # >= 1.5x in the full sweep, strictly better in smoke — with a real
    # hit rate behind it; the recall gap is exactly zero at EVERY α
    gate = next(r for r in rows if r["alpha"] == GATE_ALPHA)
    if smoke:
        assert gate["p50_cached_ms"] < gate["p50_uncached_ms"], gate
    else:
        assert gate["p50_speedup"] >= GATE_SPEEDUP, gate
    assert gate["hit_rate"] > 0.5, gate
    assert gate["rekeyed"] > 0, gate
    for r in rows:
        assert r["recall_gap"] == 0.0, r
    return rows


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--smoke":
        out = run(smoke=True)
    else:
        out = run()
    for row in out:
        emit_row(row)
    print("TABLE21-HOTSET-CACHE-OK", flush=True)
