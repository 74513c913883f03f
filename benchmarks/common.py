"""Shared benchmark harness: run retrieval methods over simulated streams
against an exact oracle; measure Recall@10, nDCG@10, latency, throughput,
memory; paired t-tests across query batches.

Relevance definitions (DESIGN.md §8.2 — the paper's labels are not
redistributable, so ground truth comes from the generator):
  * oracle top-k   — exact cosine top-k over every document streamed so far
  * Recall@10      — topic coverage: |topics(oracle@10) ∩ topics(ret@10)|
                     / |topics(oracle@10)| (semantic-coverage metric the
                     pipeline optimizes; background docs excluded)
  * nDCG@10        — graded relevance rel_i = max(cos(q, doc_i), 0),
                     normalized by the oracle's ideal DCG
"""
from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from repro.core import baselines as B
from repro.data.streams import TopicStream, make_stream


@dataclasses.dataclass
class BenchResult:
    method: str
    recall10: float
    recall10_std: float
    ndcg10: float
    ndcg10_std: float
    ingest_latency_ms: float     # per-doc pipeline latency (batch/size)
    query_latency_ms: float      # per-query end-to-end
    throughput_dps: float        # docs/sec ingest
    memory_mb: float
    extras: dict = dataclasses.field(default_factory=dict)

    def row(self) -> dict:
        d = dataclasses.asdict(self)
        d.pop("extras")
        d.update(self.extras)
        return d


class DocArchive:
    """Host-side archive for oracle computation (bench-only memory)."""

    def __init__(self, dim: int):
        self.vecs: list[np.ndarray] = []
        self.topics: list[np.ndarray] = []

    def add(self, batch):
        self.vecs.append(batch["embedding"])
        self.topics.append(batch["topic"])

    def materialize(self):
        self.V = np.concatenate(self.vecs)
        self.T = np.concatenate(self.topics)
        return self

    def oracle_topk(self, q: np.ndarray, k: int = 10):
        s = q @ self.V.T
        ids = np.argpartition(-s, k, axis=1)[:, :k]
        row = np.arange(q.shape[0])[:, None]
        order = np.argsort(-s[row, ids], axis=1)
        ids = ids[row, order]
        return ids, s[row, ids]


def ndcg_at_k(rels: np.ndarray, ideal: np.ndarray, k: int = 10) -> float:
    disc = 1.0 / np.log2(np.arange(2, k + 2))
    dcg = np.sum(np.maximum(rels[:, :k], 0.0) * disc, axis=1)
    idcg = np.sum(np.maximum(ideal[:, :k], 0.0) * disc, axis=1)
    return float(np.mean(dcg / np.maximum(idcg, 1e-9)))


def evaluate_method(method: B.Method, stream: TopicStream, *,
                    n_batches: int = 60, batch: int = 256,
                    n_query_rounds: int = 10, queries_per_round: int = 50,
                    k: int = 10, seed: int = 0, needs_warmup: bool = False,
                    warmup_batches: int = 2) -> BenchResult:
    """Stream → ingest; interleave query rounds; score vs exact oracle."""
    archive = DocArchive(stream.cfg.dim)
    key = jax.random.key(seed)

    # --- init (some methods train on a warmup sample) ---
    warm = [stream.next_batch(batch) for _ in range(warmup_batches)]
    for b in warm:
        archive.add(b)
    warm_x = np.concatenate([b["embedding"] for b in warm])
    try:
        state = method.init(key, jax.numpy.asarray(warm_x))
    except TypeError:
        state = method.init(key)
    for b in warm:
        state = method.ingest(state, jax.numpy.asarray(b["embedding"]),
                              jax.numpy.asarray(b["doc_id"]))

    # --- timed ingest ---
    t_ingest = 0.0
    query_rounds = []
    per_round = max(1, n_batches // n_query_rounds)
    for i in range(n_batches):
        b = stream.next_batch(batch)
        archive.add(b)
        x = jax.numpy.asarray(b["embedding"])
        ids = jax.numpy.asarray(b["doc_id"])
        t0 = time.perf_counter()
        state = method.ingest(state, x, ids)
        jax.block_until_ready(jax.tree.leaves(state)[0])
        t_ingest += time.perf_counter() - t0
        if (i + 1) % per_round == 0:
            query_rounds.append(_query_round(
                method, state, stream, archive, queries_per_round, k))

    total_docs = n_batches * batch
    rec = np.array([r["recall"] for r in query_rounds])
    ndcg = np.array([r["ndcg"] for r in query_rounds])
    qlat = np.array([r["latency_ms"] for r in query_rounds])
    return BenchResult(
        method=method.name,
        recall10=float(rec.mean()), recall10_std=float(rec.std()),
        ndcg10=float(ndcg.mean()), ndcg10_std=float(ndcg.std()),
        ingest_latency_ms=1e3 * t_ingest / n_batches,
        query_latency_ms=float(qlat.mean()),
        throughput_dps=total_docs / max(t_ingest, 1e-9),
        memory_mb=method.memory_bytes() / 1e6,
        extras={"recall_rounds": rec.tolist()},
    )


def _query_round(method, state, stream, archive, n_q, k):
    qs = stream.queries(n_q)
    q = jax.numpy.asarray(qs["embedding"])
    t0 = time.perf_counter()
    out = method.query(state, q, k)
    jax.block_until_ready(out[0])
    lat = (time.perf_counter() - t0) / n_q * 1e3

    arc = archive.materialize()
    oracle_ids, oracle_scores = arc.oracle_topk(qs["embedding"], k)

    scores, _rows, doc_ids = out[0], out[1], out[2]
    doc_ids = np.asarray(doc_ids)
    qv = qs["embedding"]

    recalls, rels = [], np.zeros((n_q, k))
    for i in range(n_q):
        o_topics = {t for t in arc.T[oracle_ids[i]] if t >= 0}
        got = [int(d) for d in doc_ids[i] if 0 <= d < len(arc.T)]
        r_topics = {arc.T[d] for d in got if arc.T[d] >= 0}
        recalls.append(len(o_topics & r_topics) / max(len(o_topics), 1))
        for j, d in enumerate(doc_ids[i][:k]):
            if 0 <= d < len(arc.V):
                rels[i, j] = float(qv[i] @ arc.V[int(d)])
    return {
        "recall": float(np.mean(recalls)),
        "ndcg": ndcg_at_k(rels, oracle_scores, k),
        "latency_ms": lat,
    }


def paired_t(a: np.ndarray, b: np.ndarray) -> tuple[float, float]:
    """Two-tailed paired Student t-test (the paper's significance test)."""
    from scipy import stats

    t, p = stats.ttest_rel(a, b)
    return float(t), float(p)


def default_methods(dim: int, budget_docs: int = 256):
    """The paper's seven methods at comparable state budgets."""
    from repro.configs.streaming_rag import paper_pipeline_config

    cfg = paper_pipeline_config(dim=dim, k=150, capacity=100,
                                update_interval=256, alpha=0.1)
    return [
        # static snapshot freezes after ~1k docs -> staleness shows within
        # the bench horizon (the paper's central dynamic)
        B.make_static_rag(dim, capacity=1024),
        B.make_full_rebuild(dim, buffer_size=1024, k=100,
                            rebuild_interval=256),
        B.make_reservoir(dim, k=256),
        B.make_heap_only(dim, n_anchors=512, capacity=100),
        B.make_ivfpq(dim, capacity=2048, nlist=32, m=8, nprobe=8),
        B.make_sakr(dim, k=100, capacity=100),
        B.make_streaming_rag(cfg),
    ]


def write_csv(path: str, rows: list[dict]):
    import csv
    keys = sorted({k for r in rows for k in r}, key=lambda k: (k != "method", k))
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=keys, extrasaction="ignore")
        w.writeheader()
        for r in rows:
            w.writerow(r)


def device_info() -> dict:
    """The devices this process runs on, as JAX reports them — every
    printed benchmark row names them, so no number is mistaken for one
    taken on another device."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def emit_row(row: dict) -> None:
    """Print one result row as ``ROW <json>``, naming its devices."""
    print("ROW " + json.dumps({**row, **device_info()}), flush=True)


def run_multi_device(module: str, args: list, part, n: int = 4
                     ) -> list[dict]:
    """Run a table's ``n``-device ``part`` and return the rows it emitted.

    Held to the CPU (``JAX_PLATFORMS=cpu``), the part runs in a child
    (``python -m module *args``) with ``n`` forced host devices, because a
    process that has touched JAX cannot change its device count. On an
    accelerator host it runs in this process over the real devices — a
    child could not reach a chip this process holds — and refuses when
    there are fewer than ``n``."""
    from repro.launch.mesh import cpu_requested

    if cpu_requested():
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in ("src", ".", env.get("PYTHONPATH", "")) if p)
        proc = subprocess.run(
            [sys.executable, "-m", module, *map(str, args)],
            capture_output=True, text=True, timeout=3600, env=env)
        if proc.returncode != 0:
            raise RuntimeError(f"{module} child failed:\n"
                               f"{proc.stderr[-3000:]}")
        out = proc.stdout
    else:
        info = device_info()
        if info["device_count"] < n:
            raise RuntimeError(
                f"{module} needs {n} devices; this {info['platform']} host "
                f"has {info['device_count']}")
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            part()
        out = buf.getvalue()
    return [json.loads(line[4:]) for line in out.splitlines()
            if line.startswith("ROW ")]
