"""Table 17 — quantized tiered store: int8 rings at 4x depth vs fp32 rings
at 1x depth, at equal store bytes (synthetic drifting bursty stream,
routed two-stage retrieval).

The memory argument of the whole system is per-byte retrieval quality.
fp32 ring slots spend ``4*dim`` bytes per document embedding; int8 slots
(quantize-on-admit + per-slot fp32 scale) spend ``dim + 12``. At the
paper's dim=384 an int8 ring of depth ``4*D`` costs within ~2.5% of an
fp32 ring of depth ``D`` — so the comparison isolates exactly what PR 1
showed matters: ring *depth* (recent docs per cluster) is where two-stage
recall comes from.

Variants (one PipelineConfig family, same stream replay):

  * fp32_d16      — the PR-1 store: fp32 rings, depth 16.
  * int8_d16      — same depth, int8 rings: isolates the pure quantization
                    cost (recall gap must be ~0: scores only move by the
                    quant error, ids/stamps identical — pinned in tests).
  * int8_d64      — 4x depth at ~equal store bytes: the headline. Deeper
                    rings hold docs from more topics through bursty churn,
                    so Recall@10 beats fp32_d16.
  * sharded_*     — fp32_d16 and int8_d64 served from ``ShardedEngine`` on
                    a forced (1, 4) CPU mesh: cluster-sharded int8 rings,
                    per-device bytes = full/4, recall within noise of the
                    single-device engine.

Also reports per-query two-stage latency (routing + rerank) per variant —
the dequant-rerank path at 4x depth scores 4x the candidates.

The measurement needs ``--xla_force_host_platform_device_count=4`` set
before jax initializes, so ``run()`` re-execs itself as a child process
with the right env and parses its JSON rows — safe to call from
``benchmarks.run`` in an already-initialized parent.
"""
from __future__ import annotations

import sys

from benchmarks.common import emit_row, run_multi_device

DIM = 384          # paper dim: int8@4x-depth bytes ~= fp32@1x-depth bytes
NPROBE = 16
DEPTH = 16
K_CLUSTERS = 64    # few clusters over many topics -> rings are contended
TOPK = 10


def _stream(seed: int = 0):
    """Bursty drifting load: bursts flush shallow rings (one hot topic
    overwrites a whole cluster ring within a batch or two), so ring depth
    — not prototype count — governs how many topics the store retains."""
    from repro.data.streams import StreamConfig, TopicStream

    return TopicStream(StreamConfig(
        "synthetic-burst", dim=DIM, n_topics=128, zipf_s=1.02, drift=0.02,
        burstiness=0.25, noise=0.5, background_frac=0.10, seed=300 + seed))


def _config(depth: int, store_dtype: str):
    from repro.configs.streaming_rag import paper_pipeline_config

    return paper_pipeline_config(dim=DIM, k=K_CLUSTERS, capacity=64,
                                 update_interval=256, alpha=0.1,
                                 store_depth=depth, store_dtype=store_dtype)


def _warmup(batch: int, seed: int):
    import numpy as np

    stream = _stream(seed)
    return np.concatenate(
        [stream.next_batch(batch)["embedding"] for _ in range(2)])


def _eval_engine(engine, *, n_batches: int, batch: int, seed: int,
                 rounds: int = 4):
    """Ingest the stream; interleave two-stage query rounds scored against
    the exact oracle (topic-coverage Recall@10, as tables 14/15). Returns
    (recall_rounds, query_latency_ms_rounds)."""
    import numpy as np

    from benchmarks.common import DocArchive, _query_round

    class _Q:  # adapt the engine to the Method.query protocol
        def query(self, _state, q, k):
            return engine.query(np.asarray(q), k, two_stage=True,
                                nprobe=NPROBE)

    stream = _stream(seed)
    archive = DocArchive(DIM)
    recalls, lats = [], []
    per_round = max(1, n_batches // rounds)
    for i in range(2 + n_batches):
        b = stream.next_batch(batch)
        archive.add(b)
        engine.ingest(b["embedding"], b["doc_id"])
        if i >= 2 and (i - 1) % per_round == 0:
            if hasattr(engine, "reconcile"):
                engine.reconcile()
            r = _query_round(_Q(), None, stream, archive, 50, TOPK)
            recalls.append(r["recall"])
            lats.append(r["latency_ms"])
    return recalls, lats


def _child(n_batches: int, batch: int, seed: int):
    import jax
    import numpy as np

    from repro.engine import Engine
    from repro.engine.sharded import ShardedEngine
    from repro.store import docstore

    warm = _warmup(batch, seed)
    variants = [("fp32_d16", DEPTH, "fp32"),
                ("int8_d16", DEPTH, "int8"),
                ("int8_d64", 4 * DEPTH, "int8")]
    rows = []
    for label, depth, dtype in variants:
        cfg = _config(depth, dtype)
        eng = Engine(cfg, jax.random.key(seed), warmup=warm)
        rec, lat = _eval_engine(eng, n_batches=n_batches, batch=batch,
                                seed=seed)
        rows.append({"table": "table17", "variant": label,
                     "store_dtype": dtype, "depth": depth,
                     "recall10": float(np.mean(rec)), "recall_rounds": rec,
                     "query_latency_ms": float(np.mean(lat)),
                     "store_bytes": docstore.memory_bytes(cfg.store)})

    # equal-budget guard: 4x-depth int8 rings cost ~the fp32 bytes
    by = {r["variant"]: r for r in rows}
    assert by["int8_d64"]["store_bytes"] <= \
        1.03 * by["fp32_d16"]["store_bytes"], \
        (by["int8_d64"]["store_bytes"], by["fp32_d16"]["store_bytes"])
    # headline: depth bought by quantization converts into recall
    assert by["int8_d64"]["recall10"] > by["fp32_d16"]["recall10"], \
        (by["int8_d64"]["recall10"], by["fp32_d16"]["recall10"])
    # equal-depth quantization cost stays under half a recall point
    assert abs(by["int8_d16"]["recall10"] - by["fp32_d16"]["recall10"]) \
        <= 0.005, (by["int8_d16"]["recall10"], by["fp32_d16"]["recall10"])

    # ---- 4-device mesh: cluster-sharded serving of both stores ----
    for label, depth, dtype in (("fp32_d16", DEPTH, "fp32"),
                                ("int8_d64", 4 * DEPTH, "int8")):
        cfg = _config(depth, dtype)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 4), ("data", "model"))
        eng = ShardedEngine(cfg, mesh, jax.random.key(seed), warmup=warm,
                            reconcile_every=10**9)  # reconcile per round
        rec, lat = _eval_engine(eng, n_batches=n_batches, batch=batch,
                                seed=seed)
        full = docstore.memory_bytes(cfg.store)
        per_dev = eng.store_bytes_per_device()
        assert per_dev * 4 == full, (per_dev, full)
        row = {"table": "table17", "variant": f"sharded_{label}",
               "store_dtype": dtype, "depth": depth,
               "recall10": float(np.mean(rec)), "recall_rounds": rec,
               "query_latency_ms": float(np.mean(lat)),
               "store_bytes": full, "store_bytes_per_device": per_dev,
               "recall_gap_vs_single":
                   round(float(np.mean(rec)) - by[label]["recall10"], 4)}
        assert abs(row["recall_gap_vs_single"]) < 0.1, row
        rows.append(row)

    gain = by["int8_d64"]["recall10"] - by["fp32_d16"]["recall10"]
    for row in rows:
        row["recall_gain_int8_4x"] = round(gain, 4)
        emit_row(row)


def run(n_batches: int = 24, batch: int = 128, seed: int = 0) -> list[dict]:
    rows = run_multi_device(
        "benchmarks.table17_quantized_store",
        ["--child", n_batches, batch, seed],
        lambda: _child(n_batches, batch, seed))
    for row in rows:
        row.pop("recall_rounds", None)
    return rows


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    else:
        for r in run():
            print(r)
