"""Table 15 — sharded streaming engine vs the single-device engine on a
forced 4-device CPU mesh (synthetic drifting stream, routed two-stage
retrieval).

Three variants at one PipelineConfig:

  * single      — ``engine.Engine`` on one device (the PR-1 path).
  * sharded_1x4 — ``ShardedEngine`` on mesh (1, 4): ingest unsharded, the
                  serving doc store cluster-sharded 4 ways over the model
                  axis. Headline: Recall@10 matches single-device within
                  noise while per-device doc-store bytes drop exactly 4x.
  * sharded_4x1 — ``ShardedEngine`` on mesh (4, 1): the stream
                  data-sharded 4 ways with periodic exact reconciliation.
                  Recall@10 stays within noise of the sequential ingest
                  (counters merge exactly; centroids merge count-weighted).

Bit-identity of the single-device ``query``/``ingest_batch`` refactor is
asserted in tests (tests/test_engine.py, tests/test_distributed_engine.py),
not here — this bench reports the accuracy/memory trade.

The measurement needs ``--xla_force_host_platform_device_count=4`` set
before jax initializes, so ``run()`` re-execs itself as a child process
with the right env and parses its JSON rows — safe to call from
``benchmarks.run`` in an already-initialized parent.
"""
from __future__ import annotations

import sys

from benchmarks.common import emit_row, run_multi_device

DIM = 64
NPROBE = 16
DEPTH = 16
K_CLUSTERS = 152   # divisible by the 4-wide model axis
TOPK = 10


def _drift_stream(seed: int = 0):
    from repro.data.streams import StreamConfig, TopicStream

    return TopicStream(StreamConfig(
        "synthetic-drift", dim=DIM, n_topics=96, zipf_s=1.05, drift=0.03,
        burstiness=0.05, noise=0.45, background_frac=0.10, seed=100 + seed))


def _config():
    from repro.configs.streaming_rag import paper_pipeline_config

    return paper_pipeline_config(dim=DIM, k=K_CLUSTERS, capacity=100,
                                 update_interval=256, alpha=0.1,
                                 store_depth=DEPTH)


def _warmup(batch: int, seed: int):
    """Two warm batches for k-means++ init (as benchmarks.common does)."""
    import numpy as np

    stream = _drift_stream(seed)
    batches = [stream.next_batch(batch) for _ in range(2)]
    return np.concatenate([b["embedding"] for b in batches])


def _eval_engine(engine, *, n_batches: int, batch: int, seed: int,
                 rounds: int = 4) -> list[float]:
    """Ingest the drift stream (first two batches double as the warmup
    prefix, as in benchmarks.common.evaluate_method); interleave two-stage
    query rounds scored against the exact oracle (topic-coverage Recall@10,
    as table 14)."""
    import numpy as np

    from benchmarks.common import DocArchive, _query_round

    class _Q:  # adapt the engine to the Method.query protocol
        def query(self, _state, q, k):
            return engine.query(np.asarray(q), k, two_stage=True,
                                nprobe=NPROBE)

    stream = _drift_stream(seed)
    archive = DocArchive(DIM)
    recalls = []
    per_round = max(1, n_batches // rounds)
    for i in range(2 + n_batches):
        b = stream.next_batch(batch)
        archive.add(b)
        engine.ingest(b["embedding"], b["doc_id"])
        if i >= 2 and (i - 1) % per_round == 0:
            if hasattr(engine, "reconcile"):
                engine.reconcile()
            recalls.append(_query_round(_Q(), None, stream, archive,
                                        50, TOPK)["recall"])
    return recalls


def _child(n_batches: int, batch: int, seed: int):
    import jax
    import numpy as np

    from repro.engine import Engine
    from repro.engine.sharded import ShardedEngine
    from repro.store import docstore

    cfg = _config()
    full_store_bytes = docstore.memory_bytes(cfg.store)
    warm = _warmup(batch, seed)
    rows = []

    single = Engine(cfg, jax.random.key(seed), warmup=warm)
    r = _eval_engine(single, n_batches=n_batches, batch=batch, seed=seed)
    rows.append({"table": "table15", "variant": "single",
                 "recall10": float(np.mean(r)), "recall_rounds": r,
                 "store_bytes_per_device": full_store_bytes,
                 "store_shrink": 1.0})

    for (d, m) in ((1, 4), (4, 1)):
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((d, m), ("data", "model"))
        eng = ShardedEngine(cfg, mesh, jax.random.key(seed), warmup=warm,
                            reconcile_every=10**9)  # reconcile per round
        r = _eval_engine(eng, n_batches=n_batches, batch=batch, seed=seed)
        per_dev = eng.store_bytes_per_device()
        assert per_dev * m == full_store_bytes, (per_dev, full_store_bytes)
        rows.append({"table": "table15", "variant": f"sharded_{d}x{m}",
                     "recall10": float(np.mean(r)), "recall_rounds": r,
                     "store_bytes_per_device": per_dev,
                     "store_shrink": full_store_bytes / per_dev})

    # sharded retrieval matches single-device recall within noise
    base = rows[0]["recall10"]
    for row in rows[1:]:
        row["recall_gap_vs_single"] = round(row["recall10"] - base, 4)
        assert abs(row["recall10"] - base) < 0.1, (row["variant"], base,
                                                  row["recall10"])
    for row in rows:
        emit_row(row)


def run(n_batches: int = 24, batch: int = 128, seed: int = 0) -> list[dict]:
    rows = run_multi_device(
        "benchmarks.table15_sharded", ["--child", n_batches, batch, seed],
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
