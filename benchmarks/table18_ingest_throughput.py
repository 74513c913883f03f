"""Table 18 — fused ingest admission: one device program (and, on TPU, one
HBM pass) for screen + assign + quantize-on-admit, vs the staged path.

The paper's headline throughput claim (>900 docs/s under a 150 MB budget)
lives on the ingest hot path. The fused ``admit`` stage collapses
Algorithm-1 admission into ONE device program (``kernels/admit`` on TPU:
one Pallas kernel, one HBM pass over the [B, d] microbatch, no
[B, n] / [B, K] similarity matrices and no fp32 staging copy in HBM)
where the staged decomposition runs three — the prefilter screen,
nearest-centroid assignment, and quantize-on-admit inside the ring write,
each re-reading and re-normalizing x.

What the staged baseline is (read this before quoting numbers): on TPU
the three admission programs exist as three Pallas kernel launches with
three HBM passes even inside a single jit — that is the structure the
megakernel removes, and this CPU bench cannot observe HBM passes. The
``staged_loop`` baseline therefore REIFIES the per-stage structure as
per-stage jitted device programs composed on the host (full ingest
semantics, fair buffer donation). It is an execution-structure model,
not the previously shipped entry point: pre-fusion ``ingest_batch`` was
already one jitted program whose CPU (reference-dispatch) XLA is
essentially identical to today's ``fused_loop`` — so on CPU, fused_loop
vs the *shipped* prior path is ~1x, and the rows below quantify what
per-stage program structure costs, which is the cost the megakernel
removes at kernel granularity on TPU. Run this table on a TPU backend to
measure the real one-pass-vs-three claim.

Measured, at the paper-default configuration (microbatch 50, dim 384,
k=100, n=5 basis vectors; fp32 and int8 ring stores):

  * staged_loop   — per-batch host composition of the per-stage device
                    programs (screen / assign+update / count+store+reps).
  * fused_loop    — the real ``pipeline.ingest_batch``: same semantics,
                    ONE device program per microbatch.
  * fused_stream  — ``pipeline.ingest_stream``: the single-program step
                    scanned over stream chunks (one dispatch per chunk),
                    the serving engine's throughput entry point. A
                    host-composed per-stage loop has no scanned
                    equivalent at its own granularity — scanning the
                    stages together IS the fused composition. Headline:
                    >= 1.5x docs/s over staged_loop (asserted, both
                    store dtypes).

  * sharded rows  — the same staged-vs-fused comparison inside shard_map
                    on a forced 4-device data mesh (global microbatch
                    4x50): per-stage shard_map programs vs the real
                    ``ShardedEngine.ingest`` (reported; the acceptance
                    assert stays on the single-device paper-default rows).

  * recall parity — the fused Pallas admission kernel (interpret mode on
                    CPU) vs the staged reference over a drifting topic
                    stream: identical admission decisions make the stores
                    bit-identical, so two-stage Recall@10 gap == 0.000
                    exactly (asserted).

All state stays bit-identical between the paths (pinned by
tests/test_admit.py), so the speedup is pure execution structure.

Needs ``--xla_force_host_platform_device_count=4`` before jax init, so
``run()`` re-execs itself as a child process and parses JSON rows (same
pattern as tables 15-17).
"""
from __future__ import annotations

import sys

from benchmarks.common import emit_row, run_multi_device

B = 50             # paper Table 2 microbatch
DIM = 384
K_CLUSTERS = 100   # paper Table 2 k
DEPTH = 16
ALPHA = 0.1
CHUNK = 10         # microbatches per scanned stream chunk
N_DATA = 4         # forced CPU data shards for the sharded rows


def _paper_cfg(store_dtype: str):
    from repro.configs.streaming_rag import paper_pipeline_config

    return paper_pipeline_config(dim=DIM, k=K_CLUSTERS, capacity=100,
                                 update_interval=1000, alpha=ALPHA,
                                 store_depth=DEPTH, store_dtype=store_dtype)


def _staged_programs(cfg):
    """The pre-fusion admission as separate jitted device programs plus
    the (shared) downstream tail program, with fair buffer donation."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.engine import stages

    @jax.jit
    def p_screen(pre, x, live):
        return stages.screen(cfg.pre, pre, x, live)

    @jax.jit
    def p_assign(clus, x, keep):
        return stages.assign_update(cfg.clus, clus, x, keep)

    @functools.partial(jax.jit, donate_argnames=("hh0", "store"))
    def p_tail(hh0, store, rep_ids0, rep_sims0, rng0, arrivals,
               labels, keep, sims, x, doc_ids):
        rng_, k_hh = jax.random.split(rng0)
        live = doc_ids >= 0
        hh, _, hh_info = stages.count(cfg.hh, hh0, labels, keep, k_hh)
        rep_ids, rep_sims = stages.update_representatives(
            rep_ids0, rep_sims0, labels, sims, doc_ids, keep,
            cfg.clus.num_clusters)
        stored = keep & (hh_info["admitted"] | hh_info["hit"])
        stamps = arrivals + jnp.cumsum(live.astype(jnp.int32)) - 1
        store = stages.store_write(cfg.store, store, x, labels, stored,
                                   doc_ids, stamps)
        return (hh, store, rep_ids, rep_sims, rng_,
                arrivals + jnp.sum(live.astype(jnp.int32)))

    def step(st, x, doc_ids):
        live = doc_ids >= 0
        pre, _r, keep = p_screen(st.pre, x, live)
        clus, labels, sims = p_assign(st.clus, x, keep)
        hh, store, rep_ids, rep_sims, rng_, arr = p_tail(
            st.hh, st.store, st.rep_ids, st.rep_sims, st.rng, st.arrivals,
            labels, keep, sims, x, doc_ids)
        return st._replace(pre=pre, clus=clus, hh=hh, store=store,
                           rep_ids=rep_ids, rep_sims=rep_sims, rng=rng_,
                           arrivals=arr)

    return step


def _throughput(step_docs, init_state, rounds: int, sync=None):
    """Median-of-rounds docs/s for a (state -> state, n_docs) closure."""
    import time

    import jax
    import numpy as np

    if sync is None:
        sync = lambda s: jax.block_until_ready(jax.tree.leaves(s)[0])
    state = init_state()
    state, _ = step_docs(state)  # compile
    sync(state)
    rates = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        state, n = step_docs(state)
        sync(state)
        rates.append(n / (time.perf_counter() - t0))
    return float(np.median(rates))


def _single_device_rows(n_batches: int, rounds: int, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import pipeline

    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(n_batches, B, DIM)), jnp.float32)
    idss = jnp.arange(n_batches * B, dtype=jnp.int32).reshape(n_batches, B)
    rows = []
    for store_dtype in ("fp32", "int8"):
        cfg = _paper_cfg(store_dtype)
        init = lambda: pipeline.init(cfg, jax.random.key(seed))

        staged_step = _staged_programs(cfg)

        def run_staged(state):
            for i in range(n_batches):
                state = staged_step(state, xs[i], idss[i])
            return state, n_batches * B

        def run_fused(state):
            for i in range(n_batches):
                state, _ = pipeline.ingest_batch(cfg, state, xs[i], idss[i])
            return state, n_batches * B

        nc = (n_batches // CHUNK) * CHUNK
        chunks = xs[:nc].reshape(-1, CHUNK, B, DIM)
        cids = idss[:nc].reshape(-1, CHUNK, B)

        def run_stream(state):
            for c in range(chunks.shape[0]):
                state = pipeline.ingest_stream(cfg, state, chunks[c],
                                               cids[c])
            return state, nc * B

        dps = {"staged_loop": _throughput(run_staged, init, rounds),
               "fused_loop": _throughput(run_fused, init, rounds),
               "fused_stream": _throughput(run_stream, init, rounds)}
        for mode, v in dps.items():
            rows.append({"table": "table18", "variant": mode,
                         "store_dtype": store_dtype, "devices": 1,
                         "batch": B, "throughput_dps": round(v, 1),
                         "speedup_vs_staged":
                             round(v / dps["staged_loop"], 3)})
    return rows


def _sharded_rows(n_batches: int, rounds: int, seed: int):
    """The staged-vs-fused comparison on a forced 4-device data mesh,
    global microbatch 4x50.

    fused  — the real ``ShardedEngine.ingest``: ONE shard_map device
             program advances every shard's pipeline per microbatch.
    staged — the pre-fusion structure: the per-stage device programs
             applied per shard sub-batch from the host (a shard_map
             around all three stages would fuse them into one device
             program — exactly the composition being measured — so the
             staged path's program-per-stage granularity is preserved by
             construction, and its dispatch count scales with shards).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.engine.sharded import ShardedEngine

    rng = np.random.default_rng(seed)
    xs = np.asarray(rng.normal(size=(n_batches, N_DATA * B, DIM)),
                    np.float32)
    idss = np.arange(n_batches * N_DATA * B,
                     dtype=np.int32).reshape(n_batches, -1)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((N_DATA,), ("data",))
    rows = []
    for store_dtype in ("fp32", "int8"):
        cfg = _paper_cfg(store_dtype)

        def make_engine():
            return ShardedEngine(cfg, mesh, jax.random.key(seed),
                                 reconcile_every=10**9)

        def run_fused(eng):
            for i in range(n_batches):
                eng.ingest(xs[i], idss[i])
            return eng, n_batches * N_DATA * B

        staged_step = _staged_programs(cfg)

        def run_staged(states):
            for i in range(n_batches):
                xb = jnp.asarray(xs[i]).reshape(N_DATA, B, DIM)
                ib = jnp.asarray(idss[i]).reshape(N_DATA, B)
                states = [staged_step(st, xb[s], ib[s])
                          for s, st in enumerate(states)]
            return states, n_batches * N_DATA * B

        def init_staged():
            return [ShardedEngine.shard_init_state(cfg, jax.random.key(seed),
                                                   s, N_DATA)
                    for s in range(N_DATA)]

        dps_staged = _throughput(run_staged, init_staged, rounds)
        dps_fused = _throughput(run_fused, make_engine, rounds,
                                sync=lambda e: jax.block_until_ready(
                                    e.local.arrivals))
        for mode, v in (("staged_loop", dps_staged),
                        ("fused_engine", dps_fused)):
            rows.append({"table": "table18", "variant": f"sharded_{mode}",
                         "store_dtype": store_dtype, "devices": N_DATA,
                         "batch": N_DATA * B, "throughput_dps": round(v, 1),
                         "speedup_vs_staged": round(v / dps_staged, 3)})
    return rows


def _recall_parity_rows(n_batches: int, seed: int):
    """Fused Pallas admission (interpret on CPU) vs staged reference over
    a drifting topic stream: Recall@10 gap must be exactly 0.000."""
    import dataclasses

    import jax
    import numpy as np

    from benchmarks.common import DocArchive, _query_round
    from repro.data.streams import StreamConfig, TopicStream
    from repro.engine import Engine

    rows = []
    for store_dtype in ("fp32", "int8"):
        cfg_ref = _paper_cfg(store_dtype)
        cfg_ref = dataclasses.replace(cfg_ref, update_interval=256)
        cfg_pal = dataclasses.replace(
            cfg_ref, clus=dataclasses.replace(cfg_ref.clus,
                                              use_pallas=True))
        recalls = {}
        for label, cfg in (("staged", cfg_ref), ("fused", cfg_pal)):
            stream = TopicStream(StreamConfig(
                "synthetic-drift", dim=DIM, n_topics=64, zipf_s=1.05,
                drift=0.02, burstiness=0.2, noise=0.5,
                background_frac=0.1, seed=500 + seed))
            warm = np.concatenate(
                [stream.next_batch(64)["embedding"] for _ in range(2)])
            eng = Engine(cfg, jax.random.key(seed), warmup=warm)
            archive = DocArchive(DIM)

            class _Q:
                def query(self, _state, q, k):
                    return eng.query(np.asarray(q), k, two_stage=True,
                                     nprobe=8)

            recs = []
            for i in range(n_batches):
                b = stream.next_batch(64)
                archive.add(b)
                eng.ingest(b["embedding"], b["doc_id"])
                if (i + 1) % max(1, n_batches // 3) == 0:
                    recs.append(_query_round(_Q(), None, stream, archive,
                                             30, 10)["recall"])
            recalls[label] = float(np.mean(recs))
        gap = round(recalls["fused"] - recalls["staged"], 6)
        assert gap == 0.0, (recalls, "fused admission changed retrieval")
        rows.append({"table": "table18", "variant": "recall_parity",
                     "store_dtype": store_dtype, "devices": 1,
                     "recall10": recalls["fused"],
                     "recall_gap_fused_vs_staged": gap})
    return rows


def _child(n_batches: int, rounds: int, seed: int):
    rows = []
    rows += _single_device_rows(n_batches, rounds, seed)
    rows += _sharded_rows(max(4, n_batches // 2), max(2, rounds // 2), seed)
    rows += _recall_parity_rows(n_batches, seed)

    by = {(r["variant"], r["store_dtype"]): r for r in rows}
    # acceptance: fused admission >= 1.5x staged docs/s at paper defaults
    for dtype in ("fp32", "int8"):
        sp = by[("fused_stream", dtype)]["speedup_vs_staged"]
        assert sp >= 1.5, (dtype, sp, "fused ingest speedup below 1.5x")
    for row in rows:
        emit_row(row)


def run(n_batches: int = 24, rounds: int = 7, seed: int = 0) -> list[dict]:
    return run_multi_device(
        "benchmarks.table18_ingest_throughput",
        ["--child", n_batches, rounds, seed],
        lambda: _child(n_batches, rounds, seed))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    else:
        for r in run():
            print(r)
