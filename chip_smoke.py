#!/usr/bin/env python3
"""Chip smoke test: the streaming RAG server on a TPU at the paper's
150 MB / d=384 deployment.

    python3 chip_smoke.py [--seed N]              # one TPU chip
    python3 chip_smoke.py --four-chips            # four chips, sharded
    JAX_PLATFORMS=cpu python3 chip_smoke.py --cpu-rehearsal [--four-chips]

One chip: for each store that ``pipeline.budget_to_config(150, 384, ...)``
sizes — int8 rings of depth 64 and fp32 rings of depth 16 — it stands up
``serve.runtime.AsyncServer`` over ``Engine``, ingests a seeded
twitter-shaped stream through the server until the publish-time device
counters show at least half the ring slots live, then answers queries
through two-stage and prototype-only servers over that engine. Every
answer is checked against a plain numpy reference on the published
snapshot, and the kernel-trace counters must show that the ``admit``,
``serve`` and ``mips`` Pallas kernels ran and no reference fallback did.

``--four-chips`` runs only the sharded path: ``ShardedEngine`` on a
(2, 2) ("data", "model") mesh with delta publication, on the int8 store,
and compares its answers with the single-device ``Engine`` over the same
published snapshot, and with the numpy reference.

``--cpu-rehearsal`` runs the same phases on the CPU at a tiny size with
interpret-mode kernels; it prints no result line.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed
only when every phase passed on a TPU. Compile seconds, memory and counts
printed before it are smoke readings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.mesh import cpu_requested, force_host_devices  # noqa: E402

NPROBE, TOPK = 8, 10
FILL_TARGET = 0.5
# The paper's relevance threshold (alpha = 0.2) admits ~0.2% of this
# stream at d=384: the mean cosine of an on-topic doc with the 5-vector
# basis has a median of ~0.08 there, so the store would never fill.
# 0.05 keeps ~70% of docs and drops most of the isotropic background.
ALPHA = 0.05
# An answer's score may differ from the float64 reference by the MXU's
# rounding: if it rounds fp32 operands to bf16 (8-bit mantissa), the dot
# of two unit vectors is off by at most 2 * 2^-9 * sum|q_i e_i| <= 2^-8
# (Cauchy-Schwarz), ~3.9e-3. Ids may differ only among candidates whose
# exact scores lie within this of each other.
SCORE_TOL = 5e-3


@dataclasses.dataclass(frozen=True)
class Size:
    budget_mb: float
    dim: int
    stores: tuple          # ((store_dtype, ring depth), ...)
    batch: int             # docs per ingest batch
    warmup: int            # docs the clusters are initialized from
    queries: int           # queries per serving mode
    flush: int             # queries per serve program (max_batch)
    publish_every: int     # ingest batches between publications
    check_every: int       # ingest batches between fill checks
    max_docs: int          # the store must be half full by then


FULL = Size(150, 384, (("int8", 64), ("fp32", 16)), batch=2048,
            warmup=8192, queries=256, flush=32, publish_every=16,
            check_every=48, max_docs=6_000_000)
TINY = Size(0.1, 64, (("int8", 8), ("fp32", 4)), batch=256, warmup=512,
            queries=64, flush=16, publish_every=2, check_every=4,
            max_docs=200_000)


# ------------------------------------------------------------- bookkeeping
_compile_s = [0.0]


def _on_event_duration(event: str, duration: float, **_):
    if event.startswith("/jax/core/compile/"):
        _compile_s[0] += duration


@contextlib.contextmanager
def phase(name: str):
    """Time one phase; print its wall and compile seconds. A failure is
    printed and re-raised — never swallowed."""
    c0, t0 = _compile_s[0], time.perf_counter()
    try:
        yield
    except BaseException:
        print(f"phase {name}: FAIL", flush=True)
        raise
    print(f"phase {name}: pass (wall {time.perf_counter() - t0:.3f} s, "
          f"compile {_compile_s[0] - c0:.3f} s; smoke reading)", flush=True)


def make_config(size: Size, store_dtype: str, depth: int, rehearsal: bool,
                k_multiple: int = 1):
    from repro.configs.streaming_rag import paper_pipeline_config
    from repro.core import pipeline

    base = paper_pipeline_config(dim=size.dim, alpha=ALPHA,
                                 store_depth=depth, store_dtype=store_dtype)
    cfg = pipeline.budget_to_config(size.budget_mb, size.dim, base)
    k = -(-cfg.clus.num_clusters // k_multiple) * k_multiple
    # interpret-mode Pallas on the CPU; on a TPU the dispatch default
    # already picks the compiled kernels
    use_pallas = True if rehearsal else None
    return dataclasses.replace(cfg, clus=dataclasses.replace(
        cfg.clus, num_clusters=k, use_pallas=use_pallas))


def store_fill(reg) -> tuple[int, int]:
    """(live, total) ring slots from the publish-time device counters."""
    return (int(reg.gauge("pipeline_store_live").value),
            int(reg.gauge("pipeline_store_slots").value))


def ingest_until_half_full(server, stream, size: Size, reg) -> int:
    docs = 0
    while True:
        for _ in range(size.check_every):
            b = stream.next_batch(size.batch)
            server.ingest(b["embedding"], b["doc_id"])
            docs += size.batch
        server.sync()                       # ingest + publish everything
        live, slots = store_fill(reg)
        if live >= FILL_TARGET * slots:
            print(f"  store fill       : {live}/{slots} slots live "
                  f"({live / slots:.4f}) after {docs} docs", flush=True)
            return docs
        if docs >= size.max_docs:
            raise RuntimeError(f"store only {live}/{slots} full after "
                               f"{docs} docs")


def serve_all(server, queries) -> list[dict]:
    for q in queries:
        server.submit(q)
    answers = server.drain()
    assert len(answers) == len(queries), (len(answers), len(queries))
    return sorted(answers, key=lambda a: a["ticket"])


# --------------------------------------------------------------- reference
def _unit(x):
    import numpy as np

    x = np.asarray(x, np.float64)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def host_snapshot(index, route_labels, store) -> dict:
    """Published snapshot leaves as float64/int numpy (int8 dequantized)."""
    import jax
    import numpy as np

    index, route_labels, store = jax.device_get((index, route_labels, store))
    embs = np.asarray(store.embs, np.float64)
    if store.embs.dtype == np.int8:
        embs = embs * np.asarray(store.scales, np.float64)[..., None]
    return {"vectors": np.asarray(index.vectors, np.float64),
            "valid": np.asarray(index.valid), "index_ids": index.ids,
            "labels": np.asarray(route_labels), "ids": store.ids,
            "embs": embs}


def check_topk(got_keys, got_sc, cand_keys, cand_sc, certain, k) -> list:
    """Tie-aware check of one top-k answer against exact candidate scores.

    ``cand_*`` are every candidate the answer may hold, with float64
    scores; ``certain`` marks those that compete under any rounding (the
    rest sit in a routing near-tie). The answer must hold distinct
    candidates, each with its exact score to SCORE_TOL, in descending
    order, as many as the certain and all candidates allow, and must miss
    no certain candidate that beats its last score by 2 * SCORE_TOL."""
    import numpy as np

    keep = got_keys >= 0
    keys, sc = got_keys[keep], got_sc[keep]
    exact = dict(zip(cand_keys.tolist(), cand_sc.tolist()))
    bad = []
    if len(set(keys.tolist())) != keys.size:
        bad.append("duplicate keys")
    for key, s in zip(keys.tolist(), sc.tolist()):
        if key not in exact:
            bad.append(f"{key} is not a candidate")
        elif abs(exact[key] - s) > SCORE_TOL:
            bad.append(f"{key}: score {s} vs exact {exact[key]}")
    if np.any(np.diff(sc) > SCORE_TOL):
        bad.append("scores not descending")
    lo, hi = min(k, int(certain.sum())), min(k, cand_keys.size)
    if not lo <= keys.size <= hi:
        bad.append(f"{keys.size} results, expected {lo}..{hi}")
    floor = sc[-1] if keys.size == k else -np.inf
    missing = set(cand_keys[certain & (cand_sc > floor + 2 * SCORE_TOL)]
                  .tolist()) - set(keys.tolist())
    if missing:
        bad.append(f"missing {sorted(missing)[:5]}")
    return bad


def reference_check(answers, queries, snap: dict, two_stage: bool) -> dict:
    """Check every answer against numpy exact cosine on the snapshot:
    top-nprobe over the valid index rows, then top-k over the live rows
    of the routed clusters (two-stage), or top-k over the valid index
    rows (prototype-only)."""
    import numpy as np

    qn = _unit(queries)
    s1 = qn @ snap["vectors"].T
    s1[:, ~snap["valid"]] = -np.inf
    failures, exact_order, max_err = [], 0, 0.0
    for i, a in enumerate(answers):
        order = np.argsort(-s1[i], kind="stable")
        if two_stage:
            nth = s1[i, order[NPROBE - 1]]
            nxt = s1[i, order[NPROBE]] if order.size > NPROBE else -np.inf
            maybe = np.nonzero(s1[i] >= nth - 2 * SCORE_TOL)[0]
            routed = set(order[:NPROBE].tolist())
            keys, scs, cert, top, cl_of = [], [], [], [], {}
            for slot in maybe:
                c = int(snap["labels"][slot])
                if c < 0:
                    continue
                rows = np.nonzero(snap["ids"][c] >= 0)[0]
                ids = snap["ids"][c, rows]
                keys.append(ids)
                scs.append(snap["embs"][c, rows] @ qn[i])
                cert.append(np.full(rows.size,
                                    s1[i, slot] > nxt + 2 * SCORE_TOL))
                top.append(np.full(rows.size, slot in routed))
                cl_of.update(dict.fromkeys(ids.tolist(), c))
            keys = np.concatenate(keys) if keys else np.zeros(0, np.int64)
            scs = np.concatenate(scs) if scs else np.zeros(0)
            cert = np.concatenate(cert) if cert else np.zeros(0, bool)
            top = np.concatenate(top) if top else np.zeros(0, bool)
            got = np.asarray(a["doc_ids"])
            bad = check_topk(got, np.asarray(a["scores"]), keys, scs, cert,
                             TOPK)
            bad += [f"doc {d} reported in cluster {c}"
                    for d, c in zip(got.tolist(), a["clusters"].tolist())
                    if d >= 0 and cl_of.get(d) != c]
        else:
            valid = np.nonzero(snap["valid"])[0]
            keys = snap["labels"][valid].astype(np.int64)
            scs = s1[i, valid]
            top = np.ones(keys.size, bool)
            got = np.asarray(a["clusters"])
            bad = check_topk(got, np.asarray(a["scores"]), keys, scs,
                             np.ones(keys.size, bool), TOPK)
            rep = dict(zip(keys.tolist(), snap["index_ids"][valid].tolist()))
            bad += [f"cluster {c} reported doc {d}, rep is {rep.get(c)}"
                    for c, d in zip(got.tolist(), a["doc_ids"].tolist())
                    if c >= 0 and rep.get(c) != d]
        if bad:
            failures.append((i, bad[:3]))
        keep = got >= 0
        # the reference's own top-k over its top-nprobe routes
        ref = keys[top][np.argsort(-scs[top], kind="stable")][:keep.sum()]
        exact_order += int(np.array_equal(got[keep], ref))
        if keep.any():
            exact = dict(zip(keys.tolist(), scs.tolist()))
            err = [abs(exact[g] - s) for g, s in
                   zip(got[keep].tolist(), a["scores"][keep].tolist())
                   if g in exact]
            max_err = max([max_err, *err])
    return {"checked": len(answers), "failures": failures,
            "same_order_as_reference": exact_order, "max_score_err": max_err}


def require_matches(label: str, result: dict):
    print(f"  {label:<16} : {result['checked']} answers checked, "
          f"{result['same_order_as_reference']} in the reference's exact "
          f"order, max |score - exact| {result['max_score_err']:.3e} "
          f"(tolerance {SCORE_TOL})", flush=True)
    if result["failures"]:
        raise AssertionError(f"{label}: {len(result['failures'])} answers "
                             f"disagree with the reference: "
                             f"{result['failures'][:3]}")


def require_kernels(reg, kernels=("admit", "serve", "mips")):
    """Every named kernel traced its Pallas path, and no reference ran."""
    counts = {f"{k}_{p}": int(reg.counter(
        f"kernel_traces_total_{k}_{p}").value)
        for k in kernels for p in ("pallas", "ref")}
    print(f"  kernel traces    : {counts}", flush=True)
    for k in kernels:
        assert counts[f"{k}_pallas"] > 0, f"{k}: Pallas kernel never traced"
        assert counts[f"{k}_ref"] == 0, f"{k}: reference path was traced"


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


# ------------------------------------------------------------------ phases
def run_one_chip(size: Size, seed: int, rehearsal: bool, reg):
    import jax
    import numpy as np

    from repro.core import pipeline
    from repro.data.streams import make_stream
    from repro.engine.engine import Engine
    from repro.serve.runtime import AsyncServer, ServerConfig

    for store_dtype, depth in size.stores:
        tag = f"{store_dtype}-d{depth}"
        cfg = make_config(size, store_dtype, depth, rehearsal)
        print(f"config {tag}: k={cfg.clus.num_clusters} "
              f"index_cap={cfg.hh.bmax()} slots="
              f"{cfg.clus.num_clusters * depth} state_memory_bytes="
              f"{pipeline.state_memory_bytes(cfg)}", flush=True)
        stream = make_stream("twitter", dim=size.dim, seed=seed)
        warm = np.concatenate([stream.next_batch(size.batch)["embedding"]
                               for _ in range(size.warmup // size.batch)])
        two = ServerConfig(max_batch=size.flush, topk=TOPK, two_stage=True,
                           nprobe=NPROBE)
        with phase(f"{tag} init"):
            engine = Engine(cfg, jax.random.key(seed), warmup=warm)
            server = AsyncServer(cfg, two, engine=engine,
                                 publish_every=size.publish_every)
        with phase(f"{tag} ingest"):
            docs = ingest_until_half_full(server, stream, size, reg)
        st = engine.state
        snap = host_snapshot(st.index, st.route_labels, st.store)
        queries = stream.queries(size.queries)["embedding"]
        with phase(f"{tag} two-stage queries"):
            version = server.freshness_stats()["snapshot_version"]
            answers = serve_all(server, queries)
            server.close()
        with phase(f"{tag} two-stage check"):
            assert {a["snapshot_version"] for a in answers} == {version}
            require_matches("two-stage",
                            reference_check(answers, queries, snap, True))
        proto = ServerConfig(max_batch=size.flush, topk=TOPK)
        with phase(f"{tag} prototype-only queries"):
            server = AsyncServer(cfg, proto, engine=engine,
                                 publish_every=size.publish_every)
            answers = serve_all(server, queries)
            server.close()
        with phase(f"{tag} prototype-only check"):
            require_matches("prototype-only",
                            reference_check(answers, queries, snap, False))
        print(f"  smoke readings   : docs_ingested={docs} "
              f"queries_answered={2 * len(queries)} "
              f"state_memory_bytes={server.state_memory_bytes()} "
              f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}",
              flush=True)
    require_kernels(reg)


def run_four_chips(size: Size, seed: int, rehearsal: bool, reg):
    import jax
    import numpy as np

    from repro.data.streams import make_stream
    from repro.engine.engine import Engine
    from repro.engine.sharded import ShardedEngine
    from repro.launch.mesh import make_mesh
    from repro.serve.runtime import AsyncServer, ServerConfig

    store_dtype, depth = size.stores[0]
    cfg = make_config(size, store_dtype, depth, rehearsal, k_multiple=2)
    print(f"config {store_dtype}-d{depth} sharded (2, 2): "
          f"k={cfg.clus.num_clusters} index_cap={cfg.hh.bmax()}", flush=True)
    stream = make_stream("twitter", dim=size.dim, seed=seed)
    warm = np.concatenate([stream.next_batch(size.batch)["embedding"]
                           for _ in range(size.warmup // size.batch)])
    scfg = ServerConfig(max_batch=size.flush, topk=TOPK, two_stage=True,
                        nprobe=NPROBE)
    with phase("sharded init"):
        mesh = make_mesh((2, 2), ("data", "model"))
        engine = ShardedEngine(cfg, mesh, jax.random.key(seed), warmup=warm,
                               reconcile_every=10**9, reconcile_mode="delta")
        server = AsyncServer(cfg, scfg, engine=engine,
                             publish_every=size.publish_every)
    with phase("sharded ingest"):
        docs = ingest_until_half_full(server, stream, size, reg)
    queries = stream.queries(size.queries)["embedding"]
    with phase("sharded two-stage queries"):
        answers = serve_all(server, queries)
        server.close()
    snap = engine.publish()   # nothing ingested since: the served state
    for name, leaf in (("embs", snap.store.embs), ("ids", snap.store.ids)):
        per_dev = {str(s.device): s.data.nbytes
                   for s in leaf.addressable_shards}
        print(f"  store {name:<4} bytes  : {per_dev}", flush=True)
    print("  device bytes     : " + str({
        str(d): (d.memory_stats() or {}).get("bytes_in_use", "not reported")
        for d in jax.devices()}), flush=True)
    with phase("single-device queries on the same snapshot"):
        dev0 = jax.devices()[0]
        one = jax.device_put(jax.device_get(snap), dev0)
        single = Engine(cfg, jax.random.key(seed))
        outs = [single.query_snapshot(one, queries[i:i + size.flush], TOPK,
                                      two_stage=True, nprobe=NPROBE)
                for i in range(0, len(queries), size.flush)]
        ids_1 = np.concatenate([np.asarray(o[2]) for o in outs])
    host = host_snapshot(one.index, one.route_labels, one.store)
    with phase("sharded check"):
        ids_s = np.stack([a["doc_ids"] for a in answers])
        same = np.all(ids_s == ids_1, axis=1)
        print(f"  sharded == single: {int(same.sum())}/{len(same)} answers "
              f"with identical doc ids", flush=True)
        require_matches("sharded", reference_check(answers, queries, host,
                                                   True))
        differ = np.nonzero(~same)[0]
        if differ.size:   # allowed only where both sit in a score near-tie
            singles = [{"doc_ids": ids_1[i], "scores": np.asarray(
                outs[i // size.flush][0])[i % size.flush],
                "clusters": np.asarray(outs[i // size.flush][3])[
                    i % size.flush]} for i in differ]
            require_matches("single (differ)", reference_check(
                singles, queries[differ], host, True))
    print(f"  smoke readings   : docs_ingested={docs} "
          f"queries_answered={len(queries)} "
          f"store_bytes_per_device={engine.store_bytes_per_device()}",
          flush=True)
    require_kernels(reg, kernels=("admit", "serve"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded (2, 2) mesh path")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, interpret-mode kernels; "
                         "prints no result line")
    args = ap.parse_args()
    n_dev = 4 if args.four_chips else 1
    if args.cpu_rehearsal and cpu_requested():
        force_host_devices(n_dev)

    import jax

    from repro import obs
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    print(f"jax {jax.__version__}; devices: {devs}", flush=True)
    platform = devs[0].platform
    if args.cpu_rehearsal != (platform == "cpu"):
        print(f"chip_smoke: platform is {platform!r}: a run needs a TPU, "
              f"and --cpu-rehearsal needs the CPU", file=sys.stderr)
        return 2
    if platform not in ("tpu", "cpu"):
        print(f"chip_smoke: unsupported platform {platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < n_dev:
        print(f"chip_smoke: needs {n_dev} devices, found {len(devs)}",
              file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    reg, _ = obs.enable(metrics=True, trace=False)
    size = TINY if args.cpu_rehearsal else FULL
    run = run_four_chips if args.four_chips else run_one_chip
    run(size, args.seed, args.cpu_rehearsal, reg)
    if args.cpu_rehearsal:
        print("rehearsal passed on the CPU (no result line)", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
