"""After the window: the comparison that decides ``correct``, the trace
reduction, and the result line."""
from __future__ import annotations

import glob
import json
import os
import sys

import numpy as np

from bench import cells, check as ref, devtrace, schedule, series, work


def _numbers_vs_limits(numbers: dict, limits: dict) -> dict:
    return {name: {"value": numbers[name], "limit": limits[name]}
            for name in limits if name in numbers}


def _query_numbers(run, engine, control) -> dict:
    g = run.config["guarantees"]
    two_stage = run.traffic["queries"].get("two_stage", True)
    by_version: dict = {}
    for qi, (version, ids, scores, clusters) in run.answers.items():
        by_version.setdefault(version, []).append((qi, ids, scores,
                                                   clusters))
    out = {"checked": 0, "wrong_answers": 0, "rank_gap": 0.0,
           "score_err": 0.0, "no_certain_cluster": 0}
    for version, rows in sorted(by_version.items()):
        snap = ref.host_snapshot(engine.kept[version])
        queries = run.pool[[r[0] for r in rows]]
        answers = [r[1:] for r in rows]
        if not two_stage:
            if control:
                answers = ref.control_prototype_answers(queries, snap,
                                                        g["topk"])
            got = ref.check_prototype_answers(answers, queries, snap,
                                              g["topk"])
        else:
            if control:
                answers = ref.control_answers(queries, snap, g["topk"],
                                              g["nprobe"])
            got = ref.check_answers(answers, queries, snap, g["topk"],
                                    g["nprobe"])
        out["checked"] += got["checked"]
        out["wrong_answers"] += got["wrong_answers"]
        out["no_certain_cluster"] += got["no_certain_cluster"]
        out["rank_gap"] = max(out["rank_gap"], got["rank_gap"])
        out["score_err"] = max(out["score_err"], got["score_err"])
    run.notes.append(f"query check: {out['checked']} answers on "
                     f"{len(by_version)} sampled snapshot versions, "
                     f"{out['no_certain_cluster']} with no cluster routed "
                     f"for certain")
    return out


def _host_state(tree: dict) -> dict:
    import jax

    return {k: np.asarray(v) for k, v in jax.device_get(tree).items()}


def _ingest_numbers(run, engine, control) -> dict:
    cfg = run.cfg
    quantized = cfg.store_dtype == "int8"
    depth = cfg.store_depth
    alpha = cfg.pre.alpha
    totals = {"docs_misplaced": 0, "ring_slots_wrong": 0, "hh_wrong": 0,
              "row_elements": 0, "row_elements_wrong": 0, "stored": 0}
    if not control:
        totals["docs_unpublished"] = 0
    calls = sorted(engine.captured)
    base = engine.calls - run.batches_offered  # first window call
    for n in calls:
        before, after, ids = engine.captured[n]
        before, after = _host_state(before), _host_state(after)
        x, x_ids = run.window_docs[n - base]
        if not np.array_equal(x_ids, ids):
            raise RuntimeError(f"ingest call {n} is not window batch "
                               f"{n - base}")
        if control:
            decided = ref.control_admit(x, before["pre.basis"],
                                        before["clus.centroids"], alpha,
                                        quantized)
            after = ref.apply_admit(before, decided, ids, depth, quantized)
        got = ref.check_ingest_call(before, after, x, ids, alpha, depth,
                                    quantized)
        totals["stored"] += got["stored"]
        if not control:
            snap = engine.covering.get(n)
            published = _host_state({f"store.{f}": getattr(snap.store, f)
                                     for f in ("ids", "ptr", "embs",
                                               "scales")}) \
                if snap is not None else None
            totals["docs_unpublished"] += got["stored"] if snap is None \
                else ref.check_published(after, published, ids, depth,
                                         quantized)
        for key in ("docs_misplaced", "ring_slots_wrong", "hh_wrong",
                    "row_elements", "row_elements_wrong"):
            totals[key] += got[key]
    if not control:
        store = _host_state({f"store.{f}": getattr(engine.last.store, f)
                             for f in ("ids", "ptr", "embs", "scales")})
        docs = run.window_docs
        id0 = int(docs[0][1][0]) if docs else 0
        size = run.b_size
        n_docs = len(docs) * size

        def lookup(ids):
            off = ids.astype(np.int64) - id0
            mine = (off >= 0) & (off < n_docs)
            o = off[mine]
            rows = np.stack([docs[b][0][r] for b, r in
                             zip((o // size).tolist(), (o % size).tolist())]) \
                if o.size else np.zeros((0, run.cfg.clus.dim))
            return mine, rows

        got = ref.check_final_store(store, lookup, depth, quantized)
        totals["ring_slots_wrong"] += got["ring_slots_wrong"]
        totals["row_elements"] += got["row_elements"]
        totals["row_elements_wrong"] += got["row_elements_wrong"]
        run.notes.append(f"ingest check: {len(calls)} sampled calls "
                         f"({totals['stored']} stored docs), final store "
                         f"{got['stored']} window docs")
    totals["row_mismatch_share"] = (totals["row_elements_wrong"]
                                    / max(totals["row_elements"], 1))
    return totals


def check(run, engine, control=None) -> dict:
    """The numbers compared with the reference, each with its limit, and
    whether the run is correct."""
    limits = run.config["limits"]
    numbers: dict = {}
    if run.q_due is not None and len(run.q_due):
        numbers.update(_query_numbers(run, engine, control))
    if engine.captured:
        numbers.update(_ingest_numbers(run, engine, control))
    compared = _numbers_vs_limits(numbers, limits)
    ok = bool(compared) and all(c["value"] <= c["limit"]
                                for c in compared.values())
    # a query cell has to have checked answers, an ingest cell stored docs
    checked = numbers.get("checked", numbers.get("stored", 0))
    ok &= checked > 0
    unanswered = 0 if run.q_done is None else int(np.sum(np.isnan(
        run.q_done)))
    ok &= unanswered == 0 and run.batches_failed == 0
    return {"correct": bool(ok), "compared": compared,
            "checked": int(checked), "unanswered": unanswered}


def read_trace(run, window, trace_dir: str):
    """Reduce the profiler window and the obs tracer's publish spans."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        run.notes.append("trace: no xplane file was written")
        return
    run.trace = devtrace.View(devtrace.load(paths[0]))
    tr = window.obs_tracer
    if tr is not None and window.obs_clock is not None:
        us0, pc0 = window.obs_clock
        t0, t1 = run.trace_host
        for e in tr.events():
            if e.get("name") != "ingest.publish" or "dur" not in e:
                continue
            start = pc0 + (e["ts"] - us0) * 1e-6
            if t0 <= start and start + e["dur"] * 1e-6 <= t1:
                run.publish_spans_ms.append(e["dur"] * 1e-3)


def result(run, cell, checks: dict, devs, n_devices: int, platform: str,
           traced: bool) -> dict:
    run.peak = work.peaks(devs[0].device_kind) if platform == "tpu" \
        else None
    entries = cell.per_layer if traced else cell.end_to_end
    metrics = cells.read_metrics(entries, run)
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": n_devices,
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": checks["correct"]}
    if run.q_due is not None and len(run.q_due):
        line["attempted"] = int(len(run.q_due))
        line["failed"] = checks["unanswered"]
    else:
        line["attempted"] = int(run.batches_offered)
        line["failed"] = int(run.batches_failed)
    line["metrics"] = metrics
    view = run.trace
    if traced and view is not None and view.devices:
        device["busy_s"] = view.busy_s()
        device["window_s"] = view.window_s
    line["device"] = device
    if traced and view is not None:
        line["breakdown"] = {"device_ops": view.top_ops(),
                             "idle_gaps": view.idle_gaps()}
        run.notes.append("idle by host span (s): " + json.dumps(
            view.idle_by_span()))
    line["checks"] = dict(checks["compared"])
    return line


def host_stalls(run) -> list:
    """Earlier lines that set host stalls in the window beside the
    garbage collections then: each generation's collections, every
    collection of the oldest generation (start s from the window start,
    seconds), and the three longest gaps between answering flushes."""
    inside = [c for c in run.gc_window if 0 <= c[1] <= run.seconds]
    lines = []
    for gen in sorted({c[0] for c in inside}):
        d = [c[2] for c in inside if c[0] == gen]
        lines.append(f"garbage collections in the window, generation {gen}"
                     f": {len(d)}, total {sum(d)} s, longest {max(d)} s")
    full = [(round(c[1], 3), c[2]) for c in inside if c[0] == 2]
    lines.append(f"full collections in the window (start s, s): {full}")
    done = sorted(f[2] - run.t_start for f in run.flushes)
    if len(done) > 1:
        gaps = np.diff(done)
        top = np.argsort(gaps)[::-1][:3]
        lines.append("longest gaps between answering flushes (start s, s): "
                     + str([(round(done[i], 3), float(gaps[i]))
                            for i in top]))
    return lines


def emit(run, checks: dict, line: dict):
    """Earlier lines, then the numbers compared on stderr, then the
    result line last on stdout."""
    q = run.q_due is not None and len(run.q_due) > 0
    print(f"setup_s: {run.setup_s}", flush=True)
    print(f"compiles in the window: {run.compiles_in_window}", flush=True)
    if q:
        late = schedule.lateness_ms(run.t_start + run.q_due, run.q_sent)
        late = late[~np.isnan(late)]
        print(f"generator lateness ms: p50 {np.percentile(late, 50)} "
              f"p99 {np.percentile(late, 99)} max {np.max(late)} "
              f"over {late.size} queries", flush=True)
        lat = series.query_latency_ms(run)
        print("query latency ms: " + ", ".join(
            f"p{p} {np.percentile(lat, p)}" for p in (50, 90, 99, 99.9))
            + f", max {np.max(lat)}", flush=True)
    if run.gen_empty_s or run.server_idle_share is not None:
        print(f"ingest generator queue empty: {run.gen_empty_s} s of "
              f"{run.seconds} s ({run.gen_empty_s / run.seconds}); server "
              f"ingest queue empty at {run.server_idle_share} of offers",
              flush=True)
    for note in run.notes + host_stalls(run):
        print(note, flush=True)
    print(f"memory_peak_bytes: {run.memory_peak_bytes}", flush=True)
    for name, c in checks["compared"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(f"correct: {checks['correct']} ({checks['checked']} checked, "
          f"{checks['unanswered']} unanswered)", file=sys.stderr,
          flush=True)
    print(json.dumps(line), flush=True)
