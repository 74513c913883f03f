"""serve_roofline_pct: the two-stage serve kernel's share of its
roofline over the profiler window. Each ``serve_topk_pallas`` event is
matched to the ``bench.flush`` call it ran in; that call's work
(``bench.work.serve_call``) counts the whole index and the query block
once and each distinct routed cluster's ring tile once, the clusters
found by routing the call's queries in float64 against the index of the
snapshot version its answers name. The share is the least time of all
matched calls over their device time."""
import jax

from bench import devtrace, work

PATTERN = r"serve_topk_pallas(\.\d+)?"


def _index(run, version):
    key = ("index", version)
    if key not in run.host_cache:
        run.host_cache[key] = jax.device_get(run.engine.index_of[version])
    return run.host_cache[key]


def read(run):
    view = run.trace
    if view is None or not view.devices or run.peak is None:
        return None
    spans = sorted(view.spans("bench.flush"), key=lambda e: e.start_ns)
    by_seq = {f[0]: f for f in run.flushes}
    cfg = run.cfg
    g = run.config["guarantees"]
    itemsize = 1 if cfg.store_dtype == "int8" else 4
    need = took = 0.0
    bounds = {"bytes": 0, "flops": 0}
    for e in view.kernel(PATTERN):
        sp = view.containing(spans, e.start_ns)
        seq = devtrace.arg(sp, "seq") if sp is not None else None
        f = by_seq.get(int(seq)) if seq is not None else None
        if f is None or f[4] not in run.engine.index_of:
            continue
        _, _, _, n, version, idx = f
        vectors, valid, labels = _index(run, version)
        distinct = work.routed_clusters(run.pool[idx], vectors, valid,
                                        labels, g["nprobe"])
        flops, nbytes = work.serve_call(
            n, cfg.clus.dim, cfg.hh.bmax(), cfg.store_depth, itemsize,
            g["nprobe"], distinct)
        t, bound = work.min_seconds(flops, nbytes, run.peak)
        bounds[bound] += 1
        need += t
        took += e.dur_ns * 1e-9
    if not took:
        return None
    run.notes.append(f"serve_roofline_pct: calls bound by bytes "
                     f"{bounds['bytes']}, by flops {bounds['flops']}")
    return 100.0 * need / took
