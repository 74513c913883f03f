"""admit_roofline_pct: the admission kernel's share of its roofline: the
least time the chip needs for the work of every call in the profiler
window (``bench.work.admit_call``, the larger of flops over the bf16
peak and bytes over HBM bandwidth) over the device time of its
``admit_pallas`` custom-call events."""
from bench import work

PATTERN = r"admit_pallas(\.\d+)?"


def read(run):
    view = run.trace
    if view is None or not view.devices or run.peak is None:
        return None
    events = view.kernel(PATTERN)
    if not events:
        return None
    cfg = run.cfg
    flops, nbytes = work.admit_call(
        run.b_size, cfg.clus.num_clusters, cfg.pre.num_vectors, cfg.clus.dim,
        1 if cfg.store_dtype == "int8" else 4)
    need, bound = work.min_seconds(flops, nbytes, run.peak)
    took = sum(e.dur_ns for e in events) * 1e-9
    run.notes.append(f"admit_roofline_pct: {len(events)} calls of "
                     f"{run.b_size} docs, bound by {bound}")
    return 100.0 * need * len(events) / took
