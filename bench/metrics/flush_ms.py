"""flush_ms: mean host time of a flush() call that answered queries,
over the profiler window (the benchmark's clock around each call)."""


def _flushes(run):
    if run.trace_host is None:
        return []
    t0, t1 = run.trace_host
    return [f for f in run.flushes if f[1] >= t0 and f[2] <= t1]


def read(run):
    f = _flushes(run)
    return 1e3 * sum(x[2] - x[1] for x in f) / len(f) if f else None
