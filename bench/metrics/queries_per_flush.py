"""queries_per_flush: answers per flush() call that answered queries,
over the profiler window."""


def read(run):
    if run.trace_host is None:
        return None
    t0, t1 = run.trace_host
    f = [x for x in run.flushes if x[1] >= t0 and x[2] <= t1]
    return sum(x[3] for x in f) / len(f) if f else None
