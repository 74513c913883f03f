"""query_queue_ms: mean time a query waited in the front end's queue
before a flush popped it: each program ``flush`` span's ``wait_ms_mean``
arg weighted by its ``batch``, over the flushes of the profiler
window."""
from bench import devtrace, progtrace


def read(run):
    pv = progtrace.view(run)
    flushes = pv.spans("flush") if pv is not None else []
    if not flushes:
        return None
    n = [int(devtrace.arg(f, "batch")) for f in flushes]
    wait = [float(devtrace.arg(f, "wait_ms_mean")) for f in flushes]
    return sum(a * b for a, b in zip(n, wait)) / sum(n)
