"""ingest_queue_ms: mean time an ingest batch waited for the server's
ingest thread, from its enqueue to its admission: the ``wait_ms`` arg of
the program's ``ingest.admit`` spans in the profiler window."""
from bench import devtrace, progtrace


def read(run):
    pv = progtrace.view(run)
    waits = [float(w) for e in (pv.spans("ingest.admit") if pv else [])
             if (w := devtrace.arg(e, "wait_ms")) is not None]
    return sum(waits) / len(waits) if waits else None
