"""flush_wait_ms: mean time a flush that answered queries spent waiting
for the dispatch section (the program's ``dispatch.wait`` spans nested
in its ``flush`` span, held by an ingest or publish dispatch), over the
flushes of the profiler window."""
from bench import progtrace


def read(run):
    pv = progtrace.view(run)
    flushes = pv.spans("flush") if pv is not None else []
    if not flushes:
        return None
    return sum(pv.nested_ms(f, "dispatch.wait") for f in flushes) \
        / len(flushes)
