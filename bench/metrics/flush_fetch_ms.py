"""flush_fetch_ms: mean time a flush that answered queries spent copying
its answers to the host (the program's ``flush.fetch`` span, which
blocks until the serve program has run), over the flushes of the
profiler window."""
from bench import progtrace


def read(run):
    pv = progtrace.view(run)
    flushes = pv.spans("flush") if pv is not None else []
    if not flushes:
        return None
    return sum(pv.nested_ms(f, "flush.fetch") for f in flushes) \
        / len(flushes)
