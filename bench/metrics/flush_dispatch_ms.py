"""flush_dispatch_ms: mean host time a flush that answered queries spent
dispatching its serve program (the program's ``flush.dispatch`` span,
less the ``dispatch.wait`` inside it), over the flushes of the profiler
window."""
from bench import progtrace


def read(run):
    pv = progtrace.view(run)
    flushes = pv.spans("flush") if pv is not None else []
    if not flushes:
        return None
    return sum(pv.nested_ms(f, "flush.dispatch")
               - pv.nested_ms(f, "dispatch.wait") for f in flushes) \
        / len(flushes)
