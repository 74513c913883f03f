"""publish_sync_ms: mean host time a publication spent blocked on the
device: the program's ``publish.prepare`` (waiting for ingest execution)
and ``publish.signature`` (the dirty-signature fetch) spans nested in
each ``ingest.publish`` span of the profiler window."""
from bench import progtrace


def read(run):
    pv = progtrace.view(run)
    pubs = pv.spans("ingest.publish") if pv is not None else []
    if not pubs:
        return None
    return sum(pv.nested_ms(p, "publish.prepare")
               + pv.nested_ms(p, "publish.signature") for p in pubs) \
        / len(pubs)
