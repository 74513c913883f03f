"""publish_ms: mean duration of the program's own ``ingest.publish``
spans (obs tracer, enabled in the traced run) inside the profiler
window."""


def read(run):
    spans = run.publish_spans_ms
    return sum(spans) / len(spans) if spans else None
