"""ingest_docs_per_s: docs made queryable per second in the window (the
docs covered by publications inside it, over the time between the first
and the last of them)."""
from bench.series import published_docs_per_s


def read(run):
    return published_docs_per_s(run)
