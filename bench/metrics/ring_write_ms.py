"""ring_write_ms: device time of the ingest program's ring write per
call: the union of the device op intervals under the name scope
``ring_write`` within each ``jit_ingest_batch`` module of the profiler
window, averaged over the modules."""
from bench import progtrace


def read(run):
    pv = progtrace.view(run)
    return None if pv is None else pv.scope_ms_per_module("ring_write")
