"""gc_pause_ms: the longest pause of the process's garbage collector in
the window, in ms (timed by the benchmark's callbacks on the collector).
A collection holds the interpreter lock, so every thread of the server
(the flusher, the ingest thread, the load loop) waits it out."""


def read(run):
    inside = [c[2] for c in run.gc_window if 0 <= c[1] <= run.seconds]
    return 1e3 * max(inside) if inside else None
