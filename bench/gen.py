"""The flood's generator: stream batches made ahead of use on a few
threads, so that making them does not set the ingest rate.

Each batch's sequential part (the stream's dynamics) is planned on the
caller's thread; the noise, most of the work, is drawn on the pool's
threads, where numpy runs without the interpreter lock.
"""
from __future__ import annotations

import collections
import concurrent.futures
import time

from bench.stream import make_stream


class Generator:
    """Keeps ``depth`` batches of the seeded stream in the making on
    ``threads`` threads; ``get`` returns them in stream order."""

    def __init__(self, kind: str, dim: int, seed: int, batch: int,
                 depth: int, threads: int):
        self.stream = make_stream(kind, dim, seed)
        self.batch = batch
        self.pool = concurrent.futures.ThreadPoolExecutor(
            threads, thread_name_prefix="bench-generator")
        self.ahead = collections.deque(self._submit() for _ in range(depth))
        self.empty_s = 0.0      # time spent waiting for a batch not ready

    def _submit(self):
        return self.pool.submit(self.stream.make,
                                self.stream.plan(self.batch))

    def get(self, timeout: float = 120.0):
        """The next batch in stream order; time spent waiting for one
        that was not ready adds to ``empty_s``."""
        fut = self.ahead.popleft()
        t0 = time.perf_counter()
        ready = fut.done()
        item = fut.result(timeout=timeout)
        if not ready:
            self.empty_s += time.perf_counter() - t0
        self.ahead.append(self._submit())
        return item

    def close(self):
        """Drop the batches in the making and wait for the threads."""
        self.pool.shutdown(wait=True, cancel_futures=True)
