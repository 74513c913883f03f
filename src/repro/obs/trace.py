"""Structured span tracing, on the profiler's clock and exported as
Chrome trace-event JSON.

Every span a :class:`Tracer` opens is recorded twice: host-side
(monotonic clock, microsecond resolution) into a bounded buffer exported
in the Chrome trace-event format — loadable in Perfetto /
``chrome://tracing`` — and, on the same thread, as a
``jax.profiler.TraceAnnotation`` of the same name and args, so that a
``jax.profiler`` trace holds the spans beside the device's ops on one
clock. Args found mid-span are written into the annotation through
``set_metadata`` before it closes. The spans:

  * **query path**: ``flush`` (args: ``batch``, ``queue_depth``,
    ``ticket_first``/``ticket_last`` of the answered tickets, the pinned
    ``snapshot_version``, ``wait_ms_mean``/``wait_ms_max`` — the age of
    the popped queries at pop time — and the plan) with ``embed`` (when
    an embedder is set), ``flush.dispatch`` (the serve program's
    dispatch) and ``flush.fetch`` (the answers' device-to-host copies);
  * **dispatch section**: ``dispatch.wait`` (arg ``cls``: ``query`` or
    ``ingest``), recorded only when the section was busy at entry;
  * **ingest path**: ``ingest.enqueue`` (producer; arg ``seq``),
    ``ingest.admit`` (the background thread's engine dispatch; same
    ``seq``, and ``wait_ms``, the batch's time from its enqueue),
    ``ingest.publish`` (args: ``version``, ``docs`` — the doc
    watermark the snapshot covers — and the dirty-cluster counts) with
    ``publish.prepare`` (the host block on ingest execution),
    ``publish.signature`` (the dirty-signature fetch) and
    ``publish.copy`` (the snapshot copies);
  * **runtime**: ``gc.collect`` (arg ``generation``) around each full
    collection of the garbage collector, while tracing is enabled.

Correlation is by args: a flush names the ticket range it answered and
the snapshot version it answered from, so freshness questions ("which
queries saw stale data?") are a Perfetto query over
``args.snapshot_version`` against the ``ingest.publish`` spans'
versions.

Tracing shares the observability on/off contract of ``obs.metrics``:
sites fetch the active tracer once per batch via ``obs.tracer()`` and do
nothing when it is ``None`` — no span and no annotation is built.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation


class _Span:
    """Mutable in-flight span; finished on ``__exit__`` or ``end()``, on
    the thread that opened it (the profiler annotation requires it)."""

    __slots__ = ("tracer", "name", "cat", "args", "t0", "_ann", "_sent",
                 "_done")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args      # mutable: fill correlation fields mid-span
        self._sent = dict(args)
        self._ann = TraceAnnotation(name, **args)
        self._ann.__enter__()
        self.t0 = tracer.now_us()
        self._done = False

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()

    def end(self) -> None:
        if self._done:
            return
        self._done = True
        dur = self.tracer.now_us() - self.t0
        late = {k: v for k, v in self.args.items()
                if k not in self._sent or self._sent[k] != v}
        if late:
            self._ann.set_metadata(**late)
        self._ann.__exit__(None, None, None)
        self.tracer._emit_complete(self.name, self.cat, self.t0, dur,
                                   self.args)


class Tracer:
    """Bounded trace-event buffer with Chrome JSON export.

    ``max_events`` bounds memory on long runs (oldest events drop first —
    the tail of a serving run is usually what is being debugged). All
    emission paths are lock-protected; timestamps come from one process
    monotonic clock so spans from the query and ingest threads interleave
    correctly on the exported timeline. The lock is reentrant: a
    ``gc.collect`` span can end inside an allocation made under it.
    """

    def __init__(self, max_events: int = 200_000):
        self._lock = threading.RLock()
        self._events: collections.deque = collections.deque(
            maxlen=max_events)
        self._t0 = time.perf_counter()
        self._pid = os.getpid()
        self._dropped = 0
        self._gc_span: _Span | None = None

    def now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    # ------------------------------------------------------------- record
    def span(self, name: str, cat: str = "serve", **args) -> _Span:
        """Context manager recording a complete ("X") event and the
        profiler annotation of the same name. The returned span's
        ``args`` dict is mutable — correlation fields discovered
        mid-span (e.g. the snapshot version pinned at flush) can be
        added before exit."""
        return _Span(self, name, cat, args)

    def on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook: a ``gc.collect`` span around each full
        (generation 2) collection. A collection runs on one thread and
        holds the interpreter lock from start to stop."""
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_span = self.span("gc.collect", cat="runtime",
                                      generation=2)
        elif self._gc_span is not None:
            self._gc_span.end()
            self._gc_span = None

    def counter(self, name: str, values: dict, cat: str = "serve") -> None:
        """Chrome counter-track event ("C") — queue depth, lag, etc."""
        self._append({"name": name, "cat": cat, "ph": "C",
                      "ts": self.now_us(), "pid": self._pid,
                      "args": {k: float(v) for k, v in values.items()}})

    def _emit_complete(self, name, cat, ts, dur, args) -> None:
        self._append({"name": name, "cat": cat, "ph": "X",
                      "ts": ts, "dur": max(dur, 0.0),
                      "pid": self._pid, "tid": threading.get_ident(),
                      "args": args})

    def _append(self, ev: dict) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(ev)

    # ------------------------------------------------------------- export
    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome(self) -> dict:
        """The Chrome trace-event JSON object format."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "args": {"name": "repro-streaming-rag"}}]
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": dropped},
        }

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


def validate_chrome_trace(obj: dict) -> list[str]:
    """Structural validation of a Chrome trace-event JSON object; returns
    a list of problems (empty = valid). Used by the CI smoke check and
    ``tests/test_obs.py`` so "exported trace is valid" is a checked
    property, not an eyeball."""
    problems = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["missing traceEvents key"]
    events = obj["traceEvents"]
    if not isinstance(events, list) or not events:
        return ["traceEvents is not a non-empty list"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i} is not an object")
            continue
        for key in ("name", "ph", "pid"):
            if key not in ev:
                problems.append(f"event {i} ({ev.get('name')}) missing {key}")
        ph = ev.get("ph")
        if ph in ("X", "B", "E", "i", "C") and "ts" not in ev:
            problems.append(f"event {i} ({ev.get('name')}) missing ts")
        if ph == "X" and "dur" not in ev:
            problems.append(f"X event {i} ({ev.get('name')}) missing dur")
    return problems
