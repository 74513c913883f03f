"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

A trace is reduced to a flat list of events ``(plane, line, name, start_ns,
dur_ns, args)``: the device planes' ``XLA Ops`` and ``XLA Modules`` lines
and the host's ``bench.*`` annotations, all on the profiler's one clock.
``load`` reads an ``.xplane.pb`` into that form; a recorded trace in that
form (``testdata/``) checks the reduction without a chip.

Device op names are HLO text (``%serve_topk_pallas.1 = (...)
custom-call(...)``); a kernel is matched by a regular expression on the
op's name, the part before `` = ``.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
import warnings

DEVICE_PREFIX = "/device:TPU:"
OPS, MODULES = "XLA Ops", "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    args: tuple = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """The HLO op's name (``serve_topk_pallas.1``), or the name."""
        return self.name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> list:
    """The events of an ``.xplane.pb`` that the reduction reads."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS, MODULES):
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith("bench."):
                    continue
                args = ()
                if not device:
                    name, args = _split_args(name)
                    if not args:
                        with warnings.catch_warnings():
                            warnings.simplefilter("ignore",
                                                  DeprecationWarning)
                            args = tuple((str(k), str(v))
                                         for k, v in ev.stats)
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 args))
    return out


def _split_args(name: str) -> tuple:
    """``bench.flush#seq=3#`` -> (``bench.flush``, ((``seq``, ``3``),))."""
    if "#" not in name:
        return name, ()
    base, rest = name.split("#", 1)
    pairs = (kv.split("=", 1) for kv in rest.strip("#").split(",") if kv)
    return base, tuple((k, v) for k, v in pairs)


def dump(events: list, path: str, t0: float = -1e30, t1: float = 1e30):
    """Write the events that start in ``[t0, t1)`` as gzipped JSON."""
    rows = [dataclasses.astuple(e) for e in events if t0 <= e.start_ns < t1]
    with gzip.open(path, "wt") as f:
        json.dump(rows, f)


def read(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return [Event(p, ln, n, s, d, tuple(map(tuple, a)))
                for p, ln, n, s, d, a in json.load(f)]


def _merge(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(host: tuple, s: float, e: float) -> float:
    """Length of ``[s, e]`` covered by ``host`` = (starts, merged), sorted
    disjoint intervals and their starts."""
    import bisect

    starts, merged = host
    i = max(0, bisect.bisect_right(starts, s) - 1)
    total = 0.0
    while i < len(merged) and merged[i][0] < e:
        total += max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return total


class View:
    """The events of one traced window ``[t0, t1]`` (ns), the bounds of
    the benchmark's ``bench.window`` annotation."""

    def __init__(self, events: list):
        win = [e for e in events if e.name == "bench.window"]
        if not win:
            raise ValueError("the trace holds no bench.window annotation")
        self.t0, self.t1 = win[0].start_ns, win[0].end_ns
        self.events = events
        self.devices = sorted({e.plane for e in events
                               if e.plane.startswith(DEVICE_PREFIX)})

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def ops(self, device: str | None = None) -> list:
        return [e for e in self.events if e.line == OPS
                and (device is None or e.plane == device)
                and e.end_ns > self.t0 and e.start_ns < self.t1]

    def busy(self, device: str) -> list:
        """Merged intervals in which an op ran on ``device``, clipped to
        the window."""
        return _merge([(max(e.start_ns, self.t0), min(e.end_ns, self.t1))
                       for e in self.ops(device)])

    def busy_s(self) -> float | None:
        """Busy seconds, averaged over the devices in the trace."""
        if not self.devices:
            return None
        return sum(sum(e - s for s, e in self.busy(d))
                   for d in self.devices) * 1e-9 / len(self.devices)

    def kernel(self, pattern: str) -> list:
        """Device op events whose op name matches ``pattern``, starting
        inside the window."""
        rx = re.compile(pattern)
        return [e for e in self.events if e.line == OPS
                and rx.fullmatch(e.op) and self.t0 <= e.start_ns < self.t1]

    def modules(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [e for e in self.events if e.line == MODULES
                and rx.search(e.name) and self.t0 <= e.start_ns < self.t1]

    def spans(self, name: str) -> list:
        """Host annotations called ``name``, overlapping the window."""
        return [e for e in self.events if e.name == name
                and e.end_ns > self.t0 and e.start_ns < self.t1]

    def top_ops(self, n: int = 10) -> list:
        """``[[op, seconds]]`` of the ops that took most device time."""
        total: dict = {}
        for e in self.ops():
            d = min(e.end_ns, self.t1) - max(e.start_ns, self.t0)
            total[e.op] = total.get(e.op, 0.0) + d
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[op, ns * 1e-9] for op, ns in top]

    def _gaps(self) -> list:
        if not self.devices:
            return []
        busy = self.busy(self.devices[0])
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def _host(self) -> dict:
        """Merged intervals of each ``bench.*`` host span name."""
        by: dict = {}
        for e in self.events:
            if e.name.startswith("bench.") and e.name != "bench.window":
                by.setdefault(e.name, []).append((e.start_ns, e.end_ns))
        merged = {k: _merge(v) for k, v in by.items()}
        return {k: ([iv[0] for iv in v], v) for k, v in merged.items()}

    def idle_gaps(self, n: int = 10) -> list:
        """``[[label, seconds]]`` of the longest idle gaps on the first
        device, each labelled by the ``bench.*`` host span that covered
        most of it (``none`` where no span was open)."""
        host = self._host()
        out = []
        for s, e in sorted(self._gaps(), key=lambda g: g[0] - g[1])[:n]:
            cover = {k: _overlap(v, s, e) for k, v in host.items()}
            cover = {k: v for k, v in cover.items() if v > 0}
            label = max(cover, key=cover.get) if cover else "none"
            out.append([label, (e - s) * 1e-9])
        return out

    def idle_by_span(self) -> dict:
        """Idle seconds on the first device under each ``bench.*`` span
        name (spans of different threads may overlap, so the shares can
        add up to more than the idle time), and ``idle`` in all."""
        host = self._host()
        gaps = self._gaps()
        out = {"idle": sum(e - s for s, e in gaps) * 1e-9}
        for k, v in host.items():
            out[k] = sum(_overlap(v, s, e) for s, e in gaps) * 1e-9
        return out

    def containing(self, spans: list, t: float):
        """The span of ``spans`` (sorted by start) that contains ``t``."""
        import bisect

        starts = [sp.start_ns for sp in spans]
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and spans[i].start_ns <= t <= spans[i].end_ns:
            return spans[i]
        return None


def arg(event: Event, key: str):
    """A host annotation's keyword argument (``TraceAnnotation(name,
    key=...)``), from the event's stats or its encoded name."""
    for k, v in event.args:
        if k == key:
            return v
    m = re.search(rf"#.*\b{re.escape(key)}=([^,#]+)", event.name)
    return m.group(1) if m else None
