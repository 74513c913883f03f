"""The program's own spans and the device ops' name scopes in a profiler
trace.

The program's obs tracer mirrors every span it opens into a
``jax.profiler.TraceAnnotation`` of the same name and args, and the
ingest program runs its stages under ``jax.named_scope``, so one
``.xplane.pb`` holds the host spans inside a flush, a publish or an
ingest call and the device ops of each ingest stage, on the profiler's
one clock. ``load`` reads them into ``devtrace.Event`` form: the events
``devtrace.load`` keeps, plus the host events named in
``PROGRAM_SPANS`` (args from their stats; the line of a host event is
its thread, ``<line name>/<place>``) and each device op's name scope,
read from the module's HLO that the trace keeps (``op_scopes``), as arg
``scope``. The ``devtrace.View`` of the same window reads those events
exactly as it reads ``devtrace.load``'s.

A reader finds the run's trace file in the directory ``bench/run.py``
traced into (``bench-trace-*`` under the temporary directory), by its
``bench.window`` annotation, and keeps the result in ``run.host_cache``.
A program that opens none of these spans (an older tree) yields a view
with no spans, and every reader of this module then returns None.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import tempfile
import warnings

from bench import devtrace
from bench.devtrace import DEVICE_PREFIX, MODULES, OPS, Event

# Every span name of the program that a metric or the idle note reads.
PROGRAM_SPANS = frozenset({
    "flush", "flush.dispatch", "flush.fetch", "dispatch.wait",
    "ingest.enqueue", "ingest.admit", "ingest.publish",
    "publish.prepare", "publish.signature", "publish.copy", "gc.collect",
})
INGEST_MODULE = r"^jit_ingest_batch\("
_CACHE = "progtrace"


def load(path: str) -> list:
    """The events of an ``.xplane.pb`` that this module and
    ``devtrace`` read."""
    from jax.profiler import ProfileData

    scopes = op_scopes(path)
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for i, line in enumerate(plane.lines):
            if device and line.name not in (OPS, MODULES):
                continue
            # host lines are threads, and every Python thread's line is
            # called ``python``: the line's place names the thread
            where = line.name if device else f"{line.name}/{i}"
            for ev in line.events:
                name = ev.name
                if device:
                    scope = scopes.get((plane.name, name)) \
                        if line.name == OPS else None
                    args = (("scope", scope),) if scope else ()
                else:
                    name, args = devtrace._split_args(name)
                    if not (name.startswith("bench.")
                            or name in PROGRAM_SPANS):
                        continue
                    if not args:
                        args = _stats(ev)
                out.append(Event(plane.name, where, name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 args))
    return out


def _stats(ev) -> tuple:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return tuple((str(k), str(v)) for k, v in ev.stats)


# -------------------------------------------------- the ops' name scopes
# An ``XLA Ops`` event names its HLO instruction (``%fusion.13 = ...``),
# its event metadata names its module (stat ``program_id``), and the trace
# keeps each module's optimised HLO in its ``/host:metadata`` plane (stat
# ``Hlo Proto`` of an event metadata named ``<module>(<program id>)``).
# The op's scope is its instruction's ``op_name``, credited by
# ``credit_scopes`` where XLA left none. ``ProfileData`` gives an event
# metadata's name but not its stats, so these are read from the
# ``XSpace`` protobuf itself: the few fields below, by their numbers in
# ``xplane.proto`` and ``hlo.proto``.
_PLANE, _P_NAME, _P_EVENT_MD, _P_STAT_MD = 1, 2, 4, 5
_MD_NAME, _MD_STATS = 2, 5
_ST_ID, _ST_UINT, _ST_INT, _ST_STR, _ST_BYTES = 1, 3, 4, 5, 6
_HLO_MODULE, _M_COMPS, _M_ENTRY, _M_SCHEDULE = 1, 3, 6, 7
_C_INSTRS, _C_ID = 2, 5
_I_NAME, _I_METADATA, _I_ID, _I_OPERANDS, _I_CALLED = 1, 7, 35, 36, 38
_OP_NAME = 2
METADATA_PLANE, HLO_STAT, PROGRAM_STAT = "/host:metadata", "Hlo Proto", \
    "program_id"
# an ``op_name`` rooted in the jitted function's name stack
# (``jit(ingest_batch)/...``); XLA's own rewrites can leave a rootless one
# (``reduce_window_sum``)
_ROOTED = re.compile(r"^[\w\-]+\([^/]*\)/")


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """``(field, value)`` of a message in ``buf[i:end]``: an int for a
    varint or fixed field, ``(start, stop)`` for a length-delimited one."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} in a protobuf")
        yield field, value


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode()


def _ints(buf, value) -> list:
    """A repeated integer field's value: packed or one varint."""
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _map_values(buf, span):
    """The value of a protobuf map entry: ``(start, stop)``."""
    for field, value in _fields(buf, *span):
        if field == 2:
            return value
    return None


def _planes(buf):
    """``(name, event metadata spans, {stat id: stat name})`` of each
    plane of an ``XSpace``."""
    for field, span in _fields(buf, 0, len(buf)):
        if field != _PLANE:
            continue
        name, events, stat_names = None, [], {}
        for pf, pv in _fields(buf, *span):
            if pf == _P_NAME:
                name = _str(buf, pv)
            elif pf == _P_EVENT_MD:
                events.append(_map_values(buf, pv))
            elif pf == _P_STAT_MD:
                sid = sname = None
                for f2, v2 in _fields(buf, *_map_values(buf, pv)):
                    if f2 == 1:
                        sid = v2
                    elif f2 == 2:
                        sname = _str(buf, v2)
                stat_names[sid] = sname
        yield name, events, stat_names


def _event_md(buf, span, stat_names: dict, want: str):
    """``(name, value of stat ``want``)`` of an event metadata: an int,
    or ``(start, stop)`` of a string or bytes value."""
    name = value = None
    for f2, v2 in _fields(buf, *span):
        if f2 == _MD_NAME:
            name = _str(buf, v2)
        elif f2 == _MD_STATS:
            sid = got = None
            for f3, v3 in _fields(buf, *v2):
                if f3 == _ST_ID:
                    sid = v3
                elif f3 in (_ST_UINT, _ST_INT, _ST_STR, _ST_BYTES):
                    got = v3
            if stat_names.get(sid) == want:
                value = got
    return name, value


def hlo_module(buf, span) -> tuple:
    """The parts of a serialised ``HloProto`` that ``credit_scopes``
    reads: ``(entry computation id, {computation id: instruction ids in
    schedule order}, {instruction id: (name, op_name, operand ids, called
    computation ids)})``."""
    entry, comps, instrs, schedule = None, {}, {}, {}
    for field, mspan in _fields(buf, *span):
        if field != _HLO_MODULE:
            continue
        for mf, mv in _fields(buf, *mspan):
            if mf == _M_ENTRY:
                entry = mv
            elif mf == _M_COMPS:
                cid, ids = None, []
                for cf, cv in _fields(buf, *mv):
                    if cf == _C_ID:
                        cid = cv
                    elif cf == _C_INSTRS:
                        iid, name, op_name, ops, called = None, "", "", [], []
                        for f, v in _fields(buf, *cv):
                            if f == _I_ID:
                                iid = v
                            elif f == _I_NAME:
                                name = _str(buf, v)
                            elif f == _I_METADATA:
                                for f2, v2 in _fields(buf, *v):
                                    if f2 == _OP_NAME:
                                        op_name = _str(buf, v2)
                            elif f == _I_OPERANDS:
                                ops += _ints(buf, v)
                            elif f == _I_CALLED:
                                called += _ints(buf, v)
                        instrs[iid] = (name, op_name, ops, called)
                        ids.append(iid)
                comps[cid] = ids
            elif mf == _M_SCHEDULE:
                for sf, sv in _fields(buf, *mv):  # map<computation, seq>
                    key, seq = None, []
                    for f2, v2 in _fields(buf, *sv):
                        if f2 == 1:
                            key = v2
                        elif f2 == 2:
                            for f3, v3 in _fields(buf, *v2):
                                seq += _ints(buf, v3)
                    schedule[key] = seq
    for cid, seq in schedule.items():
        if cid in comps and sorted(seq) == sorted(comps[cid]):
            comps[cid] = seq
    return entry, comps, instrs


def credit_scopes(entry, comps: dict, instrs: dict) -> dict:
    """``{instruction name: op_name}`` of a module (``hlo_module``'s
    parts). An instruction with a rooted ``op_name`` keeps it. In the
    entry computation, one without takes the ``op_name`` of the scoped
    instruction it depends on (through unscoped ones) that runs last in
    the schedule: a scatter XLA rewrote from the ring write's indices and
    the admitted rows is the ring write's. One that depends on no scoped
    instruction takes its users' ``op_name`` where they agree (a layout
    copy of a state array that only the ring write reads). An instruction
    of a called computation (a loop body, a branch) without one takes its
    caller's."""
    out: dict = {}
    if entry not in comps:
        return out
    order = comps[entry]
    at: dict = {}   # instruction id -> (schedule position, op_name)
    users: dict = {}
    for k, i in enumerate(order):
        _, op_name, operands, _ = instrs[i]
        for o in operands:
            users.setdefault(o, []).append(i)
        if _ROOTED.match(op_name):
            at[i] = (k, op_name)
        else:
            got = [at[o] for o in operands if o in at]
            if got:
                at[i] = max(got)
    for i in reversed(order):
        if i not in at:
            got = {at[u][1] for u in users.get(i, ()) if u in at}
            if len(got) == 1:
                at[i] = (-1, got.pop())
    stack = []
    for i in order:
        op_name = at[i][1] if i in at else ""
        out[instrs[i][0]] = op_name
        stack += [(c, op_name) for c in instrs[i][3]]
    seen = {entry}
    while stack:
        cid, inherited = stack.pop()
        if cid in seen or cid not in comps:
            continue
        seen.add(cid)
        for i in comps[cid]:
            name, op_name, _, called = instrs[i]
            op_name = op_name if _ROOTED.match(op_name) else inherited
            out[name] = op_name
            stack += [(c, op_name) for c in called]
    return out


def op_scopes(path: str) -> dict:
    """``{(device plane, op event name): name scope}`` of the ``XLA Ops``
    of an ``.xplane.pb`` whose module's HLO the trace holds, for each op
    with a scope (``credit_scopes``)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    hlo, ops = {}, []
    for name, events, stat_names in _planes(buf):
        if name == METADATA_PLANE:
            for md in events:
                mname, blob = _event_md(buf, md, stat_names, HLO_STAT)
                m = re.search(r"\((\d+)\)$", mname or "")
                if m and isinstance(blob, tuple):
                    hlo[int(m.group(1))] = blob
        elif (name or "").startswith(DEVICE_PREFIX):
            ops += [(name, *_event_md(buf, md, stat_names, PROGRAM_STAT))
                    for md in events]
    credited: dict = {}
    out = {}
    for plane, name, program in ops:
        if not name or program not in hlo:
            continue
        if program not in credited:
            credited[program] = credit_scopes(
                *hlo_module(buf, hlo[program]))
        scope = credited[program].get(name.split(" = ", 1)[0].lstrip("%"))
        if scope:
            out[(plane, name)] = scope
    return out


def read_recorded(path: str) -> dict:
    """A recorded trace: ``{cell: events}``, each in ``devtrace.dump``'s
    row form, each with a ``bench.window`` event at its slice's bounds."""
    with gzip.open(path, "rt") as f:
        return {cell: [Event(p, ln, n, s, d, tuple(map(tuple, a)))
                       for p, ln, n, s, d, a in rows]
                for cell, rows in json.load(f).items()}


def find_xplane(window: devtrace.View):
    """The ``.xplane.pb`` of ``bench/run.py``'s trace directory whose
    ``bench.window`` annotation is ``window``'s, or None."""
    pattern = os.path.join(tempfile.gettempdir(), "bench-trace-*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        try:
            events = load(path)
        except (OSError, RuntimeError, ValueError, IndexError):
            continue  # a trace still being written, or not a trace
        win = [e for e in events if e.name == "bench.window"]
        if win and win[0].start_ns == window.t0:
            return events
    return None


def view(run):
    """The run's :class:`ProgramView`, or None without a profiler
    window. The first call adds to ``run.notes`` the idle seconds under
    each program span (where the trace has device ops) and the mean
    program ``flush`` span, beside ``flush_ms``."""
    if _CACHE in run.host_cache:
        return run.host_cache[_CACHE]
    pv = None
    if run.trace is not None:
        events = find_xplane(run.trace)
        if events is not None:
            pv = ProgramView(events, run.trace)
    run.host_cache[_CACHE] = pv
    if pv is not None and pv.any_spans() and pv.window.devices:
        run.notes.append("idle by program span (s): "
                         + json.dumps(pv.idle_by_program_span()))
    flushes = pv.spans("flush") if pv is not None else []
    if flushes:
        run.notes.append(f"program flush spans: {len(flushes)}, mean "
                         f"{sum(f.dur_ns for f in flushes) * 1e-6 / len(flushes)}"
                         " ms")
    return pv


def _scope_path(e: Event) -> list:
    for k, v in e.args:
        if k == "scope":
            return v.split("/")
    return []


def _under(e: Event, scope: str) -> bool:
    return scope in _scope_path(e)


def _depth(e: Event) -> tuple:
    """Orders open spans inward: the later start, then the earlier end."""
    return e.start_ns, -e.end_ns


def _union_ns(intervals: list) -> float:
    return sum(e - s for s, e in devtrace._merge(intervals))


class ProgramView:
    """The program's spans and the device ops' scopes over the window of
    ``window`` (a ``devtrace.View``). A span is read when it lies wholly
    inside the window; a span nests in another when both ran on the same
    thread (profiler line) and its interval lies inside the other's."""

    def __init__(self, events: list, window: devtrace.View):
        self.window = window
        t0, t1 = window.t0, window.t1
        self._all = [e for e in events if e.name in PROGRAM_SPANS
                     and not e.plane.startswith(DEVICE_PREFIX)]
        self._by_name: dict = {}
        for e in self._all:
            if t0 <= e.start_ns and e.end_ns <= t1:
                self._by_name.setdefault(e.name, []).append(e)
        # per thread, sorted by start: the candidates for nesting
        self._line: dict = {}
        for e in sorted(self._all, key=lambda e: e.start_ns):
            self._line.setdefault((e.plane, e.line), []).append(e)
        self._line_starts = {k: [e.start_ns for e in v]
                             for k, v in self._line.items()}
        self._ops = sorted((e for e in events if e.line == OPS),
                           key=lambda e: e.start_ns)
        self._op_starts = [e.start_ns for e in self._ops]
        self._modules = [e for e in events if e.line == MODULES]

    def any_spans(self) -> bool:
        return bool(self._all)

    def spans(self, name: str) -> list:
        """The spans called ``name`` inside the window, by start."""
        return sorted(self._by_name.get(name, []), key=lambda e: e.start_ns)

    def inside(self, parent: Event, name: str) -> list:
        """The spans called ``name`` nested in ``parent``."""
        key = (parent.plane, parent.line)
        events, starts = self._line.get(key, []), self._line_starts.get(
            key, [])
        i = bisect.bisect_left(starts, parent.start_ns)
        out = []
        while i < len(events) and events[i].start_ns <= parent.end_ns:
            e = events[i]
            if e.name == name and e is not parent \
                    and e.end_ns <= parent.end_ns:
                out.append(e)
            i += 1
        return out

    def nested_ms(self, parent: Event, name: str) -> float:
        return sum(e.dur_ns for e in self.inside(parent, name)) * 1e-6

    # ------------------------------------------------------- device scopes
    def scope_ms_per_module(self, scope: str,
                            module: str = INGEST_MODULE):
        """Mean over the ``module`` events inside the window of the union
        of the device op intervals under name scope ``scope`` within
        each; None when no op carries the scope. An op is under the scope
        when ``scope`` is a component of its ``scope`` arg."""
        rx = re.compile(module)
        t0, t1 = self.window.t0, self.window.t1
        mods = [m for m in self._modules if rx.search(m.name)
                and t0 <= m.start_ns and m.end_ns <= t1]
        if not mods or not any(_under(e, scope) for e in self._ops):
            return None
        ops, starts = self._ops, self._op_starts
        total = 0.0
        for m in mods:
            lo = bisect.bisect_left(starts, m.start_ns)
            hi = bisect.bisect_left(starts, m.end_ns, lo)
            total += _union_ns([(e.start_ns, min(e.end_ns, m.end_ns))
                                for e in ops[lo:hi] if e.plane == m.plane
                                and _under(e, scope)])
        return total * 1e-6 / len(mods)

    # ------------------------------------------------------------ idle note
    def idle_by_program_span(self) -> dict:
        """Idle seconds on the first device credited to the innermost
        program span open then on any thread (the open span that started
        last, of two that started together the one that ends first),
        under each span name, and under ``none`` where no program
        span was open."""
        w = self.window
        busy = w.busy(w.devices[0])
        edges = [w.t0] + [x for iv in busy for x in iv] + [w.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = [e for e in self._all
                 if e.end_ns > w.t0 and e.start_ns < w.t1]
        # sweep the span boundaries: between two of them the set of open
        # spans is fixed, and the innermost is the one started last
        marks = sorted([(max(w.t0, e.start_ns), 1, i)
                        for i, e in enumerate(spans)]
                       + [(min(w.t1, e.end_ns), 0, i)
                          for i, e in enumerate(spans)])
        out: dict = {}
        active: dict = {}
        gi = 0
        prev = w.t0
        for t, opens, i in marks + [(w.t1, 0, None)]:
            if t > prev:
                while gi < len(gaps) and gaps[gi][1] <= prev:
                    gi += 1
                idle, j = 0.0, gi
                while j < len(gaps) and gaps[j][0] < t:
                    idle += max(0.0, min(t, gaps[j][1])
                                - max(prev, gaps[j][0]))
                    j += 1
                if idle > 0:
                    label = max(active.values(), key=_depth).name \
                        if active else "none"
                    out[label] = out.get(label, 0.0) + idle * 1e-9
                prev = t
            if i is None:
                break
            if opens:
                active[i] = spans[i]
            else:
                active.pop(i, None)
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))
