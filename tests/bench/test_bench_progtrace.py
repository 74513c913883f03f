"""The readers of the program's spans and the ingest stages' device
scopes (``bench/progtrace.py`` and the metrics that use it), on
hand-made events."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, devtrace, progtrace  # noqa: E402
from bench.devtrace import Event  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"
FLUSHER, INGEST, MAIN = "python/1", "python/2", "python/0"
SPAN_METRICS = ["flush_wait_ms", "flush_dispatch_ms", "flush_fetch_ms",
                "query_queue_ms", "ingest_queue_ms", "publish_sync_ms"]
SCOPE_METRICS = ["counter_scan_ms", "ring_write_ms"]


def _span(name, start, end, line=FLUSHER, **kw):
    return Event(HOST, line, name, start, end - start,
                 tuple((k, str(v)) for k, v in kw.items()))


def _op(name, start, end, scope=None):
    args = (("scope", f"jit(ingest_batch)/{scope}/{name}"),) if scope \
        else ()
    return Event(DEV, devtrace.OPS, f"%{name}.1 = f32[8] fusion()", start,
                 end - start, args)


# window [0, 1000] ns; two flushes, one 50-doc ingest and its publish on
# the ingest thread, one ingest module with its device ops
EVENTS = [
    Event(HOST, "python", "bench.window", 0, 1000),
    _span("ingest.enqueue", 50, 60, MAIN, seq=1, batch=50),
    _span("ingest.admit", 80, 90, INGEST, seq=1, batch=50, wait_ms=0.03),
    _span("ingest.admit", 95, 98, INGEST, seq=2, batch=50, wait_ms=0.01),
    _span("flush", 100, 200, batch=2, wait_ms_mean=1.5, wait_ms_max=2.0),
    _span("flush.dispatch", 110, 150),
    _span("dispatch.wait", 120, 130, cls="query"),
    _span("flush.fetch", 150, 190),
    # the ingest thread's wait is not the flusher's
    _span("dispatch.wait", 105, 115, INGEST, cls="ingest"),
    _span("flush", 300, 400, batch=6, wait_ms_mean=0.5, wait_ms_max=0.9),
    _span("flush.dispatch", 310, 320),
    _span("flush.fetch", 320, 390),
    _span("ingest.publish", 500, 600, INGEST, docs=50, version=3),
    _span("publish.prepare", 500, 520, INGEST),
    _span("publish.signature", 530, 540, INGEST),
    _span("publish.copy", 540, 590, INGEST),
    Event(DEV, devtrace.MODULES, "jit_ingest_batch(7)", 700, 100),
    _op("while", 700, 760, "counter_scan"),
    _op("add", 710, 720, "counter_scan"),         # nested in the loop
    _op("scatter", 760, 770, "ring_write"),
    _op("copy", 790, 800),                          # no scope
    _op("while", 850, 860, "counter_scan"),         # outside the module
    Event(DEV, devtrace.MODULES, "jit_ingest_batch(8)", 900, 100),
    _op("while", 900, 940, "counter_scan"),
    _op("add", 910, 920, "counter_scan"),
    _op("scatter", 965, 970, "ring_write"),
    _op("copy", 980, 990),
]


def _run(events):
    view = devtrace.View(events)
    run = types.SimpleNamespace(trace=view, notes=[], host_cache={})
    run.host_cache["progtrace"] = progtrace.ProgramView(events, view)
    return run


def _read(name, run):
    return cells.load_reader(name)(run)


@pytest.mark.parametrize("name,want", [
    ("flush_wait_ms", 5e-6),              # (10 + 0) / 2 flushes
    ("flush_dispatch_ms", 20e-6),         # ((40 - 10) + 10) / 2
    ("flush_fetch_ms", 55e-6),            # (40 + 70) / 2
    ("query_queue_ms", 0.75),             # (2 * 1.5 + 6 * 0.5) / 8
    ("ingest_queue_ms", 0.02),            # (0.03 + 0.01) / 2 admits
    ("publish_sync_ms", 30e-6),           # prepare 20 + signature 10
    ("counter_scan_ms", 50e-6),           # ([700, 760] + [900, 940])
    #                                       / 2 modules
    ("ring_write_ms", 7.5e-6),            # (10 + 5) / 2
])
def test_readers_on_hand_made_events(name, want):
    assert _read(name, _run(EVENTS)) == pytest.approx(want)


@pytest.mark.parametrize("name", SPAN_METRICS + SCOPE_METRICS)
def test_readers_find_nothing_in_a_program_without_spans(name):
    """An older program opens no such span and has no scopes: every
    reader returns None and none raises."""
    old = [e for e in EVENTS if e.name not in progtrace.PROGRAM_SPANS]
    old = [Event(e.plane, e.line, e.name, e.start_ns, e.dur_ns)
           if e.line == devtrace.OPS else e for e in old]
    assert _read(name, _run(old)) is None


@pytest.mark.parametrize("name", SPAN_METRICS + SCOPE_METRICS)
def test_readers_without_a_trace(name):
    run = types.SimpleNamespace(trace=None, notes=[], host_cache={})
    assert _read(name, run) is None


def test_idle_goes_to_the_innermost_open_program_span():
    pv = _run(EVENTS).host_cache["progtrace"]
    got = pv.idle_by_program_span()
    want = {"none": 532, "flush.fetch": 110, "publish.copy": 50,
            "flush.dispatch": 40, "flush": 35, "ingest.publish": 20,
            "publish.prepare": 20, "dispatch.wait": 15,
            "ingest.enqueue": 10, "ingest.admit": 13,
            "publish.signature": 10}
    assert got == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert sum(got.values()) == pytest.approx(
        pv.window.window_s - pv.window.busy_s())


def test_view_adds_its_notes_once(monkeypatch):
    monkeypatch.setattr(progtrace, "find_xplane", lambda window: EVENTS)
    run = types.SimpleNamespace(trace=devtrace.View(EVENTS), notes=[],
                                host_cache={})
    pv = progtrace.view(run)
    assert progtrace.view(run) is pv
    assert len(run.notes) == 2
    assert run.notes[0].startswith("idle by program span (s): ")
    head, mean = run.notes[1].rsplit(" mean ", 1)
    assert head == "program flush spans: 2,"
    assert float(mean.split()[0]) == pytest.approx(100e-6)


def test_the_window_view_reads_the_same_with_program_events():
    """The program's events and the ops' scopes change no reading of
    ``devtrace.View``."""
    plain = [Event(e.plane, e.line, e.name, e.start_ns, e.dur_ns)
             if e.line == devtrace.OPS else e for e in EVENTS
             if e.name not in progtrace.PROGRAM_SPANS]
    a, b = devtrace.View(EVENTS), devtrace.View(plain)
    assert a.busy_s() == b.busy_s()
    assert a.top_ops() == b.top_ops()
    assert a.idle_gaps() == b.idle_gaps()
    assert a.idle_by_span() == b.idle_by_span()


# ------------------------------------------------- the ops' scopes, decoded
def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _vi(field, n):
    return _varint(field << 3) + _varint(n)


def _ld(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _f64(field, x):
    import struct

    return _varint(field << 3 | 1) + struct.pack("<d", x)


def _entry(field, key, value):
    return _ld(field, _vi(1, key) + _ld(2, value))


def _instr(iid, name, op_name="", operands=(), called=()):
    md = _ld(7, _ld(2, op_name)) if op_name else b""
    return _ld(2, _ld(1, name) + md + _vi(35, iid)
               + (_ld(36, b"".join(_varint(o) for o in operands))
                  if operands else b"")
               + b"".join(_vi(38, c) for c in called))


# a module as XLA leaves it: the ring write's scatter without metadata,
# its index arithmetic with a rootless name stack, a layout copy of the
# store that only the scatter reads, a loop whose body carries the scope
# (the schedule runs the loop first)
S = "jit(ingest_batch)/"
LOOP_BODY = _vi(5, 2) + _instr(20, "add.2", S + "counter_scan/add") \
    + _instr(21, "copy.2")
ENTRY = (_vi(5, 1)
         + _instr(1, "store.1", "state.store")             # a parameter
         + _instr(2, "rows.1", S + "admit/pallas_call")
         + _instr(3, "cumsum.1", "reduce_window_sum", [2])
         + _instr(4, "slot.1", S + "ring_write/add", [3])
         + _instr(5, "layout.1", "", [1])
         + _instr(6, "fusion.13", "", [5, 4, 2])
         + _instr(7, "while.16", S + "counter_scan/while", [2], [2])
         + _instr(8, "tuple.1", "", [6, 7]))
SCHEDULE = _ld(7, _entry(1, 1, _ld(1, b"".join(
    _varint(i) for i in (1, 2, 7, 3, 4, 5, 6, 8)))))
MODULE = _ld(1, _ld(1, "jit_ingest_batch") + _vi(6, 1) + _ld(3, ENTRY)
             + _ld(3, LOOP_BODY) + SCHEDULE)
CREDITED = {
    "store.1": S + "ring_write/add",      # its user's
    "rows.1": S + "admit/pallas_call",
    "cumsum.1": S + "admit/pallas_call",
    "slot.1": S + "ring_write/add",
    "layout.1": S + "ring_write/add",     # its user's
    "fusion.13": S + "ring_write/add",    # the latest scoped producer
    "while.16": S + "counter_scan/while",
    "tuple.1": S + "ring_write/add",      # the later-scheduled producer
    "add.2": S + "counter_scan/add",
    "copy.2": S + "counter_scan/while",   # its loop's
}


def test_credit_scopes_follow_the_dataflow():
    buf = memoryview(MODULE)
    entry, comps, instrs = progtrace.hlo_module(buf, (0, len(buf)))
    assert entry == 1 and comps[1] == [1, 2, 7, 3, 4, 5, 6, 8]
    assert progtrace.credit_scopes(entry, comps, instrs) == CREDITED


def test_op_scopes_read_the_modules_hlo(tmp_path):
    """A hand-encoded ``XSpace``: the metadata plane holds the module's
    HLO under ``<module>(<program id>)``, and each device op's event
    metadata names its program; an op of a module whose HLO the trace
    lacks has no scope, as has an op without one."""
    host_stats = _entry(5, 1, _vi(1, 1) + _ld(2, "Hlo Proto"))
    metadata = (_vi(1, 9) + _ld(2, "/host:metadata") + host_stats
                + _entry(4, 1, _vi(1, 1) + _ld(2, "jit_ingest_batch(77)")
                         + _ld(5, _vi(1, 1) + _ld(6, MODULE))))
    dev_stats = (_entry(5, 1, _vi(1, 1) + _ld(2, "program_id"))
                 + _entry(5, 2, _vi(1, 2) + _ld(2, "flops")))

    def op(mid, name, program):
        return _entry(4, mid, _vi(1, mid) + _ld(2, name)
                      + _ld(5, _vi(1, 2) + _f64(2, 1.5))
                      + _ld(5, _vi(1, 1) + _vi(3, program)))

    device = (_vi(1, 3) + _ld(2, DEV) + dev_stats
              + op(5, "%fusion.13 = s8[] fusion()", 77)
              + op(6, "%while.16 = s32[] while()", 77)
              + op(7, "%add.2 = s32[] add()", 77)
              + op(8, "%fusion.13 = f32[] fusion()", 78)
              + op(9, "%copy.140 = s32[] copy()", 77))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_ld(1, metadata) + _ld(1, device)
                     + _ld(1, _vi(1, 4) + _ld(2, HOST)))
    assert progtrace.op_scopes(str(path)) == {
        (DEV, "%fusion.13 = s8[] fusion()"): S + "ring_write/add",
        (DEV, "%while.16 = s32[] while()"): S + "counter_scan/while",
        (DEV, "%add.2 = s32[] add()"): S + "counter_scan/add",
    }


def test_the_modules_hlo_of_a_real_trace(tmp_path):
    """The HLO a CPU profile keeps reads as the compiled module's text
    says: every instruction of the entry computation, in its schedule,
    with its ``op_name``."""
    import glob
    import re

    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x, i):
        with jax.named_scope("first"):
            y = jnp.tanh(x) * 2
        with jax.named_scope("second"):
            return x.at[i].set(y[0]).sum()

    x, i = jnp.ones((64, 8)), jnp.int32(3)
    step(x, i).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        step(x, i).block_until_ready()
    text = step.lower(x, i).compile().as_text()
    entry_text = text[text.index("\nENTRY "):].split("\n}\n")[0]
    want = [(m.group(1), m.group(2) or "") for m in re.finditer(
        r"^\s+(?:ROOT )?%(\S+) = .*?(?:op_name=\"([^\"]*)\".*)?$",
        entry_text, re.M)]
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    buf = memoryview(open(path[0], "rb").read())
    modules = {}
    for name, events, stat_names in progtrace._planes(buf):
        if name == progtrace.METADATA_PLANE:
            for md in events:
                mname, blob = progtrace._event_md(buf, md, stat_names,
                                                  progtrace.HLO_STAT)
                modules[mname.split("(")[0]] = progtrace.hlo_module(buf,
                                                                    blob)
    entry, comps, instrs = modules["jit_step"]
    assert [instrs[i][:2] for i in comps[entry]] == want
    credited = progtrace.credit_scopes(entry, comps, instrs)
    assert any("/second/" in v for v in credited.values())
