"""The trace reduction: busy union, kernel sums and idle-gap attribution,
on hand-made events and on a short trace recorded on a TPU v5e."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import devtrace  # noqa: E402
from bench.devtrace import Event  # noqa: E402

DEV = "/device:TPU:0"
RECORDED = os.path.join(ROOT, "bench", "testdata", "chip_trace_v5e.json.gz")


def _op(name, start, dur):
    return Event(DEV, devtrace.OPS, f"%{name} = f32[8] custom-call()",
                 start, dur)


def _host(name, start, dur, **kw):
    return Event("/host:CPU", "python3", name, start, dur,
                 tuple((k, str(v)) for k, v in kw.items()))


@pytest.fixture
def view():
    # window [100, 200]; ops [90, 110] (clipped to [100, 110]),
    # [105, 120] overlaps it, [150, 160]; two serve kernel calls
    events = [
        _host("bench.window", 100, 100),
        _op("fusion.3", 90, 20),
        _op("serve_topk_pallas.1", 105, 15),
        _op("serve_topk_pallas.2", 150, 10),
        Event(DEV, devtrace.MODULES, "jit_ingest_batch(123)", 140, 30),
        _host("bench.flush", 100, 25, seq=7),
        _host("bench.flush", 145, 20, seq=8),
        _host("bench.ingest", 125, 13),
    ]
    return devtrace.View(events)


def test_busy_union_clips_and_merges(view):
    assert view.window_s == pytest.approx(100e-9)
    assert view.busy(DEV) == [[100, 120], [150, 160]]
    assert view.busy_s() == pytest.approx(30e-9)


def test_kernel_sums_and_modules(view):
    k = view.kernel(r"serve_topk_pallas(\.\d+)?")
    assert [e.dur_ns for e in k] == [15, 10]
    assert view.kernel(r"admit_pallas(\.\d+)?") == []
    assert [m.dur_ns for m in view.modules(r"^jit_ingest_batch\(")] == [30]


def test_idle_gaps_labelled_by_host_span(view):
    # gaps: [120, 150] (ingest covers 125-138 = 13, the flushes
    # 120-125 and 145-150 = 10), [160, 200] (flush covers 160-165 = 5)
    gaps = view.idle_gaps()
    assert gaps == [["bench.flush", pytest.approx(40e-9)],
                    ["bench.ingest", pytest.approx(30e-9)]]
    by = view.idle_by_span()
    assert by["idle"] == pytest.approx(70e-9)
    assert by["bench.flush"] == pytest.approx(15e-9)
    assert by["bench.ingest"] == pytest.approx(13e-9)


def test_containing_span_and_its_seq(view):
    spans = sorted(view.spans("bench.flush"), key=lambda e: e.start_ns)
    k = view.kernel(r"serve_topk_pallas(\.\d+)?")
    seqs = [devtrace.arg(view.containing(spans, e.start_ns), "seq")
            for e in k]
    assert seqs == ["7", "8"]


def test_encoded_annotation_args_are_split():
    assert devtrace._split_args("bench.flush#seq=3#") == (
        "bench.flush", (("seq", "3"),))
    assert devtrace._split_args("bench.ingest") == ("bench.ingest", ())


def test_recorded_chip_trace():
    """A 43 ms slice of serving on one TPU v5e (16-query flushes, 50-doc
    ingests): the counts and sums below were read off the trace."""
    v = devtrace.View(devtrace.read(RECORDED))
    assert v.devices == [DEV]
    assert v.window_s == pytest.approx(0.042863562)
    assert v.busy_s() == pytest.approx(0.003768257)
    serve = v.kernel(r"serve_topk_pallas(\.\d+)?")
    assert len(serve) == 8
    assert sum(e.dur_ns for e in serve) * 1e-9 == pytest.approx(
        0.000616546)
    assert len(v.kernel(r"admit_pallas(\.\d+)?")) == 2
    assert len(v.modules(r"^jit_ingest_batch\(")) == 2
    assert v.top_ops(1) == [["while.16", pytest.approx(0.002142592)]]
    gaps = v.idle_gaps(3)
    assert [g[0] for g in gaps] == ["bench.flush"] * 3
    by = v.idle_by_span()
    assert by["idle"] == pytest.approx(v.window_s - v.busy_s())
