"""The program's own spans in a profiler trace: an ``AsyncServer`` run
under ``jax.profiler`` on the CPU, read back from its ``.xplane.pb`` by
``bench.progtrace.load``; with obs off the run records none of them and
builds no annotation."""
import dataclasses
import glob
import json
import os
import sys
import threading
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, devtrace, drive, progtrace  # noqa: E402
from bench import run as bench_run  # noqa: E402
from repro import obs  # noqa: E402
from repro.core import clustering, heavy_hitter, pipeline, prefilter  # noqa: E402
from repro.data.streams import make_stream  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.serve.runtime import AsyncServer, ServerConfig  # noqa: E402

DIM = 32
pytestmark = pytest.mark.timeout(300)


@pytest.fixture(autouse=True)
def _fresh_obs():
    was = obs.enabled()
    obs.disable()
    yield
    obs.disable()
    if was:
        obs.enable()


def _cfg():
    return pipeline.PipelineConfig(
        pre=prefilter.PrefilterConfig(num_vectors=3, dim=DIM, alpha=0.0,
                                      basis="fixed"),
        clus=clustering.ClusterConfig(num_clusters=16, dim=DIM),
        hh=heavy_hitter.HHConfig(capacity=8, admit_prob=0.5),
        update_interval=32, store_depth=4)


def _serve_under_profiler(tmp_path):
    """Ingest, publish and answer queries under the profiler; one flush
    waits for the dispatch section while the test holds it. Returns the
    trace's events and the tracer's clock (us) at its start and end."""
    stream = make_stream("iot", dim=DIM)
    server = AsyncServer(_cfg(), ServerConfig(max_batch=8, max_wait_ms=0.0,
                                              topk=5, two_stage=True,
                                              nprobe=4),
                         key=jax.random.key(0), publish_every=2)
    for _ in range(2):             # compile outside the trace
        server.ingest(**_batch(stream))
        server.submit(stream.queries(1)["embedding"][0])
        server.flush()
    server.sync()
    tr = obs.tracer()
    jax.profiler.start_trace(str(tmp_path))
    t0 = tr.now_us() if tr is not None else None
    try:
        for _ in range(4):
            server.ingest(**_batch(stream))
            for q in stream.queries(4)["embedding"]:
                server.submit(q)
            server.flush()
        server.sync()
        for q in stream.queries(3)["embedding"]:
            server.submit(q)
        with server._dispatch.ingest():      # hold the section
            t = threading.Thread(target=server.flush)
            t.start()
            deadline = time.monotonic() + 60
            while (server._dispatch._queries_waiting == 0
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            time.sleep(0.1)
        t.join()
        server.drain()
        t1 = tr.now_us() if tr is not None else None
    finally:
        jax.profiler.stop_trace()
        server.close()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return progtrace.load(path[0]), (t0, t1)


def _batch(stream):
    b = stream.next_batch(16)
    return {"embeddings": b["embedding"], "doc_ids": b["doc_id"]}


def _spans(events, name):
    return [e for e in events if e.name == name]


def _within(inner, outer):
    return (inner.line == outer.line and outer.start_ns <= inner.start_ns
            and inner.end_ns <= outer.end_ns)


def test_program_spans_nest_on_the_profilers_clock(tmp_path):
    obs.enable(metrics=False, trace=True)
    events, (t0, t1) = _serve_under_profiler(tmp_path)
    tr = obs.tracer()

    flushes = _spans(events, "flush")
    assert flushes
    for f in flushes:
        a = dict(f.args)
        assert {"ticket_first", "ticket_last", "snapshot_version",
                "wait_ms_mean", "wait_ms_max", "batch"} <= set(a)
        d = [e for e in _spans(events, "flush.dispatch") if _within(e, f)]
        x = [e for e in _spans(events, "flush.fetch") if _within(e, f)]
        assert len(d) == 1 and len(x) == 1
        assert d[0].end_ns <= x[0].start_ns
    # the flush that found the section held waited inside its dispatch
    waits = [w for w in _spans(events, "dispatch.wait")
             if devtrace.arg(w, "cls") == "query"]
    assert waits
    assert any(_within(w, d) and _within(d, f) for w in waits
               for d in _spans(events, "flush.dispatch") for f in flushes)
    assert max(w.dur_ns for w in waits) >= 0.1e9

    enq = {devtrace.arg(e, "seq"): e for e in _spans(events,
                                                     "ingest.enqueue")}
    admits = _spans(events, "ingest.admit")
    assert len(admits) == 4 and set(enq) == {devtrace.arg(a, "seq")
                                             for a in admits}
    for a in admits:             # two threads, one clock
        e = enq[devtrace.arg(a, "seq")]
        assert a.line != e.line
        # the wait runs from inside the enqueue span to the admit's start
        assert 0 <= float(devtrace.arg(a, "wait_ms")) * 1e6 <= (
            a.start_ns - e.start_ns)

    pubs = _spans(events, "ingest.publish")
    assert pubs
    for p in pubs:
        assert devtrace.arg(p, "version") is not None
        assert int(devtrace.arg(p, "docs")) > 0
        for part in ("publish.prepare", "publish.signature",
                     "publish.copy"):
            assert [e for e in _spans(events, part) if _within(e, p)], part

    # every span the tracer recorded is in the profiler trace, by name
    # and args (a collection may fall between the clocks' readings and
    # the profiler's start or stop)
    chrome = sorted((e["name"], tuple(sorted(
        (k, str(v if not isinstance(v, bool) else int(v)))
        for k, v in e["args"].items())))
        for e in tr.events()
        if e["ph"] == "X" and e["name"] != "gc.collect"
        and t0 <= e["ts"] and e["ts"] + e["dur"] <= t1)
    prof = sorted((e.name, tuple(sorted(e.args))) for e in events
                  if e.name in progtrace.PROGRAM_SPANS
                  and e.name != "gc.collect")
    assert prof == chrome
    assert not _spans(events, "query")


def test_obs_off_records_no_span_and_builds_no_annotation(tmp_path,
                                                           monkeypatch):
    built = []

    class Counting(obs_trace.TraceAnnotation):
        def __init__(self, *a, **kw):
            built.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Counting)
    events, _ = _serve_under_profiler(tmp_path)
    assert built == []
    assert not [e for e in events if e.name in progtrace.PROGRAM_SPANS]


def test_full_collection_span_in_the_profiler_trace(tmp_path):
    import gc

    obs.enable(metrics=False, trace=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = _spans(progtrace.load(path[0]), "gc.collect")
    assert spans and all(devtrace.arg(e, "generation") == "2"
                         for e in spans)


@pytest.fixture
def reference_kernels(monkeypatch):
    """Serve through the jnp reference path: interpret-mode Pallas would
    take most of a minute."""
    real = drive.pipeline_config

    def config(config, rehearsal):
        cfg = real(config, rehearsal)
        return dataclasses.replace(cfg, clus=dataclasses.replace(
            cfg.clus, use_pallas=False))

    monkeypatch.setattr(drive, "pipeline_config", config)


def test_traced_rehearsal_reports_the_program_span_metrics(
        reference_kernels, capsys):
    """A traced CPU rehearsal of the query cell finds its own trace file
    and reads every program-span metric the cell lists."""
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spans = {m["name"] for m in spec["per_layer"]
             if m["source"] == "program_span" and m["name"] != "publish_ms"}
    cell = next(w["name"] for w in spec["workloads"]
                if spans <= {m["name"] for m in
                             cells.load_cell(w["name"]).per_layer})
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 11),
                         "--seconds", "3.0", "--trace", "1",
                         "--cpu-rehearsal"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert spans <= set(line["metrics"]), line["metrics"]
