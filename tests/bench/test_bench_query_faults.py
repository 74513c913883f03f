"""The comparison that decides ``correct`` in a query cell comes out false
when the timed path is broken underneath it, and for the reference in
bfloat16 put in the program's place (the control).

Each fault is armed only for the measured window, in the program's
``Engine.query_snapshot`` (the call every flush serves through); set-up
and the chip check are the real ones, except that the kernels run on the
program's jnp reference path to keep the test short."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import drive, run as bench_run  # noqa: E402
from repro.engine.engine import Engine  # noqa: E402

CELL = "rag150-int8-d64.query"
REAL = Engine.query_snapshot
REAL_INGEST = Engine.ingest
REAL_PUBLISH = Engine.publish


def altered(self, snap, q, *a, **kw):
    """A doc id altered where the answer is produced."""
    s, r, ids, cl = REAL(self, snap, q, *a, **kw)
    return s, r, ids.at[:, 0].add(1_000_000_000), cl


def half_left_out(self, snap, q, *a, **kw):
    """Only the first half of the batch is served; the rest get none."""
    n = q.shape[0]
    h = n // 2
    k = a[0] if a else kw["k"]
    s = jnp.full((n, k), -jnp.inf)
    r = ids = cl = jnp.full((n, k), -1, jnp.int32)
    if h:
        s2, r2, i2, c2 = REAL(self, snap, q[:h], *a, **kw)
        s, r, ids, cl = (s.at[:h].set(s2), r.at[:h].set(r2),
                         ids.at[:h].set(i2), cl.at[:h].set(c2))
    return s, r, ids, cl


def unchanged(self, snap, q, *a, **kw):
    """The serve step hands back what it returned last time."""
    last = getattr(self, "_fault_last", None)
    if last is not None and last[0].shape[0] == q.shape[0]:
        return last
    self._fault_last = REAL(self, snap, q, *a, **kw)
    return self._fault_last


def ingest_keeping_previous(self, x, ids):
    self._fault_previous = jax.tree.map(jnp.copy, self.state)
    return REAL_INGEST(self, x, ids)


def lagging_publish(self):
    """A publication one ingest call behind its watermark: it snapshots
    the state from before the last call."""
    now = self.state
    self.state = getattr(self, "_fault_previous", now)
    try:
        return REAL_PUBLISH(self)
    finally:
        self.state = now


@pytest.fixture
def quick(monkeypatch):
    real = drive.pipeline_config

    def config(config, rehearsal):
        cfg = real(config, rehearsal)
        return dataclasses.replace(cfg, clus=dataclasses.replace(
            cfg.clus, use_pallas=False))

    monkeypatch.setattr(drive, "pipeline_config", config)


def _run(capsys, *extra):
    rc = bench_run.main(["--workload", CELL, "--seed", "21", "--seconds",
                         "1.0", "--trace", "0", "--cpu-rehearsal", *extra])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [altered, half_left_out, unchanged],
                         ids=lambda f: f.__name__)
def test_broken_serve_path_is_not_correct(fault, quick, monkeypatch,
                                          capsys):
    real_window = drive.Window.run_window

    def armed(self):
        monkeypatch.setattr(Engine, "query_snapshot", fault)
        return real_window(self)

    monkeypatch.setattr(drive.Window, "run_window", armed)
    line = _run(capsys)
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


def test_publish_behind_its_watermark_is_not_correct(quick, monkeypatch,
                                                     capsys):
    real_window = drive.Window.run_window

    def armed(self):
        monkeypatch.setattr(Engine, "ingest", ingest_keeping_previous)
        monkeypatch.setattr(Engine, "publish", lagging_publish)
        return real_window(self)

    monkeypatch.setattr(drive.Window, "run_window", armed)
    line = _run(capsys)
    assert line["correct"] is False
    assert line["checks"]["docs_unpublished"]["value"] > 0


def test_bf16_control_is_not_correct(quick, capsys):
    line = _run(capsys, "--control", "bf16")
    assert line["correct"] is False
    failed = [k for k, c in line["checks"].items()
              if c["value"] > c["limit"]]
    assert failed, line["checks"]


def test_sound_run_is_correct(quick, capsys):
    line = _run(capsys)
    assert line["correct"] is True
    assert np.isfinite(line["checks"]["rank_gap"]["value"])
    assert line["checks"]["docs_unpublished"]["value"] == 0
