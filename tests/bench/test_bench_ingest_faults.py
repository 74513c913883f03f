"""The comparison that decides ``correct`` in the ingest cell comes out
false when the ingest path is broken underneath it, and for the
reference in bfloat16 put in the program's place (the control).

Each fault is armed only for the measured window, in the program's
``Engine.ingest`` (the call the server's ingest thread makes per batch);
the kernels run on the program's jnp reference path to keep it short."""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import drive, run as bench_run  # noqa: E402
from repro.engine.engine import Engine  # noqa: E402

CELL = "rag150-int8-d64.ingest"
REAL = Engine.ingest


def unchanged(self, x, ids):
    """The ingest step returns its state unchanged."""
    return {}


def half_left_out(self, x, ids):
    """Only the first half of each batch is ingested."""
    n = len(ids) // 2
    return REAL(self, np.asarray(x)[:n], np.asarray(ids)[:n])


def altered(self, x, ids):
    """Each stored row is altered where it is produced."""
    x = np.asarray(x, np.float32)
    noise = np.random.default_rng(0).normal(size=x.shape).astype(np.float32)
    y = x + 0.05 * noise / np.sqrt(x.shape[1])
    return REAL(self, y / np.linalg.norm(y, axis=1, keepdims=True), ids)


@pytest.fixture
def quick(monkeypatch):
    real = drive.pipeline_config

    def config(config, rehearsal):
        cfg = real(config, rehearsal)
        return dataclasses.replace(cfg, clus=dataclasses.replace(
            cfg.clus, use_pallas=False))

    monkeypatch.setattr(drive, "pipeline_config", config)


def _run(capsys, *extra):
    rc = bench_run.main(["--workload", CELL, "--seed", "31", "--seconds",
                         "1.0", "--trace", "0", "--cpu-rehearsal", *extra])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [unchanged, half_left_out, altered],
                         ids=lambda f: f.__name__)
def test_broken_ingest_path_is_not_correct(fault, quick, monkeypatch,
                                           capsys):
    real_window = drive.Window.run_window

    def armed(self):
        monkeypatch.setattr(Engine, "ingest", fault)
        return real_window(self)

    monkeypatch.setattr(drive.Window, "run_window", armed)
    line = _run(capsys)
    assert line["correct"] is False
    failed = [k for k, c in line["checks"].items()
              if c["value"] > c["limit"]]
    assert failed, line["checks"]


def test_bf16_control_is_not_correct(quick, capsys):
    line = _run(capsys, "--control", "bf16")
    assert line["correct"] is False
    assert line["checks"]["row_mismatch_share"]["value"] > \
        line["checks"]["row_mismatch_share"]["limit"]


def test_sound_run_is_correct(quick, capsys):
    line = _run(capsys)
    assert line["correct"] is True
