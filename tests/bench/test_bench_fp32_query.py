"""The comparison that decides ``correct`` in the float32 store's query
cell: a sound run passes it, and it comes out false for the reference in
bfloat16 put in the program's place (the control) and for ring rows
rounded to bfloat16 where the ring write stores them.

The faults that do not depend on the store's dtype are planted in the
int8 cell (``test_bench_query_faults.py``). As there, a fault is armed
only for the measured window, and the kernels run on the program's jnp
reference path to keep the test short."""
import dataclasses
import json
import os
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import drive, run as bench_run  # noqa: E402
from repro.engine.engine import Engine  # noqa: E402

CELL = "rag150-fp32-d16.query"
REAL_INGEST = Engine.ingest


def ring_rows_in_bf16(self, x, ids):
    """The ring write stores each new row rounded to bfloat16; slots the
    call did not write keep their float32 rows."""
    before = jnp.copy(self.state.store.ids)
    info = REAL_INGEST(self, x, ids)
    store = self.state.store
    written = (store.ids != before)[..., None]
    rounded = store.embs.astype(jnp.bfloat16).astype(store.embs.dtype)
    self.state = self.state._replace(store=store._replace(
        embs=jnp.where(written, rounded, store.embs)))
    return info


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


def _over(line, name):
    c = line["checks"][name]
    return c["value"] > c["limit"]


def test_sound_run_is_correct(quick, capsys):
    """The jnp path stores float32 rows exactly: no ring row differs from
    the reference's."""
    line = _run(capsys)
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["row_mismatch_share"]["value"] == 0
    assert line["checks"]["docs_unpublished"]["value"] == 0


def test_bf16_control_is_not_correct(quick, capsys):
    line = _run(capsys, "--control", "bf16")
    assert line["correct"] is False
    assert _over(line, "row_mismatch_share"), line["checks"]
    assert _over(line, "rank_gap"), line["checks"]


def test_ring_rows_rounded_to_bf16_are_not_correct(quick, monkeypatch,
                                                   capsys):
    real_window = drive.Window.run_window

    def armed(self):
        monkeypatch.setattr(Engine, "ingest", ring_rows_in_bf16)
        return real_window(self)

    monkeypatch.setattr(drive.Window, "run_window", armed)
    line = _run(capsys)
    assert line["correct"] is False
    assert (_over(line, "row_mismatch_share")
            or _over(line, "docs_misplaced")), line["checks"]
