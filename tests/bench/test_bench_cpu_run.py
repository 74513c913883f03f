"""bench/run.py on the CPU: it refuses to measure without a TPU, refuses
to run without the program beside it, and a ``--cpu-rehearsal`` run of
each cell prints a last line of the result's shape.

The rehearsals here serve the kernels through the program's jnp
reference path (use_pallas=False) instead of interpret-mode Pallas, which
would take most of a minute per cell; the command line's rehearsal keeps
interpret mode."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import drive, run as bench_run  # noqa: E402

CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.fixture
def reference_kernels(monkeypatch):
    real = drive.pipeline_config

    def config(config, rehearsal):
        cfg = real(config, rehearsal)
        return dataclasses.replace(cfg, clus=dataclasses.replace(
            cfg.clus, use_pallas=False))

    monkeypatch.setattr(drive, "pipeline_config", config)


def _last_json(out: str):
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return json.loads(lines[-1]) if lines else None


def test_refuses_without_a_tpu(capsys):
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "{" not in out.out
    assert "TPU" in out.err


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_a_result_line(cell, reference_kernels, capsys):
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 7),
                         "--seconds", "1.0", "--trace", "0",
                         "--cpu-rehearsal"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = _last_json(out.out)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True, out.err[-2000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert {"count", "kind", "memory_peak_bytes"} <= set(line["device"])
    assert "setup_s" in line["metrics"]
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())
    # the numbers compared also end stderr
    assert out.err.strip().splitlines()[-1].startswith("correct: True")


def test_traced_rehearsal_reads_host_metrics(reference_kernels, capsys):
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "11",
                         "--seconds", "1.5", "--trace", "1",
                         "--cpu-rehearsal"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = _last_json(out.out)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    # host-clock metrics only: a CPU trace has no device plane to read
    assert {"flush_ms", "queries_per_flush"} <= set(line["metrics"])
    assert "serve_roofline_pct" not in line["metrics"]
    assert "idle_pct.query" not in line["metrics"]
