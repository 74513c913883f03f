"""A configuration, a traffic mix or a per-layer metric is added by adding
files and BENCHMARK.json entries, with no edit to any existing file."""
import dataclasses
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, drive, run as bench_run  # noqa: E402
from repro.engine.engine import Engine  # noqa: E402


def _copy_tree(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_existing_cells_load():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer + cell.end_to_end:
            assert callable(cells.load_reader(m["name"]))


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = _copy_tree(tmp_path)
    before = {p: open(p, "rb").read() for p in
              (root / "bench").rglob("*") if p.is_file()}
    spec = json.loads((root / "BENCHMARK.json").read_text())
    # a configuration: a new file, sizes as in the int8 one
    cfg = json.loads((root / "bench/configs/rag150-int8-d64.json")
                     .read_text())
    cfg["name"] = "new-config"
    (root / "bench/configs/new-config.json").write_text(json.dumps(cfg))
    # a traffic mix: a new data file at another rate
    mix = json.loads((root / "bench/traffic/query_open.json").read_text())
    mix["queries"]["rate_qps"] = 123
    (root / "bench/traffic/new_mix.json").write_text(json.dumps(mix))
    # a per-layer metric: a small reader of its own
    (root / "bench/metrics/new.metric.py").write_text(
        "def read(run):\n    return 2.5 * run.seconds\n")
    spec["configs"].append({"name": "new-config", "source": "x",
                            "file": "bench/configs/new-config.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "new-config.q", "config": "new-config",
                              "traffic": "new_mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "new.metric", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "frontend and flush",
                              "moves": "query_p50_ms",
                              "workloads": ["new-config.q"]})
    for m in spec["end_to_end"]:
        if m["name"].startswith("query_"):
            m["workloads"].append("new-config.q")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = cells.load_cell("new-config.q", root=root)
    assert cell.config["name"] == "new-config"
    assert cell.traffic["queries"]["rate_qps"] == 123
    assert [m["name"] for m in cell.per_layer] == ["new.metric"]
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms",
                                                    "setup_s"}
    run = drive.Run(cell=cell.name, seed=1, seconds=4.0, rehearsal=True,
                    config=cell.config, traffic=cell.traffic, cfg=None)
    assert cells.read_metrics(cell.per_layer, run, root=root) == {
        "new.metric": {"value": 10.0, "unit": "ms"}}
    # nothing that was there before changed
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


@pytest.mark.parametrize("control", [None, "bf16"])
def test_prototype_only_mix_is_data_only(tmp_path, monkeypatch, capsys,
                                         control):
    """A mix whose queries are answered from the prototype index alone
    (no ring rerank) runs from a new data file and entries, is checked
    against its own reference, and its bf16 control is not correct."""
    root = _copy_tree(tmp_path)
    mix = json.loads((root / "bench/traffic/query_open.json").read_text())
    mix["queries"]["two_stage"] = False
    (root / "bench/traffic/proto_open.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "rag150-int8-d64.proto",
                              "config": "rag150-int8-d64",
                              "traffic": "proto_open", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"].startswith("query_"):
            m["workloads"].append("rag150-int8-d64.proto")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(cells, "BENCH", root / "bench")
    real = drive.pipeline_config
    monkeypatch.setattr(drive, "pipeline_config", lambda c, r: dataclasses
                        .replace(real(c, r), clus=dataclasses.replace(
                            real(c, r).clus, use_pallas=False)))
    modes = set()
    served = Engine.query_snapshot

    def recording(self, snap, q, *a, **kw):
        modes.add(kw.get("two_stage"))
        return served(self, snap, q, *a, **kw)

    monkeypatch.setattr(Engine, "query_snapshot", recording)
    rc = bench_run.main(["--workload", "rag150-int8-d64.proto", "--seed",
                         "41", "--seconds", "1.0", "--trace", "0",
                         "--cpu-rehearsal"]
                        + (["--control", control] if control else []))
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    assert modes == {False}
    assert line["correct"] is (control is None), line["checks"]
    if control:
        assert any(c["value"] > c["limit"]
                   for c in line["checks"].values()), line["checks"]
