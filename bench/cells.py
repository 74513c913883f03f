"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own under ``bench/``:

    configs/<config>.json    deployment sizes, guarantees, source
    traffic/<traffic>.json   the mix's parameters, read by ``drive.py``
    metrics/<metric>.py      ``read(run) -> float | None``

``BENCHMARK.json`` names them; nothing here lists them, so a later change
adds a configuration, a mix or a metric by adding files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list   # BENCHMARK.json entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str, e2e_here: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_here


def load_cell(name: str, root: Path | None = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    root = Path(root) if root is not None else BENCH.parent
    spec = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[name]
    bench = root / "bench"
    config = json.loads((bench / "configs" / f"{w['config']}.json")
                        .read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    here = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, here)]
    return Cell(name, w["chips"], config, traffic, e2e, per_layer)


def load_reader(metric: str, root: Path | None = None):
    """The ``read`` function of ``bench/metrics/<metric>.py``, loaded by
    path (metric names may hold dots)."""
    root = Path(root) if root is not None else BENCH.parent
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: list, run, root: Path | None = None) -> dict:
    """``{name: {"value", "unit"}}`` for every entry whose reader found
    something to read; a reader that returns None is left out."""
    out = {}
    for m in entries:
        value = load_reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
