"""ingest_step_ms: device time per ingest call: the summed device
duration of the ingest program's HLO module over the calls that ran in
the profiler window, over their number. The module's name pattern is in
``ingest_step_ms.json`` beside this file."""
import json
from pathlib import Path

PATTERN = json.loads(
    (Path(__file__).with_suffix(".json")).read_text())["module"]


def read(run):
    view = run.trace
    if view is None or not view.devices:
        return None
    mods = view.modules(PATTERN)
    return 1e-6 * sum(m.dur_ns for m in mods) / len(mods) if mods else None
