"""Find a cell's configuration, mix, metrics and limits by name.

A cell is one entry of ``workloads`` in BENCHMARK.json. Everything else a
run needs lives in files named after the entries, so a later cell is added
with new files and entries and no edit to this code.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    known = [c["name"] for c in spec["workloads"]]
    raise KeyError(f"unknown workload {name!r}; known: {known}")


def load_config(spec: dict, name: str, root: Path = ROOT) -> dict:
    for cfg in spec["configs"]:
        if cfg["name"] == name:
            return json.loads((root / cfg["file"]).read_text())
    raise KeyError(f"unknown configuration {name!r}")


def load_mix(name: str) -> dict:
    return json.loads((BENCH / "mixes" / f"{name}.json").read_text())


def load_limits(cell: str) -> dict:
    """The limits of the numbers compared for ``correct``: the cell's own
    file if it has one, else the defaults."""
    path = BENCH / "limits" / f"{cell}.json"
    if not path.exists():
        path = BENCH / "limits" / "default.json"
    doc = json.loads(path.read_text())
    return {name: entry["limit"] for name, entry in doc["limits"].items()}


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that this cell reports."""
    return [m for m in spec[kind] if "workloads" not in m or cell in m["workloads"]]


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(run)``: a number, or None when the run
    holds nothing for it to read."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
