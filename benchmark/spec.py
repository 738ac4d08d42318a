"""Finds a cell's parts by name: ``BENCHMARK.json`` -> config, traffic,
generator, reference, per-layer metrics and their readers.

No table of known names lives in code. A later PR adds a cell with one entry
in ``workloads`` plus data files, and a per-layer metric with one JSON file
under ``layer_metrics/`` and one module under ``readers/``.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _read_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]       # benchmark/configs/<config>.json
    traffic: Dict[str, Any]      # benchmark/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]   # BENCHMARK.json entries this cell reports
    per_layer: List[Dict[str, Any]]    # layer_metrics/*.json that list this cell

    @property
    def mode(self) -> str:
        return self.traffic["mode"]


def _lists_cell(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = _read_json(ROOT, "BENCHMARK.json")
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if len(rows) != 1:
        raise SystemExit(f"no cell '{name}' in BENCHMARK.json; cells: "
                         f"{[w['name'] for w in bench['workloads']]}")
    row = rows[0]
    cfg_row = next(c for c in bench["configs"] if c["name"] == row["config"])
    config = _read_json(ROOT, cfg_row["file"])
    traffic = _read_json(HERE, "traffic", row["traffic"] + ".json")
    end_to_end = [m for m in bench["end_to_end"] if _lists_cell(m, name)]
    per_layer = []
    for fname in sorted(os.listdir(os.path.join(HERE, "layer_metrics"))):
        if fname.endswith(".json"):
            metric = _read_json(HERE, "layer_metrics", fname)
            if _lists_cell(metric, name):
                per_layer.append(metric)
    return Cell(name, int(row["chips"]), config, traffic, end_to_end,
                per_layer)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` (kind: generators, readers, reference,
    modes)."""
    return importlib.import_module(f"benchmark.{kind}.{name}")
