"""Finds a cell's files by the names BENCHMARK.json gives.

  workloads[].config   -> the configs[] entry, whose "file" holds the sizes
  workloads[].traffic  -> benchmark/traffic/<traffic>.json
  end_to_end[].name    -> benchmark/end_to_end/<name>.json
  per_layer[].name     -> benchmark/layer_metrics/<name>.json

A metric's file names its reader (benchmark/readers/<reader>.py) and the
reader's arguments; its unit, layer, the end-to-end metric it moves and its
cells stand in BENCHMARK.json alone. A reader that finds nothing to read
returns None and the metric is left out of the line."""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass

METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def _json(path):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    benchmark: dict


def load(root, workload):
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         "BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    here = os.path.dirname(os.path.abspath(__file__))
    return Cell(name=workload, chips=entry["chips"],
                config=_json(os.path.join(root, conf["file"])),
                traffic=_json(os.path.join(here, "traffic",
                                           entry["traffic"] + ".json")),
                benchmark=bench)


def metrics_of(cell, section):
    """The section's metrics that this cell reports."""
    return [m for m in cell.benchmark[section]
            if cell.name in m.get("workloads", [cell.name])]


def read_metrics(cell, section, run):
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    for metric in metrics_of(cell, section):
        spec = _json(os.path.join(here, METRIC_DIRS[section],
                                  metric["name"] + ".json"))
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out
