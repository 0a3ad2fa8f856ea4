"""Finds everything by name. `BENCHMARK.json` lists cells, configurations and
metrics; what belongs to one of them is a file of its own:

    benchmark/configs/<configuration>.json      sizes as run, source, reduced, assumed
    benchmark/workloads/<cell>.json             kind, engine settings, warm-up, traced window
    benchmark/traffic/<traffic>.json            parameters of the one generator (lib/traffic.py)
    benchmark/layer_metrics/<metric>.json       which generic reader, with its parameters

A later PR adds a cell, a configuration or a per-layer metric by adding such
files and entries; no file that is here needs an edit. `root` is the
directory that holds `BENCHMARK.json` (tests point it at a temporary copy).
"""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Registry:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = _read(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, "benchmark")

    def cell(self, name: str) -> dict:
        """The cell's entry merged over its file, with its configuration and
        traffic loaded: {"name", "config", "traffic", "chips", "kind", ...,
        "config_file": {...}, "traffic_file": {...}}."""
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if name not in entries:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                           f"(cells: {sorted(entries)})")
        entry = entries[name]
        cell = _read(os.path.join(self.dir, "workloads", f"{name}.json"))
        cell.update(entry)
        configs = {c["name"]: c for c in self.bench["configs"]}
        cell["config_file"] = _read(os.path.join(self.root, configs[entry["config"]]["file"]))
        cell["traffic_file"] = _read(
            os.path.join(self.dir, "traffic", f"{entry['traffic']}.json"))
        return cell

    def metrics(self, section: str, cell: str) -> list[dict]:
        """The metrics of `end_to_end` or `per_layer` that this cell reports."""
        return [m for m in self.bench[section]
                if "workloads" not in m or cell in m["workloads"]]

    def layer_metric(self, name: str) -> dict:
        return _read(os.path.join(self.dir, "layer_metrics", f"{name}.json"))
