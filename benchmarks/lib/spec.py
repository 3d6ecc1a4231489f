"""Finds a cell's files by the names in ``BENCHMARK.json``.

A later PR adds a configuration, a traffic mix, a cell or a per-layer
metric by adding files and entries; nothing here names one of them.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class LayerMetric:
    name: str
    unit: str
    layer: str
    moves: str
    reader: str  # file under layer_metrics/readers, without .py
    params: dict

    def read(self, ctx):
        """The metric's value from the traced run's context, or ``None``
        where the reader finds nothing to read."""
        path = os.path.join(BENCH_DIR, "layer_metrics", "readers", self.reader + ".py")
        spec = importlib.util.spec_from_file_location(f"bench_reader_{self.reader}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read(ctx, self.params)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple[dict, ...]  # the BENCHMARK.json entries this cell reports
    layer_metrics: tuple[LayerMetric, ...]


def _lists_cell(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
    metrics = []
    for entry in bench["per_layer"]:
        if not _lists_cell(entry, name):
            continue
        meta = _load_json(os.path.join(BENCH_DIR, "layer_metrics", entry["name"] + ".json"))
        metrics.append(
            LayerMetric(
                name=entry["name"],
                unit=entry["unit"],
                layer=entry["layer"],
                moves=entry["moves"],
                reader=meta["reader"],
                params=meta.get("params", {}),
            )
        )
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=config,
        traffic_name=w["traffic"],
        traffic=traffic,
        end_to_end=tuple(e for e in bench["end_to_end"] if _lists_cell(e, name)),
        layer_metrics=tuple(metrics),
    )
