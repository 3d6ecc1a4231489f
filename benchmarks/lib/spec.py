"""Finds a cell's files by the names in ``BENCHMARK.json``.

A later PR adds a configuration, a model family, a traffic mix, a cell or
a per-layer metric by adding files and entries; nothing here, and nothing
else under ``lib/``, names one of them or knows a model's block.

**A family** is ``families/<name>.py``, chosen by a configuration's
``model.family`` and loaded by file. It imports nothing of the program,
and of ``lib/`` at most ``lowprec`` (the control's rounding). It provides:

- ``leaves(model) -> {name: (shape, kind)}``, ``make_leaf(kind, shape, key,
  scales) -> float32 array`` and ``take_groups(model) -> [[name, ...], ...]``:
  names are the paths of the program's parameter tree; kinds are the
  family's own, so one whose initialisation is not a normal draw (a state
  matrix, a step bias) says so itself; the groups are what ``system.py``
  makes and casts together — all in one where the model is small, a layer
  each where float32 copies of the whole would not fit.
- ``tokenize(texts, model) -> (ids, lens)`` and ``tokens_of(words, model)``:
  the only place specials are counted.
- ``encode(weights, model, texts, *, quant=None, block=...) -> [n, dim]``
  unit rows: the plain reference, float32 at ``highest``; with ``quant``
  (``fp8``, ``int8``) the control. ``weights`` is the handle of
  ``weights.py``; it asks for the leaves it needs when it needs them.
- ``flops(model, token_lengths)``: forward FLOPs of encoding texts of
  these token lengths, multiply-add = 2.
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


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


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
        return _load_module(path, f"bench_reader_{self.reader}").read(ctx, self.params)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    family: object  # the module families/<config.model.family>.py
    traffic_name: str
    traffic: dict
    end_to_end: tuple[dict, ...]  # the BENCHMARK.json entries this cell reports
    layer_metrics: tuple[LayerMetric, ...]


def _lists_cell(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_family(name: str, root: str = ROOT):
    """The module of family ``name``, from ``root``'s own families."""
    folder = os.path.join(root, os.path.relpath(BENCH_DIR, ROOT), "families")
    path = os.path.join(folder, name + ".py")
    if not os.path.isfile(path):
        have = sorted(f[:-3] for f in os.listdir(folder) if f.endswith(".py")) if os.path.isdir(folder) else []
        raise SystemExit(f"no model family {name!r}: {folder} has {have}")
    return _load_module(path, f"bench_family_{name}")


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
        family=load_family(config["model"]["family"], root),
        traffic_name=w["traffic"],
        traffic=traffic,
        end_to_end=tuple(e for e in bench["end_to_end"] if _lists_cell(e, name)),
        layer_metrics=tuple(metrics),
    )
