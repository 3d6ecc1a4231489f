"""A model family is files: ``spec`` finds it by the configuration's
``model.family``, the weights handle makes its leaves from the seed and
keeps none, and a second family with a configuration and a cell of its
own runs ``correct`` from a root that adds files and entries only.

The first four tests compile no encoder and take seconds; the last is a
rehearsal like those of ``test_correct.py``.
"""

import gc
import hashlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

from benchmarks.lib import runner, spec
from benchmarks.lib.weights import make_weights
from benchmarks.tests.test_correct import SECONDS, TINY

DATA = os.path.join(os.path.dirname(__file__), "data")
CELLS = {"doc-l6.serve": "all-MiniLM-L6-v2", "doc-l12.backfill": "all-MiniLM-L12-v2"}

# From the parent of PR 28 (commit 02fee6a), where ``workarith.encoder_flops``
# and ``weights.make_weights(model, scales, seed)`` still named the block.
LENGTHS = {"a": [128, 128], "b": [5, 9, 14, 256, 77], "c": list(range(18, 257, 7))}
PARENT_FLOPS = {
    "all-MiniLM-L6-v2": {"a": 5737807872.0, "b": 8326757376.0, "c": 109481702400.0},
    "all-MiniLM-L12-v2": {"a": 11475615744.0, "b": 16653514752.0, "c": 218963404800.0},
}
# SHA-256 over every leaf's name and the SHA-256 of its float32 bytes, names sorted
PARENT_WEIGHTS = {
    ("all-MiniLM-L6-v2", 5): "229b6037017b523173bf0509c9d918384a0471de92c274cb31cac7c8ab74c761",
    ("all-MiniLM-L6-v2", 2**31 + 77): "5435443c14dbcf5bf3f4d84e2ee6df87bcb8f2976648e17cf00bd84d2061dcf1",
    ("all-MiniLM-L12-v2", 5): "19027ce10b31fc4ac59f57044bc14272fa034cf2fdbde84b325cd0a9c6db87e1",
    ("all-MiniLM-L12-v2", 2**31 + 77): "cb1e04fefc296d684d458ccacd26418ec1f22ca455629c7d68a71f6e35edff32",
}


def _leaf_sha(array) -> str:
    return hashlib.sha256(np.asarray(array).tobytes()).hexdigest()


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_spec_finds_the_family_of_each_cell(cell_name):
    cell = spec.load_cell(cell_name)
    assert cell.config["model"]["family"] == "bert"
    assert cell.family.__file__ == os.path.join(spec.BENCH_DIR, "families", "bert.py")
    for provided in ("leaves", "make_leaf", "take_groups", "tokenize", "tokens_of", "encode", "flops"):
        assert callable(getattr(cell.family, provided))


def test_a_missing_family_names_the_folder_and_what_it_has():
    with pytest.raises(SystemExit) as e:
        spec.load_family("no_such_block")
    assert os.path.join("benchmarks", "families") in str(e.value) and "'bert'" in str(e.value)


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_family_flops_are_the_parents(cell_name):
    cell = spec.load_cell(cell_name)
    model = cell.config["model"]
    assert {k: cell.family.flops(model, v) for k, v in LENGTHS.items()} == PARENT_FLOPS[CELLS[cell_name]]
    words = np.array([1, 12, 254])
    assert cell.family.tokens_of(words, model).tolist() == [3, 14, 256]
    assert cell.family.tokens_of(7, model) == 9


@pytest.mark.parametrize("seed", [5, 2**31 + 77])
@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_handle_makes_the_parents_weights_and_keeps_none(cell_name, seed):
    cell = spec.load_cell(cell_name)
    handle = make_weights(cell.family, cell.config["model"], cell.config["weights"], seed)
    names = handle.names()
    assert names == sorted(cell.family.leaves(cell.config["model"])) and handle.groups() == [names]
    gc.collect()
    before = len(jax.live_arrays())
    made = handle.take(names)
    assert all(a.dtype == np.float32 and tuple(a.shape) == handle.shape(n) for n, a in made.items())
    per_leaf = {n: _leaf_sha(a) for n, a in made.items()}
    whole = hashlib.sha256("".join(n + per_leaf[n] for n in names).encode()).hexdigest()
    assert whole == PARENT_WEIGHTS[CELLS[cell_name], seed]
    # a leaf is a function of (seed, its name): alone, in any company, twice
    layer = [n for n in names if n.startswith("layer_1/")]
    again = handle.take(layer)
    assert sorted(again) == layer and all(_leaf_sha(again[n]) == per_leaf[n] for n in layer)
    del made, again
    gc.collect()
    assert len(jax.live_arrays()) == before


def _root_with_one_more_family(tmp_path) -> str:
    """BENCHMARK.json with one more configuration and cell, that
    configuration's file and its family's file: entries and files only."""
    root = str(tmp_path)
    bench_dir = os.path.join(root, os.path.relpath(spec.BENCH_DIR, spec.ROOT))
    os.makedirs(os.path.join(bench_dir, "families"))
    os.makedirs(os.path.join(bench_dir, "configs"))
    shutil.copy(os.path.join(DATA, "families", "bert_renamed.py"), os.path.join(bench_dir, "families"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = dict(spec.load_cell("doc-l6.serve").config, name="msmarco-doc-renamed")
    config["model"] = dict(config["model"], family="bert_renamed")
    config["weights"] = {"wide": 0.05, "narrow": 0.01, "dense": 0.02}
    file = "benchmarks/configs/msmarco-doc-renamed.json"
    with open(os.path.join(root, file), "w") as f:
        json.dump(config, f)
    bench["configs"].append({"name": config["name"], "source": "test", "file": file, "reduced": [], "why": "test"})
    bench["workloads"].append(
        {"name": "doc-renamed.serve", "config": config["name"], "traffic": "serve", "chips": 1, "why": "test"}
    )
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "doc-l6.serve" in metric.get("workloads", []):
            metric["workloads"].append("doc-renamed.serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _harness_files() -> dict:
    """path -> time of last change, for the code a new family may not touch."""
    folders = [os.path.join(spec.BENCH_DIR, *sub) for sub in (["lib"], ["layer_metrics", "readers"], ["families"])]
    paths = [os.path.join(spec.BENCH_DIR, "run.py")]
    paths += [os.path.join(d, f) for folder in folders for d, _, files in os.walk(folder) for f in files if f.endswith(".py")]
    return {p: os.stat(p).st_mtime_ns for p in paths}


def test_a_second_family_is_files_and_entries_only(tmp_path):
    before = _harness_files()
    root = _root_with_one_more_family(tmp_path)
    cell = spec.load_cell("doc-renamed.serve", root=root)
    assert cell.family.__file__.startswith(root) and len(cell.family.take_groups(cell.config["model"])) == 7
    assert {m.name for m in cell.layer_metrics} == {m.name for m in spec.load_cell("doc-l6.serve").layer_metrics}
    result = runner.run_cell(cell, 5, SECONDS, False, control="fp8", rehearsal=TINY)
    assert result["correct"], result["check"]
    assert result["control"]["fails"], result["control"]
    assert _harness_files() == before
