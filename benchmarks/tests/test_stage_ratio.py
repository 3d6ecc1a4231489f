"""The ``stage_ratio`` reader, on the CPU: each kind of term against a
hand-made table of stage totals and the trace recorded on the chip, the
zero denominator, a program without the stages, and the five metrics
that use it as ``BENCHMARK.json`` names them."""

import json
import os

import pytest

import pathway_tpu.tracing
from benchmarks.lib import spec, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")

TOTALS = {
    "index_remove": {"calls": 100, "seconds": 0.13, "rows": 100, "queries": 0, "tokens": 0},
    "index_replace": {"calls": 1, "seconds": 0.005, "rows": 4, "queries": 0, "tokens": 0},
    "index_add": {"calls": 3, "seconds": 0.025, "rows": 96, "queries": 0, "tokens": 0},
    "index_publish": {"calls": 103, "seconds": 0.12, "rows": 0, "queries": 0, "tokens": 0},
    "embed_batch": {"calls": 3, "seconds": 0.0024, "rows": 96, "queries": 0, "tokens": 12000},
    "query_batch": {"calls": 12, "seconds": 0.168, "rows": 0, "queries": 190, "tokens": 0},
    "query_device": {"calls": 12, "seconds": 0.12, "rows": 0, "queries": 190, "tokens": 0},
}


@pytest.fixture()
def ctx(monkeypatch):
    """The recorded 0.3 s of doc-l6.serve (3 write batches, 12 query
    dispatches) and the totals a program would have counted over it."""
    monkeypatch.setattr(pathway_tpu.tracing, "stage_totals", lambda: TOTALS, raising=False)
    events = trace_reduce.load_recorded(os.path.join(DATA, "trace_doc-l6.serve.json"))
    return {"trace": trace_reduce.reduce(events, chips=1)}


def _metric(params):
    return spec.LayerMetric(name="m", unit="u", layer="l", moves="e", reader="stage_ratio", params=params)


def test_stage_terms_subtracted_terms_and_scale(ctx):
    plain = _metric({"numerator": [["index_remove", "seconds"]], "denominator": [["index_remove", "rows"]], "scale": 1e6})
    assert plain.read(ctx) == pytest.approx(1300.0)
    minus = _metric(
        {"numerator": [["query_batch", "seconds"], ["-", "query_device", "seconds"]],
         "denominator": [["query_batch", "calls"]], "scale": 1e3}
    )
    assert minus.read(ctx) == pytest.approx(4.0)
    unscaled = _metric({"numerator": [["embed_batch", "tokens"]], "denominator": [["embed_batch", "rows"]]})
    assert unscaled.read(ctx) == pytest.approx(125.0)


def test_module_seconds_come_from_the_trace(ctx):
    module_s = ctx["trace"]["module_s"]
    scatters = module_s["jit_scatter"] + module_s["jit_scatter_dev"] + module_s["jit_scatter_tomb"]
    assert 0.0002 < scatters < 0.0004 and "jit_fused" in module_s
    m = _metric(
        {"numerator": [["module_s", "^jit_scatter(_dev|_tomb)?$"]], "denominator": [["index_add", "calls"]], "scale": 1e3}
    )
    assert m.read(ctx) == pytest.approx(1e3 * scatters / 3)
    both = _metric(
        {"numerator": [["module_s", "^jit_fused$"], ["-", "module_s", "^jit_scatter_dev$"]],
         "denominator": [["module_s", "^jit_fused$"]]}
    )
    assert both.read(ctx) == pytest.approx(1 - module_s["jit_scatter_dev"] / module_s["jit_fused"])


@pytest.mark.parametrize(
    "denominator",
    [
        [["index_flush", "calls"]],  # a stage that never ran
        [["index_remove", "tokens"]],  # a unit the stage does not count
        [["index_add", "seconds"], ["-", "index_remove", "seconds"]],  # below zero
        [["module_s", "^jit_no_such_program$"]],
    ],
)
def test_no_denominator_no_value(ctx, denominator):
    assert _metric({"numerator": [["index_remove", "seconds"]], "denominator": denominator}).read(ctx) is None


def test_a_program_without_the_stages_reads_nothing(ctx, monkeypatch):
    """The parent of the PR that brought the stages: no ``stage_totals``."""
    monkeypatch.delattr(pathway_tpu.tracing, "stage_totals")
    m = _metric({"numerator": [["module_s", "^jit_fused$"]], "denominator": [["module_s", "^jit_fused$"]]})
    assert m.read(ctx) is None


def test_the_five_metrics_as_the_benchmark_names_them(ctx):
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m["workloads"] for m in json.load(f)["per_layer"]}
    by_cell = {
        name: {m.name: m for m in spec.load_cell(name).layer_metrics if m.reader == "stage_ratio"}
        for name in ("doc-l6.serve", "doc-l12.backfill")
    }
    serve, backfill = by_cell["doc-l6.serve"], by_cell["doc-l12.backfill"]
    assert set(serve) == {"index_remove_us_per_row", "index_publish_pct", "query_host_ms", "scatter_device_ms"}
    assert set(backfill) == {"index_remove_us_per_row", "index_publish_pct", "embed_host_us_per_doc", "scatter_device_ms"}
    for cell, metrics in by_cell.items():
        for name in metrics:
            assert cell in listed[name]
    assert serve["index_remove_us_per_row"].read(ctx) == pytest.approx(1300.0)
    # the removes nested in an add are in index_remove's and in index_add's seconds: taken out once
    assert serve["index_publish_pct"].read(ctx) == pytest.approx(100 * 0.12 / (0.13 + 0.025 - 0.005))
    assert backfill["embed_host_us_per_doc"].read(ctx) == pytest.approx(25.0)
    assert serve["query_host_ms"].read(ctx) == pytest.approx(4.0)
    assert 0.05 < serve["scatter_device_ms"].read(ctx) < 0.15
    assert (serve["scatter_device_ms"].unit, serve["index_remove_us_per_row"].unit) == ("ms", "us")
