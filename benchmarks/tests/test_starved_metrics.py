"""The seven metrics that read the host's timeline, on the CPU: each file
loads through ``lib/spec.py`` in the cells ``BENCHMARK.json`` lists for
it, names only stages and fields that the program's own sites produce
(a tiny write batch and query batch with tracing on), and reads a number
from those totals through the accepted ``stage_ratio`` reader. A program
from before the timeline reads nothing, or 0 where only the numerator is
new, and does not raise."""

import json
import os

import pytest

from benchmarks.lib import spec
from pathway_tpu import tracing
from pathway_tpu.models.encoder import EncoderConfig
from pathway_tpu.models.sentence_encoder import SentenceEncoder
from pathway_tpu.ops import knn
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

SERVE = ["doc-l6.serve", "doc-l12.serve"]
INGEST = [
    "doc-l12.backfill", "doc-l6.backfill", "doc-jamba2.backfill-b32",
    "doc-pangu-moe.backfill-b32", "doc-brumby.backfill-long-b8",
]
EVERY = ["doc-l6.serve", *INGEST[:4], "doc-l12.serve", INGEST[4]]
METRICS = {
    "host_starved_pct.serve": ("%", "device", "queries_per_s", SERVE),
    "host_starved_pct.ingest": ("%", "device", "docs_per_s", INGEST),
    "query_starved_ms": ("ms", "search + merge", "queries_per_s", SERVE),
    "query_enqueue_ms": ("ms", "search + merge", "queries_per_s", SERVE),
    "query_fetch_ms": ("ms", "search + merge", "queries_per_s", SERVE),
    "embed_starved_ms_per_batch": ("ms", "tokenize + pack", "visible_lag_p95_ms", EVERY),
    "index_starved_ms_per_batch": ("ms", "index write (host)", "visible_lag_p95_ms", EVERY),
}
DOCS = [f"document {i} speaks of subject {i % 7} at length" for i in range(24)]
CTX = {"trace": {"module_s": {}}}


@pytest.fixture(scope="module")
def totals():
    """What the program counts over one write batch (three removes, an
    add that replaces two more keys) and one query batch, traced."""
    enc = SentenceEncoder(config=EncoderConfig(num_layers=1), max_seq_len=32, max_batch=8)
    embedder = SentenceTransformerEmbedder(max_batch_size=8)
    embedder._encoder = enc
    index = knn.DeviceKnnIndex(dim=enc.dim, metric="cos", reserved_space=64)
    index.attach_encoder(enc)
    index.add_batch_device(list(range(24)), embedder.encode_device(DOCS), None)
    index.search_texts_batch(DOCS[:3], 3)
    prev = tracing.set_tracing_enabled(True)
    tracing.TRACING_METRICS.reset()
    try:
        keys = [3, 4, 5, 6, 7]
        for key in keys[:3]:
            index.remove(key)
        index.add_batch_device(keys, embedder.encode_device([DOCS[k] for k in keys]), None)
        index.search_texts_batch([DOCS[4], DOCS[9]], 3)
        return tracing.stage_totals()
    finally:
        tracing.set_tracing_enabled(prev)
        tracing.TRACING_METRICS.reset()
        tracing.TRACE_STORE.reset()


def _metric(cell: str, name: str) -> spec.LayerMetric:
    (metric,) = [m for m in spec.load_cell(cell).layer_metrics if m.name == name]
    return metric


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_file_loads_in_its_cells_and_reads_the_program_s_own_stages(name, totals, monkeypatch):
    unit, layer, moves, cells = METRICS[name]
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": "program_span",
        "layer": layer, "moves": moves, "workloads": cells,
    }
    reports = {e["name"]: e.get("workloads") for e in bench["end_to_end"]}
    for cell in cells:  # each cell reports the end-to-end metric this one moves
        assert reports[moves] is None or cell in reports[moves]
    metric = _metric(cells[0], name)
    assert (metric.reader, metric.unit, metric.layer) == ("stage_ratio", unit, layer)
    assert all(_metric(cell, name) == metric for cell in cells[1:])
    terms = metric.params["numerator"] + metric.params["denominator"]
    for stage, field in terms:  # no subtraction, no device seconds: the program's own bookkeeping
        assert field in totals[stage], (stage, field)
    monkeypatch.setattr(tracing, "stage_totals", lambda: totals)
    value = metric.read(CTX)
    assert value is not None and value >= 0
    if name.startswith("host_starved_pct"):
        assert 0 < value <= 100
    if name in ("query_enqueue_ms", "query_fetch_ms"):
        assert value > 0


def test_the_parts_are_not_more_than_the_whole(totals, monkeypatch):
    """Per batch the stages' starved milliseconds are part of the
    timeline's: what is left is the caller's, and the instants of
    ``query_wait`` that were not the wait."""
    monkeypatch.setattr(tracing, "stage_totals", lambda: totals)
    query = _metric("doc-l6.serve", "query_starved_ms").read(CTX) * totals["query_batch"]["calls"]
    embed = _metric("doc-l6.serve", "embed_starved_ms_per_batch").read(CTX) * totals["embed_batch"]["calls"]
    index = _metric("doc-l6.serve", "index_starved_ms_per_batch").read(CTX) * totals["index_add"]["calls"]
    whole, caller = totals["timeline"]["starved_seconds"], totals["caller"]["starved_seconds"]
    unlisted = sum(totals.get(s, {}).get("starved_seconds", 0.0) for s in ("query_wait", "query_topk_blocks"))
    assert query + embed + index == pytest.approx(1e3 * (whole - caller - unlisted), rel=1e-9)
    assert 0 <= unlisted < whole - caller
    assert _metric("doc-l6.serve", "host_starved_pct.serve").read(CTX) == pytest.approx(
        100 * whole / totals["timeline"]["seconds"]
    )


def test_a_program_from_before_the_timeline_reads_nothing_or_zero(totals, monkeypatch):
    """The parent's totals: the stages it had, their calls and seconds,
    none of the new fields, none of the new stages."""
    old = {
        stage: {k: v for k, v in t.items() if not k.endswith("_seconds")}
        for stage, t in totals.items()
        if stage not in ("caller", "timeline", "query_sync", "query_enqueue", "query_wait", "query_fetch", "embed_gather")
    }
    monkeypatch.setattr(tracing, "stage_totals", lambda: old)
    read = {name: _metric(cells[0], name).read(CTX) for name, (_, _, _, cells) in METRICS.items()}
    assert read == {
        "host_starved_pct.serve": None, "host_starved_pct.ingest": None,
        "query_starved_ms": 0.0, "query_enqueue_ms": None, "query_fetch_ms": None,
        "embed_starved_ms_per_batch": 0.0, "index_starved_ms_per_batch": 0.0,
    }
