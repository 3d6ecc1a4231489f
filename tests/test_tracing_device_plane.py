"""The device plane's spans: ``tracing.span`` on the index's write path,
the embedder's ingest path and the fused text-query path.

With tracing off a site is one check and leaves nothing. On, a write
batch, an embed batch and a query batch are journeys of their own when
no request is bound; a bare ``remove(key)`` adds to its stage's totals
and builds no ``Span``. A ``jax.profiler`` session turns tracing on by
itself and every span of a journey is then a ``pw.<stage>`` event in the
profile. The thread that dispatches keeps a timeline: each stage's self
time, starved, overlapped or waiting by whether the device had work to
run. The named scopes inside the device programs change no operation.
"""

from __future__ import annotations

import contextlib
import glob
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from pathway_tpu import tracing
from pathway_tpu.models.encoder import EncoderConfig
from pathway_tpu.models.sentence_encoder import SentenceEncoder
from pathway_tpu.ops import knn
from pathway_tpu.ops.index_metrics import INDEX_METRICS
from pathway_tpu.tracing import TRACE_STORE, TRACING_METRICS, set_tracing_enabled, span, stage_totals
from pathway_tpu.tracing import store as trace_store
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

WRITE_STAGES = {
    "index_remove", "index_publish", "embed_batch", "embed_tokenize", "embed_pack",
    "embed_dispatch", "embed_gather", "index_add", "index_flush", "index_scatter",
}
QUERY_STAGES = {
    "query_batch", "query_tokenize", "query_sync", "query_device",
    "query_enqueue", "query_wait", "query_fetch", "query_resolve",
}
#: what the timeline adds to the totals of a thread that dispatched
PSEUDO_STAGES = {"caller", "timeline"}
STATES = ("starved_seconds", "overlapped_seconds", "waiting_seconds")


@pytest.fixture(autouse=True)
def _tracing_sandbox():
    prev = set_tracing_enabled(False)
    TRACE_STORE.reset()
    TRACING_METRICS.reset()
    yield
    set_tracing_enabled(prev)
    TRACE_STORE.reset()
    TRACING_METRICS.reset()


@pytest.fixture(scope="module")
def enc():
    return SentenceEncoder(config=EncoderConfig(num_layers=1), max_seq_len=32, max_batch=8)


@pytest.fixture(scope="module")
def embedder(enc):
    """What ``VectorStoreServer`` hands the index, over the one-layer
    encoder: seconds, not minutes, on the CPU."""
    emb = SentenceTransformerEmbedder(max_batch_size=8)
    emb._encoder = enc
    return emb


DOCS = [f"document {i} speaks of subject {i % 7} at length" for i in range(24)]


def _standing_index(enc, embedder):
    idx = knn.DeviceKnnIndex(dim=enc.dim, metric="cos", reserved_space=64)
    idx.attach_encoder(enc)
    idx.add_batch_device(list(range(24)), embedder.encode_device(DOCS), None)
    idx.search_texts_batch(DOCS[:3], 3)
    return idx


@pytest.fixture()
def index(enc, embedder):
    """24 standing rows, put there with tracing off and the programs of
    the write and query paths warm."""
    return _standing_index(enc, embedder)


class FakeHandle:
    """What a dispatch site hands the timeline, with the test saying
    when the device is done."""

    def __init__(self, ready=False, deleted=False):
        self.ready, self.deleted, self.probes = ready, deleted, 0

    def is_deleted(self):
        return self.deleted

    def is_ready(self):
        self.probes += 1
        if self.deleted:  # of a real array this call ends the process
            raise AssertionError("a deleted array was asked whether it is ready")
        return self.ready


def _write_batch(idx, embedder, keys):
    for key in keys:
        idx.remove(key)
    texts = [DOCS[k] for k in keys]
    idx.add_batch_device(keys, embedder.encode_device(texts), None)
    return texts


def _spans_by_stage():
    out: dict[str, list[dict]] = {}
    for s in TRACE_STORE.recent_spans(limit=4096):
        out.setdefault(s["stage"], []).append(s)
    return out


# -- off -----------------------------------------------------------------


def test_off_a_write_batch_and_a_query_leave_nothing(index, embedder):
    from pathway_tpu.internals.http_monitoring import MonitoringHttpServer

    before = (TRACE_STORE.spans_total, MonitoringHttpServer._tracing_lines())
    _write_batch(index, embedder, [3, 4, 5])
    got = index.search_texts_batch([DOCS[4], DOCS[9]], 3)
    assert got[0][0][0] == 4 and got[1][0][0] == 9
    assert stage_totals() == {}
    assert not TRACING_METRICS.active() and not TRACE_STORE.active()
    assert (TRACE_STORE.spans_total, MonitoringHttpServer._tracing_lines()) == before == (0, [])


def test_off_a_site_is_one_check(monkeypatch):
    """No ``Span``, no id, no annotation object, no lock: the shared
    no-op comes back before anything is built."""

    def refuse(*args, **kwargs):
        raise AssertionError("built with tracing off")

    monkeypatch.setattr(trace_store, "Span", refuse)
    monkeypatch.setattr(trace_store, "gen_trace_id", refuse)
    monkeypatch.setattr(trace_store, "gen_span_id", refuse)
    monkeypatch.setattr(trace_store, "_ANNOTATION", None)
    monkeypatch.setattr(TRACING_METRICS, "_lock", None)
    monkeypatch.setattr(TRACING_METRICS, "timeline", refuse)
    monkeypatch.setattr(TRACE_STORE, "_lock", None)
    a, b = span("index_remove", rows=1), span("query_batch", new_trace=True, queries=3)
    assert a is b and not isinstance(a, span)
    with a as sp:
        assert sp is None
    handle = FakeHandle()
    assert tracing.dispatched(handle) is None and tracing.waited() is None
    assert handle.probes == 0


def test_import_and_the_check_stay_jax_free():
    code = (
        "import sys, pathway_tpu.tracing as t\n"
        "assert not t.tracing_enabled()\n"
        "with t.span('index_remove', rows=1) as sp: assert sp is None\n"
        "assert 'jax' not in sys.modules and t.stage_totals() == {}\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "PATHWAY_TRACING"}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=120)


# -- on: stages, work units, ids ------------------------------------------


def test_on_a_write_batch_gives_every_stage_with_its_units(index, enc, embedder):
    set_tracing_enabled(True)
    keys = [3, 4, 5, 6, 7]
    texts = _write_batch(index, embedder, keys)
    totals = stage_totals()
    assert WRITE_STAGES <= set(totals) and not QUERY_STAGES & set(totals)
    assert totals["index_remove"]["calls"] == totals["index_remove"]["rows"] == len(keys)
    assert totals["index_publish"]["calls"] == 1  # the add's, which pays what the removes owe
    assert totals["index_add"]["calls"] == 1 and totals["index_add"]["rows"] == len(keys)
    assert totals["index_scatter"]["rows"] == len(keys)
    assert totals["index_flush"]["rows"] == len(keys)  # the removes' tombstones
    assert totals["embed_batch"]["calls"] == 1 and totals["embed_batch"]["rows"] == len(keys)
    # real tokens, counted where they are made
    real_tokens = sum(len(enc.tokenizer.encode(t, enc.max_seq_len)) for t in texts)
    assert totals["embed_tokenize"]["tokens"] == real_tokens > 0
    assert totals["embed_tokenize"]["rows"] == totals["embed_pack"]["rows"] == len(keys)
    assert totals["embed_dispatch"]["calls"] == 1
    for stage in WRITE_STAGES:
        assert totals[stage]["seconds"] > 0
    assert "index_replace" not in totals  # the keys were removed first


def test_on_a_query_batch_counts_real_queries_not_padded(index):
    set_tracing_enabled(True)
    got = index.search_texts_batch([DOCS[4], DOCS[9], DOCS[11]], 3)
    assert [row[0][0] for row in got] == [4, 9, 11]
    totals = stage_totals()
    assert set(totals) - PSEUDO_STAGES == QUERY_STAGES
    for stage in QUERY_STAGES:
        assert totals[stage]["calls"] == 1 and totals[stage]["queries"] == 3  # the program ran 8
        assert totals[stage]["rows"] == totals[stage]["tokens"] == 0
    inner = sum(totals[s]["seconds"] for s in ("query_tokenize", "query_sync", "query_device", "query_resolve"))
    assert 0 < inner <= totals["query_batch"]["seconds"]
    # the "+1.6 ms" of a dispatch, in three parts under query_device
    parts = sum(totals[s]["seconds"] for s in ("query_enqueue", "query_wait", "query_fetch"))
    assert 0 < parts <= totals["query_device"]["seconds"]
    assert totals["query_wait"]["waiting_seconds"] > 0


@pytest.mark.parametrize(
    "boundary, children",
    [
        ("embed_batch", {"embed_tokenize", "embed_pack", "embed_dispatch", "embed_gather"}),
        ("index_add", {"index_flush", "index_scatter", "index_publish"}),
        ("query_batch", {"query_tokenize", "query_sync", "query_device", "query_resolve"}),
    ],
)
def test_with_no_request_the_batch_is_the_journey(index, embedder, boundary, children):
    set_tracing_enabled(True)
    _write_batch(index, embedder, [8, 9])
    index.search_texts_batch([DOCS[8]], 2)
    spans = _spans_by_stage()
    (root,) = spans[boundary]
    assert root["parent"] == ""
    for stage in children:
        for child in spans[stage]:
            assert (child["trace"], child["parent"]) == (root["trace"], root["span"])
    # three batches, three journeys
    assert len({spans[b][0]["trace"] for b in ("embed_batch", "index_add", "query_batch")}) == 3


def test_under_a_request_the_batches_join_its_trace(index, embedder):
    set_tracing_enabled(True)
    with span("request", new_trace=True) as request:
        _write_batch(index, embedder, [10])
        index.search_texts_batch([DOCS[10]], 1)
    spans = _spans_by_stage()
    for stage in ("index_remove", "embed_batch", "index_add", "query_batch"):
        (sp,) = spans[stage]
        assert (sp["trace"], sp["parent"]) == (request.trace_id, request.span_id)
    (publish,) = spans["index_publish"]  # one for the remove and the add, inside the add
    assert publish["parent"] == spans["index_add"][0]["span"]


def test_a_bare_remove_builds_no_span(index):
    set_tracing_enabled(True)
    for key in range(12):
        index.remove(key)
    index.remove("never added")  # a call all the same
    assert TRACE_STORE.spans_total == 0 and TRACE_STORE.traces_total == 0
    assert TRACE_STORE.recent_spans() == [] and TRACE_STORE.exemplar_traces() == []
    totals = stage_totals()
    assert set(totals) == {"index_remove"}  # the publish is owed: twelve removes, none told
    assert totals["index_remove"]["calls"] == totals["index_remove"]["rows"] == 13
    # a scrape is told once, by a publish that builds no span either
    assert INDEX_METRICS.snapshot()["indexes"][index.name]["docs"] == 12
    INDEX_METRICS.snapshot()
    assert TRACE_STORE.spans_total == 0 and TRACE_STORE.recent_spans() == []
    totals = stage_totals()
    assert set(totals) == {"index_remove", "index_publish"}
    assert totals["index_publish"]["calls"] == 1 and totals["index_publish"]["seconds"] > 0


def test_replaced_keys_nest_in_the_add_and_can_be_taken_out(index, embedder):
    """``index_replace`` holds the removes nested in an add, so that
    remove + add - replace is the wall of the top-level calls."""
    set_tracing_enabled(True)
    index.remove(0)
    index.add_batch_device([0, 1, 2], embedder.encode_device(DOCS[:3]), None)  # 1 and 2 are replaced
    totals = stage_totals()
    assert totals["index_remove"]["calls"] == 3
    assert totals["index_replace"]["calls"] == 1 and totals["index_replace"]["rows"] == 2
    spans = _spans_by_stage()
    (add,), (replace,) = spans["index_add"], spans["index_replace"]
    assert replace["parent"] == add["span"]
    assert [s["parent"] for s in spans["index_remove"]] == [replace["span"]] * 2  # the bare one built none
    nested = sum(s["dur_ms"] for s in spans["index_remove"]) / 1e3
    assert nested <= totals["index_replace"]["seconds"] <= totals["index_add"]["seconds"]
    assert len(index) == 24


def test_embed_batch_is_one_span_over_the_encoder_s_halves(enc, embedder):
    """40 texts at ``max_batch`` 8: ``SentenceEncoder.encode_device``
    halves the batch by calling itself. The boundary stands in the
    embedder's call, so the halves share one ``embed_batch``; the
    encoder called bare leaves totals and builds no ``Span``."""
    texts = [DOCS[i % 24] + f" copy {i}" for i in range(40)]
    off = np.asarray(embedder.encode_device(texts))
    set_tracing_enabled(True)
    on = np.asarray(embedder.encode_device(texts))
    np.testing.assert_array_equal(on, off)
    totals = stage_totals()
    assert totals["embed_batch"]["calls"] == 1 and totals["embed_batch"]["rows"] == 40
    assert totals["embed_tokenize"]["calls"] > 1 and totals["embed_tokenize"]["rows"] == 40
    assert totals["embed_tokenize"]["tokens"] == sum(len(enc.tokenizer.encode(t, 32)) for t in texts)
    assert totals["embed_dispatch"]["calls"] == 5
    spans = TRACE_STORE.spans_total
    enc.encode_device(texts)
    assert TRACE_STORE.spans_total == spans and stage_totals()["embed_batch"]["calls"] == 1
    assert stage_totals()["embed_dispatch"]["calls"] == 10


def test_embed_dispatch_tokens_are_what_the_layer_kernel_computes():
    """Through the whole-layer kernel (interpret mode) a group costs its
    sequences' live row tiles, not rows x bucket: the span's ``tokens``,
    the kernel gauges' ``computed_tokens`` and the helper the kernel's
    branch shares are one number."""
    from pathway_tpu.internals.profiler import ENCODER_KERNEL_STATS
    from pathway_tpu.models.batching import bucket
    from pathway_tpu.ops.fused_layer import ROW_TILE, computed_tokens

    cfg = EncoderConfig(
        vocab_size=30000, hidden_size=32, num_layers=1, num_heads=2,
        intermediate_size=64, max_position=256, layer_impl="interpret",
    )
    kernel_enc = SentenceEncoder(config=cfg, checkpoint_dir="/nonexistent", max_seq_len=256, max_batch=8)
    texts = ["short", "word " * 60, "word " * 140, "word " * 200, "word " * 127]
    lens = np.asarray([len(kernel_enc.tokenizer.encode(t, 256)) for t in texts])
    seq = bucket(int(lens.max()), kernel_enc._seq_buckets)
    assert lens.min() < ROW_TILE < lens.max() and seq > ROW_TILE
    ENCODER_KERNEL_STATS.reset()
    set_tracing_enabled(True)
    with span("embed_batch", new_trace=True, rows=len(texts)):  # as the embedder opens it
        kernel_enc.encode_device(texts)
    totals = stage_totals()
    snap = ENCODER_KERNEL_STATS.snapshot()
    ENCODER_KERNEL_STATS.reset()
    want = int(np.minimum(-(-lens // ROW_TILE) * ROW_TILE, seq).sum())
    assert totals["embed_dispatch"]["calls"] == snap["dispatches"] == 1
    assert totals["embed_dispatch"]["tokens"] == snap["computed_tokens"] == want == computed_tokens(lens, seq)
    assert totals["embed_tokenize"]["tokens"] == snap["real_tokens"] == int(lens.sum())
    assert int(lens.sum()) < want < 8 * seq  # the batch bucket's three padding rows cost nothing
    # the flax module computes every row of the program, and says so
    set_tracing_enabled(False)
    TRACING_METRICS.reset()
    plain = SentenceEncoder(config=EncoderConfig(num_layers=1), max_seq_len=256, max_batch=8)
    set_tracing_enabled(True)
    plain.encode_device(texts)
    assert stage_totals()["embed_dispatch"]["tokens"] == 8 * seq


def test_totals_sum_workers_and_take_units_from_attributes():
    TRACING_METRICS.observe("index_add", 0.25, "", worker=0, units={"rows": 7, "index": "docs"})
    TRACING_METRICS.observe("index_add", 0.5, "ab" * 16, worker=1, units={"rows": 5, "tokens": 11})
    TRACING_METRICS.observe("admission", 0.125, "")
    assert stage_totals() == {
        "index_add": {"calls": 2, "seconds": 0.75, "rows": 12, "queries": 0, "tokens": 11},
        "admission": {"calls": 1, "seconds": 0.125, "rows": 0, "queries": 0, "tokens": 0},
    }
    assert TRACING_METRICS.snapshot()["index_add[w1]"] == {"count": 1, "sum": 0.5, "rows": 5, "tokens": 11}


# -- the host's timeline ---------------------------------------------------


def _approx(x):
    return pytest.approx(x, abs=1e-9)


def test_the_headings_sum_to_self_time_and_self_time_is_seconds_less_the_children():
    set_tracing_enabled(True)
    handle = FakeHandle()
    with span("index_add", new_trace=True, rows=3):
        with span("index_replace", rows=2):
            for _ in range(2):
                with span("index_remove", rows=1):
                    time.sleep(0.002)
        with span("index_scatter", rows=3):
            tracing.dispatched(handle)
        time.sleep(0.002)
    with span("query_batch", new_trace=True, queries=1):
        with span("query_wait", queries=1):
            time.sleep(0.002)
            tracing.waited()
    totals = stage_totals()
    stages = set(totals) - {"timeline"}
    assert stages == {"index_add", "index_replace", "index_remove", "index_scatter", "query_batch", "query_wait", "caller"}
    for stage in stages:
        assert totals[stage]["self_seconds"] == _approx(sum(totals[stage][s] for s in STATES))
    whole = totals["timeline"]
    assert whole["seconds"] == _approx(sum(totals[stage]["self_seconds"] for stage in stages))
    for state in STATES:
        assert whole[state] == _approx(sum(totals[stage][state] for stage in stages))
    assert whole["calls"] == 0 and "self_seconds" not in whole
    # inclusive seconds are what they were; self time takes the children out
    add, replace, remove = (totals[s] for s in ("index_add", "index_replace", "index_remove"))
    assert add["seconds"] > replace["seconds"] > remove["seconds"] > 0.004
    assert remove["self_seconds"] == _approx(remove["seconds"])
    assert replace["self_seconds"] == _approx(replace["seconds"] - remove["seconds"])
    assert add["self_seconds"] == _approx(add["seconds"] - replace["seconds"] - totals["index_scatter"]["seconds"])
    assert add["self_seconds"] > 0.002 > replace["self_seconds"]
    assert totals["caller"]["seconds"] == totals["caller"]["self_seconds"] and totals["caller"]["calls"] == 0


def test_an_interval_is_starved_overlapped_or_waiting():
    set_tracing_enabled(True)
    handle = FakeHandle()
    with span("embed_batch", new_trace=True, rows=1):
        time.sleep(0.003)  # nothing in flight
        with span("embed_dispatch", rows=1):
            tracing.dispatched(handle)
        time.sleep(0.003)  # the device has the batch
    with span("query_batch", new_trace=True, queries=1):
        with span("query_wait", queries=1):
            time.sleep(0.003)
            tracing.waited()
    totals = stage_totals()
    embed, wait = totals["embed_batch"], totals["query_wait"]
    assert embed["starved_seconds"] > 0.003 and embed["overlapped_seconds"] > 0.003 and embed["waiting_seconds"] == 0
    assert embed["self_seconds"] < 0.009
    assert wait["waiting_seconds"] > 0.003 and wait["starved_seconds"] == 0 and wait["overlapped_seconds"] < 0.001
    # what led up to the dispatch was starved, what followed it overlapped
    assert totals["embed_dispatch"]["starved_seconds"] > 0 and totals["embed_dispatch"]["overlapped_seconds"] > 0
    assert totals["query_batch"]["starved_seconds"] == 0  # the handle never came ready
    assert handle.probes > 0


def test_a_handle_seen_ready_is_dropped_and_not_asked_again(index):
    set_tracing_enabled(True)
    handle = FakeHandle()
    tracing.dispatched(handle)
    with span("index_publish"):
        pass
    assert handle.probes == 2  # a boundary, a probe, while something is in flight
    handle.ready = True
    for key in range(100):  # 24 of them have a row
        index.remove(key)
    assert handle.probes == 3 and TRACING_METRICS.timeline().handle is None
    removes = stage_totals()["index_remove"]
    assert removes["calls"] == 100 and removes["starved_seconds"] == _approx(removes["seconds"])
    assert removes["overlapped_seconds"] == 0  # the one overlapped interval was the caller's
    again = FakeHandle()
    tracing.dispatched(again)
    index.remove(0)
    assert again.probes == 2 and handle.probes == 3


def test_a_deleted_array_counts_as_ready():
    set_tracing_enabled(True)
    gone = jax.numpy.zeros((3,))
    gone.delete()
    for handle in (gone, FakeHandle(deleted=True)):
        tracing.dispatched(handle)
        assert TRACING_METRICS.timeline().handle is handle
        with span("index_flush", rows=1):
            pass
        assert TRACING_METRICS.timeline().handle is None
    assert stage_totals()["index_flush"]["overlapped_seconds"] == 0


def test_two_threads_keep_two_timelines():
    """One holds the device busy, one has seen it empty, a third never
    dispatches: no thread's handle colours another's seconds, and only
    the threads that dispatch are on the timeline."""
    set_tracing_enabled(True)
    busy = FakeHandle()

    def work(stage, handle):
        if handle is not None:
            tracing.dispatched(handle)
        with span(stage, rows=1):
            time.sleep(0.004)

    threads = [
        threading.Thread(target=work, args=args)
        for args in (("index_scatter", busy), ("index_flush", FakeHandle(ready=True)), ("admission", None))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    totals = stage_totals()
    assert totals["index_scatter"]["overlapped_seconds"] > 0.004 and totals["index_scatter"]["starved_seconds"] == 0
    assert totals["index_flush"]["starved_seconds"] > 0.004 and totals["index_flush"]["overlapped_seconds"] == 0
    assert totals["admission"]["calls"] == 1 and "self_seconds" not in totals["admission"]
    assert totals["timeline"]["seconds"] == _approx(
        sum(totals[s]["self_seconds"] for s in ("index_scatter", "index_flush", "caller"))
    )
    assert 0.008 < totals["timeline"]["seconds"] < 0.1
    # a thread that ended keeps its seconds when the next one registers
    last = threading.Thread(target=work, args=("index_scatter", FakeHandle()))
    last.start()
    last.join(timeout=30)
    assert not last.is_alive()
    after = stage_totals()
    assert after["index_scatter"]["overlapped_seconds"] > 0.008
    assert after["index_flush"]["starved_seconds"] == totals["index_flush"]["starved_seconds"]
    # and its bare spans, which it counted without the registry's lock
    assert [after[s]["calls"] for s in ("index_scatter", "index_flush", "admission")] == [2, 1, 1]
    assert after["index_scatter"]["rows"] == 2 and after["index_scatter"]["seconds"] > 0.008
    assert {row["stage"]: row["count"] for row in TRACING_METRICS.series()} == {
        "index_scatter": 2, "index_flush": 1, "admission": 1,
    }


def test_bare_spans_of_many_threads_lose_no_count_while_the_totals_are_read():
    """Each thread counts its bare spans in histograms of its own, with
    no lock; a reader sums them while they grow, and nothing is lost."""
    set_tracing_enabled(True)
    workers, each = 4 * (os.cpu_count() or 2), 500
    go = threading.Event()

    def work():
        go.wait(30)
        for _ in range(each):
            with span("index_remove", rows=1):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        go.set()
        seen, deadline = 0, time.monotonic() + 120
        while any(t.is_alive() for t in threads) and time.monotonic() < deadline:
            now = stage_totals().get("index_remove", {}).get("calls", 0)
            assert now >= seen  # a sum that only grows
            seen = now
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    totals = stage_totals()["index_remove"]
    assert totals["calls"] == totals["rows"] == workers * each
    assert "self_seconds" not in totals  # no thread dispatched


def test_a_reset_or_the_switch_charges_the_gap_to_no_stage():
    set_tracing_enabled(True)
    tracing.dispatched(FakeHandle())
    with span("index_flush", rows=1):
        pass
    set_tracing_enabled(False)
    time.sleep(0.01)  # off: nobody's
    set_tracing_enabled(True)
    with span("index_flush", rows=1):
        pass
    assert stage_totals()["timeline"]["seconds"] < 0.005
    TRACING_METRICS.reset()
    with span("index_flush", rows=1):
        pass
    assert set(stage_totals()) == {"index_flush"}  # the thread has not dispatched since


def test_every_dispatch_site_hands_over_a_handle_and_tracing_changes_no_bit(enc, embedder, monkeypatch):
    from pathway_tpu.internals.http_monitoring import MonitoringHttpServer
    from pathway_tpu.models import sentence_encoder

    keys, queries = [3, 4, 5, 6, 7], [DOCS[4], DOCS[9], DOCS[11]]
    plain = _standing_index(enc, embedder)
    _write_batch(plain, embedder, keys)
    want = plain.search_texts_batch(queries, 3)
    traced = _standing_index(enc, embedder)

    handed = []

    def record(handle):
        handed.append((TRACING_METRICS.timeline().stack[-1], handle))
        tracing.dispatched(handle)

    monkeypatch.setattr(knn, "_dispatched", record)
    monkeypatch.setattr(sentence_encoder, "_dispatched", record)
    set_tracing_enabled(True)
    _write_batch(traced, embedder, keys)
    got = traced.search_texts_batch(queries, 3)
    set_tracing_enabled(False)
    assert got == want  # keys and scores, to the bit
    np.testing.assert_array_equal(np.asarray(traced._dev_matrix), np.asarray(plain._dev_matrix))
    np.testing.assert_array_equal(np.asarray(traced._dev_valid), np.asarray(plain._dev_valid))
    assert [stage for stage, _ in handed] == [
        "embed_dispatch", "embed_gather", "index_flush", "index_scatter", "query_enqueue",
    ]
    for stage, handle in handed:
        assert isinstance(handle, jax.Array), stage
    by_stage = dict(handed)
    assert by_stage["index_scatter"].shape == (traced.capacity,)  # the validity column, not the slab
    assert by_stage["query_enqueue"].shape == (8, 2 * 8)  # the packed answer
    totals = stage_totals()
    assert WRITE_STAGES | QUERY_STAGES | PSEUDO_STAGES == set(totals)
    assert totals["embed_gather"]["rows"] == totals["embed_batch"]["rows"] == len(keys)
    for stage in ("query_sync", "query_enqueue", "query_wait", "query_fetch"):
        assert totals[stage]["calls"] == 1 and totals[stage]["queries"] == 3
    for stage in set(totals) - {"timeline"}:
        assert totals[stage]["self_seconds"] == _approx(sum(totals[stage][s] for s in STATES))
    assert totals["timeline"]["starved_seconds"] > 0 and totals["query_wait"]["waiting_seconds"] > 0
    lines = MonitoringHttpServer._tracing_lines()
    assert "# TYPE pathway_stage_device_seconds counter" in lines
    for state in ("starved", "overlapped", "waiting"):
        assert any(
            line.startswith(f'pathway_stage_device_seconds{{stage="query_wait",state="{state}",worker="0"}} ')
            for line in lines
        )
    assert not any('stage="timeline"' in line for line in lines)


# -- the profiler's switch ------------------------------------------------


def test_a_profiler_session_turns_tracing_on_and_names_the_spans(index, embedder, tmp_path, monkeypatch):
    """Every stage of a journey is a ``pw.<stage>`` event, as many as
    its calls; a bare top-level ``remove`` has its totals and no event."""
    monkeypatch.delenv("PATHWAY_TRACING", raising=False)
    assert not tracing.tracing_enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.tracing_enabled()
        _write_batch(index, embedder, [3, 4])  # two bare removes
        index.add_batch_device([6], embedder.encode_device([DOCS[6]]), None)  # and one nested in the add's journey
        index.search_texts_batch([DOCS[3]], 2)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.tracing_enabled()
    index.remove(5)  # off again: nothing more
    totals = stage_totals()
    assert WRITE_STAGES | QUERY_STAGES <= set(totals) and totals["index_remove"]["calls"] == 3
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    seen: dict[str, int] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("pw."):
                    assert plane.name.startswith("/host:")
                    seen[event.name[3:]] = seen.get(event.name[3:], 0) + 1
    calls = {stage: t["calls"] for stage, t in totals.items() if stage not in PSEUDO_STAGES}
    assert seen == {**calls, "index_remove": 1}


# -- the scopes are names only --------------------------------------------


def _lowered_text(build, args, **static):
    return build().lower(*args, **static).as_text()


def _fused(enc):
    shapes = (
        jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), enc.params),
        jax.ShapeDtypeStruct((8, 16), np.int32),
        jax.ShapeDtypeStruct((8,), np.int32),
        jax.ShapeDtypeStruct((64, enc.dim), np.float32),
        jax.ShapeDtypeStruct((64,), np.bool_),
    )
    return (lambda: knn._fused_query_fn(enc.module, enc.cfg)), shapes, {"k": 8, "l2": False}


def _slab(*extra):
    slab = (
        jax.ShapeDtypeStruct((64, 384), np.float32),
        jax.ShapeDtypeStruct((64,), np.bool_),
        jax.ShapeDtypeStruct((64,), np.float32),
    )
    return slab + extra


def _scatter_dev(enc):
    args = _slab(jax.ShapeDtypeStruct((8,), np.int32), jax.ShapeDtypeStruct((8, 384), np.float32))
    return knn._scatter_dev_fn, args, {"l2": False, "normalize": True}


def _scatter_tomb(enc):
    return knn._scatter_tomb_fn, _slab(jax.ShapeDtypeStruct((8,), np.int32))[1:], {}


def _hybrid_forward(enc):
    """The hybrid state-space / attention encoder's forward, at its test
    preset (the scan in the Pallas interpreter: this lowers for the CPU)."""
    from pathway_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMEncoder

    module = HybridSSMEncoder(HybridSSMConfig.tiny_for_tests(scan_impl="interpret"))
    shapes = (
        jax.eval_shape(module.init),
        jax.ShapeDtypeStruct((8, 16), np.int32),
        jax.ShapeDtypeStruct((8, 16), np.bool_),
    )
    return (lambda: jax.jit(module.apply)), shapes, {}


def _latent_moe_forward(enc):
    """The latent-attention / sparse-expert encoder's forward, at its test
    preset (the grouped product in the Pallas interpreter: this lowers
    for the CPU)."""
    from pathway_tpu.models.latent_moe import LatentMoEConfig, LatentMoEEncoder

    module = LatentMoEEncoder(LatentMoEConfig.tiny_for_tests(expert_impl="interpret"))
    shapes = (
        jax.eval_shape(module.init),
        jax.ShapeDtypeStruct((8, 16), np.int32),
        jax.ShapeDtypeStruct((8, 16), np.bool_),
    )
    return (lambda: jax.jit(module.apply)), shapes, {}


def _power_retention_forward(enc):
    """The power-retention encoder's packed forward, at its test preset
    (the retention in the Pallas interpreter: this lowers for the CPU):
    a stream of four chunks, so the loops over the chunks are there."""
    from pathway_tpu.models.power_retention import PowerRetentionConfig, PowerRetentionEncoder

    module = PowerRetentionEncoder(PowerRetentionConfig.tiny_for_tests(retention_impl="interpret"))
    shapes = (
        jax.eval_shape(module.init),
        jax.ShapeDtypeStruct((256,), np.int32),
        jax.ShapeDtypeStruct((32,), np.int32),
        jax.ShapeDtypeStruct((32,), np.int32),
    )
    return (lambda: jax.jit(module.apply_stream)), shapes, {}


RETENTION_SCOPES = tuple("pw.encode." + s for s in ("ret_qkv", "retention", "ret_out", "mlp", "pool"))
LATENT_MOE_SCOPES = tuple(
    "pw.encode." + s for s in ("mla_q", "mla_kv", "attn", "moe_route", "moe_experts", "moe_shared", "mlp", "pool")
)
HYBRID_SCOPES = tuple("pw.encode." + s for s in ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "attn", "mlp", "pool"))


@pytest.mark.parametrize(
    "program, scopes",
    [
        (_hybrid_forward, HYBRID_SCOPES),
        (_latent_moe_forward, LATENT_MOE_SCOPES),
        (_power_retention_forward, RETENTION_SCOPES),
        (_fused, ("pw.query.encode", "pw.query.scan", "pw.query.topk")),
        (_scatter_dev, ("pw.index.scatter",)),
        (_scatter_tomb, ("pw.index.tomb",)),
    ],
)
def test_named_scopes_change_no_operation(enc, monkeypatch, program, scopes):
    build, args, static = program(enc)
    monkeypatch.setattr(knn, "_UPDATE_JIT", {})
    with_scopes = build().lower(*args, **static)
    for scope in scopes:  # the names are there, in the locations
        assert scope in with_scopes.as_text(debug_info=True)
    monkeypatch.setattr(knn, "_UPDATE_JIT", {})
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    without = build().lower(*args, **static)
    assert "pw." not in without.as_text(debug_info=True)
    assert with_scopes.as_text() == without.as_text()
