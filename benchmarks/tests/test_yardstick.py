"""The yardstick's own arithmetic, on the CPU: operations and bytes from
shapes, the reduction from a trace to numbers on a trace recorded on the
chip, the traffic generator, and the files ``BENCHMARK.json`` names."""

import json
import os
import re

import numpy as np
import pytest

from benchmarks.lib import spec, trace_reduce, workarith
from benchmarks.lib.traffic import doc_lengths, make_traffic

DATA = os.path.join(os.path.dirname(__file__), "data")
L6 = {"hidden_size": 384, "intermediate_size": 1536, "num_hidden_layers": 6}


def test_encoder_flops_by_hand():
    # one layer, one token among 128: qkv 2*384*1152, scores and values
    # 4*128*384, output 2*384*384, FFN 4*384*1536
    bert = spec.load_family("bert")
    per_layer = 884_736 + 196_608 + 294_912 + 2_359_296
    assert bert.flops(L6, [128]) == 128 * 6 * per_layer
    assert bert.flops(L6, [128, 128]) == 2 * 128 * 6 * per_layer
    l12 = dict(L6, num_hidden_layers=12)
    assert bert.flops(l12, [64]) == 2 * bert.flops(L6, [64])


def test_scan_work_by_hand():
    assert workarith.scan_bytes(3_213_835, 384, 4) == 3_213_835 * 1536
    assert workarith.scan_flops(16, 1000, 384) == 2 * 16 * 1000 * 384


def test_peaks_known_and_unknown():
    peaks = workarith.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        workarith.peaks_for("TPU v9 imaginary")


def _event(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": float(start), "dur_ns": float(dur)}


def test_reduce_synthetic():
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        _event(host, "t", trace_reduce.WINDOW_SPAN, 0, 1000),
        _event(dev, "XLA Ops", "%a.1 = f32[8]{0} fusion(f32[8]{0} %x)", 100, 200),  # 100..300
        _event(dev, "XLA Ops", "%a.2 = f32[8]{0} fusion(f32[8]{0} %y)", 250, 100),  # overlaps: 250..350
        _event(dev, "XLA Ops", "%b = f32[8]{0} custom-call(f32[8]{0} %x)", 900, 400),  # cut at 1000
        _event(dev, "XLA Modules", "jit_f(123)", 100, 250),
        _event(dev, "XLA Ops", "%late = f32[8]{0} fusion(f32[8]{0} %x)", 2000, 50),  # outside
        _event(host, "t", "bench.write.remove", 0, 100),
        _event(host, "t", "bench.query.search", 340, 600),
    ]
    r = trace_reduce.reduce(events, chips=1)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((250 + 100) * 1e-9)  # 100..350 and 900..1000
    assert r["op_s"]["%a = f32[8] fusion"] == pytest.approx(300e-9)
    assert r["module_s"] == {"jit_f": pytest.approx(250e-9)} and r["module_runs"] == {"jit_f": 1}
    gaps = dict(r["idle_gaps"])
    assert gaps["write.remove"] == pytest.approx(100e-9)  # 0..100
    assert gaps["query.search"] == pytest.approx(550e-9)  # 350..900
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_reduce_recorded_chip_trace():
    """0.3 s of doc-l6.serve on a TPU v5 lite (PR 25): 12 query dispatches
    of ~10 ms each and 3 write batches."""
    events = trace_reduce.load_recorded(os.path.join(DATA, "trace_doc-l6.serve.json"))
    r = trace_reduce.reduce(events, chips=1)
    assert r["window_s"] == pytest.approx(0.3)
    assert r["module_runs"]["jit_fused"] == 12 and r["module_runs"]["jit_fwd_group"] == 3
    assert 0.009 < r["module_s"]["jit_fused"] / 12 < 0.011
    # busy time, by a sweep over the edges that shares no code with _union
    ops = [e for e in events if e["line"] == trace_reduce.OPS_LINE]
    edges = sorted([(max(e["start_ns"], 0.0), 1) for e in ops] + [(min(e["start_ns"] + e["dur_ns"], 0.3e9), -1) for e in ops])
    depth, busy, last = 0, 0.0, 0.0
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0.40 < r["busy_s"] / r["window_s"] < 0.43
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["device_ops"][0][0] == "%convolution_select_fusion = f32[16,3276800] fusion"
    # the fused query program's share of the HBM roofline, as the reader computes it
    least = 12 * workarith.scan_bytes(3_213_835, 384, 4) / 819e9
    assert 55 < 100 * least / r["module_s"]["jit_fused"] < 65


def test_short_name_is_stable_and_idempotent():
    hlo = '%fwd_group.9 = bf16[8192,384]{1,0:T(8,128)(2,1)S(1)} custom-call(s32[32]{0:T(128)S(1)} %copy-done.21), custom_call_target="tpu_custom_call"'
    assert trace_reduce.short_name(hlo) == "%fwd_group = bf16[8192,384] custom-call"
    assert trace_reduce.short_name(trace_reduce.short_name(hlo)) == trace_reduce.short_name(hlo)
    assert trace_reduce.short_name("jit_fused(9494350518324623239)") == "jit_fused"


CONFIG = {
    "rows": 4096,
    "pool_docs": 1024,
    "documents": {"median_words": 120, "sigma": 0.5, "min_words": 16, "max_words": 254,
                  "length_strata": 32, "own_words": 8, "vocab_words": 5000},
    "queries": {"min_words": 3, "max_words": 12},
}
MIX = {"writer": {"batch": 32}, "queries": {"fresh_share": 0.5, "fresh_window_batches": 8}}


def test_traffic_same_seed_same_inputs_and_seeds_differ_in_order_only():
    a, b, c = make_traffic(CONFIG, MIX, 7), make_traffic(CONFIG, MIX, 7), make_traffic(CONFIG, MIX, 2**31 + 8)
    assert a.pool_texts == b.pool_texts and (a.key_order == b.key_order).all()
    assert a.pool_texts != c.pool_texts
    assert sorted(a.pool_words) == sorted(c.pool_words) == sorted(doc_lengths(CONFIG["documents"], 1024))
    for t in (a, c):  # every write batch has the same longest document
        per_batch = t.pool_words.reshape(-1, 32)
        assert len(set(per_batch.max(axis=1))) == 1
        assert [len(x.split()) for x in t.pool_texts] == t.pool_words.tolist()
    keys, docs = a.write_batch(3)
    assert len(keys) == 32 and len(set(keys)) == 32 and docs.tolist() == list(range(96, 128))
    rng = np.random.default_rng(0)
    text, doc = a.query(rng, handed=5)
    own = {a.vocab[w] for w in a.own_words[doc]}
    assert 3 <= len(text.split()) <= 12 and set(text.split()) <= own


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["reduced"] == [] and cell.config["rows"] * 384 * 4 > 0.25 * 16e9
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for metric in cell.layer_metrics:
            assert metric.moves in {m["name"] for m in cell.end_to_end}
            assert os.path.exists(os.path.join(spec.BENCH_DIR, "layer_metrics", "readers", metric.reader + ".py"))
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", m["name"])
