"""One run of one cell: set-up, the measured window, the metrics, and
the comparison that decides ``correct``."""

from __future__ import annotations

import copy
import gc
import os
import shutil
import sys
import threading
import time

import numpy as np

from . import check, spec, trace_reduce, workarith
from .traffic import make_traffic
from .window import Spans, Window


def _log(t0: float, msg: str) -> None:
    print(f"[bench +{time.perf_counter() - t0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def _query_buckets(clients: int, cap: int) -> list[int]:
    """The query-batch sizes whose programs the window can reach: the
    index pads a dispatch of n queries to 8, 16, 32, ..."""
    most, sizes, b = min(clients, cap), [], 8
    while True:
        sizes.append(min(b, most))
        if b >= most:
            return sizes
        b *= 2


def _p95(values, weights=None) -> float:
    values = np.asarray(values, np.float64)
    if weights is not None:
        values = np.repeat(values, weights)
    return float(np.percentile(values, 95))


def _end_to_end(window, answered, close: float, window_s: float) -> dict:
    """Rates over the whole window; tails over every request handed over
    in it, those drained after the close too."""
    out = {}
    if window.writes:
        out["docs_per_s"] = sum(len(b.keys) for b in window.writes if b.visible <= close) / window_s
        out["visible_lag_p95_ms"] = 1e3 * _p95(
            [b.visible - b.handed for b in window.writes], [len(b.keys) for b in window.writes]
        )
    if window.queries:
        out["queries_per_s"] = len(answered) / window_s
        out["query_p95_ms"] = 1e3 * _p95([q.done - q.sent for q in window.queries])
    return out


class Tracer:
    """Traces a few seconds of the window in a thread of its own, so the
    loop never waits for the profiler to start or to write."""

    def __init__(self, out_dir: str, start_s: float, seconds: float):
        self.out_dir, self.start_s, self.seconds = out_dir, start_s, seconds
        self.error: BaseException | None = None
        shutil.rmtree(out_dir, ignore_errors=True)
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.start_s)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.out_dir, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                    time.sleep(self.seconds)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # reported by the run, which then fails
            self.error = e


def run_cell(
    cell: spec.Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    control: str | None = None,
    rehearsal: dict | None = None,
    t_process: float | None = None,
    dump_trace: str | None = None,
) -> dict:
    """-> the result line as a dict. ``rehearsal`` (tests only) overrides
    keys of the configuration with tiny sizes and runs wherever JAX runs;
    its result carries the comparison and no metric."""
    t0 = time.perf_counter() if t_process is None else t_process
    config, mix = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    if rehearsal is not None:
        for path, value in rehearsal.items():
            node = config
            if path.startswith("mix."):
                node, path = mix, path[len("mix.") :]
            *parents, leaf = path.split(".")
            for p in parents:
                node = node[p]
            node[leaf] = value
    import jax

    from . import system as system_mod
    from .weights import make_weights

    if rehearsal is None:
        device = system_mod.device_facts(cell.chips)
        peaks = workarith.peaks_for(device["kind"])
    else:
        device, peaks = {"platform": jax.devices()[0].platform, "kind": "rehearsal", "count": 1}, None
    listed = time.perf_counter()
    _log(t0, f"device {device}")
    if not system_mod.native_tokenizer_loaded():
        raise SystemExit("the native tokenizer did not build; the served path needs it")
    cache_dir, cache_stats = system_mod.compile_cache()
    _log(t0, f"compile cache at {cache_dir}")

    # ---- set-up: traffic, weights, the system, the standing corpus, warm-up
    traffic = make_traffic(config, mix, seed)
    family, model = cell.family, config["model"]
    weights = make_weights(family, model, config["weights"], seed)
    pool_tokens = family.tokens_of(traffic.pool_words, model)
    _log(t0, "traffic made")
    system = system_mod.System(config, weights)
    _log(t0, "system built")
    pool_emb = system.embed_pool(traffic.pool_texts)
    _log(t0, f"pool of {traffic.pool_size} documents embedded")
    system.fill(pool_emb, config, seed)
    del pool_emb
    _log(t0, f"{len(system.index)} standing rows on the device")
    warm_rng = np.random.default_rng([seed, 3])
    clients, cap = int(mix["queries"]["clients"]), int(mix["queries"]["cap"])
    for size in _query_buckets(clients, cap):
        system.search([traffic.query(warm_rng, 0)[0] for _ in range(size)])
    warm_batches = int(mix["writer"]["warm_batches"])
    for b in range(warm_batches):
        keys, docs = traffic.write_batch(b)
        system.remove(keys)
        system.embed_and_add(keys, [traffic.pool_texts[d] for d in docs])
        system.block_until_visible()
    system.search([traffic.query(warm_rng, 0)[0] for _ in range(min(clients, cap))])
    system.events.clear()
    # the index's host mirror is millions of objects; what set-up built is
    # set aside, so that no collection inside the window walks it
    gc.collect()
    gc.freeze()
    # from the device listing on: how long the TPU runtime takes to come up
    # (9 to 20 s from one process to the next) is not this system's set-up
    setup_s = time.perf_counter() - listed
    _log(t0, f"warm; set-up took {setup_s:.1f}s after {listed - t0:.1f}s to the device listing")

    # ---- the window
    spans = Spans(annotate=trace)
    window = Window(system, traffic, pool_tokens, mix, seed, spans, first_batch=warm_batches)
    tracer = None
    if trace:
        tr = mix["trace"]
        out_dir = os.path.join(spec.BENCH_DIR, ".trace", cell.name)
        tracer = Tracer(out_dir, min(tr["start_s"], seconds / 4), min(tr["seconds"], seconds / 2))
        tracer.thread.start()
    before = cache_stats()
    start, close = window.run(seconds)
    after = cache_stats()
    window_s = close - start
    _log(t0, f"window of {window_s:.2f}s closed: {len(window.writes)} write batches, {len(window.queries)} queries")
    if tracer is not None:
        tracer.thread.join()
        if tracer.error is not None:
            raise tracer.error
    if rehearsal is None:
        device["memory_peak_bytes"] = system_mod.memory_peak_bytes(cell.chips)

    # ---- end-to-end metrics, over the whole window
    answered = [q for q in window.queries if q.done <= close]
    measured = {"setup_s": setup_s, **_end_to_end(window, answered, close, window_s)}
    attempted = len(window.queries) + sum(len(b.keys) for b in window.writes)
    exact = {
        "malformed": check.malformed(
            [q.answer for q in window.queries], int(config["index"]["k"]), int(config["rows"])
        ),
        "unfinished": len(window.failures),
        "slab_moves": sum(system.slab_moves().values()),
        "compiles_in_window": after["requests"] - before["requests"],
    }
    for failure in window.failures:
        _log(t0, f"FAILED: {failure}")

    # ---- per-layer metrics, from the traced part and the window's counters
    traced: dict = {}
    layer_values = {}
    if trace and rehearsal is None:
        events = trace_reduce.load_events(tracer.out_dir)
        if dump_trace:
            import gzip
            import json

            os.makedirs(os.path.dirname(dump_trace), exist_ok=True)
            with gzip.open(dump_trace, "wt") as f:
                json.dump(events, f)
        reduced = trace_reduce.reduce(events, cell.chips)
        shutil.rmtree(tracer.out_dir, ignore_errors=True)
        rows, dim = int(config["rows"]), int(config["index"]["dimensions"])
        q_tokens = family.tokens_of([len(q.text.split()) for q in answered], model)
        ctx = {
            "trace": reduced,
            "spans": [s for s in spans.rows if start <= s[1] and s[2] <= close],
            "window_s": window_s,
            "peaks": peaks,
            "work": {
                "ingest_flops": family.flops(
                    model, np.concatenate([b.tokens for b in window.writes if b.visible <= close] or [[]])
                ),
                "serve_flops": family.flops(model, q_tokens) + workarith.scan_flops(len(answered), rows, dim),
                "encoder_flops_per_write_batch": family.flops(model, pool_tokens[: traffic.batch]),
                "scan_bytes_per_dispatch": workarith.scan_bytes(
                    rows, dim, np.dtype(config["index"]["row_dtype"]).itemsize
                ),
            },
        }
        for metric in cell.layer_metrics:
            value = metric.read(ctx)
            if value is not None:
                layer_values[metric.name] = {"value": float(value), "unit": metric.unit}
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        traced["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}

    # ---- the comparison, once the program's state is freed
    queries, first_version = window.queries, warm_batches
    dispatch_sizes = {str(n): c for n, c in sorted(window.dispatch_sizes.items())}
    skipped, n_writes = window.skipped_ticks, len(window.writes)
    system.free()
    del system, window
    gc.collect()
    t_check = time.perf_counter()
    judged = check.judge(config, traffic, family, weights, seed, queries, first_version, control=control)
    numbers = check.numbers(config, judged, exact)
    _log(t0, f"reference took {time.perf_counter() - t_check:.1f}s over {judged['sampled']} sampled answers")

    units = {e["name"]: e["unit"] for e in cell.end_to_end}
    if rehearsal is not None:
        metrics = {}
    elif trace:
        metrics = layer_values
    else:
        missing = [n for n in units if n not in measured]
        if missing:
            raise SystemExit(f"the window produced nothing for {missing}")
        metrics = {n: {"value": measured[n], "unit": units[n]} for n in units}
    result = {
        "correct": all(n.ok for n in numbers),
        "attempted": attempted,
        "failed": exact["malformed"] + exact["unfinished"],
        "metrics": metrics,
        "device": device,
        **traced,
        "window": {
            "seconds": window_s,
            "device_listing_s": listed - t0,
            "write_batches": n_writes,
            "dispatch_sizes": dispatch_sizes,
            "skipped_ticks": skipped,
            "cache": {"hits": after["hits"], "misses": after["misses"]},
        },
    }
    if control:
        limits = config["correct"]["limits"]
        c = judged["control"]
        result["control"] = {
            "precision": control,
            "rank_gap": c["rank_gap"],
            "score_err": c["score_err"],
            "fails": c["rank_gap"] > limits["rank_gap"] or c["score_err"] > limits["score_err"],
        }
    result["check"] = {n.name: [n.value, n.limit] for n in numbers}
    for n in numbers:
        print(n.line(), file=sys.stderr, flush=True)
    return result
