"""Headline benchmark: MiniLM-L6 embedding throughput (embeddings/sec)
on the available accelerator.

North-star (BASELINE.md): >=1M embeddings/sec on v5e-16 with
all-MiniLM-L6-v2 => 62,500 embeddings/sec/chip. vs_baseline is measured
throughput per chip divided by that per-chip target.

Two numbers are measured:
- device-scan: one jit'd lax.scan chains R batches on device so the
  per-dispatch latency is amortized — sustained on-device rate through
  the fused-attention encoder (ops/fused_attention.py).
- framework-path: SentenceTransformerEmbedder.encode_device — the
  batch-ingest surface (reference embedders.py:270): raw strings
  through the C++ batched tokenizer, bucketed padding, and a single
  scanned dispatch, to device-resident embeddings (the streaming
  pipeline feeds these straight into the on-device KNN index).

Prints ONE JSON line; "value"/"vs_baseline" carry the headline
device-scan number, framework_path_eps / framework_vs_raw report the
ingest surface.
"""

from __future__ import annotations

import functools
import json
import os
import time
from typing import Any

import numpy as np


def bench_device_scan() -> float:
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import EncoderConfig, TextEncoder, init_params
    from pathway_tpu.parallel.sharding import make_mesh

    devices = jax.devices()
    n_chips = max(1, len(devices))
    R, B, S = 8, 16384 * n_chips, 32  # R batches chained on device

    cfg = EncoderConfig.minilm_l6()
    module = TextEncoder(cfg)
    params = init_params(module, cfg)

    def run_all(p, ids, mask):
        def body(carry, batch):
            i, m = batch
            out = module.apply(p, i, m)
            return carry, jnp.sum(out[:, 0])

        return jax.lax.scan(body, jnp.float32(0.0), (ids, mask))[1]

    fn = jax.jit(run_all)

    rng = np.random.default_rng(0)
    ids = rng.integers(999, 29000, (R, B, S)).astype(np.int32)
    ids[:, :, 0] = 101
    ids[:, :, -1] = 102
    mask = np.ones((R, B, S), bool)
    if n_chips > 1:  # data-parallel over every chip
        mesh = make_mesh(model_parallel=1)
        # batch axis is dim 1 inside the scan; shard it across chips
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh, P(None, "data", None))
        ids = jax.device_put(ids, sh)
        mask = jax.device_put(mask, sh)
    else:
        ids = jnp.asarray(ids)
        mask = jnp.asarray(mask)

    sums = np.asarray(fn(params, ids, mask))  # compile + warm
    t0 = time.perf_counter()
    sums = np.asarray(fn(params, ids, mask))
    dt = time.perf_counter() - t0
    assert np.all(np.isfinite(sums))
    return R * B / dt, n_chips


_CHUNK_WORDS = (
    "streaming dataflow engines maintain incremental state across epochs "
    "so that retractions and late data revise previously emitted results "
    "without recomputing the whole pipeline from scratch every time"
).split()


def _realistic_chunks(n: int, words: int = 130) -> list[str]:
    """Documents at TokenCountSplitter-scale chunk lengths (~128-256
    wordpieces — VERDICT r2 Weak #5: S=32 snippets flatter the rate)."""
    out = []
    for i in range(n):
        body = " ".join(_CHUNK_WORDS[(i + j) % len(_CHUNK_WORDS)] for j in range(words))
        out.append(f"chunk {i} variant {i % 977}: {body}")
    return out


def bench_chip_peak_probe() -> float:
    """Sustained bf16 matmul rate of the attached chip (4096^3, 256
    chained so dispatch latency amortizes) — context for vs_baseline:
    the per-chip target assumes a full v5e-class part."""
    import jax
    import jax.numpy as jnp

    a = jnp.ones((4096, 4096), jnp.bfloat16)
    b = jnp.ones((4096, 4096), jnp.bfloat16)

    @jax.jit
    def mm(a, b):
        # carry-dependent operand (no loop hoisting) and a full-product
        # reduction (no slice-of-dot simplification): XLA must run all
        # 256 matmuls end to end
        def body(c, _):
            out = (a + c.astype(jnp.bfloat16)) @ b
            return jnp.sum(out, dtype=jnp.float32) * jnp.float32(1e-12), None

        return jax.lax.scan(body, jnp.float32(0), None, length=256)[0]

    np.asarray(mm(a, b))
    t0 = time.perf_counter()
    np.asarray(mm(a, b))
    dt = time.perf_counter() - t0
    return round(2 * 4096**3 * 256 / dt / 1e12, 1)


def _encoder_flops_per_token(seq: int) -> float:
    """MiniLM-L6 forward FLOPs per (padded) token at padded length
    ``seq``: qkv + attention scores/values + output proj + FFN, 6
    layers, multiply-add = 2 FLOPs."""
    d, interm, layers = 384, 1536, 6
    per_layer = (
        2 * d * 3 * d  # qkv projection
        + 2 * 2 * seq * d  # scores + probs@V
        + 2 * d * d  # output projection
        + 2 * 2 * d * interm  # FFN in + out
    )
    return float(layers * per_layer)


def bench_framework_path(words: int = 130, n: int = 32768):
    """Strings -> device-resident embeddings through the embedder's
    ``encode_device`` ingest surface, at realistic chunk lengths
    (~150 wordpieces, the TokenCountSplitter regime). Embeddings stay
    on device (they feed the on-device KNN index in the streaming
    pipeline); only a checksum returns, so the device->host copy of the
    embeddings doesn't masquerade as framework overhead.

    Returns (emb/s, padded seq bucket, achieved model TFLOP/s,
    kernel pad fraction over the measured run)."""
    from pathway_tpu.models.batching import DEFAULT_SEQ_BUCKETS, bucket
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    emb = SentenceTransformerEmbedder(max_batch_size=4096)
    texts = _realistic_chunks(n, words)
    ids_mat, lens = emb._encoder.tokenizer.batch_encode_matrix(
        texts, emb._encoder.max_seq_len
    )
    seq = bucket(int(lens.max()), DEFAULT_SEQ_BUCKETS)
    s = np.asarray(emb.encode_device(texts).sum())  # compile + warm
    from pathway_tpu.internals.profiler import ENCODER_KERNEL_STATS

    ENCODER_KERNEL_STATS.reset()  # attribute the measured run only
    t0 = time.perf_counter()
    out = emb.encode_device(texts)
    s = np.asarray(out.sum())
    dt = time.perf_counter() - t0
    assert out.shape == (n, emb.get_embedding_dimension()) and np.isfinite(s)
    tflops = n * seq * _encoder_flops_per_token(seq) / dt / 1e12
    pad_fraction = round(ENCODER_KERNEL_STATS.pad_fraction(), 4)
    return n / dt, seq, round(tflops, 1), pad_fraction


def bench_device_scan_bound(seq: int, n: int = 32768) -> float:
    """The honest upper bound for the framework path: the SAME encoder
    dispatch (jit lax.scan over B=4096 batches) on pre-staged synthetic
    ids at the SAME padded length — no tokenizer, no packing, no
    scatter. framework/bound is the framework overhead ratio."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import EncoderConfig, TextEncoder, init_params
    from pathway_tpu.ops.fused_layer import encoder_forward, use_fused_encoder

    cfg = EncoderConfig.minilm_l6()
    module = TextEncoder(cfg)
    params = init_params(module, cfg)
    B = 4096
    R = n // B
    use_fused = use_fused_encoder(cfg, seq)

    def run_all(p, ids, mask):
        def body(carry, batch):
            i, m = batch
            if use_fused:  # same whole-layer kernel the framework path runs
                out = encoder_forward(p, cfg, i, m)
            else:
                out = module.apply(p, i, m)
            return carry, jnp.sum(out[:, 0])

        return jax.lax.scan(body, jnp.float32(0.0), (ids, mask))[1]

    fn = jax.jit(run_all)
    rng = np.random.default_rng(0)
    ids = jax.device_put(rng.integers(999, 29000, (R, B, seq)).astype(np.int32))
    mask = jax.device_put(np.ones((R, B, seq), bool))
    np.asarray(fn(params, ids, mask))
    t0 = time.perf_counter()
    sums = np.asarray(fn(params, ids, mask))
    dt = time.perf_counter() - t0
    assert np.all(np.isfinite(sums))
    return n / dt


def main() -> list[str]:
    # the SLO suite runs first so every BASELINE.md config lands in the
    # round's bench record (VERDICT r2 Weak #5: report them all, every
    # round); the headline stays the LAST line for the driver
    failed = run_suite()
    raw_eps, n_chips = bench_device_scan()
    fw_eps, fw_seq, fw_tflops, fw_pad = bench_framework_path()
    bound_eps = bench_device_scan_bound(fw_seq)
    fw_per_chip = fw_eps / n_chips
    peak = bench_chip_peak_probe()
    # first-class summary lines (not just headline fields): the driver's
    # FINAL SUMMARY tail must carry the framework-path rate and its
    # fraction of the device-scan bound on their own records
    _emit(
        "framework_path_eps",
        fw_eps,
        "embeddings/s",
        seq_bucket=fw_seq,
        achieved_tflops=fw_tflops,
        pad_fraction=fw_pad,
        per_chip=round(fw_per_chip, 1),
    )
    _emit(
        "vs_device_scan_bound",
        fw_eps / bound_eps,
        "ratio",
        device_scan_bound_eps=round(bound_eps, 1),
        note="1.0 = framework path saturates the same jit scan dispatch "
        "on pre-staged ids; the shortfall is host-side overhead the "
        "epoch pipeline is meant to hide",
    )
    headline = {
                "metric": "minilm_l6_embeddings_per_sec",
                "value": round(fw_eps, 1),
                "unit": "embeddings/s",
                "vs_baseline": round(fw_per_chip / 62500.0, 4),
                "mode": "framework path: strings -> device-resident "
                "embeddings at ~150-wordpiece chunks (TokenCountSplitter "
                "regime), via the C++ batched tokenizer + bucketed "
                "scanned encoder with tokenize/compute overlap",
                "achieved_tflops": fw_tflops,
                "pad_fraction": fw_pad,
                "seq_bucket": fw_seq,
                "device_scan_bound_eps": round(bound_eps, 1),
                "vs_device_scan_bound": round(fw_eps / bound_eps, 3),
                "bound_note": "bound = same jit scan dispatch on "
                "pre-staged synthetic ids at the SAME padded length — "
                "no tokenizer/packing/scatter; the ratio is the "
                "framework overhead",
                "device_scan_eps": round(raw_eps, 1),
                "device_scan_mode": "jit lax.scan, synthetic S=32 ids — "
                "short-snippet upper bound, not comparable to the "
                "150-wordpiece headline",
                "chip_peak_probe_tflops": peak,
                "chip_peak_note": "sustained bf16 4096^3 matmul x256 "
                "chained; the 62.5k/chip target assumes ~200 TFLOPs peak "
                "(full v5e)",
    }
    print(json.dumps(headline), flush=True)
    print_final_summary(headline)
    return failed


# ---------------------------------------------------------------------------
# `bench.py --suite`: the BASELINE.md configs 1-5 plus the KNN scale/churn
# and ETL micro-benchmarks. One JSON line per metric.
# ---------------------------------------------------------------------------


#: every metric emitted during the run, re-printed compactly at the end
#: so the driver's bounded tail capture always contains every number
#: (VERDICT r4 Weak #5: the knn/vector-store/RAG/CLIP records scrolled
#: out of the 4KB BENCH_r04.json tail)
_RECORDS: list[dict] = []


#: ``device`` label of every record a :func:`_virtual_cpu_child` produced
_VIRTUAL_CPU = "8 virtual CPU devices (child process, JAX_PLATFORMS=cpu)"


def _virtual_cpu_child(prog: str, what: str) -> Any:
    """Run ``prog`` in a child on eight virtual CPU devices and return
    the JSON on the last line of its output. This process has touched
    JAX and holds the chip, so a child must never inherit the TPU; what
    the child times is XLA's CPU backend, and every record built from
    it says so (``device=_VIRTUAL_CPU``)."""
    import subprocess
    import sys

    env = dict(os.environ)
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", prog], env=env, capture_output=True, text=True, timeout=900
    )
    if r.returncode != 0:
        raise RuntimeError(f"{what} failed:\n{r.stderr[-3000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _emit(metric: str, value: float, unit: str, **extra) -> None:
    rec = {"metric": metric, "value": round(value, 3), "unit": unit, **extra}
    _RECORDS.append(rec)
    print(json.dumps(rec), flush=True)


def _compact(rec: dict) -> dict:
    """Numbers only — drop prose fields so each summary line stays small
    (failures keep their error string: a summary that hides a failed
    suite reads as if it never ran)."""
    return {
        k: v
        for k, v in rec.items()
        if k in ("metric", "unit", "error") or not isinstance(v, str)
    }


def print_final_summary(headline: dict) -> None:
    print("=== FINAL SUMMARY (one line per metric) ===", flush=True)
    for rec in _RECORDS:
        print(json.dumps(_compact(rec)), flush=True)
    # the headline is the LAST line, as the driver contract requires
    print(json.dumps(_compact(headline)), flush=True)
    # persist the same summary to the metrics journal when one is
    # configured (PATHWAY_JOURNAL_DIR): `pathway perf snapshot` folds
    # these records into a BENCH_r*-style JSON without re-running
    try:
        from pathway_tpu.perf.journal import append_record

        append_record(
            "bench",
            {
                "records": [_compact(r) for r in _RECORDS],
                "headline": _compact(headline),
            },
        )
    except Exception:
        pass  # the journal must never take the bench down


def suite_knn_10k() -> None:
    """Config 1: brute-force KNN over 10k x 384 vectors (the reference's
    stdlib.ml.index CPU config, /root/reference/python/pathway/stdlib/ml/index.py:9)."""
    from pathway_tpu.ops.knn import DeviceKnnIndex

    rng = np.random.default_rng(0)
    idx = DeviceKnnIndex(dim=384, metric="cos", reserved_space=10_000)
    vecs = rng.normal(size=(10_000, 384)).astype(np.float32)
    idx.add_batch_arrays(list(range(10_000)), vecs)
    q = rng.normal(size=(100, 384)).astype(np.float32)
    idx.search_batch(q, 10)  # sync + compile
    t0 = time.perf_counter()
    rounds = 20
    for _ in range(rounds):
        idx.search_batch(q, 10)
    dt = time.perf_counter() - t0
    lat = []
    one = q[:1]
    for _ in range(30):
        t1 = time.perf_counter()
        idx.search_batch(one, 10)
        lat.append((time.perf_counter() - t1) * 1e3)
    _emit(
        "knn_10k_384_queries_per_sec",
        rounds * len(q) / dt,
        "queries/s",
        p50_single_query_ms=round(float(np.percentile(lat, 50)), 3),
        mode="batched-100 + single-query p50",
    )


def suite_vector_store_ingest() -> None:
    """Config 2: VectorStore batch ingest — strings through the batched
    tokenizer + MiniLM embedder into the device index (the ingest path
    of reference vector_store.py:39 + embedders.py:270)."""
    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    emb = SentenceTransformerEmbedder(max_batch_size=8192)
    n = 16384
    texts = [
        f"document {i}: retrieval corpora need text of plausible short "
        f"length to index under load {i % 997}"
        for i in range(n)
    ]
    idx = DeviceKnnIndex(dim=emb.get_embedding_dimension(), metric="cos", reserved_space=n)
    q0 = np.zeros((1, emb.get_embedding_dimension()), np.float32)

    def ingest_all():
        # device-resident ingest: embeddings go encoder-jit -> index
        # scatter entirely in HBM (the engine's _index_add route for
        # jax payloads); re-adding existing keys exercises the same path
        for lo in range(0, n, 8192):
            chunk = texts[lo : lo + 8192]
            idx.add_batch_device(
                list(range(lo, lo + len(chunk))), emb.encode_device(chunk)
            )
        idx.search_batch(q0, 1)  # force device sync

    ingest_all()  # compile every shape on the measured path
    ingest_all()
    t0 = time.perf_counter()
    ingest_all()
    dt = time.perf_counter() - t0
    _emit(
        "vector_store_ingest_docs_per_sec",
        n / dt,
        "docs/s",
        mode="tokenize+embed+index-scatter, embeddings stay device-resident "
        "(no host bounce between encoder and index)",
    )


def suite_adaptive_rag_p50() -> None:
    """Config 3: adaptive-RAG query path — embed the query, KNN top-20
    over 10k docs, CrossEncoder rerank, top-5 (reference
    question_answering.py:620 + rerankers.py:186)."""
    from pathway_tpu.models.sentence_encoder import CrossEncoderScorer
    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    from pathway_tpu.models.sentence_encoder import SentenceEncoder
    from pathway_tpu.ops.fused_rag import FusedRagPipeline

    enc = SentenceEncoder(max_batch=4096)
    scorer = CrossEncoderScorer("cross-encoder/ms-marco-MiniLM-L-6-v2")
    n = 4096
    docs = [
        f"passage {i} about streaming dataflow engines and their "
        f"recovery semantics variant {i % 131}"
        for i in range(n)
    ]
    pipe = FusedRagPipeline(enc, scorer, reserved_space=n, doc_seq_len=64)
    pipe.add_docs(list(range(n)), docs)
    queries = [f"how does recovery variant {i} work" for i in range(20)]

    pipe.query(queries[0], k=5, k_retrieve=16)  # compile the fused kernel
    lat = []
    for qt in queries:
        t0 = time.perf_counter()
        out = pipe.query(qt, k=5, k_retrieve=16)
        lat.append((time.perf_counter() - t0) * 1e3)
        assert len(out) == 5
    _emit(
        "adaptive_rag_query_p50_ms",
        float(np.percentile(lat, 50)),
        "ms",
        p90_ms=round(float(np.percentile(lat, 90)), 3),
        mode="FUSED single dispatch: tokenize -> encode -> knn@4k top-16 -> "
        "on-device doc-token gather -> cross-encoder -> top-5",
    )


def suite_clip() -> None:
    """Config 4: CLIP-ViT-B/32 multimodal throughput (reference
    parsers.py ImageParser vision path)."""
    from pathway_tpu.models.clip import CLIPEncoder

    enc = CLIPEncoder(max_batch=256)
    rng = np.random.default_rng(0)
    # uint8 input: the ingest contract (decoded images); the wire format
    # is YUV 4:2:0 (1.5 B/px — the chroma resolution of the JPEGs CLIP
    # trains on), reconstructed on device inside the jit
    n_img = 512
    images = (
        rng.random((n_img, enc.cfg.image_size, enc.cfg.image_size, 3)) * 255
    ).astype(np.uint8)
    texts = [f"a photo of object number {i}" for i in range(256)]
    enc.encode_image(images)  # compile the measured shapes
    enc.encode_text(texts)
    # the headline includes the host->device image transfer: report
    # the median of 3 timed passes
    img_walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        enc.encode_image(images)
        img_walls.append(time.perf_counter() - t0)
    dt_img = float(np.median(img_walls))
    t0 = time.perf_counter()
    enc.encode_text(texts)
    dt_txt = time.perf_counter() - t0
    # decomposition: stage the packed rows on device OUTSIDE the timed
    # window, then run the same jitted vision tower — the compute-only
    # rate, which transfer/compute overlap can approach
    import jax

    flat = enc._pack_yuv420(images[:256])
    flat_dev = jax.device_put(flat)
    np.asarray(enc._vfwd_yuv420(enc.vparams, flat_dev).sum())
    t0 = time.perf_counter()
    np.asarray(enc._vfwd_yuv420(enc.vparams, flat_dev).sum())
    dt_dev = time.perf_counter() - t0
    _emit(
        "clip_vit_b32_images_per_sec",
        n_img / dt_img,
        "images/s",
        texts_per_sec=round(len(texts) / dt_txt, 1),
        img_walls_s=[round(w, 2) for w in img_walls],
        device_compute_images_per_sec=round(256 / dt_dev, 1),
        transport="yuv420 (1.5 B/px wire; >=0.997 cos vs exact RGB)",
        mode="includes host->device image transfer; device_compute rate = "
        "vision tower on pre-staged rows, the gap to the headline is the "
        "transfer",
    )


def suite_collab_ingest() -> None:
    """Collaborative CPU<->device ingest (pathway_tpu/ingest/): the
    WindVE-style host worker pool + ordered committer vs the strict
    inline prep path, for both the text ingest chain (native tokenizer
    shards -> bucketed encoder) and the CLIP image chain (quantize/
    YUV-pack workers -> donated ring). Model geometry is scaled so the
    suite runs green on CPU; on-chip, the same path targets >=100k
    docs/s text ingest and CLIP within 5x of its device-compute bound.
    Byte-identity at any worker count is asserted, not assumed."""
    import jax

    from pathway_tpu.ingest import INGEST_METRICS, configure_stage, shutdown_stage
    from pathway_tpu.models.clip import CLIPConfig, CLIPEncoder
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.models.sentence_encoder import SentenceEncoder

    import os

    INGEST_METRICS.reset()
    shutdown_stage()  # strict inline baseline first
    workers = int(os.environ.get("PATHWAY_INGEST_WORKERS") or 4)

    # -- text leg: tokenize (host) -> bucketed encoder (device) --
    cfg = EncoderConfig(
        vocab_size=30522,
        hidden_size=128,
        num_layers=2,
        num_heads=4,
        intermediate_size=256,
        max_position=128,
    )
    enc = SentenceEncoder(config=cfg, max_seq_len=64, max_batch=512)
    n = 4096
    texts = [
        (
            f"short doc {i} tag {i % 31}"
            if i % 4
            else (
                f"long document {i}: "
                + "streaming ingest needs straggler isolation " * 6
            )
        )
        for i in range(n)
    ]
    ref = enc.encode(texts)  # compile + inline reference output
    t0 = time.perf_counter()
    ref = enc.encode(texts)
    dt_inline = time.perf_counter() - t0
    # device bound: same encode with tokenization OUTSIDE the window —
    # what the chip does once host prep is fully hidden
    m = enc.tokenizer.batch_encode_matrix(texts, enc.max_seq_len)
    if m is not None:
        enc._encode_matrix(*m)
        t0 = time.perf_counter()
        enc._encode_matrix(*m)
        dt_bound = time.perf_counter() - t0
    else:
        dt_bound = dt_inline
    configure_stage(workers)
    out = enc.encode(texts)  # warm the collaborative path
    t0 = time.perf_counter()
    out = enc.encode(texts)
    dt_collab = time.perf_counter() - t0
    assert np.array_equal(np.asarray(out), np.asarray(ref)), (
        "collaborative ingest output diverged from the inline path"
    )
    snap = INGEST_METRICS.snapshot()
    collab_eps = n / dt_collab
    bound_eps = n / dt_bound
    _emit(
        "collab_ingest_eps",
        collab_eps,
        "docs/s",
        inline_eps=round(n / dt_inline, 1),
        device_scan_bound_eps=round(bound_eps, 1),
        vs_device_scan_bound=round(collab_eps / bound_eps, 3),
        host_workers=snap["host_workers"],
        host_stage_utilization=snap["utilization"],
        queue_high_water=snap["queue_high_water"],
        routed_short=snap["routed_short"],
        routed_long=snap["routed_long"],
        mode=f"{workers}-worker host stage, ordered committer; output "
        "byte-identical to inline (asserted)",
    )

    # -- CLIP leg: quantize/YUV-pack (host) -> vision tower (device) --
    shutdown_stage()
    INGEST_METRICS.reset()
    ccfg = CLIPConfig(
        image_size=64,
        patch_size=32,
        vision_width=128,
        vision_layers=2,
        vision_heads=4,
        text_width=64,
        text_layers=2,
        text_heads=2,
        context_length=32,
        embed_dim=64,
    )
    cenc = CLIPEncoder(ccfg, max_batch=64)
    rng = np.random.default_rng(0)
    n_img = 256
    images = (
        rng.random((n_img, ccfg.image_size, ccfg.image_size, 3)) * 255
    ).astype(np.uint8)
    cref = cenc.encode_image(images)  # compile + inline reference
    t0 = time.perf_counter()
    cref = cenc.encode_image(images)
    dt_img_inline = time.perf_counter() - t0
    # device-compute bound: vision tower on pre-staged packed rows
    flat = cenc._pack_yuv420(images[:64])
    flat_dev = jax.device_put(flat)
    np.asarray(cenc._vfwd_yuv420(cenc.vparams, flat_dev).sum())
    t0 = time.perf_counter()
    for _ in range(n_img // 64):
        np.asarray(cenc._vfwd_yuv420(cenc.vparams, flat_dev).sum())
    dt_dev = time.perf_counter() - t0
    configure_stage(workers)
    cout = cenc.encode_image(images)  # warm the collaborative path
    t0 = time.perf_counter()
    cout = cenc.encode_image(images)
    dt_img_collab = time.perf_counter() - t0
    shutdown_stage()
    assert np.array_equal(np.asarray(cout), np.asarray(cref)), (
        "collaborative CLIP ingest output diverged from the inline path"
    )
    csnap = INGEST_METRICS.snapshot()
    collab_ips = n_img / dt_img_collab
    bound_ips = n_img / dt_dev
    ratio = collab_ips / bound_ips
    _emit(
        "clip_ingest_vs_device_bound",
        ratio,
        "ratio",
        collab_images_per_sec=round(collab_ips, 1),
        inline_images_per_sec=round(n_img / dt_img_inline, 1),
        device_compute_images_per_sec=round(bound_ips, 1),
        host_stage_utilization=csnap["utilization"],
        queue_high_water=csnap["queue_high_water"],
        note="1.0 = ingest saturates the vision tower on pre-staged "
        "rows; the on-chip target is >= 0.2 (within 5x of the bound)",
    )
    headline = {
        "metric": "collab_ingest_eps",
        "value": round(collab_eps, 1),
        "unit": "docs/s",
        "vs_device_scan_bound": round(collab_eps / bound_eps, 3),
        "clip_ingest_vs_device_bound": round(ratio, 3),
        "host_workers": workers,
        "mode": "WindVE-style host stage: parallel prep workers, one "
        "ordered committer, byte-identical output (asserted)",
    }
    print(json.dumps(headline), flush=True)
    print_final_summary(headline)


def suite_streaming_8shard() -> None:
    """Config 5: the 8-worker streaming pipeline (source -> embed ->
    KNN -> query) sharded over a virtual 8-device mesh (reference worker
    model config.rs:36-120; ICI collectives stand in for timely TCP)."""
    prog = r"""
import json, os, time
import numpy as np
import jax
import pathway_tpu as pw
from pathway_tpu.internals.graph_runner import GraphRunner
from pathway_tpu.models.encoder import EncoderConfig
from pathway_tpu.models.sentence_encoder import SentenceEncoder
from pathway_tpu.parallel.sharding import make_mesh
from pathway_tpu.stdlib.ml.index import KNNIndex

cfg = EncoderConfig(vocab_size=512, hidden_size=32, num_layers=1, num_heads=2,
                    intermediate_size=64, max_position=32, pooling="mean")
mesh = make_mesh(model_parallel=1)
enc = SentenceEncoder(config=cfg, checkpoint_dir="/nonexistent", max_seq_len=16,
                      max_batch=2048, mesh=mesh)
rng = np.random.default_rng(0)
N, BATCH = 30000, 6000
doc_toks = rng.integers(3, cfg.vocab_size, (N, 8))
doc_tok_lists = [tuple(r) for r in doc_toks.tolist()]
def embed_batch(toks_list):
    # rows carry np.float32 arrays (engine FloatArray values) — the
    # columnar BatchApplyNode hands the whole epoch to ONE call here
    return list(enc.encode_tokens([list(t) for t in toks_list]))
emb_udf = pw.udfs.udf(embed_batch, executor=pw.udfs.batch_executor(max_batch_size=2048))
# a streaming engine compiles its shapes once at startup; warm them so
# steady-state throughput (the metric) isn't charged for XLA compiles
for warm_n in (2048, 16):
    enc.encode_tokens([doc_tok_lists[i % N] for i in range(warm_n)])

class DocSource(pw.io.python.ConnectorSubject):
    def run(self):
        for lo in range(0, N, BATCH):
            hi = min(lo + BATCH, N)
            self.next_batch(doc_id=list(range(lo, hi)), toks=doc_tok_lists[lo:hi])
            self.commit()

class DocSchema(pw.Schema):
    doc_id: int
    toks: tuple

docs = pw.io.python.read(DocSource(), schema=DocSchema, autocommit_duration_ms=None)
docs = docs.select(pw.this.doc_id, emb=emb_udf(pw.this.toks))
queries = pw.debug.table_from_rows(
    schema=DocSchema,
    rows=[(10_000_000 + i, tuple(int(x) for x in rng.integers(3, cfg.vocab_size, 8))) for i in range(16)],
)
queries = queries.select(pw.this.doc_id, emb=emb_udf(pw.this.toks))
idx = KNNIndex(docs.emb, docs, n_dimensions=cfg.hidden_size, reserved_space=N)
res = idx.get_nearest_items(queries.emb, k=3).select(qid=queries.doc_id, nearest=pw.this.doc_id)
# warm the device-index jits at the capacity/query shapes the run hits
from pathway_tpu.ops.knn import DeviceKnnIndex
_wi = DeviceKnnIndex(dim=cfg.hidden_size, metric="l2", reserved_space=N)
_wi.add_batch_arrays(list(range(64)), np.zeros((64, cfg.hidden_size), np.float32))
_wi.search_batch(np.zeros((16, cfg.hidden_size), np.float32), 3)
runner = GraphRunner(n_workers=8)
cap, names = runner.capture(res)
epoch_walls = []
def on_epoch(engine):
    epoch_walls.append(time.perf_counter())
t0 = time.perf_counter()
runner.run(monitoring_callback=on_epoch)
dt = time.perf_counter() - t0
assert len(cap.state) == 16
n_feed = N // BATCH
# steady state: epochs after the first (the first eats remaining
# first-touch costs); each feed epoch carries BATCH rows
if len(epoch_walls) >= n_feed and n_feed > 1:
    steady = (n_feed - 1) * BATCH / (epoch_walls[n_feed - 1] - epoch_walls[0])
else:
    steady = N / dt
print(json.dumps({"rows_per_sec": steady, "wall_s": dt, "total_rows_per_sec": N / dt}))
"""
    data = _virtual_cpu_child(prog, "8-shard pipeline")
    emit = functools.partial(_emit, device=_VIRTUAL_CPU)
    emit(
        "streaming_8shard_rows_per_sec",
        data["rows_per_sec"],
        "rows/s",
        wall_s=round(data["wall_s"], 2),
        total_rows_per_sec=round(data.get("total_rows_per_sec", 0.0), 1),
        mode="8 engine shards on virtual CPU mesh: source->embed->knn->query; "
        "value = steady-state rate over the epochs after the first "
        "(columnar BatchApplyNode: one embed call per epoch chunk)",
    )


def suite_mesh_scaling() -> None:
    """Config 5c: GSPMD scale-out of the KNN index — ONE logical index
    sharded over the mesh's data axis at FIXED per-shard capacity, mesh
    sizes 1/2/4/8 (virtual CPU devices). Claims measured: (1) logical
    docs capacity scales >= 0.9x linearly with mesh size (it is exactly
    n_shards * per-shard capacity by construction; the bench fills every
    slot to prove the router + slab layout actually hold that many), and
    (2) the cross-chip merge collective (phase 2 of a sharded search)
    stays under 15% of the per-shard search time."""
    prog = r"""
import json, time
import numpy as np
import jax
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.ops.index_metrics import INDEX_METRICS
from pathway_tpu.parallel.mesh import resolve_mesh

DIM, PER_SHARD, Q, K = 128, 2048, 32, 10
rng = np.random.default_rng(0)
queries = rng.normal(size=(Q, DIM)).astype(np.float32)
out = []
for n in (1, 2, 4, 8):
    mesh = resolve_mesh(n) if n > 1 else None
    idx = DeviceKnnIndex(dim=DIM, metric="cos",
                         reserved_space=n * PER_SHARD, mesh=mesh)
    cap = idx.capacity
    vecs = rng.normal(size=(cap, DIM)).astype(np.float32)
    # fill EVERY slot: the capacity claim is that the hash router +
    # slab layout really hold n * PER_SHARD docs without growing.
    # Keys are probed so each lands on a shard with room (the router is
    # a fixed hash; a blind 0..cap key range would overflow one shard
    # first and trigger growth, changing the capacity under test).
    from pathway_tpu.ops.knn import _shard_of_key
    key, added = 0, 0
    while added < cap:
        while not idx._free_shard[_shard_of_key(key, idx.n_shards)]:
            key += 1
        idx.add(key, vecs[added])
        key += 1
        added += 1
    assert len(idx) == cap and idx.capacity == cap, (len(idx), cap)
    idx.search_batch(queries, K)  # compile + upload
    INDEX_METRICS.reset()
    lat = []
    for _ in range(30):
        t0 = time.perf_counter()
        idx.search_batch(queries, K)
        lat.append(time.perf_counter() - t0)
    wall = sum(lat)
    merge = INDEX_METRICS.snapshot()["merge_seconds"]["sum"]
    out.append({
        "shards": n, "docs_capacity": cap,
        "p50_ms": float(np.percentile(np.asarray(lat) * 1e3, 50)),
        "merge_s": merge, "wall_s": wall,
    })
print(json.dumps(out))
"""
    rows = _virtual_cpu_child(prog, "mesh scaling bench")
    emit = functools.partial(_emit, device=_VIRTUAL_CPU)
    base = next(x for x in rows if x["shards"] == 1)
    top = next(x for x in rows if x["shards"] == 8)
    scaling = (top["docs_capacity"] / base["docs_capacity"]) / 8
    # merge overhead vs the per-shard scan: phase 2 wall over phase 1
    # wall (total search minus the timed merge collective)
    merge_frac = top["merge_s"] / max(1e-9, top["wall_s"] - top["merge_s"])
    emit(
        "mesh_docs_capacity",
        top["docs_capacity"],
        "docs",
        linear_scaling_x=round(scaling, 3),
        per_shard_capacity=base["docs_capacity"],
        capacities={str(x["shards"]): x["docs_capacity"] for x in rows},
        mode="ONE logical index, fixed per-shard capacity, mesh 1/2/4/8 "
        "virtual CPU devices; every slot filled through the hash router",
    )
    emit(
        "mesh_query_p50_ms",
        top["p50_ms"],
        "ms",
        shards=8,
        p50_by_shards={str(x["shards"]): round(x["p50_ms"], 3) for x in rows},
        merge_overhead_frac=round(merge_frac, 4),
        note="p50 of 32-query batched search, k=10; merge_overhead_frac = "
        "cross-chip merge wall / per-shard scan wall at 8 shards",
    )
    assert scaling >= 0.9, f"capacity scaling {scaling:.2f}x below 0.9x linear"
    assert merge_frac < 0.15, f"merge overhead {merge_frac:.1%} >= 15%"


def suite_streaming_tpu_chip() -> None:
    """Config 5b: the streaming shape on the REAL chip, device-resident
    end-to-end — a TEXT column flows into an embedder-attached index, so
    embeddings go tokenizer -> encoder jit -> index scatter entirely in
    HBM (the engine's add_batch_device route); queries run the fused
    tokenize->encode->top-k dispatch. Nothing bounces through the host
    between encode and index."""
    import time as _t

    import pathway_tpu as pw
    from pathway_tpu.internals.graph_runner import GraphRunner
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    emb = SentenceTransformerEmbedder(max_batch_size=8192)
    N, BATCH = 32768, 8192
    texts = _realistic_chunks(N, 60)
    # a streaming engine compiles its shapes at startup; warm the
    # encoder group program and the index scatter at the pad buckets
    # the run hits
    from pathway_tpu.ops.knn import DeviceKnnIndex

    np.asarray(emb.encode_device(texts[: 2 * BATCH]).sum())
    warm_idx = DeviceKnnIndex(
        dim=emb.get_embedding_dimension(), metric="cos", reserved_space=N
    )
    # exactly the engine's ingest shapes: encode pads to the pow2 bucket
    # and the scatter sees (pad, dim) vectors — epochs can coalesce into
    # any multiple of BATCH, so cover them all
    for n_w in (BATCH, 2 * BATCH, 3 * BATCH, 4 * BATCH):
        pad = 1 << (n_w - 1).bit_length()
        warm_idx.add_batch_device(
            list(range(n_w)), emb.encode_device(texts[:n_w], pad_to=pad)
        )
    warm_idx.search_batch(np.zeros((16, emb.get_embedding_dimension()), np.float32), 3)
    warm_idx.attach_encoder(emb._encoder)
    # warm the fused text-query dispatch at the REAL query length — a
    # short literal here would warm a different seq bucket and the
    # first in-run query would eat a compile
    warm_idx.search_texts_batch([texts[0]] * 16, 3)

    class DocSchema(pw.Schema):
        doc_id: int
        text: str

    def one_pass(depth: int = 1):
        class DocSource(pw.io.python.ConnectorSubject):
            def run(self):
                for lo in range(0, N, BATCH):
                    hi = min(lo + BATCH, N)
                    self.next_batch(doc_id=list(range(lo, hi)), text=texts[lo:hi])
                    self.commit()

        docs = pw.io.python.read(
            DocSource(), schema=DocSchema, autocommit_duration_ms=None
        )
        queries = pw.debug.table_from_rows(
            schema=DocSchema, rows=[(10_000_000 + i, texts[i * 7]) for i in range(16)]
        )
        factory = BruteForceKnnFactory(
            dimensions=emb.get_embedding_dimension(),
            embedder=emb,
            reserved_space=N,
        )
        index = factory.build_index(docs.text, docs)
        # INCREMENTAL standing queries: re-answered on every ingest
        # epoch, so the run's wall covers the full device pipeline and
        # the final answers are real top-3 neighbors over all N docs
        # (r4 used asof_now, which answered against the still-empty
        # index before the first doc epoch — vacuously fast and
        # semantically empty)
        res = index.query(queries.text, number_of_matches=3).select(
            nearest=pw.this.doc_id
        )
        runner = GraphRunner(pipeline_depth=depth)
        cap, _names = runner.capture(res)
        t0 = _t.perf_counter()
        c0 = _t.process_time()
        runner.run()
        dt = _t.perf_counter() - t0
        host_cpu = _t.process_time() - c0
        pw.clear_graph()
        assert len(cap.state) == 16
        n_empty = sum(1 for v in cap.state.values() if not v[0])
        assert n_empty == 0, f"{n_empty} queries answered with no neighbors"
        pstats = getattr(runner.engine, "pipeline_stats", None)
        return dt, host_cpu, (pstats.as_dict() if pstats is not None else None)

    # steady state: a streaming engine compiles/warms once at startup
    # and then runs for days — the first pass (reported alongside)
    # still hits one-time costs the warm-up can't reach
    first_dt, _, _ = one_pass()
    dt, host_cpu, _ = one_pass()
    # same steady-state pass through the overlapped epoch pipeline:
    # epoch N+1's drain/tokenize/stage overlaps epoch N's device time,
    # so the blocked-on-device remainder should shrink vs depth 1
    dt2, host_cpu2, pstats = one_pass(depth=2)
    _emit(
        "streaming_tpu_chip_rows_per_sec",
        N / dt,
        "rows/s",
        wall_s=round(dt, 2),
        host_cpu_s=round(host_cpu, 2),
        device_wait_s=round(max(0.0, dt - host_cpu), 2),
        first_run_wall_s=round(first_dt, 2),
        pipelined_rows_per_sec=round(N / dt2, 3),
        pipelined_wall_s=round(dt2, 2),
        pipelined_device_wait_s=round(max(0.0, dt2 - host_cpu2), 2),
        overlap_ratio=(pstats or {}).get("overlap_ratio", 0.0),
        mode="single real chip, single worker: text source -> embedder-attached "
        "device index (HBM-resident ingest, fused text queries) through the "
        "engine; 16 standing queries re-answered each epoch, final answers "
        "asserted non-empty; steady-state pass (first engine pass reported as "
        "first_run_wall_s); host_cpu_s itemizes the engine's python time, "
        "device_wait_s the blocked-on-device remainder; pipelined_* repeats "
        "the pass at pipeline_depth=2 (overlapped epoch formation)",
    )


def suite_knn_churn(n_docs: int = 625_000) -> None:
    """KNN at the stated budget point — 625k x 384 docs/chip (the
    50ms@10M-over-v5e-16 budget, BASELINE.md) — with retraction churn
    riding the ZERO-HOST-BOUNCE ingest path: removes tombstone, re-adds
    arrive as device-resident arrays (add_batch_device)."""
    import jax

    from pathway_tpu.ops.knn import DeviceKnnIndex

    rng = np.random.default_rng(0)
    dim = 384
    idx = DeviceKnnIndex(dim=dim, metric="cos", reserved_space=n_docs)
    block = 125_000
    for lo in range(0, n_docs, block):
        vecs = rng.normal(size=(min(block, n_docs - lo), dim)).astype(np.float32)
        idx.add_batch_arrays(list(range(lo, lo + len(vecs))), vecs)
    q = rng.normal(size=(1, dim)).astype(np.float32)
    idx.search_batch(q, 16)  # sync + compile
    # churn warm: compile the tombstone-flush + device-add scatters
    dev_vecs = jax.device_put(rng.normal(size=(1024, dim)).astype(np.float32))
    for j in range(0, 1000):
        idx.remove(j)
    idx.add_batch_device(list(range(0, 1000)), dev_vecs)
    idx.search_batch(q, 16)
    lat = []
    for round_i in range(1, 6):
        # churn: retract + re-add 1k docs via the device path, then query
        base = (round_i * 1009) % (n_docs - 1000)
        for j in range(base, base + 1000):
            idx.remove(j)
        idx.add_batch_device(list(range(base, base + 1000)), dev_vecs)
        t0 = time.perf_counter()
        idx.search_batch(q, 16)
        lat.append((time.perf_counter() - t0) * 1e3)
    # steady-state (no churn between queries)
    steady = []
    for _ in range(20):
        t0 = time.perf_counter()
        idx.search_batch(q, 16)
        steady.append((time.perf_counter() - t0) * 1e3)
    _emit(
        "knn_1m_churn_query_p50_ms",
        float(np.percentile(steady, 50)),
        "ms",
        p50_after_churn_ms=round(float(np.percentile(lat, 50)), 3),
        churn_over_steady=round(
            float(np.percentile(lat, 50)) / float(np.percentile(steady, 50)), 3
        ),
        budget_ms=50.0,
        n_docs=n_docs,
        mode="1 chip at the 625k docs/chip budget point; churn re-adds ride "
        "add_batch_device (no host bounce)",
    )


def suite_tiered_recall() -> None:
    """Tiered index beyond-HBM curve: recall@10 and query p50 as the
    HBM hot tier shrinks below the corpus (1x = fits hot, 2x and 4x =
    corpus over-subscribes HBM by that factor, overflow lives in the
    int8 host cold tier).  The acceptance gate is recall@10 >= 0.95 at
    the 4x point; ground truth is exact f32 brute force over the same
    vectors."""
    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.ops.tiered_knn import TierConfig, TieredKnnIndex, hot_row_bytes

    # SIFT-like cluster structure: ~78 docs/center so rank-10 score
    # gaps stay well above the int8 noise floor (a 32-center pile-up
    # makes near-ties no 8-bit code can rank through — measured rank
    # 10/11 gap 1e-4 vs int8 rms error 7e-4)
    rng = np.random.default_rng(7)
    dim = 96
    n_docs = 20_000
    n_centers = 256
    centers = rng.normal(size=(n_centers, dim)).astype(np.float32) * 2.0
    assign = rng.integers(0, n_centers, size=n_docs)
    vecs = (centers[assign] + rng.normal(size=(n_docs, dim)) * 1.0).astype(np.float32)
    keys = list(range(n_docs))
    q = (
        centers[rng.integers(0, n_centers, size=64)]
        + rng.normal(size=(64, dim)) * 1.0
    ).astype(np.float32)

    flat = DeviceKnnIndex(dim=dim, metric="cos", reserved_space=n_docs)
    flat.add_batch_arrays(keys, vecs)
    truth = [set(k for k, _ in row) for row in flat.search_batch(q, 10)]

    curve = []
    for over in (1, 2, 4):
        hot_rows = n_docs // over
        idx = TieredKnnIndex(
            dim=dim,
            metric="cos",
            reserved_space=n_docs,
            tiers=TierConfig(
                hot_rows=hot_rows, n_clusters=64, n_probe=24, cold_dtype="int8"
            ),
        )
        idx.add_batch_arrays(keys, vecs)
        idx.search_batch(q, 10)  # sync + compile both tiers
        got = idx.search_batch(q, 10)
        recall = float(
            np.mean([len(truth[i] & {k for k, _ in got[i]}) / 10 for i in range(len(q))])
        )
        lat = []
        one = q[:1]
        for _ in range(30):
            t0 = time.perf_counter()
            idx.search_batch(one, 10)
            lat.append((time.perf_counter() - t0) * 1e3)
        point = {
            "beyond_hbm_x": over,
            "hot_rows": hot_rows,
            "hbm_budget_bytes": hot_rows * hot_row_bytes(dim, "f32"),
            "hot_docs": idx.hot_docs(),
            "cold_docs": idx.cold_docs(),
            "recall_at_10": round(recall, 4),
            "p50_ms": round(float(np.percentile(lat, 50)), 3),
        }
        curve.append(point)
        _emit(
            f"tiered_recall_at10_{over}x",
            recall,
            "recall",
            **{k: v for k, v in point.items() if k != "recall_at_10"},
        )
    at4x = curve[-1]
    assert at4x["cold_docs"] > 0, "4x point kept everything hot"
    _emit(
        "tiered_recall_at10_4x_beyond_hbm",
        at4x["recall_at_10"],
        "recall",
        gate=0.95,
        p50_ms=at4x["p50_ms"],
        p50_fits_hot_ms=curve[0]["p50_ms"],
        n_docs=n_docs,
        dim=dim,
        mode="int8 scale-per-vector cold tier, 64 clusters probe 24; "
        "curve points are 1x/2x/4x HBM over-subscription; ground truth "
        "exact f32 brute force",
    )


def suite_decode_serving() -> None:
    """Decode-plane serving suite: sustained continuous-batching
    generation through the paged-KV engine, queries arriving while
    earlier ones are mid-stream. Two passes measure the rerank split:

    - rerank ON: every query first scores 8 candidates through the
      on-device cross-encoder (models/reranker.py — the stage that
      replaced the HTTP xpack hop), then generates max_new_tokens.
    - rerank OFF: the degrade path — rerank skipped and generation
      clamped to degrade_max_new_tokens, exactly what admission applies
      under pressure.

    Headline: tokens/s-per-chip with the p99 query completion latency
    under the budget (0.0 when the budget is blown, like
    serving_qps_at_p99_budget)."""
    import jax

    from pathway_tpu.decode import DecodeConfig, DecodeEngine, DecoderConfig
    from pathway_tpu.decode.metrics import DECODE_METRICS
    from pathway_tpu.models.reranker import DeviceReranker
    from pathway_tpu.models.sentence_encoder import CrossEncoderScorer, EncoderConfig

    n_chips = max(1, jax.device_count())
    N_QUERIES = 64
    BUDGET_MS = 5000.0  # per-query completion budget under full load

    mcfg = DecoderConfig(
        vocab_size=8000,
        hidden_size=128,
        num_layers=2,
        num_heads=4,
        intermediate_size=256,
        max_position=256,
    )
    dcfg = DecodeConfig(
        pages=512,
        page_size=16,
        lanes=8,
        max_new_tokens=32,
        degrade_max_new_tokens=8,
        max_seq=160,
        impl="auto",
    )
    ecfg = EncoderConfig(
        vocab_size=30522,
        hidden_size=64,
        num_layers=2,
        num_heads=2,
        intermediate_size=128,
        max_position=64,
        pooling="mean",
    )
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(1, mcfg.vocab_size, int(n)).tolist()
        for n in rng.integers(4, 64, N_QUERIES)
    ]
    cand_docs = [f"candidate document {i} about topic {i % 7}" for i in range(8)]

    def run_once(rerank: bool) -> dict:
        DECODE_METRICS.reset()
        engine = DecodeEngine(mcfg, dcfg)
        reranker = (
            DeviceReranker(
                scorer=CrossEncoderScorer(
                    config=ecfg,
                    checkpoint_dir="/nonexistent",
                    max_seq_len=64,
                    max_batch=64,
                )
            )
            if rerank
            else None
        )
        tickets: list = []
        done_at: dict[int, float] = {}

        def poll() -> None:
            now = time.monotonic()
            for idx, (_t_sub, tk) in enumerate(tickets):
                if idx not in done_at and tk.done.is_set():
                    done_at[idx] = now

        # warmup: compile every prefill bucket + the fused step + the
        # reranker forward outside the timed window
        for prompt in prompts[:8]:
            engine.submit(prompt, degraded=not rerank)
        engine.drain()
        if reranker is not None:
            reranker.order("warmup", cand_docs)
        DECODE_METRICS.reset()
        t0 = time.perf_counter()
        for qi, prompt in enumerate(prompts):
            if reranker is not None:
                reranker.order(f"query {qi}", cand_docs)
            tk = engine.submit(prompt, degraded=not rerank)
            tickets.append((time.monotonic(), tk))
            engine.step()  # arrivals interleave with in-flight decoding
            poll()
        while engine.busy():
            engine.step()
            poll()
        poll()
        wall = time.perf_counter() - t0
        lats = sorted(
            (done_at[i] - tickets[i][0]) * 1e3 for i in range(len(tickets))
        )
        total_tokens = sum(len(tk.tokens) for _, tk in tickets)

        def pct(p: float) -> float:
            return lats[min(len(lats) - 1, int(p * len(lats)))]

        return {
            "tokens": total_tokens,
            "wall_s": wall,
            "tok_per_s": total_tokens / wall,
            "p50_ms": pct(0.50),
            "p99_ms": pct(0.99),
        }

    on = run_once(rerank=True)
    off = run_once(rerank=False)
    _emit(
        "decode_tokens_per_s_rerank_on",
        on["tok_per_s"],
        "tokens/s",
        p50_ms=round(on["p50_ms"], 1),
        p99_ms=round(on["p99_ms"], 1),
        queries=N_QUERIES,
        lanes=dcfg.lanes,
        max_new_tokens=dcfg.max_new_tokens,
    )
    _emit(
        "decode_tokens_per_s_rerank_off",
        off["tok_per_s"],
        "tokens/s",
        p50_ms=round(off["p50_ms"], 1),
        p99_ms=round(off["p99_ms"], 1),
        note="degrade path: rerank skipped, generation clamped to "
        f"{dcfg.degrade_max_new_tokens} tokens",
    )
    _emit(
        "tokens_per_s_per_chip_at_p99",
        on["tok_per_s"] / n_chips if on["p99_ms"] <= BUDGET_MS else 0.0,
        "tokens/s/chip",
        p99_ms=round(on["p99_ms"], 1),
        budget_ms=BUDGET_MS,
        n_chips=n_chips,
        rerank_off_per_chip=round(off["tok_per_s"] / n_chips, 3),
        mode="continuous batching over the paged-KV pool; rerank ON "
        "pass pays the on-device cross-encoder per query",
    )

    # --- prefix-cache phase: a 10x-longer shared prefix must cost ~0
    # extra prefill at steady state (every shared full page is served
    # from the refcounted cache, only the tail re-prefills) ---
    N_CACHED = 32

    def build_prefix_engine(prefix_len: int):
        cfg = DecodeConfig(**{**dcfg.as_dict(), "prefix_cache": True})
        engine = DecodeEngine(mcfg, cfg)
        prng = np.random.default_rng(11)
        shared = prng.integers(1, mcfg.vocab_size, prefix_len).tolist()
        qs = [
            shared + prng.integers(1, mcfg.vocab_size, 8).tolist()
            for _ in range(N_CACHED)
        ]
        # warmup: compile the buckets AND publish the shared prefix so
        # the timed rounds measure the steady (warm-cache) state — the
        # second query takes the warm-hit path, compiling its tail-chunk
        # program outside the timed windows
        engine.submit(qs[0])
        engine.drain()
        engine.submit(qs[1])
        engine.drain()
        return engine, qs

    def timed_round(engine, qs) -> tuple:
        t0 = time.perf_counter()
        tickets = [engine.submit(q) for q in qs]
        occupancy = 0.0
        while engine.busy():
            engine.step()
            occupancy = max(
                occupancy, engine.pool.pages_in_use / engine.pool.n_pages
            )
        wall = time.perf_counter() - t0
        return sum(len(tk.tokens) for tk in tickets) / wall, occupancy

    def run_prefix() -> tuple:
        """Interleaved A/B rounds: wall-clock drift (frequency scaling,
        allocator aging) lands on BOTH prefix lengths, so the ratio
        compares like with like; per-phase value is the round median."""
        import statistics

        eng_s, qs_s = build_prefix_engine(8)
        eng_l, qs_l = build_prefix_engine(80)
        DECODE_METRICS.reset()
        tps_s, tps_l, occupancy = [], [], 0.0
        for _ in range(3):
            tp, _occ = timed_round(eng_s, qs_s)
            tps_s.append(tp)
            tp, occ = timed_round(eng_l, qs_l)
            tps_l.append(tp)
            occupancy = max(occupancy, occ)
        snap = DECODE_METRICS.snapshot()
        short = {"tok_per_s": statistics.median(tps_s)}
        long = {
            "tok_per_s": statistics.median(tps_l),
            "hit_ratio": float(snap.get("prefix_hit_ratio", 0.0)),
            "cached_pages": int(snap.get("prefix_cached_pages", 0)),
            "occupancy": occupancy,
        }
        return short, long

    short, long = run_prefix()
    assert long["hit_ratio"] > 0.5, f"cold cache at steady state: {long}"
    assert long["tok_per_s"] * 1.1 >= short["tok_per_s"], (
        "10x shared prefix degraded tokens/s by more than 1.1x: "
        f"{short['tok_per_s']:.1f} -> {long['tok_per_s']:.1f}"
    )
    _emit(
        "decode_prefix_hit_ratio",
        long["hit_ratio"],
        "ratio",
        gate=0.5,
        cached_pages=long["cached_pages"],
        prefix_tokens=80,
        queries=N_CACHED,
        mode="80-token shared prefix + 8 unique tokens per prompt, "
        "cache warmed by one query outside the timed window",
    )
    _emit(
        "decode_kv_pool_occupancy",
        long["occupancy"],
        "ratio",
        pages=dcfg.pages,
        note="physical pages in use / pool pages with every query "
        "admitted (shared prefix pages booked once, not per lane)",
    )
    _emit(
        "decode_prefix_cache_speedup_10x",
        long["tok_per_s"] / max(short["tok_per_s"], 1e-9),
        "x",
        gate=1 / 1.1,
        tok_per_s_short_prefix=round(short["tok_per_s"], 1),
        tok_per_s_10x_prefix=round(long["tok_per_s"], 1),
    )

    # --- speculative phase: layer-skip self-draft proposes k tokens,
    # the target verifies them in one batched forward; on the
    # self-similar toy workload acceptance must clear 0.5 and the
    # emitted-tokens/s headline must beat the greedy baseline 1.5x ---
    N_SPEC = 32
    spec_prompts = [
        rng.integers(1, mcfg.vocab_size, 12).tolist() for _ in range(N_SPEC)
    ]

    def run_spec(spec: int, **draft) -> dict:
        DECODE_METRICS.reset()
        cfg = DecodeConfig(**{**dcfg.as_dict(), "spec_tokens": spec, **draft})
        engine = DecodeEngine(mcfg, cfg)
        for q in spec_prompts[:4]:  # compile draft/verify outside timing
            engine.submit(q)
        engine.drain()
        DECODE_METRICS.reset()
        t0 = time.perf_counter()
        tickets = [engine.submit(q) for q in spec_prompts]
        engine.drain()
        wall = time.perf_counter() - t0
        snap = DECODE_METRICS.snapshot()
        return {
            "tok_per_s": sum(len(tk.tokens) for tk in tickets) / wall,
            "acceptance": float(snap.get("spec_acceptance_rate", 0.0)),
        }

    greedy = run_spec(spec=0)
    spec = run_spec(spec=4, draft_ngram=2)
    selfdraft = run_spec(spec=4, draft_layers=1)
    speedup = spec["tok_per_s"] / max(greedy["tok_per_s"], 1e-9)
    assert spec["acceptance"] >= 0.5, (
        f"draft acceptance below the 0.5 gate: {spec['acceptance']:.3f}"
    )
    assert speedup >= 1.5, (
        f"speculative decode speedup below 1.5x: {speedup:.2f} "
        f"({greedy['tok_per_s']:.1f} -> {spec['tok_per_s']:.1f} tok/s)"
    )
    _emit(
        "decode_spec_acceptance_rate",
        spec["acceptance"],
        "ratio",
        gate=0.5,
        spec_tokens=4,
        draft_ngram=2,
        acceptance_selfdraft=round(selfdraft["acceptance"], 4),
        queries=N_SPEC,
    )
    _emit(
        "decode_spec_tokens_per_s",
        spec["tok_per_s"],
        "tokens/s",
        gate_speedup=1.5,
        speedup_vs_greedy=round(speedup, 2),
        greedy_tok_per_s=round(greedy["tok_per_s"], 1),
        tok_per_s_selfdraft=round(selfdraft["tok_per_s"], 1),
        mode="prompt-lookup draft (ngram=2), k=4, commit = longest "
        "agreeing prefix + first correction; streams bitwise equal to "
        "greedy; self-draft (1 of 2 layers) reported alongside",
    )


def suite_etl() -> None:
    """ETL micro-bench: 1M-row select+filter+groupby through the
    columnar vectorized engine; vs_round1 is against the per-row
    engine's 10.6s on this host (VERDICT #3)."""
    import pathway_tpu as pw
    from pathway_tpu.internals.graph_runner import GraphRunner

    N = 1_000_000
    rng = np.random.default_rng(0)
    rows = list(zip(rng.integers(0, 1000, N).tolist(), rng.random(N).tolist()))

    class S(pw.Schema):
        a: int
        b: float

    t = pw.debug.table_from_rows(schema=S, rows=rows)
    r = t.select(pw.this.a, pw.this.b, c=pw.this.a * 2 + 1, d=pw.this.b * pw.this.a)
    r = r.filter(pw.this.c % 3 != 0)
    g = r.groupby(pw.this.a).reduce(
        pw.this.a, s=pw.reducers.sum(pw.this.d), n=pw.reducers.count()
    )
    runner = GraphRunner()
    cap, _names = runner.capture(g)
    t0 = time.perf_counter()
    runner.run()
    dt = time.perf_counter() - t0
    pw.clear_graph()
    assert len(cap.state) == 1000 or len(cap.state) > 0
    _emit(
        "etl_1m_select_filter_groupby_rows_per_sec",
        N / dt,
        "rows/s",
        wall_s=round(dt, 2),
        vs_round1=round(10.6 / dt, 2),
        mode="columnar vectorized engine, single worker",
    )


def suite_serving_qps() -> None:
    """Sustained-QPS overload suite for the serving plane: bursty
    arrivals (24 queries every 50ms, ~480/s offered) against a
    simulated device whose fused dispatch costs base+per-item time,
    with a periodic slow-device chaos injection. Run twice:

    - shed ON: admission control (bounded queue, 100ms deadlines) +
      adaptive batching. Expect bounded p99 on completed queries and an
      explicit shed_rate.
    - shed OFF (control): same arrivals, no admission, unbounded queue,
      no deadlines. Expect the queue to grow and p99 to blow up —
      quantifying what the admission plane buys.
    """
    import threading as _threading

    from pathway_tpu import tracing as _trc
    from pathway_tpu.resilience import chaos as _chaos
    from pathway_tpu.serving import (
        AdaptiveBatcher,
        AdmissionController,
        Deadline,
        OverloadError,
        ServingConfig,
    )
    from pathway_tpu.serving.metrics import ServingMetrics

    BURST, PERIOD_S, ROUNDS = 24, 0.05, 50  # ~480 q/s offered for 2.5s
    BUDGET_MS = 100.0
    BASE_S, PER_ITEM_S = 0.003, 0.0015  # fused dispatch: 3ms + 1.5ms/item

    def run_once(shed: bool):
        latencies: list[float] = []
        journeys: list[tuple] = []  # (arrival, done, TraceContext)
        shed_count = [0]
        lock = _threading.Lock()
        metrics = ServingMetrics()
        cfg = ServingConfig(
            max_queue=32 if shed else 1_000_000,
            default_deadline_ms=BUDGET_MS if shed else None,
            batch_max=8,
            batch_window_ms=2.0,
            latency_budget_ms=BUDGET_MS,
            query_share=0.5,
        )
        ctl = AdmissionController(cfg, metrics=metrics) if shed else None

        def dispatch(items):
            time.sleep(BASE_S + PER_ITEM_S * len(items))
            done = time.monotonic()
            with lock:
                for arrival, ticket in items:
                    latencies.append((done - arrival) * 1e3)
                    if ctl is not None and ticket is not None:
                        if ticket.trace is not None:
                            journeys.append((arrival, done, ticket.trace))
                        ctl.release(ticket)

        def on_expired(item):
            _arrival, ticket = item
            with lock:
                shed_count[0] += 1
            if ctl is not None and ticket is not None:
                ctl.release(ticket)

        batcher = AdaptiveBatcher(
            dispatch, config=cfg, metrics=metrics, on_expired=on_expired
        )
        # periodic slow-device injection: every 5th dispatch stalls 20ms
        _chaos.activate(
            {
                "site": "serving.before_dispatch",
                "action": "delay",
                "delay_s": 0.02,
                "hit": 5,
                "repeat": True,
            }
        )
        t0 = time.perf_counter()
        try:
            for _ in range(ROUNDS):
                for _ in range(BURST):
                    deadline = Deadline(cfg.default_deadline_ms)
                    ticket = None
                    if ctl is not None:
                        try:
                            ticket = ctl.admit(deadline)
                        except OverloadError:
                            with lock:
                                shed_count[0] += 1
                            continue
                    batcher.submit(
                        (time.monotonic(), ticket),
                        deadline,
                        trace=ticket.trace if ticket is not None else None,
                    )
                time.sleep(PERIOD_S)
            # drain: give in-flight work (bounded when shedding) time out
            drain_until = time.monotonic() + (2.0 if shed else 10.0)
            while batcher.pending() and time.monotonic() < drain_until:
                time.sleep(0.02)
        finally:
            _chaos.deactivate()
            batcher.stop()
        wall = time.perf_counter() - t0
        # close each journey's root span off the timed path: the member
        # queue/dispatch spans are recorded by the batcher thread after
        # the dispatch callback returns, so the root (whose finish
        # completes the trace and triggers exemplar retention) must be
        # recorded last — and recording here keeps tracing bookkeeping
        # out of the measured window entirely
        for arrival, done, trace in journeys:
            _trc.record_span(
                "request", start_mono=arrival, end_mono=done, root_of=trace
            )
        offered = BURST * ROUNDS
        lat = sorted(latencies)

        def pct(p):
            return lat[min(len(lat) - 1, int(p * len(lat)))] if lat else float("inf")

        return {
            "offered": offered,
            "completed": len(lat),
            "shed": shed_count[0],
            "shed_rate": shed_count[0] / offered,
            "p50_ms": pct(0.50),
            "p99_ms": pct(0.99),
            "goodput_qps": len(lat) / wall,
            "wall_s": wall,
        }

    on = run_once(shed=True)
    off = run_once(shed=False)

    _emit(
        "serving_qps_at_p99_budget",
        on["goodput_qps"] if on["p99_ms"] <= BUDGET_MS * 1.5 else 0.0,
        "queries/s",
        p50_ms=round(on["p50_ms"], 2),
        p99_ms=round(on["p99_ms"], 2),
        p99_budget_ms=BUDGET_MS,
        shed_rate=round(on["shed_rate"], 4),
        offered_qps=round(BURST / PERIOD_S, 1),
        completed=on["completed"],
        mode="admission(max_queue=32) + 100ms deadlines + adaptive "
        "batching, bursty 24q/50ms arrivals, periodic 20ms slow-device "
        "chaos on serving.before_dispatch",
    )
    _emit(
        "serving_shed_off_p99_blowup",
        (off["p99_ms"] / on["p99_ms"]) if on["p99_ms"] > 0 else float("inf"),
        "ratio",
        shed_on_p99_ms=round(on["p99_ms"], 2),
        shed_off_p99_ms=round(off["p99_ms"], 2),
        shed_off_completed=off["completed"],
        note="control: same arrivals with no admission/deadlines — the "
        "unbounded queue's p99 vs the shed-on bounded p99; >1 means the "
        "admission plane is buying bounded latency, not hiding work",
    )

    # -- request tracing: on/off overhead + tail attribution ------------
    # Same shed-on workload run again with the per-request tracing plane
    # enabled: every admitted query gets a journey (admission → queue →
    # dispatch spans), the slowest ones survive as exemplars, and the
    # tail-attribution report says where each slow request's wall went.
    # Two gates ride on this: per-stage attribution must cover ≥95% of
    # each reported request's wall, and tracing-on p50 must stay within
    # 5% of tracing-off (min-of-2 per side to shed scheduler noise).
    import tempfile as _tempfile

    from pathway_tpu import tracing as _trc

    off2 = run_once(shed=True)
    prev_tracing = _trc.set_tracing_enabled(True)
    try:
        _trc.TRACE_STORE.reset()
        traced_runs = [run_once(shed=True), run_once(shed=True)]
        report = _trc.slow_report(_trc.TRACE_STORE.exemplar_traces(), top_n=10)
        print(_trc.render_slow_report(report), flush=True)
        dump_dir = _tempfile.mkdtemp(prefix="pathway-bench-traces-")
        dump_path = _trc.TRACE_STORE.dump(dump_dir)
    finally:
        _trc.set_tracing_enabled(prev_tracing)

    p50_off = min(on["p50_ms"], off2["p50_ms"])
    p50_on = min(r["p50_ms"] for r in traced_runs)
    rows = report["traces"]
    min_coverage = min((r["coverage"] for r in rows), default=0.0)
    _emit(
        "serving_tracing_overhead_p50",
        (p50_on / p50_off) if p50_off > 0 else float("inf"),
        "ratio",
        p50_on_ms=round(p50_on, 2),
        p50_off_ms=round(p50_off, 2),
        target="<1.05 (tracing must cost <5% p50)",
        note="same shed-on workload, tracing off vs on; min-of-2 p50 "
        "per side",
    )
    _emit(
        "serving_tracing_attribution_coverage",
        min_coverage,
        "fraction",
        traces=len(rows),
        slowest_trace=rows[0]["trace_id"][:16] if rows else "",
        slowest_wall_ms=rows[0]["wall_ms"] if rows else 0.0,
        aggregate_pct=report["aggregate_pct"],
        target=">=0.95 (stage spans must explain each slow request)",
        note="min interval-union coverage across the top-10 slowest "
        "retained exemplar traces",
    )

    # the post-mortem path must reproduce the same breakdown: dump the
    # store and ask the CLI for its slow report over the dump files
    cli_ok = 0.0
    cli_note = "pathway trace slow over the run's dump"
    try:
        from click.testing import CliRunner

        from pathway_tpu.cli import cli as _pathway_cli

        res = CliRunner().invoke(
            _pathway_cli, ["trace", "slow", "--dir", dump_dir, "--top", "10"]
        )
        print(res.output, flush=True)
        if res.exit_code == 0 and rows and rows[0]["trace_id"][:16] in res.output:
            cli_ok = 1.0
        else:
            cli_note = f"exit={res.exit_code}: {res.output[:160]!r}"
    except Exception as exc:  # pragma: no cover - bench robustness
        cli_note = f"{type(exc).__name__}: {exc}"
    _emit(
        "serving_tracing_cli_roundtrip",
        cli_ok,
        "bool",
        dump=dump_path or "",
        note=cli_note,
    )


CLUSTER_MTTR_PROGRAM = """
import os, time
import pathway_tpu as pw
from pathway_tpu.io._connector import input_table_from_reader
from pathway_tpu.internals import flight_recorder

N = int(os.environ["CM_N"])
NPROC = int(os.environ.get("PATHWAY_PROCESSES", "1"))
WORDS = ["cat", "dog", "bird"]

class S(pw.Schema):
    word: str

def reader(ctx):
    start = int(ctx.offsets.get("pos", 0))
    for i in range(N):
        if i % NPROC != ctx.process_id:
            continue
        if i < start:
            continue
        ctx.insert({"word": WORDS[i % 3]}, offsets={"pos": i + 1})
        ctx.commit()
        time.sleep(0.02)

t = input_table_from_reader(
    S, reader, name="cm_src", parallel_readers=True,
    persistent_id="cm", supports_offsets=True,
    autocommit_duration_ms=50,
)
c = t.groupby(pw.this.word).reduce(pw.this.word, n=pw.reducers.count())
pw.io.jsonlines.write(c, os.environ["CM_OUT"] + "." + os.environ.get("PATHWAY_PROCESS_ID", "0"))
pw.run(
    monitoring_level="none",
    persistence_config=pw.persistence.Config.simple_config(
        pw.persistence.Backend.filesystem(os.environ["CM_STORE"]),
        snapshot_interval_ms=200,
    ),
)
if int(os.environ.get("PATHWAY_PROCESS_ID", "0")) == 0:
    # the coordinator process survives the partial restart, so its ring
    # holds the whole story: delivered epochs, the lease expiry, the
    # partial restart, and the post-restart delivered epochs
    flight_recorder.dump("bench.end")
"""


def suite_cluster_mttr() -> None:
    """Cluster fault-domain suite. Two segments:

    - **degraded serving** (in-process): one shard marked down in
      CLUSTER_HEALTH; shed mode keeps answering every healthy-shard
      query and sheds the down shard's with a typed 503, degrade mode
      converts them to degraded tickets instead.
    - **detection latency + MTTR** (2-process cluster): a chaos
      partition rule silences worker 1's side of the cluster channel
      mid-run; the coordinator's lease expires, it runs a partial
      restart, and the worker rejoins once the partition heals.
      detection = lease expiry minus the last pre-failure delivered
      epoch; MTTR = first post-restart delivered epoch minus the lease
      expiry. Both read from the coordinator's flight-recorder ring.
    """
    import os
    import shutil
    import subprocess
    import sys
    import tempfile

    from pathway_tpu.resilience.cluster import CLUSTER_HEALTH
    from pathway_tpu.serving import (
        AdmissionController,
        ServingConfig,
        ShardUnavailable,
    )
    from pathway_tpu.serving.metrics import ServingMetrics

    # -- segment 1: shed-mode serving keeps answering healthy shards --
    CLUSTER_HEALTH.mark_down([1], retry_after_s=1.5)
    try:
        ctl = AdmissionController(
            ServingConfig(max_queue=256), metrics=ServingMetrics()
        )
        healthy = down_shed = 0
        for i in range(200):
            try:
                ticket = ctl.admit(shard=i % 2)
                ctl.release(ticket)
                healthy += 1
            except ShardUnavailable:
                down_shed += 1
        dctl = AdmissionController(
            ServingConfig(max_queue=256, shed="degrade"),
            metrics=ServingMetrics(),
        )
        degraded = 0
        for _ in range(100):
            ticket = dctl.admit(shard=1)
            degraded += int(ticket.degraded)
            dctl.release(ticket)
    finally:
        CLUSTER_HEALTH.mark_all_up()
    _emit(
        "cluster_degraded_serving",
        healthy,
        "queries",
        offered=200,
        healthy_shard_answered=healthy,
        down_shard_shed=down_shed,
        degrade_mode_degraded=degraded,
        mode="shard 1 down: shed mode answers every healthy-shard query "
        "and 503s the down shard's; degrade mode serves them degraded",
    )

    # -- segment 2: partition -> lease expiry -> partial restart --
    tmp = tempfile.mkdtemp(prefix="pathway-bench-mttr-")
    try:
        prog = os.path.join(tmp, "cm.py")
        with open(prog, "w") as f:
            f.write(CLUSTER_MTTR_PROGRAM)
        import socket as _socket

        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        lease_ms = 1200.0
        # worker 1 goes silent on its 25th cluster-channel send: replies
        # AND heartbeats dropped, for longer than one lease — a partition
        # shorter than the lease is sub-lease message loss, which TCP
        # excludes and the lease cannot see. generation=0 keeps the rule
        # from re-arming after the regroup bumps the generation.
        chaos_spec = json.dumps(
            {
                "site": "cluster.send",
                "action": "partition",
                "process": 1,
                "hit": 25,
                "duration_s": 2.5,
                "generation": 0,
            }
        )
        procs = []
        for pid in range(2):
            env = dict(os.environ)
            env.pop("PATHWAY_CHAOS", None)
            env.update(
                CM_N="240",
                CM_OUT=os.path.join(tmp, "out.jsonl"),
                CM_STORE=os.path.join(tmp, "store"),
                JAX_PLATFORMS="cpu",
                PATHWAY_THREADS="1",
                PATHWAY_PROCESSES="2",
                PATHWAY_PROCESS_ID=str(pid),
                PATHWAY_FIRST_PORT=str(port),
                PATHWAY_CLUSTER_TOKEN="bench-mttr",
                PATHWAY_CLUSTER_LEASE_MS=str(lease_ms),
                PATHWAY_CLUSTER_RESPAWN="0",
                # the partition outlives the first re-formation, so a
                # second regroup is expected; leave headroom
                PATHWAY_CLUSTER_PARTIAL_RESTARTS="5",
                PATHWAY_CHAOS=chaos_spec,
                PATHWAY_FLIGHT_RECORDER_DIR=os.path.join(tmp, "blackbox"),
                # the ring must hold the whole run: the default 512
                # events get evicted by post-restart epochs before the
                # bench.end dump is written
                PATHWAY_FLIGHT_RECORDER_SIZE="16384",
                PYTHONPATH=os.path.dirname(os.path.abspath(__file__))
                + os.pathsep
                + env.get("PYTHONPATH", ""),
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, prog],
                    env=env,
                    cwd=tmp,
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.PIPE,
                    text=True,
                )
            )
        errs = []
        for p in procs:
            try:
                _, err = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                p.kill()
                _, err = p.communicate()
            errs.append((p.returncode, (err or "")[-2000:]))
        if any(rc != 0 for rc, _ in errs):
            raise RuntimeError(f"cluster run failed: {errs}")

        from pathway_tpu.internals import flight_recorder as fr

        dump_dir = os.path.join(tmp, "blackbox")
        final = None
        for path in fr.list_dumps(dump_dir):
            data = fr.load_dump(path)
            if data.get("reason") == "bench.end":
                final = data
        if final is None:
            raise RuntimeError(
                f"no bench.end dump in {sorted(os.listdir(dump_dir))}"
            )
        events = final["events"]
        delivered = [e["time"] for e in events if e["kind"] == "epoch.delivered"]
        expiries = [
            e["time"] for e in events if e["kind"] == "cluster.lease_expired"
        ]
        restarts = [
            e["time"] for e in events if e["kind"] == "cluster.partial_restart"
        ]
        if not (expiries and restarts):
            raise RuntimeError(
                f"no lease expiry / partial restart in the ring: "
                f"{sorted({e['kind'] for e in events})}"
            )
        detect_at, restart_at = expiries[0], restarts[0]
        before = [t for t in delivered if t < detect_at]
        after = [t for t in delivered if t > restart_at]
        if not (before and after):
            raise RuntimeError(
                f"delivered epochs do not bracket the failure "
                f"(before={len(before)}, after={len(after)})"
            )
        detection_ms = (detect_at - before[-1]) * 1e3
        mttr_ms = (after[0] - detect_at) * 1e3
        _emit(
            "cluster_detection_latency_ms",
            detection_ms,
            "ms",
            lease_ms=lease_ms,
            device="host only (2 processes, JAX_PLATFORMS=cpu)",
            note="lease expiry minus the last delivered epoch before it; "
            "bounded by the lease plus one epoch",
        )
        _emit(
            "cluster_mttr_ms",
            mttr_ms,
            "ms",
            partial_restarts=len(restarts),
            delivered_before=len(before),
            delivered_after=len(after),
            device="host only (2 processes, JAX_PLATFORMS=cpu)",
            note="first delivered epoch after the partial restart minus "
            "the lease expiry: regroup + re-formation + snapshot replay",
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def suite_encoder_mfu() -> None:
    """Kernel microbench for the fused encoder layer, per seq bucket.

    Two legs share one entry point so `bench.py suite_encoder_mfu` is
    runnable anywhere:

    - off-TPU (CI / tier-1): the SAME pallas kernel in interpret mode at
      miniature geometry — asserts the ragged (lens-driven) dispatch is
      bit-identical to the dense dispatch on the live rows, dead
      all-padding blocks come back zero, and the kernel matches the
      per-op XLA module. Green here means a kernel regression can't hide
      behind "no TPU in CI".
    - on TPU: per-bucket achieved model TFLOP/s of the real kernel, plus
      the pad-skip speedup when half the rows are padding (the ragged
      grid should approach 2x — that is the 150-wordpiece tax refund).
    """
    import jax

    if jax.default_backend() == "tpu":
        _encoder_mfu_measure()
    else:
        _encoder_mfu_interpret_check()


def _encoder_mfu_interpret_check() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import EncoderConfig, TextEncoder, init_params
    from pathway_tpu.ops.fused_layer import (
        _pack_rows,
        encoder_flops_per_token,
        encoder_forward,
    )

    cfg = EncoderConfig(
        vocab_size=1000,
        hidden_size=32,
        num_layers=2,
        num_heads=2,
        intermediate_size=64,
        max_position=64,
    )
    module = TextEncoder(cfg)
    params = init_params(module, cfg)
    rng = np.random.default_rng(0)
    for seq in (16, 32):
        p = _pack_rows(seq)
        b = p  # one live block exactly, so the ragged run appends a dead one
        ids = rng.integers(5, 999, (b, seq)).astype(np.int32)
        lens = rng.integers(max(1, seq // 2), seq + 1, (b,)).astype(np.int32)
        mask = np.arange(seq)[None, :] < lens[:, None]
        dense = np.asarray(
            encoder_forward(
                params, cfg, jnp.asarray(ids), jnp.asarray(mask),
                lens=jnp.asarray(lens), interpret=True,
            )
        )
        ids_r = np.concatenate([ids, np.zeros_like(ids)], axis=0)
        lens_r = np.concatenate([lens, np.zeros_like(lens)])
        mask_r = np.arange(seq)[None, :] < lens_r[:, None]
        ragged = np.asarray(
            encoder_forward(
                params, cfg, jnp.asarray(ids_r), jnp.asarray(mask_r),
                lens=jnp.asarray(lens_r), interpret=True,
            )
        )
        if not np.array_equal(ragged[:b], dense):
            raise AssertionError(
                f"ragged dispatch != dense dispatch on live rows at seq={seq}"
            )
        if not np.all(ragged[b:] == 0.0):
            raise AssertionError(f"dead all-padding block not zeroed at seq={seq}")
        ref = np.asarray(module.apply(params, jnp.asarray(ids), jnp.asarray(mask)))
        err = float(np.abs(ref - dense).max())
        if err > 3e-2:
            raise AssertionError(f"kernel vs XLA parity err {err} at seq={seq}")
        _emit(
            "encoder_mfu_interpret_parity",
            err,
            "max_abs_err",
            seq=seq,
            rows_per_block=p,
            gflops_per_row=round(seq * encoder_flops_per_token(cfg, seq) / 1e9, 6),
            note="CPU leg: interpret-mode kernel; ragged==dense bitwise, "
            "dead blocks zeroed, XLA parity within bf16 tolerance",
        )


def _encoder_mfu_measure() -> None:
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import EncoderConfig, TextEncoder, init_params
    from pathway_tpu.ops.fused_layer import encoder_flops_per_token, encoder_forward

    cfg = EncoderConfig.minilm_l6()
    params = init_params(TextEncoder(cfg), cfg)
    rng = np.random.default_rng(0)
    rounds = 10
    for seq in (32, 128, 160, 256):
        B = 4096 if seq <= 160 else 2048

        def run(p, ids, mask, lens):
            return jnp.sum(encoder_forward(p, cfg, ids, mask, lens=lens)[:, 0])

        fn = jax.jit(run)
        ids = jax.device_put(rng.integers(999, 29000, (B, seq)).astype(np.int32))
        mask = jax.device_put(np.ones((B, seq), bool))
        full = jax.device_put(np.full((B,), seq, np.int32))
        # half the rows are padding: the ragged grid skips their blocks
        lens_half = np.full((B,), seq, np.int32)
        lens_half[B // 2:] = 0
        mask_half = np.arange(seq)[None, :] < lens_half[:, None]
        mask_half_d = jax.device_put(mask_half)
        half = jax.device_put(lens_half)

        def timed(m, l) -> float:
            fn(params, ids, m, l).block_until_ready()  # compile + warm
            t0 = time.perf_counter()
            out = None
            for _ in range(rounds):
                out = fn(params, ids, m, l)
            out.block_until_ready()
            return time.perf_counter() - t0

        dt_dense = timed(mask, full)
        dt_half = timed(mask_half_d, half)
        tflops = rounds * B * seq * encoder_flops_per_token(cfg, seq) / dt_dense / 1e12
        _emit(
            "encoder_mfu_tflops",
            tflops,
            "TFLOP/s",
            seq=seq,
            batch=B,
            mode="dense, per-layer fused kernel",
        )
        _emit(
            "encoder_mfu_pad_skip_speedup",
            dt_dense / dt_half,
            "x",
            seq=seq,
            note="half the rows all-padding; the ragged grid should "
            "approach 2x by skipping their blocks",
        )


def suite_hbm_ledger() -> None:
    """Resource-ledger accounting suite: churn the index and decode
    planes, then audit the ledger's books two ways.

    - hbm_accounted_fraction: ledger total vs the device's own live
      array bytes (``jax.live_arrays``). Gate >= 0.9 — the ledger must
      explain at least 90% of what the device is actually holding; on
      CPU the per-account rows are additionally checked exactly against
      the backing arrays' nbytes.
    - time_to_oom_forecast_error: a constant-rate synthetic ramp
      replayed through the watchdog's growth EWMA; relative error of
      the forecast vs the analytic headroom/rate answer.
    """
    import gc

    import jax

    from pathway_tpu.decode import DecodeConfig, DecodeEngine, DecoderConfig
    from pathway_tpu.internals.ledger import (
        DEFAULT_RULES,
        LEDGER,
        HealthWatchdog,
        pytree_nbytes,
    )
    from pathway_tpu.ops.tiered_knn import TierConfig, TieredKnnIndex

    LEDGER.reset()
    # arrays allocated before this suite (other suites/tests in the same
    # process) are not the ledger's to explain — audit only our growth
    pre_existing = {id(a) for a in jax.live_arrays()}
    rng = np.random.default_rng(11)

    # index plane: a tiered index whose hot slab holds half the corpus
    dim = 96
    n_docs = 8_000
    vecs = rng.normal(size=(n_docs, dim)).astype(np.float32)
    idx = TieredKnnIndex(
        dim=dim,
        metric="cos",
        reserved_space=n_docs,
        tiers=TierConfig(
            hot_rows=n_docs // 2, n_clusters=32, n_probe=8, cold_dtype="int8"
        ),
    )
    idx.add_batch_arrays(list(range(n_docs)), vecs)
    q = rng.normal(size=(8, dim)).astype(np.float32)
    idx.search_batch(q, 10)  # sync: uploads the hot slab, books index.hot

    # decode plane: a small engine — books decode.kv (pool) + weights
    mcfg = DecoderConfig(
        vocab_size=4000,
        hidden_size=128,
        num_layers=2,
        num_heads=4,
        intermediate_size=256,
        max_position=128,
    )
    dcfg = DecodeConfig(
        pages=128,
        page_size=16,
        lanes=4,
        max_new_tokens=8,
        degrade_max_new_tokens=4,
        max_seq=96,
        impl="auto",
    )
    engine = DecodeEngine(mcfg, dcfg)
    for n in (8, 12, 16, 24):
        engine.submit(rng.integers(1, mcfg.vocab_size, int(n)).tolist())
    engine.drain()

    gc.collect()  # drop step temporaries before auditing live arrays
    snap = LEDGER.snapshot()
    accounts = snap["accounts"]
    for name in ("index.hot", "decode.kv", "weights"):
        assert name in accounts, f"ledger missing account {name!r}"
    live_bytes = sum(
        int(a.nbytes) for a in jax.live_arrays() if id(a) not in pre_existing
    )
    fraction = snap["total_bytes"] / live_bytes if live_bytes else 0.0

    exact_cpu = jax.default_backend() == "cpu"
    if exact_cpu:
        hot = idx.hot
        want_hot = sum(
            int(a.nbytes) for a in (hot._dev_matrix, hot._dev_valid, hot._dev_bias)
        )
        assert accounts["index.hot"]["bytes"] == want_hot
        assert accounts["decode.kv"]["bytes"] == int(engine.pool.pool_bytes)
        assert accounts["weights"]["bytes"] == pytree_nbytes(engine.params)

    # forecast accuracy: 1 MiB/s ramp against a 16 GiB budget for 20
    # one-second samples; analytic answer is headroom / rate
    budget = 16 * 2**30
    rate = float(2**20)
    wd = HealthWatchdog(rules=DEFAULT_RULES, budget_bytes=budget)
    n_samples = 20
    forecast = None
    for i in range(n_samples):
        v = wd.evaluate_once({"t": float(i), "hbm_bytes": int(rate * i)})
        for r in v["rules"]:
            if r["name"] == "hbm_headroom":
                forecast = r["value"]
    analytic = (budget - rate * (n_samples - 1)) / rate
    assert forecast is not None, "watchdog produced no time-to-OOM forecast"
    forecast_err = abs(forecast - analytic) / analytic
    _emit(
        "time_to_oom_forecast_error",
        forecast_err,
        "relative",
        gate=0.1,
        forecast_s=round(float(forecast), 1),
        analytic_s=round(analytic, 1),
        samples=n_samples,
        mode="constant 1 MiB/s ramp through the growth EWMA (alpha 0.25)",
    )
    _emit(
        "hbm_accounted_fraction",
        fraction,
        "fraction",
        gate=0.9,
        ledger_bytes=snap["total_bytes"],
        device_live_bytes=live_bytes,
        accounts={k: v["bytes"] for k, v in accounts.items()},
        exact_cpu_check=exact_cpu,
        mode="tiered index hot slab + paged-KV pool + decoder weights "
        "audited against jax.live_arrays",
    )
    LEDGER.reset()


def suite_tenant_isolation() -> None:
    """Multi-tenant noisy-neighbor suite: one tenant floods at 10x its
    QPS quota while the quiet tenants keep querying the SAME shared
    packed slab through the same admission controller and fair-share
    batcher. Three properties audited:

    - the flooder is held to its quota (admitted attempts stay near
      qps*elapsed + burst; the rest shed as typed 429
      ``tenant_rate_limited``);
    - the quiet tenants' p99 under contention holds within 1.2x of
      their solo baseline (``tenant_isolation_p99_ratio``, gate 1.2);
    - a tenant's masked top-k over the shared slab is bit-identical to
      a private index holding only its rows.

    PATHWAY_BENCH_TENANT_QUIET (default 99) sizes the quiet population,
    PATHWAY_BENCH_TENANT_QUERIES (default 3) the per-tenant query count
    — the bench_smoke CI gate runs a miniature 3-tenant version.
    """
    import threading

    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.serving import (
        AdaptiveBatcher,
        AdmissionController,
        OverloadError,
        ServingConfig,
    )
    from pathway_tpu.tenancy import TENANCY_METRICS, TenantPackedIndex, use_tenancy

    n_quiet = max(2, int(os.environ.get("PATHWAY_BENCH_TENANT_QUIET", "99") or 99))
    n_q = max(1, int(os.environ.get("PATHWAY_BENCH_TENANT_QUERIES", "3") or 3))
    dim, per_docs, k = 64, 32, 5
    flood_qps, flood_burst = 50.0, 8
    rng = np.random.default_rng(17)

    idx = TenantPackedIndex(dim=dim, metric="cos", reserved_space=1024)
    quiet = [f"t{i:03d}" for i in range(n_quiet)]
    flood = "flood"
    vecs = {}
    for t in quiet + [flood]:
        v = rng.normal(size=(per_docs, dim)).astype(np.float32)
        vecs[t] = v
        idx.add_tenant_batch(t, [f"{t}-{j}" for j in range(per_docs)], v)

    # bit-identity: the tenant mask must make the shared slab answer
    # exactly like a private index holding only this tenant's rows
    probe = quiet[0]
    solo_idx = DeviceKnnIndex(dim=dim, metric="cos", reserved_space=1024)
    solo_idx.add_batch_arrays(
        [f"{probe}-{j}" for j in range(per_docs)], vecs[probe]
    )
    qprobe = rng.normal(size=(4, dim)).astype(np.float32)
    assert idx.search_tenant_batch(probe, qprobe, k) == solo_idx.search_batch(
        qprobe, k
    ), "tenant-masked top-k diverged from a private index"

    queries = {
        t: rng.normal(size=(n_q, dim)).astype(np.float32) for t in quiet
    }
    flood_q = rng.normal(size=(1, dim)).astype(np.float32)
    tenancy_spec = {
        "quotas": {flood: {"qps": flood_qps, "burst": flood_burst}},
        "default": {"weight": 1.0},
    }

    def run_phase(with_flooder: bool):
        lat: list[float] = []
        lat_lock = threading.Lock()
        done = threading.Event()
        want = n_quiet * n_q
        count = [0]

        def dispatch(items):
            for tenant, q, t0 in items:
                idx.search_tenant_batch(tenant, q[None], k)
                if tenant != flood:
                    with lat_lock:
                        lat.append(time.perf_counter() - t0)
                        count[0] += 1
                        if count[0] >= want:
                            done.set()

        cfg = ServingConfig(max_queue=4096, default_deadline_ms=None)
        ac = AdmissionController(cfg, route="/bench/tenant")
        batcher = AdaptiveBatcher(dispatch, config=cfg, name="bench:tenant")
        shed = [0]
        admitted = [0]
        halt = threading.Event()

        def flooder():
            while not halt.is_set():
                try:
                    ticket = ac.admit(tenant=flood)
                except OverloadError:
                    shed[0] += 1
                else:
                    admitted[0] += 1
                    batcher.submit(
                        (flood, flood_q[0], time.perf_counter()), tenant=flood
                    )
                    ac.release(ticket)
                # 10x the quota's refill rate: one attempt per
                # 1/(10*qps) seconds
                halt.wait(1.0 / (10.0 * flood_qps))

        t_start = time.perf_counter()
        fl = None
        if with_flooder:
            fl = threading.Thread(target=flooder, daemon=True)
            fl.start()
        # quiet tenants interleave round-robin, paced only by admission
        for j in range(n_q):
            for t in quiet:
                ticket = ac.admit(tenant=t)
                batcher.submit(
                    (t, queries[t][j], time.perf_counter()), tenant=t
                )
                ac.release(ticket)
        done.wait(timeout=60.0)
        elapsed = time.perf_counter() - t_start
        halt.set()
        if fl is not None:
            fl.join(timeout=2.0)
        batcher.stop()
        assert count[0] >= want, (
            f"quiet queries incomplete: {count[0]}/{want}"
        )
        p99 = float(np.percentile(np.asarray(lat) * 1e3, 99))
        return p99, elapsed, admitted[0], shed[0]

    with use_tenancy(tenancy_spec):
        TENANCY_METRICS.reset()
        # warm the batch-1 masked-search compile so the solo baseline
        # measures steady state, not the first dispatch
        idx.search_tenant_batch(probe, qprobe[:1], k)
        solo_p99, _, _, _ = run_phase(with_flooder=False)
        cont_p99, elapsed, admitted, shed = run_phase(with_flooder=True)

    # the quota must actually have held the flooder: its admitted count
    # stays near bucket capacity for the window, and sheds happened
    quota_ceiling = flood_qps * elapsed + flood_burst
    assert admitted <= quota_ceiling * 1.5 + 5, (
        f"flooder over quota: {admitted} admits vs ceiling {quota_ceiling:.0f}"
    )
    ratio = cont_p99 / solo_p99 if solo_p99 > 0 else 0.0
    _emit(
        "tenant_isolation_p99_ratio",
        ratio,
        "ratio",
        gate=1.2,
        solo_p99_ms=round(solo_p99, 3),
        contended_p99_ms=round(cont_p99, 3),
        quiet_tenants=n_quiet,
        queries_per_tenant=n_q,
        flooder_admitted=admitted,
        flooder_shed=shed,
        flooder_quota_qps=flood_qps,
        bit_identical_packed_results=True,
        mode="1 tenant floods at 10x its QPS quota against "
        f"{n_quiet} quiet tenants on one shared packed slab; quiet p99 "
        "under contention vs solo baseline (gate 1.2)",
    )


def suite_chip_attribution() -> None:
    """Config 18: composed encode -> retrieve with the chip-time ledger
    on. The contract under test is the attribution itself: after a
    measured window of real device dispatches, the ledger's plane
    accounts (encode, index.search, index.merge, compile) must cover
    >= 0.95 of the measured wall (gate) — i.e. the booked
    device-seconds plus attributed stalls explain where the window
    went. Also reports per-plane shares and the accounting overhead
    (ledger-on wall vs ledger-off wall over the same work)."""
    from pathway_tpu.internals.chip_ledger import CHIP_LEDGER
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.models.sentence_encoder import SentenceEncoder
    from pathway_tpu.ops.knn import DeviceKnnIndex

    cfg = EncoderConfig(
        vocab_size=30522,
        hidden_size=128,
        num_layers=2,
        num_heads=4,
        intermediate_size=256,
        max_position=128,
    )
    enc = SentenceEncoder(config=cfg, max_seq_len=64, max_batch=512)
    texts = [f"chip attribution doc {i} tag {i % 17}" for i in range(512)]
    m = enc.tokenizer.batch_encode_matrix(texts, enc.max_seq_len)

    rng = np.random.default_rng(7)
    idx = DeviceKnnIndex(dim=384, metric="cos", reserved_space=20_000)
    idx.add_batch_arrays(
        list(range(20_000)), rng.normal(size=(20_000, 384)).astype(np.float32)
    )
    q = rng.normal(size=(64, 384)).astype(np.float32)

    def one_round() -> None:
        if m is not None:
            enc._encode_matrix(*m)
        idx.search_batch(q, 10)

    one_round()  # compile both planes outside every measured window
    rounds = 5
    # ledger-off baseline for the overhead number
    t0 = time.perf_counter()
    for _ in range(rounds):
        one_round()
    wall_off = time.perf_counter() - t0

    CHIP_LEDGER.reset()
    CHIP_LEDGER.set_enabled(True)
    try:
        t0 = time.perf_counter()
        for _ in range(rounds):
            one_round()
        wall_on = time.perf_counter() - t0
        snap = CHIP_LEDGER.snapshot(wall_on)
    finally:
        CHIP_LEDGER.set_enabled(None)
        CHIP_LEDGER.reset()

    overhead = wall_on / wall_off - 1.0 if wall_off > 0 else 0.0
    shares = {
        name: round(acc["share"], 3) for name, acc in snap["accounts"].items()
    }
    _emit(
        "chip_accounting_overhead",
        overhead,
        "fraction",
        wall_off_s=round(wall_off, 3),
        wall_on_s=round(wall_on, 3),
        gate=0.05,
        mode="same composed work, ledger off vs on (sync-to-read-clock tax)",
    )
    _emit(
        "chip_time_accounted_fraction",
        snap["accounted_fraction"],
        "fraction",
        gate=0.95,
        busy_s=round(snap["busy_seconds"], 3),
        wall_s=round(snap["wall_seconds"], 3),
        stranded_fraction=round(snap["stranded_fraction"], 3),
        dispatches=sum(a["dispatches"] for a in snap["accounts"].values()),
        plane_shares=shares,
        mode=f"{rounds} rounds of encode(512 docs)+search(64 q, 20k x 384), "
        "accounts: " + ", ".join(sorted(snap["accounts"])),
    )


def suite_freshness() -> None:
    """Config 20: end-to-end freshness plane under streaming churn. A
    python connector commits `rounds` batches of docs into a KNN index
    with the watermark plane on; every commit becomes an ingest epoch
    whose arrival->visible lag the plane measures and splits across the
    ingest_queue/staging/epoch/publish planes. Gated claim: the
    per-plane accrual split covers >= 0.95 of the measured end-to-end
    visibility lag (otherwise `pathway freshness` cannot attribute
    where the lag went). Also reports the lag distribution (p50/p99),
    the per-plane split, and the plane-on overhead over the identical
    churn workload with the plane off."""
    import pathway_tpu as pw
    from pathway_tpu.freshness import FRESHNESS
    from pathway_tpu.internals.graph_runner import GraphRunner
    from pathway_tpu.stdlib.ml.index import KNNIndex

    rounds, batch, dim = 12, 64, 32

    class _DocSchema(pw.Schema):
        doc: int

    class _Docs(pw.io.python.ConnectorSubject):
        def run(self):
            k = 0
            for _ in range(rounds):
                for _ in range(batch):
                    self.next(doc=k)
                    k += 1
                self.commit()

    def _emb(i: int):
        rng = np.random.default_rng(i)
        return tuple(float(v) for v in rng.normal(size=dim))

    def churn() -> float:
        docs = pw.io.python.read(
            _Docs(), schema=_DocSchema, autocommit_duration_ms=None
        )
        docs = docs.select(emb=pw.apply_with_type(_emb, pw.ANY, docs.doc))
        queries = pw.debug.table_from_markdown(
            """
            | doc
          1 | 3
        """
        )
        queries = queries.select(
            emb=pw.apply_with_type(_emb, pw.ANY, queries.doc)
        )
        index = KNNIndex(
            docs.emb,
            docs,
            n_dimensions=dim,
            reserved_space=rounds * batch,
            distance_type="cosine",
        )
        res = index.get_nearest_items(queries.emb, k=4, with_distances=True)
        runner = GraphRunner()
        runner.capture(res)
        t0 = time.perf_counter()
        runner.run()
        wall = time.perf_counter() - t0
        pw.clear_graph()
        return wall

    churn()  # compile the scatter/search programs outside the windows
    wall_off = min(churn() for _ in range(3))

    FRESHNESS.reset()
    FRESHNESS.set_enabled(True)
    try:
        wall_on = min(churn() for _ in range(3))
        snap = FRESHNESS.snapshot()
    finally:
        FRESHNESS.set_enabled(None)
        FRESHNESS.reset()

    lag = snap["lag"]
    planes_ms = {
        name: round(row["seconds"] * 1e3, 3)
        for name, row in snap["planes"].items()
        if row["events"]
    }
    overhead = wall_on / wall_off - 1.0 if wall_off > 0 else 0.0
    _emit(
        "freshness_visibility_lag_p50_ms",
        float(lag["p50_ms"]),
        "ms",
        n_samples=lag["count"],
        epochs=snap["epochs"],
        rounds=rounds,
        batch=batch,
        mode=f"{rounds} commits x {batch} docs into a {dim}-d KNN, "
        "arrival -> per-shard visible watermark",
    )
    _emit(
        "freshness_visibility_lag_p99_ms",
        float(lag["p99_ms"]),
        "ms",
        ewma_ms=round(float(lag["ewma_ms"] or 0.0), 3),
    )
    for name, ms in planes_ms.items():
        _emit(
            f"freshness_plane_{name}_ms",
            ms,
            "ms",
            events=snap["planes"][name]["events"],
        )
    _emit(
        "freshness_accounting_overhead",
        overhead,
        "fraction",
        wall_off_s=round(wall_off, 3),
        wall_on_s=round(wall_on, 3),
        gate=0.05,
        mode="same churn workload, plane off vs on (min of 3 each)",
    )
    _emit(
        "freshness_accrual_coverage",
        float(snap["coverage"] or 0.0),
        "fraction",
        gate=0.95,
        total_lag_ms=round(float(lag["total_s"]) * 1e3, 3),
        plane_split_ms=planes_ms,
        mode="sum of per-plane accruals over the measured e2e lag: "
        "`pathway freshness` must attribute >= 95% of where the lag went",
    )


def suite_elastic_reshard() -> None:
    """Config 19: elastic mesh — live 2->4 grow and 4->2 shrink under a
    step-function query load (the offered load doubles the moment the
    grow starts), on 8 virtual CPU devices. Three claims, all gated:

    - **zero dropped requests**: every query issued while the chunked
      migrations run is answered (served fraction == 1.0);
    - **bounded tail blowup**: p99 latency inside the migration
      windows stays under 2x the steady-state p99 (chunk imports hold
      the handle lock only per bounded chunk, never for the slab);
    - **bit-identical serving**: after grow + shrink the handle
      answers the probe queries byte-identically to its own
      never-resharded state (keys AND scores).

    MTTR here is the full migration wall (intent -> cutover) as the
    reshard protocol reports it, per direction.
    """
    prog = r"""
import json, threading, time
import numpy as np
import jax
from pathway_tpu import elastic
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.parallel.mesh import resolve_mesh

DIM, N, Q, K = 64, 4096, 16, 10
rng = np.random.default_rng(11)
vecs = rng.normal(size=(N, DIM)).astype(np.float32)
probes = rng.normal(size=(Q, DIM)).astype(np.float32)
load_q = rng.normal(size=(8, DIM)).astype(np.float32)

def canon(res):
    return [[(int(k), float(s)) for k, s in row] for row in res]

elastic.reset_registry()
# prewarm the XLA cache for every shape the migration will touch: a
# reshard target spawns at reserved_space=64 and grows through the
# shared per-shard-growth path, so throwaway indexes built the same
# way compile the identical programs (module-level jit cache). The
# gate measures migration mechanics, not one-time compiles — a real
# deployment serves these shapes long before it reshards.
for warm_shards in (2, 4):
    tmp = DeviceKnnIndex(DIM, mesh=resolve_mesh(warm_shards), reserved_space=64)
    tmp.add_batch_arrays(list(range(N)), vecs)
    tmp.search_batch(load_q, K)
    tmp.search_batch(probes, K)
    del tmp

idx = DeviceKnnIndex(DIM, mesh=resolve_mesh(2), reserved_space=N)
idx.add_batch_arrays(list(range(N)), vecs)
h = elastic.register_handle(idx)
baseline = canon(h.search_batch(probes, K))
h.search_batch(load_q, K)

samples = []   # (t_start, seconds, ok)
dropped = [0]
stop = threading.Event()
step_up = threading.Event()

def loader(wait_for_step):
    if wait_for_step and not step_up.wait(timeout=60):
        return
    while not stop.is_set():
        t0 = time.perf_counter()
        try:
            h.search_batch(load_q, K)
            samples.append((t0, time.perf_counter() - t0, True))
        except Exception:
            dropped[0] += 1
            samples.append((t0, time.perf_counter() - t0, False))

threads = [threading.Thread(target=loader, args=(w,)) for w in (False, True)]
for t in threads:
    t.start()

time.sleep(1.0)                  # steady state at base load, 2 shards
step_up.set()                    # load steps up ...
g0 = time.perf_counter()
grow = elastic.reshard(4, chunk_rows=256)   # ... and the mesh grows
g1 = time.perf_counter()
time.sleep(0.5)
s0 = time.perf_counter()
shrink = elastic.reshard(2, chunk_rows=256)
s1 = time.perf_counter()
time.sleep(0.5)
stop.set()
for t in threads:
    t.join()

after = canon(h.search_batch(probes, K))
in_window = [s for t0, s, _ in samples if g0 <= t0 <= g1 or s0 <= t0 <= s1]
# the blowup denominator must hold the offered load fixed: steady
# samples AT the stepped (doubled) load, outside both migration
# windows — otherwise the ratio charges the load step to the reshard
steady = [s for t0, s, _ in samples if t0 > g1 and not (s0 <= t0 <= s1)]
base = [s for t0, s, _ in samples if t0 < g0]
print(json.dumps({
    "served": sum(1 for _, _, ok in samples if ok),
    "dropped": dropped[0],
    "in_window": len(in_window),
    "p99_base_ms": float(np.percentile(np.asarray(base) * 1e3, 99)),
    "p99_steady_ms": float(np.percentile(np.asarray(steady) * 1e3, 99)),
    "p99_migrating_ms": float(np.percentile(np.asarray(in_window) * 1e3, 99)),
    "grow_mttr_s": grow["mttr_s"],
    "shrink_mttr_s": shrink["mttr_s"],
    "grow_rows": grow["rows_migrated"],
    "shrink_rows": shrink["rows_migrated"],
    "generation": shrink["generation"],
    "identical": after == baseline,
}))
"""
    row = _virtual_cpu_child(prog, "elastic reshard bench")
    emit = functools.partial(_emit, device=_VIRTUAL_CPU)
    offered = row["served"] + row["dropped"]
    served_frac = row["served"] / max(1, offered)
    blowup = row["p99_migrating_ms"] / max(1e-9, row["p99_steady_ms"])
    emit(
        "elastic_zero_drop_fraction",
        served_frac,
        "fraction",
        gate=1.0,
        offered=offered,
        dropped=row["dropped"],
        in_migration_window=row["in_window"],
        mode="step-function load (2nd loader joins at grow start) over "
        "live 2->4 grow + 4->2 shrink, 4096 docs, chunk_rows=256",
    )
    emit(
        "elastic_reshard_mttr_s",
        row["grow_mttr_s"],
        "s",
        shrink_mttr_s=round(row["shrink_mttr_s"], 3),
        rows_migrated=row["grow_rows"],
        final_generation=row["generation"],
        mode="full migration wall (durable intent -> atomic cutover) "
        "as reshard() reports it; value = 2->4 grow, extra = 4->2 shrink",
    )
    emit(
        "elastic_p99_blowup_ratio",
        blowup,
        "ratio",
        gate=2.0,
        p99_steady_ms=round(row["p99_steady_ms"], 3),
        p99_migrating_ms=round(row["p99_migrating_ms"], 3),
        p99_base_load_ms=round(row["p99_base_ms"], 3),
        mode="p99 of queries issued inside the migration windows over "
        "steady-state p99 at the SAME stepped load (same handle, same "
        "batch shape); p99_base_load_ms = pre-step single-loader p99",
    )
    emit(
        "elastic_bit_identical",
        1.0 if row["identical"] else 0.0,
        "fraction",
        gate=1.0,
        mode="post-grow+shrink probe answers (keys AND scores) equal the "
        "handle's never-resharded answers",
    )
    assert row["dropped"] == 0, f"{row['dropped']} queries dropped mid-reshard"
    assert row["identical"], "serving not bit-identical after grow+shrink"
    assert blowup < 2.0, f"migration p99 blowup {blowup:.2f}x >= 2x"


#: `--suite` registry; any name here is also directly invocable as
#: `python bench.py <suite_name>`
SUITES = (
    suite_etl,
    suite_serving_qps,
    suite_cluster_mttr,
    suite_knn_10k,
    suite_vector_store_ingest,
    suite_adaptive_rag_p50,
    suite_clip,
    suite_collab_ingest,
    suite_encoder_mfu,
    suite_streaming_8shard,
    suite_mesh_scaling,
    suite_streaming_tpu_chip,
    suite_knn_churn,
    suite_tiered_recall,
    suite_decode_serving,
    suite_hbm_ledger,
    suite_tenant_isolation,
    suite_chip_attribution,
    suite_freshness,
    suite_elastic_reshard,
)


def run_suite() -> list[str]:
    """Run every suite; returns the names of those that raised (the
    process then exits non-zero — see ``__main__``)."""
    import traceback

    failed = []
    for fn in SUITES:
        try:
            fn()
        except Exception as e:  # one config failing must not hide the rest
            failed.append(fn.__name__)
            _RECORDS.append({"metric": fn.__name__, "error": f"{type(e).__name__}: {e}"})
            print(
                json.dumps(
                    {
                        "metric": fn.__name__,
                        "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-1500:],
                    }
                ),
                flush=True,
            )
    return failed


def _startup() -> None:
    """What every ``python bench.py ...`` does first: refuse to run on
    the Python tokenizer fallback, place the compile cache, and name
    the device as the first line of output."""
    import jax

    from pathway_tpu import native
    from pathway_tpu.internals.compile_cache import configure_compile_cache

    if not native.is_available():
        raise SystemExit("bench.py: the native library did not build; refusing to run")
    configure_compile_cache()
    dev = jax.devices()
    print(
        json.dumps(
            {
                "platform": dev[0].platform,
                "device_kind": dev[0].device_kind,
                "device_count": len(dev),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    import sys

    _startup()
    _by_name = {fn.__name__: fn for fn in SUITES}
    named = [a for a in sys.argv[1:] if a in _by_name]
    failed: list[str] = []
    if named:
        for a in named:
            _by_name[a]()
        # suite-only invocations still end with the driver's FINAL
        # SUMMARY contract: the last record emitted is the headline
        if _RECORDS:
            print_final_summary(_RECORDS.pop())
    elif "--suite" in sys.argv:
        failed = run_suite()
    else:
        failed = main()
    if failed:  # every suite ran and printed; the exit code says some raised
        sys.exit(f"bench.py: suites failed: {', '.join(failed)}")
