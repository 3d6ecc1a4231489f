"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py        # one process; needs a TPU and nothing else

Drives the main path once — tokenize -> encode -> index scatter -> top-k
-> rerank -> decode — through the entry points a user calls, at the
published width of the models (all-MiniLM-L6-v2: 384 x 6 layers x 12
heads, vocab 30,522; the cross-encoder at the same width; the default
decoder). Weights are seeded, never loaded; nothing touches the network.
Every check fails the run: an exception, a mismatch, or a phase that did
not run exits non-zero, and only a full pass prints the result line.

On a host with four chips (or more) the server phase runs with
``pw.run(mesh=4)`` and a mesh embedder instead, and the placement and
one-chip-agreement checks of that mode are added.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import json
import os
import socket
import sys
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(HERE, "chip_smoke_out")  # git-ignored

# ---- tolerances, against the plain-XLA reference of each kernel -----------
# Encoder: activations are bf16 (8 significant bits) through 6 layers on
# both sides, with different fusion and accumulation order. Embeddings are
# unit vectors with components of ~0.05, so one bf16 ulp of a component is
# ~2e-4; the bounds leave a factor of five over what the v5e measured
# (min cos 0.999993, max abs err 1.1e-3).
ENCODER_MIN_COS = 0.9999
ENCODER_ATOL = 5e-3
# Attention kernels alone, bf16 probabilities and outputs on both sides:
# a few bf16 ulps (2^-8 each), measured as |got - want| / max(1, |want|).
ATTENTION_TOL = 3e-2
# Cross-encoder scores (f32 head over a bf16 trunk), O(1) values.
RERANK_ATOL = 5e-2
# Top-k: every neighbour returned must score, in exact host arithmetic,
# within this of the true k-th best, and returned scores must be this
# close to the exact ones. On TPU an f32 matmul at default precision
# multiplies in bf16, so near-ties may legitimately swap; the bound is one
# bf16 ulp of a score of 1.0 (2^-8). Measured on the v5e: up to 1.3e-3 on
# the server's nearly collinear embeddings, 4e-4 on random unit vectors.
TOPK_SCORE_TOL = 2.0**-8
# Paged decode attention, f32 on N(0, 1) data: the XLA reference multiplies
# in bf16 (default precision), so whatever Mosaic does with the kernel's f32
# dots the two differ by bf16-grade rounding of values up to ~3; a lane of
# length 1 returns v itself, rounded on one side only. Measured 7.8e-3.
PAGED_ATOL = 2e-2
# Selective scan at the published width of the hybrid embedder (a packed
# stream of 8,192 x 5,120 channels, state 16): float32 state on both sides; the kernel's exp
# and the order of its sums differ from XLA's, and its output is rounded to
# bf16 (2^-9 relative) on both. Relative to the largest output.
SCAN_RTOL = 1e-2
# Power retention at the published heads (40 on 8 of 128): the same bf16
# q, k, v on both sides; the kernel rounds a block's weights to bf16 before
# they meet the values and its output to bf16 (2^-9 relative each), the
# recurrence keeps float32 throughout. Relative to the largest output.
RETENTION_RTOL = 2e-2
# Decode: greedy tokens must equal the impl="xla" engine's, except that the
# first token to differ may be one the plain XLA forward scores within this
# of its best (logits have std ~0.3 and a typical top-2 gap of 0.05).
LOGIT_TIE_TOL = 2e-2

N_DOCS = 4096
#: words per document, by class; one token per word plus [CLS] and [SEP], so
#: length-sorted groups of 1,024 land in the 48, 96, 160 and 256 buckets
DOC_WORDS = ((30, 46), (70, 94), (130, 158), (200, 254))
K = 5
N_RAG_DOCS = 256
N_PROMPTS = 4
MAX_NEW = 16

_checks: list[str] = []
_t0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _t0:5.1f}s] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[chip_smoke] FAILED: {what}")
    _checks.append(what)
    say(f"ok: {what}")


def has_mosaic_kernel(jitted, *args, name: str | None = None, **kwargs) -> bool:
    """Whether the program XLA compiled for these arguments calls a
    Mosaic kernel (the one called ``name``, where given) — a path that
    quietly took the XLA route does not."""
    text = jitted.lower(*args, **kwargs).compile().as_text()
    return any("tpu_custom_call" in line and (name is None or f"%{name}" in line) for line in text.splitlines())


# ---- corpus ----------------------------------------------------------------


def make_corpus(rng: np.random.Generator) -> list[str]:
    """Each document repeats eight words of its own. With seeded (not
    trained) weights a mean-pooled embedding is a common vector plus a
    small part that depends on the words; documents of a few hundred
    all-different words come out with cosine 0.9995 to one another,
    closer than the index's bf16-multiply scores can tell apart."""
    vocab = [f"w{i:04d}" for i in range(5000)]
    docs = []
    for i in range(N_DOCS):
        lo, hi = DOC_WORDS[i % len(DOC_WORDS)]
        own = rng.choice(len(vocab), size=8, replace=False)
        words = own[rng.integers(0, len(own), size=int(rng.integers(lo, hi + 1)))]
        docs.append(" ".join(vocab[w] for w in words))
    return docs


def write_corpus(docs: list[str]) -> str:
    import shutil

    corpus_dir = os.path.join(WORK_DIR, "corpus")
    shutil.rmtree(corpus_dir, ignore_errors=True)
    os.makedirs(corpus_dir)
    for i, text in enumerate(docs):
        with open(os.path.join(corpus_dir, f"doc_{i:05d}.txt"), "w") as f:
            f.write(text)
    return corpus_dir


def doc_id_of(path: str) -> int:
    return int(os.path.basename(path)[len("doc_") : -len(".txt")])


# ---- kernels against their references -------------------------------------


def xla_encoder(cfg):
    """The flax module with every kernel choice set to the plain XLA chain."""
    from pathway_tpu.models.encoder import TextEncoder

    return TextEncoder(dataclasses.replace(cfg, layer_impl="xla", attention_impl="xla"))


def check_fused_layer(minilm, rng) -> None:
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import TextEncoder, init_params
    from pathway_tpu.ops.fused_layer import _pack_rows, encoder_forward

    xla = xla_encoder(minilm)
    params = init_params(TextEncoder(minilm), minilm)

    @jax.jit
    def kernel(p, ids, lens):
        mask = jnp.arange(ids.shape[1])[None, :] < lens[:, None]
        return encoder_forward(p, minilm, ids, mask, lens=lens)

    @jax.jit
    def reference(p, ids, lens):
        mask = jnp.arange(ids.shape[1])[None, :] < lens[:, None]
        return xla.apply(p, ids, mask)

    for seq in (32, 160, 256, 512):
        p = _pack_rows(seq)
        live = 2 * p + max(1, p // 2)  # two full blocks and a partial one
        batch = 4 * p  # ... and one block that is all padding
        ids = rng.integers(999, minilm.vocab_size, (batch, seq)).astype(np.int32)
        lens = np.zeros((batch,), np.int32)
        lens[:live] = rng.integers(max(2, seq // 3), seq + 1, live)
        lens[0], lens[1] = seq, 1  # every row tile live, and one token of one tile
        got = np.asarray(kernel(params, ids, lens))
        want = np.asarray(reference(params, ids, lens))
        check(bool(np.isfinite(got).all()), f"fused_layer S={seq}: finite")
        check(
            bool((got[-p:] == 0).all()),
            f"fused_layer S={seq}: the all-padding block comes back zero",
        )
        cos = float((got[:live] * want[:live]).sum(axis=1).min())
        err = float(np.abs(got[:live] - want[:live]).max())
        check(
            cos >= ENCODER_MIN_COS and err <= ENCODER_ATOL,
            f"fused_layer S={seq} B={batch} vs layer_impl=xla: "
            f"min cos {cos:.6f} (>= {ENCODER_MIN_COS}), max abs err {err:.2e} (<= {ENCODER_ATOL})",
        )
    check(
        has_mosaic_kernel(kernel, params, ids, lens),
        "encoder_forward program contains a tpu_custom_call",
    )


def attention_error(got, want, live) -> float:
    """Worst |got - want| / max(1, |want|) over the live positions."""
    return float((np.abs(got - want) / np.maximum(1.0, np.abs(want)) * live[:, :, None]).max())


def check_fused_attention(minilm, rng) -> None:
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops.fused_attention import _xla_reference, attention

    d, heads, batch = minilm.hidden_size, minilm.num_heads, 24
    fused = jax.jit(lambda qkv, m: attention(qkv, m, n_heads=heads, impl="fused"))
    ref = jax.jit(lambda qkv, m: _xla_reference(qkv, m, heads))
    for seq in (32, 160, 512):
        qkv = jnp.asarray(rng.normal(size=(batch, seq, 3 * d)), jnp.bfloat16)
        lens = rng.integers(max(2, seq // 3), seq + 1, batch)
        mask = jnp.asarray(np.arange(seq)[None, :] < lens[:, None])
        got = np.asarray(fused(qkv, mask), np.float32)
        want = np.asarray(ref(qkv, mask), np.float32)
        err = attention_error(got, want, np.asarray(mask))
        check(
            bool(np.isfinite(got).all()) and err <= ATTENTION_TOL,
            f"fused attention S={seq} vs _xla_reference: max err {err:.2e} (<= {ATTENTION_TOL})",
        )


def neighbours_within_tolerance(idx, true_scores, k: int) -> tuple[bool, float]:
    """``idx`` [q, k] against exact scores [q, n]: every returned
    neighbour must score within TOPK_SCORE_TOL of the true k-th best.
    Returns (ok, worst shortfall)."""
    kth = np.sort(true_scores, axis=1)[:, -k]
    got = np.take_along_axis(true_scores, idx, axis=1)
    distinct = all(len(set(row)) == len(row) for row in idx.tolist())
    shortfall = float((kth[:, None] - got).max())
    return distinct and shortfall <= TOPK_SCORE_TOL, shortfall


def check_pallas_knn(rng, mesh) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pathway_tpu.ops.pallas_knn import knn_topk, knn_topk_sharded

    n_docs, dim = 10_240, 384
    docs = rng.normal(size=(n_docs, dim)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    queries = docs[rng.choice(n_docs, 16, replace=False)] + 0.05 * rng.normal(
        size=(16, dim)
    ).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    exact = queries.astype(np.float64) @ docs.astype(np.float64).T
    bias = np.zeros((n_docs,), np.float32)
    for k in (10, 64):
        vals, idx = knn_topk(queries, docs, k=k, bias=bias)
        vals, idx = np.asarray(vals), np.asarray(idx)
        ok, shortfall = neighbours_within_tolerance(idx, exact, k)
        score_err = float(np.abs(vals - np.take_along_axis(exact, idx, axis=1)).max())
        same = float((idx == np.argsort(-exact, axis=1)[:, :k]).mean())
        check(
            ok and score_err <= TOPK_SCORE_TOL,
            f"knn_topk N={n_docs} k={k} vs exact host top-k: worst shortfall to the k-th "
            f"best {shortfall:.2e}, max score err {score_err:.2e} (<= {TOPK_SCORE_TOL:.1e}); "
            f"{same:.1%} of ranks identical",
        )
        xla_idx = np.asarray(jax.lax.top_k(jax.numpy.asarray(queries) @ docs.T, k)[1])
        check(
            neighbours_within_tolerance(xla_idx, exact, k)[0],
            f"lax.top_k k={k} meets the same rule (the reference is no more exact)",
        )
    check(
        has_mosaic_kernel(knn_topk, queries, docs, k=10, bias=bias),
        "knn_topk program contains a tpu_custom_call",
    )
    if mesh is None:
        return
    d_sh = jax.device_put(docs, NamedSharding(mesh, P("data", None)))
    b_sh = jax.device_put(bias, NamedSharding(mesh, P("data")))
    q_rep = jax.device_put(queries, NamedSharding(mesh, P()))
    vals, idx = knn_topk_sharded(q_rep, d_sh, b_sh, k=10, mesh=mesh)
    ok, shortfall = neighbours_within_tolerance(np.asarray(idx), exact, 10)
    check(ok, f"knn_topk_sharded over {mesh.shape['data']} chips: shortfall {shortfall:.2e}")


def check_topk_select(rng) -> None:
    """The query programs' two-stage selection against ``lax.top_k`` at
    a row the shape rule sends through the blocks: seeded scores with
    no ties, so values and slots must both be equal, exactly."""
    import jax

    from pathway_tpu.ops.knn import _select_topk, _topk_route

    q, n, k = 16, 409_600, 16
    scores = jax.numpy.asarray(rng.permutation(q * n).reshape(q, n).astype(np.float32) / (q * n))
    vals, idx = jax.jit(_select_topk, static_argnames="k")(scores, k=k)
    want_vals, want_idx = jax.lax.top_k(scores, k)
    check(
        _topk_route(n, k) == "blocks"
        and np.array_equal(np.asarray(vals), np.asarray(want_vals))
        and np.array_equal(np.asarray(idx), np.asarray(want_idx)),
        f"_select_topk [{q}, {n}] k={k} takes the block route and returns lax.top_k's "
        "values and slots exactly",
    )


def check_paged_attention(decoder, rng) -> None:
    import jax

    from pathway_tpu.ops.paged_attention import (
        paged_attention_reference,
        paged_decode_attention,
        pages_for,
    )

    d, heads, lanes, ctx = decoder.hidden_size, decoder.num_heads, 8, 512
    kernel = jax.jit(functools.partial(paged_decode_attention, n_heads=heads))
    reference = jax.jit(functools.partial(paged_attention_reference, n_heads=heads))
    for page_size in (8, 16, 32):
        pages_per_seq = ctx // page_size
        lens = np.array([0, 1, page_size - 1, page_size, 100, 257, ctx - 1, ctx], np.int32)
        n_pages = int(sum(pages_for(int(n), page_size) for n in lens)) + 3
        k_pages = rng.normal(size=(n_pages, page_size, d)).astype(np.float32)
        v_pages = rng.normal(size=(n_pages, page_size, d)).astype(np.float32)
        q = rng.normal(size=(lanes, d)).astype(np.float32)
        free = list(rng.permutation(n_pages))
        tables = np.full((lanes, pages_per_seq), n_pages, np.int32)  # sentinel
        for b, n in enumerate(lens):
            for j in range(pages_for(int(n), page_size)):
                tables[b, j] = free.pop()
        got = np.asarray(kernel(q, k_pages, v_pages, tables, lens))
        want = np.asarray(reference(q, k_pages, v_pages, tables, lens))
        err = float(np.abs(got - want).max())
        check(
            bool(np.isfinite(got).all()) and bool((got[0] == 0).all()) and err <= PAGED_ATOL,
            f"paged_decode_attention page={page_size} d={d} vs paged_attention_reference: "
            f"max abs err {err:.2e} (<= {PAGED_ATOL}), empty lane exactly zero",
        )
    check(
        has_mosaic_kernel(kernel, q, k_pages, v_pages, tables, lens),
        "paged_decode_attention program contains a tpu_custom_call",
    )


# ---- the server ------------------------------------------------------------


def check_selective_scan(rng) -> None:
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops.selective_scan import TIME_CHUNK, selective_scan, selective_scan_reference

    channels, n = 5120, 16
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (channels, n))
    d_skip = jnp.ones((channels,), jnp.float32)
    # a write batch: 32 documents of 18-256 tokens, each aligned to the
    # kernel's chunk, in a stream of 8,192 with room for 512; and the query
    # program's 8 x 16
    lens = rng.integers(18, 257, size=32)
    for tokens, doc_lens in ((8192, lens), (128, np.full(8, 11))):
        padded = -(-doc_lens // TIME_CHUNK) * TIME_CHUNK
        begins = np.cumsum(padded) - padded
        starts = np.full((tokens // TIME_CHUNK,), tokens, np.int32)
        starts[: len(begins)] = begins
        live = int(begins[-1] + doc_lens[-1])
        real = np.zeros(tokens, bool)
        for at, length in zip(begins, doc_lens):
            real[at : at + length] = True
        u = jnp.asarray(rng.normal(size=(tokens, channels)), jnp.bfloat16)
        z = jnp.asarray(rng.normal(size=(tokens, channels)), jnp.bfloat16)
        dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(tokens, channels)) - 3.0, jnp.float32))
        b = jnp.asarray(rng.normal(size=(tokens, n)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(tokens, n)), jnp.float32)
        args = (u, dt, z, b, c, a, d_skip, jnp.asarray(starts))
        kernel = jax.jit(lambda *xs: selective_scan(*xs[:-1], live=xs[-1]))
        shape = f"a stream of {tokens} x {channels}, state {n}, {len(doc_lens)} documents, {live} tokens live"
        check(has_mosaic_kernel(kernel, *args, jnp.int32(live)), f"selective scan over {shape} compiles to a Mosaic kernel")
        got = np.asarray(kernel(*args, jnp.int32(live)), np.float32)[real]
        want = np.asarray(jax.jit(selective_scan_reference)(*args), np.float32)[real]
        err = float(np.abs(got - want).max() / np.abs(want).max())
        check(
            bool(np.isfinite(got).all()) and err <= SCAN_RTOL,
            f"selective scan over {shape} vs the lax.scan recurrence cleared at every start: max err {err:.2e} of the largest output (<= {SCAN_RTOL})",
        )

    from pathway_tpu.models.sentence_encoder import SentenceEncoder
    from pathway_tpu.ops.knn import DeviceKnnIndex

    name = "hybrid-ssm-tiny-for-tests"
    enc = SentenceEncoder(name)
    docs = [" ".join(f"w{int(w):04d}" for w in rng.integers(0, 2000, size=int(k))) for k in rng.integers(8, 250, size=64)]
    rows = enc.encode_device(docs)
    stream = jax.ShapeDtypeStruct((1024,), np.int32), *(jax.ShapeDtypeStruct((64,), np.int32),) * 2
    check(
        has_mosaic_kernel(enc._fwd_stream.__wrapped__, enc.params, *stream),
        f"{name}: the stream forward compiles its scan to a Mosaic kernel",
    )
    index = DeviceKnnIndex(enc.dim, metric="cos", reserved_space=64)
    index.attach_encoder(enc)
    index.add_batch_device(list(range(len(docs))), rows, None)
    answers = index.search_texts_batch(docs[:16], 1)
    norms = np.linalg.norm(np.asarray(rows), axis=1)
    check(
        bool(np.isfinite(norms).all()) and float(np.abs(norms - 1.0).max()) < 1e-3
        and all(a and a[0][0] == i and a[0][1] > 0.99 for i, a in enumerate(answers)),
        f"{name} embeds 64 documents of 10-252 tokens as packed streams to unit rows and each of 16 finds itself first through DeviceKnnIndex",
    )


def check_latent_moe(rng) -> None:
    """The latent-attention / sparse-expert encoder at its test preset,
    through the embedder's table, ``encode_device``, ``add_batch_device``
    and the fused text-query program; its expert layer and, for texts of
    128, its attention compile to Mosaic kernels."""
    import jax

    from pathway_tpu.models.sentence_encoder import SentenceEncoder
    from pathway_tpu.ops.knn import DeviceKnnIndex

    enc = SentenceEncoder("latent-moe-tiny-for-tests")
    # buckets of 64 and 128: the second runs the attention kernel
    docs = [" ".join(f"w{int(w):04d}" for w in rng.integers(0, 2000, size=int(n))) for n in rng.integers(8, 120, size=64)]
    rows = enc.encode_device(docs)
    lens = jax.ShapeDtypeStruct((8,), np.int32)
    for seq, kernel, what in ((64, "expert_grouped_matmul", "expert layer"), (128, "mla_attention", "attention")):
        check(
            has_mosaic_kernel(enc._fwd_group.__wrapped__, enc.params, jax.ShapeDtypeStruct((8, seq), np.int16), lens, name=kernel),
            f"latent-moe-tiny-for-tests: the group forward at 8 x {seq} compiles its {what} to a Mosaic kernel",
        )
    index = DeviceKnnIndex(enc.dim, metric="cos", reserved_space=64)
    index.attach_encoder(enc)
    index.add_batch_device(list(range(len(docs))), rows, None)
    answers = index.search_texts_batch(docs[:16], 1)
    norms = np.linalg.norm(np.asarray(rows), axis=1)
    check(
        bool(np.isfinite(norms).all()) and float(np.abs(norms - 1.0).max()) < 1e-3
        and all(a and a[0][0] == i and a[0][1] > 0.99 for i, a in enumerate(answers)),
        "latent-moe-tiny-for-tests embeds 64 documents to unit rows and each of 16 finds itself first through DeviceKnnIndex",
    )


def check_power_retention(rng) -> None:
    """The retention kernel at the published heads (40 on 8 of 128) over a
    stream of three documents and padding, against the recurrence; then
    the encoder at its test preset (heads of 128, kernel blocks the chip
    can tile), through ``encode_device`` — one packed stream —
    ``add_batch_device`` and the fused text-query program."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.power_retention import PowerRetentionConfig
    from pathway_tpu.models.sentence_encoder import SentenceEncoder
    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.ops.power_retention import power_retention, power_retention_reference

    t, lens = 1024, (500, 130, 200)
    seg, pos, at = np.full(t, -1, np.int32), np.zeros(t, np.int32), 0
    for i, n in enumerate(lens):
        seg[at : at + n], pos[at : at + n] = i, np.arange(n)
        at += -(-n // 128) * 128

    def unit_heads(x):  # as the per-head RMSNorm leaves them: |q_i . k_j| <= sqrt(128)
        heads = x.reshape(t, -1, 128)
        return (heads / np.linalg.norm(heads, axis=-1, keepdims=True) * 128**0.25).reshape(x.shape)

    q = jnp.asarray(unit_heads(rng.normal(size=(t, 5120))), jnp.bfloat16)
    k = jnp.asarray(unit_heads(rng.normal(size=(t, 1024))), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(t, 1024)), jnp.bfloat16)
    log_g = jnp.asarray(-0.02 * np.abs(rng.normal(size=(t, 8))), jnp.float32)
    args = (q, k, v, log_g, jnp.asarray(seg), jnp.asarray(pos))
    kernel = jax.jit(power_retention)
    check(has_mosaic_kernel(kernel, *args), "power retention at 1,024 x (40 | 8) x 128 compiles to a Mosaic kernel")
    got = np.asarray(kernel(*args), np.float32)[seg >= 0]
    want = np.asarray(jax.jit(power_retention_reference)(*args), np.float32)[seg >= 0]
    err = float(np.abs(got - want).max() / np.abs(want).max())
    check(
        bool(np.isfinite(got).all()) and err <= RETENTION_RTOL,
        f"power retention vs the lax.scan recurrence: max err {err:.2e} of the largest output (<= {RETENTION_RTOL})",
    )

    name = "power-retention-tiny-for-tests"
    cfg = PowerRetentionConfig.tiny_for_tests(head_dim=128, token_chunk=256, blocks=(128, 128), max_group_tokens=1024)
    enc = SentenceEncoder(name, config=cfg)
    docs = [" ".join(f"w{int(w):04d}" for w in rng.integers(0, 2000, size=int(n))) for n in rng.integers(8, 250, size=64)]
    rows = enc.encode_device(docs)
    stream = jax.ShapeDtypeStruct((1024,), np.int32), *(jax.ShapeDtypeStruct((128,), np.int32),) * 2
    check(
        has_mosaic_kernel(enc._fwd_stream.__wrapped__, enc.params, *stream),
        f"{name}: the stream forward compiles its retention to a Mosaic kernel",
    )
    index = DeviceKnnIndex(enc.dim, metric="cos", reserved_space=64)
    index.attach_encoder(enc)
    index.add_batch_device(list(range(len(docs))), rows, None)
    answers = index.search_texts_batch(docs[:16], 1)
    norms = np.linalg.norm(np.asarray(rows), axis=1)
    check(
        bool(np.isfinite(norms).all()) and float(np.abs(norms - 1.0).max()) < 1e-3
        and all(a and a[0][0] == i and a[0][1] > 0.99 for i, a in enumerate(answers)),
        f"{name} embeds 64 documents of 10-252 tokens as packed streams to unit rows and each of 16 finds itself first through DeviceKnnIndex",
    )


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def post(port: int, route: str, payload: dict, timeout: float):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def wait_for_port(port: int, thread, timeout: float = 120.0) -> None:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if not thread.is_alive():
            raise SystemExit("[chip_smoke] FAILED: the server thread died before listening")
        with socket.socket() as s:
            if s.connect_ex(("127.0.0.1", port)) == 0:
                return
        time.sleep(0.2)
    raise SystemExit("[chip_smoke] FAILED: the server never opened its port")


def live_index(n_docs: int):
    """The index the running server built. The engine owns it and no
    public handle leads there, so it is found among the live objects."""
    from pathway_tpu.ops.knn import DeviceKnnIndex

    found = [
        o for o in gc.get_objects() if isinstance(o, DeviceKnnIndex) and len(o) == n_docs
    ]
    check(len(found) == 1, f"one live device index holds all {n_docs} chunks")
    return found[0]


def serve_and_check(docs: list[str], mesh_chips: int | None) -> None:
    import jax

    import pathway_tpu as pw
    from pathway_tpu.internals import flight_recorder
    from pathway_tpu.parallel.mesh import resolve_mesh
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    corpus_dir = write_corpus(docs)
    mesh = resolve_mesh(mesh_chips)
    embedder = SentenceTransformerEmbedder("all-MiniLM-L6-v2", mesh=mesh)
    enc = embedder._encoder
    check(
        (enc.cfg.hidden_size, enc.cfg.num_layers, enc.cfg.num_heads, enc.cfg.vocab_size)
        == (384, 6, 12, 30522),
        "embedder is MiniLM-L6 at its published width (384 x 6 x 12 heads, vocab 30,522)",
    )
    server = VectorStoreServer(
        pw.io.fs.read(corpus_dir, format="binary", mode="static", with_metadata=True),
        embedder=embedder,
        splitter=TokenCountSplitter(),
    )
    port = free_port()
    run_kwargs = {} if mesh_chips is None else {"mesh": mesh_chips}
    thread = server.run_server("127.0.0.1", port, threaded=True, **run_kwargs)
    wait_for_port(port, thread)

    # the first answer waits behind the ingest epoch and its compiles
    status, stats = post(port, "/v1/statistics", {}, timeout=900)
    check(
        status == 200 and stats["file_count"] == N_DOCS,
        f"/v1/statistics: HTTP 200, file_count {stats.get('file_count')} == {N_DOCS}",
    )
    idx = live_index(N_DOCS)
    matrix = np.asarray(idx._dev_matrix, np.float64)
    slot_doc = np.full((idx.capacity,), -1, np.int64)
    for key, slot in idx._slot_of.items():
        meta = idx._meta[key]
        slot_doc[slot] = doc_id_of(getattr(meta, "value", meta)["path"])
    doc_slot = np.argsort(slot_doc)[-N_DOCS:]  # slots by doc id
    check(
        bool((np.sort(slot_doc[slot_doc >= 0]) == np.arange(N_DOCS)).all()),
        "every document is in the index exactly once (one chunk each)",
    )

    if mesh is None:
        buckets = sorted(
            {e["seq"] for e in flight_recorder.RECORDER.events() if e["kind"] == "kernel.dispatch"}
        )
        check(
            len(buckets) >= 4 and {160, 256} <= set(buckets),
            f"ingest ran the encoder kernel at seq buckets {buckets}",
        )
        group = enc._fwd_group.__wrapped__
        check(
            has_mosaic_kernel(
                group,
                enc.params,
                jax.ShapeDtypeStruct((enc.max_batch, 160), np.int16),
                jax.ShapeDtypeStruct((enc.max_batch,), np.int32),
            ),
            "the embedder's group-forward program contains a tpu_custom_call",
        )
    else:
        check_mesh_placement(idx, enc, mesh)

    # index rows against the plain XLA module, same seeded weights
    sample = list(range(0, N_DOCS, N_DOCS // 64))
    xla = xla_encoder(enc.cfg)
    params = jax.device_put(enc.params, jax.devices()[0])
    toks = [enc.tokenizer.encode(docs[i], enc.max_seq_len) for i in sample]
    width = max(len(t) for t in toks)
    ids = np.zeros((len(toks), width), np.int32)
    mask = np.zeros((len(toks), width), bool)
    for r, t in enumerate(toks):
        ids[r, : len(t)] = t
        mask[r, : len(t)] = True
    want = np.asarray(jax.jit(xla.apply)(params, ids, mask), np.float64)
    got = matrix[doc_slot[sample]]
    cos = float((got * want).sum(axis=1).min())
    err = float(np.abs(got - want).max())
    check(
        cos >= ENCODER_MIN_COS and err <= ENCODER_ATOL,
        f"indexed embeddings vs the XLA module on {len(sample)} chunks: "
        f"min cos {cos:.6f} (>= {ENCODER_MIN_COS}), max abs err {err:.2e} (<= {ENCODER_ATOL})",
    )

    # retrieval: verbatim chunks, one per length class
    valid = slot_doc >= 0
    for probe in (0, 1, 2, 3, N_DOCS // 4 + 2, N_DOCS // 2 + 3):
        status, hits = post(port, "/v1/retrieve", {"query": docs[probe], "k": K}, timeout=600)
        got_docs = [doc_id_of(h["metadata"]["path"]) for h in hits]
        check(
            status == 200 and len(hits) == K and got_docs[0] == probe,
            f"/v1/retrieve doc {probe} ({len(docs[probe].split())} words): HTTP {status}, "
            f"{len(hits)} results (want {K}), documents {got_docs} with the verbatim chunk "
            f"first, dists {[round(h['dist'], 4) for h in hits]}",
        )
        check(hits[0]["text"] == docs[probe], "and its text comes back unchanged")
        q = np.asarray(enc.encode([docs[probe]]), np.float64)[0]
        exact = np.where(valid, matrix @ q, -np.inf)[None, :]
        ok, shortfall = neighbours_within_tolerance(doc_slot[got_docs][None, :], exact, K)
        dist_err = max(abs(-h["dist"] - exact[0, doc_slot[d]]) for h, d in zip(hits, got_docs))
        check(
            ok and dist_err <= TOPK_SCORE_TOL,
            f"/v1/retrieve doc {probe}: top-{K} equals the exact host top-{K} over the index's "
            f"own rows (shortfall {shortfall:.2e}, score err {dist_err:.2e}, tol {TOPK_SCORE_TOL:.1e})",
        )
    check(thread.is_alive(), "the server is still up after the queries")


def check_mesh_placement(idx, enc, mesh) -> None:
    """Four-chip mode: where things live, and agreement with one chip."""
    import jax

    from pathway_tpu.ops.knn import DeviceKnnIndex

    n = int(mesh.shape["data"])
    shards = idx._dev_matrix.addressable_shards
    check(
        len({s.device for s in shards}) == n
        and all(s.data.shape == (idx.shard_capacity, idx.dim) for s in shards),
        f"index matrix: {n} shards of {idx.shard_capacity} rows on {n} distinct devices "
        f"({sorted(s.device.id for s in shards)}), documents per shard {idx._docs_shard}",
    )
    check(
        all(c > 0 for c in idx._docs_shard) and sum(idx._docs_shard) == N_DOCS,
        "every chip holds documents and together they hold them all",
    )
    ids = np.full((8 * n, 160), 1000, np.int32)
    mask = np.ones((8 * n, 160), bool)
    out = enc._run_padded(ids, mask)
    check(
        len({s.device for s in out.addressable_shards}) == n
        and all(s.data.shape == (8, enc.dim) for s in out.addressable_shards),
        f"mesh embedder output is data-sharded: {n} shards of (8, {enc.dim})",
    )
    check(
        has_mosaic_kernel(
            enc._fwd.__wrapped__,
            enc.params,
            jax.device_put(ids, enc._data_sharding),
            jax.device_put(mask, enc._data_sharding),
        ),
        "the mesh embedder's program contains a tpu_custom_call",
    )
    # the same rows in a one-chip index: same neighbours from the Pallas
    # sharded top-k (k <= 64) and from the XLA two-phase one (k > 64)
    keys = [k for k in idx._keys if k is not None]
    vecs = np.asarray(idx._dev_matrix, np.float32)[[idx._slot_of[k] for k in keys]]
    solo = DeviceKnnIndex(dim=idx.dim, metric=idx.metric, reserved_space=len(keys))
    solo.add_batch_arrays(keys, vecs)
    rng = np.random.default_rng(7)
    queries = vecs[:: len(keys) // 16][:16]
    queries = queries + 0.05 * rng.normal(size=queries.shape).astype(np.float32)
    exact = dict(zip(keys, vecs @ queries.T))
    for k, path in ((10, "knn_topk_sharded"), (100, "_sharded_topk")):
        differ = []
        for qi, (a, b) in enumerate(zip(idx.search_batch(queries, k), solo.search_batch(queries, k))):
            a_keys, b_keys = {key for key, _ in a}, {key for key, _ in b}
            kth = min(exact[key][qi] for key in b_keys)
            # a neighbour only one side returned must be a near-tie of the k-th
            odd = [abs(exact[key][qi] - kth) for key in a_keys ^ b_keys]
            if len(a_keys) != k or len(b_keys) != k or any(gap > TOPK_SCORE_TOL for gap in odd):
                differ.append(qi)
        check(
            not differ,
            f"{path} (k={k}): {len(queries)} queries, same neighbour sets as the one-chip "
            f"index (queries that differ: {differ})",
        )


# ---- rerank and decode -----------------------------------------------------


def tie_gap(model, params, prompt, got, want) -> tuple[int, float]:
    """Where two greedy streams first differ, and how far the token
    ``got`` took there is below the best logit of the plain XLA forward
    over the shared prefix. Only that first token can be judged:
    everything after it continues a different prefix."""
    import jax

    from pathway_tpu.decode.engine import _prefill_logits_math

    at = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
    ctx = [t % model.vocab_size for t in prompt] + want[:at]
    ids = np.zeros((256,), np.int32)
    ids[: len(ctx)] = ctx
    logits = jax.jit(functools.partial(_prefill_logits_math, cfg=model))(
        params, ids=ids, length=np.int32(len(ctx))
    )[2]
    logits = np.asarray(logits)
    return at, float(logits.max() - logits[got[at]])


def rag_and_decode(docs: list[str]) -> None:
    import jax.numpy as jnp

    from pathway_tpu.decode.config import DecodeConfig
    from pathway_tpu.decode.engine import DecodeEngine, DecoderConfig, DecodeService
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.models.reranker import DeviceReranker
    from pathway_tpu.models.sentence_encoder import SentenceEncoder
    from pathway_tpu.ops.fused_rag import FusedRagPipeline

    short = [d for d in docs if len(d.split()) <= 94][:N_RAG_DOCS]  # fit doc_seq_len=128
    queries = [" ".join(d.split()[3:15]) for d in short[:: N_RAG_DOCS // 4][:4]]
    cross_cfg = EncoderConfig.cross_encoder_l6()
    check(
        (cross_cfg.hidden_size, cross_cfg.num_layers) == (384, 6),
        "reranker is the cross-encoder at L6 width (384 x 6)",
    )

    def answers(impl: str, k: int):
        enc_cfg = dataclasses.replace(EncoderConfig.minilm_l6(), attention_impl=impl, layer_impl=impl)
        reranker = DeviceReranker(config=dataclasses.replace(cross_cfg, attention_impl=impl))
        pipe = FusedRagPipeline(SentenceEncoder(config=enc_cfg), reranker, reserved_space=N_RAG_DOCS)
        pipe.add_docs(list(range(len(short))), short)
        return pipe, pipe.query_batch(queries, k=k, k_retrieve=16)

    pipe, got = answers("auto", K)
    # the all-XLA pipeline's scores for all 16 candidates, so a hit is
    # compared by score even where near-ties reorder the top five
    _, want = answers("xla", 16)
    for qi, (g, w) in enumerate(zip(got, want)):
        w_score = dict(w)
        gaps = [abs(s - w_score[key]) for key, s in g if key in w_score]
        kth = sorted(w_score.values())[-K]
        shortfall = max(kth - w_score[key] for key, _ in g if key in w_score)
        check(
            len(g) == K
            and len(gaps) >= K - 1  # one may sit on the retrieval cut of 16
            and max(gaps) <= RERANK_ATOL
            and shortfall <= RERANK_ATOL,
            f"FusedRagPipeline query {qi}: {K} reranked hits, scores within {max(gaps):.2e} of "
            f"the all-XLA pipeline's and {shortfall:.2e} of its fifth best (<= {RERANK_ATOL})",
        )
    ids, lens_p, kr = pipe._padded_queries(queries, 16)
    check(
        has_mosaic_kernel(
            pipe._fused_fn(), pipe.enc.params, pipe.cross.params, ids, lens_p,
            pipe.index._dev_matrix, pipe.index._dev_valid, pipe._tok_dev, pipe._len_dev,
            kr=kr, kf=K,
        ),
        "the fused embed -> retrieve -> rerank program contains a tpu_custom_call",
    )

    # decode: the prompts a RAG answer decodes from — query, then top hit
    tok = pipe.enc.tokenizer
    prompts = [
        tok.encode(q + " " + short[hits[0][0]], 120) for q, hits in zip(queries, got)
    ][:N_PROMPTS]
    model = DecoderConfig()
    engine = DecodeEngine(model, DecodeConfig(impl="auto"))
    check(engine.impl == "paged", "DecodeEngine(impl='auto') chose the paged kernel")
    service = DecodeService(engine)
    try:
        tickets = [service.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
        streams = [t.result(timeout=600) for t in tickets]
    finally:
        service.stop()
    check(
        service.error is None
        and all(len(s) == MAX_NEW and not t.preempted for s, t in zip(streams, tickets)),
        f"DecodeService: {len(prompts)} concurrent prompts, {MAX_NEW} tokens each",
    )
    reference = DecodeEngine(model, DecodeConfig(impl="xla")).generate(
        prompts, max_new_tokens=MAX_NEW
    )
    for pi, (prompt, got, want) in enumerate(zip(prompts, streams, reference)):
        if got == want:
            check(True, f"decode prompt {pi}: {MAX_NEW} greedy tokens equal impl='xla'")
            continue
        at, gap = tie_gap(model, engine.params, prompt, got, want)
        check(
            gap <= LOGIT_TIE_TOL,
            f"decode prompt {pi}: tokens equal impl='xla' up to {at}; the token chosen there is "
            f"within {gap:.2e} of the plain XLA forward's best logit (<= {LOGIT_TIE_TOL})",
        )
    check(
        has_mosaic_kernel(
            engine._step_fn(), engine.params, engine.pool.k, engine.pool.v,
            jnp.asarray(engine._page_tables), jnp.asarray(engine._lens),
            jnp.zeros((engine.config.lanes,), jnp.int32),
        ),
        "the decode-step program contains a tpu_custom_call",
    )


# ---- main ------------------------------------------------------------------


def main() -> None:
    import jax

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind, "count": len(dev)}
    say(f"jax {jax.__version__}; device: {json.dumps(device)}")
    if device["platform"] != "tpu":
        raise SystemExit(
            f"[chip_smoke] FAILED: needs a TPU, JAX found platform {device['platform']!r}"
        )

    from pathway_tpu import native
    from pathway_tpu.decode.engine import DecoderConfig
    from pathway_tpu.internals.compile_cache import compile_cache_stats, configure_compile_cache
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.parallel.mesh import resolve_mesh

    check(
        native.is_available(),
        "the native library built from native/pathway_native.cc is loaded (C++ tokenizer)",
    )
    say(f"compile cache: {configure_compile_cache()}")
    mesh_chips = 4 if device["count"] >= 4 else None

    rng = np.random.default_rng(0)
    minilm = EncoderConfig.minilm_l6()
    check_fused_layer(minilm, rng)
    check_fused_attention(minilm, rng)
    check_pallas_knn(rng, resolve_mesh(mesh_chips))
    check_topk_select(rng)
    check_paged_attention(DecoderConfig(), rng)
    check_selective_scan(rng)
    check_latent_moe(rng)
    check_power_retention(rng)

    docs = make_corpus(rng)
    serve_and_check(docs, mesh_chips)
    rag_and_decode(docs)

    stats = compile_cache_stats()
    say(
        f"compile cache {stats['dir']}: {stats['hits']} hits, {stats['misses']} misses "
        f"of {stats['requests']} compile requests"
    )
    say(f"{len(_checks)} checks passed")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    import traceback

    # the server's run thread and its HTTP loop have no stop call, so the
    # process leaves through os._exit either way: 0 only after main()
    # returned, having printed the result line last
    code = 1
    try:
        main()
        code = 0
    except SystemExit as e:
        print(e, file=sys.stderr)
    except BaseException:
        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
