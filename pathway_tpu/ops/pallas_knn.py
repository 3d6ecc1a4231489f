"""Pallas TPU kernel: fused KNN scores + top-k.

The RAG query hot path (reference USearch HNSW search,
/root/reference/src/external_integration/usearch_integration.rs:53,
rebuilt as brute-force matmul top-k in ops/knn.py) materializes a
[Q, N] score matrix in HBM before `lax.top_k`. At index scale (10M
docs) that matrix dominates HBM traffic and capacity. This kernel
blocks over the document axis and keeps a running per-query top-k in
VMEM, so scores never round-trip through HBM: one pass over the doc
matrix, O(Q·k) output.

Grid: (query_tiles, doc_blocks); the doc axis is `arbitrary` (sequential
on TPU), accumulating into the output block that lives in VMEM across
the inner iterations. Top-k per block via k iterative max-extractions
on the VPU (k is small: 8-64), then merged with the running top-k the
same way. ``interpret=True`` (CPU tests) is only ever an explicit
argument; the entry points never infer it from the backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -3.0e38  # sentinel below any real score


def _merge_topk(cand_scores, cand_idx, k: int):
    """Top-k of candidates [TQ, C] via k max-extractions (VPU-friendly:
    no sort, no dynamic gathers). Returns ([TQ, k], [TQ, k]).

    k <= 64 unrolls at trace time; larger k runs the extraction as a
    fori_loop whose [TQ, k] carry is written via one-hot iota selects
    (dynamic_update_slice has no Mosaic lowering) to keep compile time
    flat."""
    tq, c = cand_scores.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (tq, c), 1)
    if k <= 64:
        out_s = []
        out_i = []
        s = cand_scores
        for _ in range(k):
            best = jnp.max(s, axis=1)
            arg = jnp.argmax(s, axis=1)
            hit = iota == arg[:, None]
            out_s.append(best)
            out_i.append(jnp.max(jnp.where(hit, cand_idx, -1), axis=1))
            s = jnp.where(hit, NEG, s)
        return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1)

    # one-hot select instead of dynamic_update_slice (which has no
    # Mosaic lowering): position t of the output is claimed by the
    # t-th extraction via an iota mask — pure elementwise ops
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (tq, k), 1)

    def body(t, carry):
        s, out_s, out_i = carry
        best = jnp.max(s, axis=1)
        arg = jnp.argmax(s, axis=1)
        hit = iota == arg[:, None]
        picked = jnp.max(jnp.where(hit, cand_idx, -1), axis=1)
        sel = iota_k == t
        out_s = jnp.where(sel, best[:, None], out_s)
        out_i = jnp.where(sel, picked[:, None], out_i)
        return jnp.where(hit, NEG, s), out_s, out_i

    out_s0 = jnp.full((tq, k), NEG, cand_scores.dtype)
    out_i0 = jnp.full((tq, k), -1, jnp.int32)
    _, out_s, out_i = jax.lax.fori_loop(
        0, k, body, (cand_scores, out_s0, out_i0)
    )
    return out_s, out_i


def _kernel(
    q_ref, d_ref, bias_ref, vals_ref, idx_ref, *, k: int, block_n: int, n_docs: int, factor: float
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full(vals_ref.shape, NEG, vals_ref.dtype)
        idx_ref[...] = jnp.full(idx_ref.shape, -1, idx_ref.dtype)

    scores = jnp.dot(
        q_ref[...], d_ref[...].T, preferred_element_type=jnp.float32
    )  # [TQ, BN]
    # bias folds in validity masking (NEG for dead slots) and, for L2,
    # the -|doc|^2 term: top-k by factor*dot + bias
    scores = scores * factor + bias_ref[...].reshape(1, -1)
    base = j * block_n
    block_idx = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    # padded doc rows (zero vectors) must never displace real matches
    scores = jnp.where(block_idx < n_docs, scores, NEG)
    # candidates = running top-k ∪ this block's scores
    cand_s = jnp.concatenate([vals_ref[...], scores], axis=1)
    cand_i = jnp.concatenate([idx_ref[...], block_idx], axis=1)
    new_s, new_i = _merge_topk(cand_s, cand_i, k)
    vals_ref[...] = new_s
    idx_ref[...] = new_i


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_n", "interpret", "factor")
)
def knn_topk(
    queries,
    docs,
    *,
    k: int,
    bias=None,
    factor: float = 1.0,
    block_q: int = 128,
    block_n: int = 2048,
    interpret: bool = False,
):
    """Fused top-k of ``factor * (queries @ docs.T) + bias``:
    queries [Q, D] x docs [N, D] (+ bias [N]) -> (scores [Q, k],
    indices [Q, k]). bias carries validity masking (NEG for dead index
    slots) and the -|doc|^2 term for L2 distance. Pads Q/N to block
    multiples; padded docs never surface."""
    # the extraction merge keeps [block_q, block_n + k] candidate copies
    # live in VMEM — shrink the query tile as k grows to stay inside
    # the ~16MB scoped budget
    if k > 64:
        block_q = min(block_q, 32)
    elif k > 16:
        block_q = min(block_q, 64)
    q, d = jnp.asarray(queries, jnp.float32), jnp.asarray(docs, jnp.float32)
    Q, D = q.shape
    N = d.shape[0]
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    bias = jnp.asarray(bias, jnp.float32).reshape(N, 1)
    qpad = (-Q) % block_q
    npad = (-N) % block_n
    if qpad:
        q = jnp.pad(q, ((0, qpad), (0, 0)))
    if npad:
        d = jnp.pad(d, ((0, npad), (0, 0)))
        bias = jnp.pad(bias, ((0, npad), (0, 0)), constant_values=NEG)
    grid = (q.shape[0] // block_q, d.shape[0] // block_n)

    vals, idx = pl.pallas_call(
        functools.partial(_kernel, k=k, block_n=block_n, n_docs=N, factor=factor),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, D), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((q.shape[0], k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, d, bias)
    return vals[:Q], idx[:Q]


@functools.partial(
    jax.jit,
    static_argnames=("k", "mesh", "factor", "block_q", "block_n", "interpret"),
)
def knn_topk_sharded(
    queries,
    docs,
    bias,
    *,
    k: int,
    mesh,
    factor: float = 1.0,
    block_q: int = 128,
    block_n: int = 2048,
    interpret: bool = False,
):
    """Sharded fused top-k: ``docs``/``bias`` are row-sharded over the
    mesh's "data" axis; each device runs the VMEM kernel on its shard,
    then the per-shard top-k candidates (k per device) concatenate over
    ICI and one tiny lax.top_k picks the global winners — the
    cross-device merge of the reference's sharded index story
    (usearch_integration.rs:53 redesigned for the mesh). Queries are
    replicated. Returns global ([Q, k], [Q, k])."""
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape["data"]
    shard_len = docs.shape[0] // n_shards
    assert docs.shape[0] % n_shards == 0, "docs must pad to the mesh"

    def local(q, d, b):
        vals, idx = knn_topk(
            q,
            d,
            k=k,
            bias=b,
            factor=factor,
            block_q=block_q,
            block_n=block_n,
            interpret=interpret,
        )
        base = jax.lax.axis_index("data").astype(jnp.int32) * shard_len
        # dead candidates (idx -1) must keep a non-doc index after the
        # base shift so they can never collide with a real document
        return vals, jnp.where(idx >= 0, idx + base, -1)

    # check_vma off: pallas_call's out_shape carries no vma annotation
    vals, idx = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, None), P("data", None), P("data")),
        out_specs=(P(None, "data"), P(None, "data")),
        check_vma=False,
    )(queries, docs, bias)
    # [Q, n_shards*k] candidates -> global top-k (tiny)
    best, pos = jax.lax.top_k(vals, k)
    return best, jnp.take_along_axis(idx, pos, axis=1)
