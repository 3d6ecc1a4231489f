"""Pallas TPU kernel: fused block-diagonal self-attention for short
sequences (the MiniLM/CrossEncoder embed hot path).

The reference runs sentence-transformers attention via torch SDPA
(/root/reference/python/pathway/xpacks/llm/embedders.py:270); the XLA
lowering of the equivalent einsum chain materializes [B, h, S, S]
scores and head-split [B, h, S, hd] tensors in HBM. At MiniLM geometry
(S=32, hd=32) every one of those tensors has a 32-wide minor dimension,
so each materialization runs at ~1/25 of HBM bandwidth on the (8, 128)
native tile — measured: attention is ~73% of encoder runtime while
holding ~1.5% of its FLOPs.

This kernel packs p = 128//S sequences into one 128-row token block
(zero-copy reshape), computes scores per head with a block-diagonal
+ key-padding bias, does the stable softmax on the VPU, and applies the
probs to V — entirely in VMEM. Scores never touch HBM; HBM traffic is
exactly qkv in, ctx out. Numerics match the XLA path: the softmax rows
see only their own sequence's keys, in f32.

Backward: custom_vjp recomputes the XLA reference path (attention is
cheap in FLOPs, so recompute beats storing probs) — training works
unchanged. Off-TPU the public entry point uses the XLA reference
directly; interpret=True is for kernel tests on CPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_OFF = -1.0e30  # additive bias outside the block diagonal
KEY_OFF = -1.0e9  # additive bias on padded keys

# At/above this sequence length the packed block is tiled as p
# independent (seq, seq) diagonal score tiles instead of one
# rows x rows matmul: the off-diagonal tiles carried BLOCK_OFF and
# contributed exactly zero probability, so skipping them is
# numerically identical and deletes (p-1)/p of the score FLOPs and
# softmax VPU work.  Below it, p small (seq, seq) matmuls would
# starve the MXU's 128-deep pipeline — the full block stays.
DIAG_MIN_SEQ = 128


def _heads_softmax_pv(qkv, bias, d: int, n_heads: int, scale: float, out_dtype):
    """scores -> stable f32 softmax -> probs @ V, per head, over one
    token block. ``bias`` broadcasts across score rows."""
    hd = d // n_heads
    parts = []
    for i in range(n_heads):
        qh = qkv[:, i * hd : (i + 1) * hd]
        kh = qkv[:, d + i * hd : d + (i + 1) * hd]
        vh = qkv[:, 2 * d + i * hd : 2 * d + (i + 1) * hd]
        s = (
            jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
            + bias
        )
        m = jnp.max(s, axis=1, keepdims=True)
        e = jnp.exp(s - m)
        p = (e / jnp.sum(e, axis=1, keepdims=True)).astype(qkv.dtype)
        parts.append(
            jnp.dot(p, vh, preferred_element_type=jnp.float32).astype(out_dtype)
        )
    return jnp.concatenate(parts, axis=1)


def _kernel(qkv_ref, kbias_ref, out_ref, *, n_heads: int, seq: int, scale: float):
    rows = out_ref.shape[0]  # p * seq packed tokens
    d = out_ref.shape[1]
    qkv = qkv_ref[...]
    if seq >= DIAG_MIN_SEQ:
        # ragged diagonal tiling: each packed sequence attends inside
        # its own (seq, seq) tile; cross-sequence tiles never computed
        blocks = []
        for j in range(rows // seq):
            kb = kbias_ref[0, 0:1, j * seq : (j + 1) * seq]
            sub = qkv[j * seq : (j + 1) * seq, :]
            blocks.append(
                _heads_softmax_pv(sub, kb, d, n_heads, scale, out_ref.dtype)
            )
        out_ref[...] = jnp.concatenate(blocks, axis=0)
        return
    # block-diagonal bias: token q may attend token k iff same sequence
    qi = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0) // seq
    ki = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1) // seq
    bias = jnp.where(qi == ki, 0.0, BLOCK_OFF) + kbias_ref[0, 0:1, :]  # (rows, rows)
    out_ref[...] = _heads_softmax_pv(qkv, bias, d, n_heads, scale, out_ref.dtype)


def _xla_reference(qkv, key_mask, n_heads: int):
    """The plain XLA attention chain (also the backward path)."""
    b, s, three_d = qkv.shape
    d = three_d // 3
    hd = d // n_heads
    q, k, v = jnp.split(qkv, 3, axis=-1)
    fold = lambda t: t.reshape(b, s, n_heads, hd)
    q, k, v = fold(q), fold(k), fold(v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    scores = jnp.where(
        key_mask[:, None, None, :], scores, jnp.finfo(scores.dtype).min
    )
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(qkv.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return ctx.reshape(b, s, d)


def _fused_call(qkv, key_mask, n_heads: int, interpret: bool):
    b, s, three_d = qkv.shape
    d = three_d // 3
    # block packing (measured on v5e): short sequences pack to 256-row
    # blocks (best at S=32: beats both 128 and 512); mid sizes
    # (128 < S < 256) pack to ~512 rows so the per-head matmuls see
    # 384-480 row tiles instead of MXU-starved 144-row ones; S >= 256
    # runs one sequence per block. VMEM stays bounded: scores are
    # rows^2 f32.
    if s <= 128:
        p = max(1, 256 // s)
    elif s < 256:
        p = max(1, 512 // s)
    else:
        p = 1
    rows = p * s
    pad = (-b) % p
    if pad:
        qkv = jnp.pad(qkv, ((0, pad), (0, 0), (0, 0)))
        key_mask = jnp.pad(key_mask, ((0, pad), (0, 0)))
    bp = qkv.shape[0] // p
    tokens = qkv.reshape(bp * rows, three_d)
    kbias = jnp.where(key_mask, 0.0, KEY_OFF).astype(jnp.float32).reshape(bp, rows)
    # tile the per-group key bias to 8 sublanes (Mosaic sublane tiling;
    # non-128-multiple lane dims like rows=480 lower fine — Mosaic pads
    # the lane dimension internally, verified on v5e)
    kbias = jnp.broadcast_to(kbias[:, None, :], (bp, 8, rows))
    out = pl.pallas_call(
        functools.partial(
            _kernel, n_heads=n_heads, seq=s, scale=1.0 / math.sqrt(d // n_heads)
        ),
        grid=(bp,),
        in_specs=[
            pl.BlockSpec((rows, three_d), lambda i: (i, 0)),
            pl.BlockSpec((1, 8, rows), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp * rows, d), qkv.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(tokens, kbias)
    return out.reshape(bp * p, s, d)[:b]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused_attention(qkv, key_mask, n_heads: int, interpret: bool):
    return _fused_call(qkv, key_mask, n_heads, interpret)


def _fwd(qkv, key_mask, n_heads, interpret):
    return _fused_call(qkv, key_mask, n_heads, interpret), (qkv, key_mask)


def _bwd(n_heads, interpret, res, g):
    qkv, key_mask = res
    _, vjp = jax.vjp(lambda t: _xla_reference(t, key_mask, n_heads), qkv)
    return (vjp(g)[0], None)


_fused_attention.defvjp(_fwd, _bwd)


def attention(qkv, key_mask, *, n_heads: int, impl: str = "auto"):
    """Multi-head self-attention on fused qkv.

    qkv: [B, S, 3*D] (q | k | v, heads minor within each), key_mask:
    [B, S] bool. Returns ctx [B, S, D]. impl: "fused" (pallas kernel),
    "xla" (reference chain), "interpret" (kernel in interpret mode, for
    tests), or "auto" — the kernel on TPU when S fits a packed block,
    XLA otherwise.
    """
    s = qkv.shape[1]
    fits = s <= 512 and qkv.shape[2] % (3 * n_heads) == 0
    if impl == "auto":
        impl = "fused" if (jax.default_backend() == "tpu" and fits) else "xla"
    if impl == "fused":
        return _fused_attention(qkv, key_mask, n_heads, False)
    if impl == "interpret":
        return _fused_attention(qkv, key_mask, n_heads, True)
    return _xla_reference(qkv, key_mask, n_heads)

