"""Long-context encoding: ring attention over a sequence-sharded mesh.

The reference has no sequence parallelism (its models are small CPU
inference UDFs, /root/reference/python/pathway/xpacks/llm/embedders.py);
this framework makes long-context first-class: documents far beyond one
chip's HBM window encode with the sequence axis sharded across the mesh
and K/V blocks rotating over ICI (`lax.ppermute`), accumulating exact
softmax attention with the numerically-stable online update — the ring
attention recipe, expressed as a `shard_map` so XLA schedules the
compute/ICI overlap.

API:
  ring_attention(q, k, v, axis_name)  — inside shard_map/pmap
  ring_encode(params, module, ids, mask, mesh, axis)  — whole-encoder
      sequence-parallel forward for one long document
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _online_block(q, k_blk, v_blk, mask_blk, acc, m, l, scale):
    """One flash-attention style accumulation step.

    q: [B, H, Sq, d]   k_blk/v_blk: [B, H, Sk, d]   mask_blk: [B, Sk]
    acc: [B, H, Sq, d] running weighted values; m/l: [B, H, Sq] running
    max / normalizer.
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
    big_neg = jnp.asarray(jnp.finfo(scores.dtype).min, scores.dtype)
    scores = jnp.where(mask_blk[:, None, None, :], scores, big_neg)
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked blocks: exp(big_neg - big_neg) must not blow up
    correction = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    p = jnp.where(mask_blk[:, None, None, :], p, 0.0)
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk)
    return acc_new, m_new, l_new


def ring_attention(q, k, v, mask, axis_name: str):
    """Exact attention with the KV sequence sharded over ``axis_name``.

    Call inside shard_map/pmap. q/k/v: [B, H, S_local, d] (this shard's
    slice of the sequence); mask: [B, S_local]. Each of the N steps
    attends q against the currently-held K/V block, then rotates K/V and
    mask one hop around the ring — N-1 ppermutes over ICI, overlap
    scheduled by XLA. Returns [B, H, S_local, d]."""
    n = jax.lax.psum(1, axis_name)
    scale = 1.0 / math.sqrt(q.shape[-1])
    acc = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full(q.shape[:-1], jnp.finfo(jnp.float32).min, jnp.float32)
    l = jnp.zeros(q.shape[:-1], jnp.float32)
    qf = q.astype(jnp.float32)

    def step(i, carry):
        acc, m, l, k_blk, v_blk, mask_blk = carry
        acc, m, l = _online_block(
            qf, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32), mask_blk, acc, m, l, scale
        )

        def rotate(blks):
            perm = [(j, (j + 1) % n) for j in range(n)]
            return tuple(jax.lax.ppermute(b, axis_name, perm) for b in blks)

        # the last block's rotation would be discarded: skip it (N-1
        # ppermutes total, as the ring recipe prescribes)
        k_blk, v_blk, mask_blk = jax.lax.cond(
            i < n - 1, rotate, lambda blks: blks, (k_blk, v_blk, mask_blk)
        )
        return acc, m, l, k_blk, v_blk, mask_blk

    acc, m, l, _k, _v, _mask = jax.lax.fori_loop(0, n, step, (acc, m, l, k, v, mask))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    return out.astype(q.dtype)


def _sp_encoder_forward(params, cfg, ids, mask, axis_name: str):
    """Sequence-parallel TextEncoder forward (one long document per
    batch row): everything is local except attention, which rings."""
    p = params["params"]
    d = cfg.hidden_size
    h = cfg.num_heads
    hd = d // h
    # local embedding lookup; positions are GLOBAL offsets
    idx = jax.lax.axis_index(axis_name)
    s_local = ids.shape[1]
    pos = idx * s_local + jnp.arange(s_local)
    x = jnp.take(p["tok_embed"]["embedding"], ids, axis=0)
    x = x + p["pos_embed"]["embedding"][pos][None, :, :]
    if "type_embed" in p:
        x = x + p["type_embed"]["embedding"][0][None, None, :]
    x = _ln(x, p["ln_embed"], cfg.layer_norm_eps).astype(cfg.dtype)
    for layer in range(cfg.num_layers):
        lp = p[f"layer_{layer}"]
        qkv = x @ lp["attention"]["qkv"]["kernel"].astype(cfg.dtype) + lp["attention"]["qkv"]["bias"].astype(cfg.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(t.shape[0], t.shape[1], h, hd).transpose(0, 2, 1, 3)

        ctx = ring_attention(heads(q), heads(k), heads(v), mask, axis_name)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[1], d)
        a = ctx @ lp["attention"]["out"]["kernel"].astype(cfg.dtype) + lp["attention"]["out"]["bias"].astype(cfg.dtype)
        x = _ln(x + a, lp["ln_att"], cfg.layer_norm_eps).astype(cfg.dtype)
        mlp = x @ lp["mlp_in"]["kernel"].astype(cfg.dtype) + lp["mlp_in"]["bias"].astype(cfg.dtype)
        mlp = jax.nn.gelu(mlp, approximate=True)
        mlp = mlp @ lp["mlp_out"]["kernel"].astype(cfg.dtype) + lp["mlp_out"]["bias"].astype(cfg.dtype)
        x = _ln(x + mlp, lp["ln_mlp"], cfg.layer_norm_eps).astype(cfg.dtype)
    # masked mean pool: local partial sums + cross-shard psum
    mf = mask[:, :, None].astype(jnp.float32)
    local_sum = jnp.sum(x.astype(jnp.float32) * mf, axis=1)
    local_cnt = jnp.sum(mf, axis=1)
    total = jax.lax.psum(local_sum, axis_name)
    cnt = jax.lax.psum(local_cnt, axis_name)
    pooled = total / jnp.maximum(cnt, 1e-9)
    if cfg.normalize:
        pooled = pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12
        )
    return pooled


def _ln(x, lnp, eps):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return y * lnp["scale"] + lnp["bias"]


_RING_JIT: dict = {}


def ring_encode(params, cfg, ids, mask, mesh: Mesh, axis: str = "data"):
    """Encode [B, S] token ids with S sharded over ``axis`` of ``mesh``
    (S must divide by the axis size). Returns [B, hidden] pooled
    embeddings, replicated."""
    n = mesh.shape[axis]
    B, S = ids.shape
    assert S % n == 0, f"sequence {S} must divide across {n} shards"
    if S > cfg.max_position:
        # JAX clamps out-of-range embedding lookups: tokens past
        # max_position would silently share one position vector
        raise ValueError(
            f"sequence length {S} exceeds max_position {cfg.max_position}; "
            "raise EncoderConfig.max_position for long-context encoding"
        )
    from flax import linen as nn

    params = nn.meta.unbox(params)  # raw pytree access below
    # cache the compiled sequence-parallel forward per (config, mesh,
    # axis): re-wrapping per call would recompile per document
    key = (repr(cfg), tuple(map(id, mesh.devices.flat)), mesh.axis_names, axis)
    fn = _RING_JIT.get(key)
    if fn is None:
        fwd = functools.partial(_sp_encoder_forward, axis_name=axis)
        shard = jax.shard_map(
            lambda p, i, m: fwd(p, cfg, i, m),
            mesh=mesh,
            in_specs=(P(), P(None, axis), P(None, axis)),
            out_specs=P(),
            check_vma=False,
        )
        fn = _RING_JIT[key] = jax.jit(shard)
    return fn(params, ids, mask)
