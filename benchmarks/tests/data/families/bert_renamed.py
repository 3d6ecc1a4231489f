"""A second family for ``test_family.py``: the same block as ``bert`` (the
program builds it from the model's name, so it has to be), written apart
and different wherever the contract of ``lib/spec.py`` leaves a family
free: its own leaf kinds and scale names, tokens counted from its own
wrapping, leaves made and used a layer at a time, no length-sorted blocks
and no scan. Not the reference of any cell.
"""

import math
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.lowprec import roundtrip

HIGHEST = jax.lax.Precision.HIGHEST
WORD = re.compile(r"[A-Za-z0-9]+|[^\sA-Za-z0-9]")


def _wrap(ids: list) -> list:
    return [101, *ids, 102]


def _layer_leaves(model, i):
    d, inter = model["hidden_size"], model["intermediate_size"]
    out = {}
    for name, shape in (("attention/qkv", (d, 3 * d)), ("attention/out", (d, d)), ("mlp_in", (d, inter)), ("mlp_out", (inter, d))):
        out[f"layer_{i}/{name}/kernel"] = (shape, "gauss.dense")
        out[f"layer_{i}/{name}/bias"] = ((shape[1],), "zeros")
    for ln in ("ln_att", "ln_mlp"):
        out[f"layer_{i}/{ln}/scale"] = ((d,), "ones")
        out[f"layer_{i}/{ln}/bias"] = ((d,), "zeros")
    return out


def _embed_leaves(model):
    d = model["hidden_size"]
    return {
        "tok_embed/embedding": ((model["vocab_size"], d), "gauss.wide"),
        "pos_embed/embedding": ((model["max_position_embeddings"], d), "gauss.narrow"),
        "type_embed/embedding": ((model["type_vocab_size"], d), "gauss.narrow"),
        "ln_embed/scale": ((d,), "ones"),
        "ln_embed/bias": ((d,), "zeros"),
    }


def leaves(model):
    out = _embed_leaves(model)
    for i in range(model["num_hidden_layers"]):
        out.update(_layer_leaves(model, i))
    return out


def take_groups(model):
    return [sorted(_embed_leaves(model))] + [sorted(_layer_leaves(model, i)) for i in range(model["num_hidden_layers"])]


def make_leaf(kind, shape, key, scales):
    if kind.startswith("gauss."):
        return scales[kind[len("gauss.") :]] * jax.random.normal(key, shape, jnp.float32)
    return jnp.full(shape, {"ones": 1.0, "zeros": 0.0}[kind], jnp.float32)


def tokens_of(words, model):
    return np.minimum(np.asarray(words) + len(_wrap([])), model["max_seq_len"])


def tokenize(texts, model):
    width, span = model["max_seq_len"], model["vocab_size"] - 1000
    ids = np.zeros((len(texts), width), np.int32)
    lens = np.zeros((len(texts),), np.int32)
    for i, text in enumerate(texts):
        words = [999 + zlib.crc32(w.encode()) % span for w in WORD.findall(text.lower())]
        row = _wrap(words[: width - len(_wrap([]))])
        ids[i, : len(row)], lens[i] = row, len(row)
    return ids, lens


def _norm(x, p, name, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p[name + "/scale"] + p[name + "/bias"]


def _dense(x, p, name, quant):
    w = p[name + "/kernel"]
    if quant:
        x, w = roundtrip(x, -1, quant), roundtrip(w, 0, quant)
    return jnp.matmul(x, w, precision=HIGHEST) + p[name + "/bias"]


@jax.jit
def _embed(p, ids, eps):
    x = p["tok_embed/embedding"][ids] + p["pos_embed/embedding"][None, : ids.shape[1]] + p["type_embed/embedding"][0]
    return _norm(x, p, "ln_embed", eps)


def _layer(p, x, mask, heads, eps, quant):
    p = {name.split("/", 1)[1]: leaf for name, leaf in p.items()}
    b, s, d = x.shape
    q, k, v = (t.reshape(b, s, heads, d // heads) for t in jnp.split(_dense(x, p, "attention/qkv", quant), 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(d // heads)
    probs = jax.nn.softmax(jnp.where(mask[:, None, None, :], scores, -3.0e38), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST).reshape(b, s, d)
    x = _norm(x + _dense(ctx, p, "attention/out", quant), p, "ln_att", eps)
    m = _dense(jax.nn.gelu(_dense(x, p, "mlp_in", quant), approximate=True), p, "mlp_out", quant)
    return _norm(x + m, p, "ln_mlp", eps)


_layer_jit = jax.jit(_layer, static_argnames=("heads", "eps", "quant"))


def encode(weights, model, texts, *, quant=None, block=64):
    ids, lens = tokenize(texts, model)
    width = min(-(-int(lens.max()) // 64) * 64, ids.shape[1])
    ids, eps = ids[:, :width], model["layer_norm_eps"]
    mask = np.arange(width)[None, :] < lens[:, None]
    groups = weights.groups()
    p = weights.take(groups[0])
    x = [_embed(p, ids[lo : lo + block], eps) for lo in range(0, len(ids), block)]
    for group in groups[1:]:  # a layer's leaves at a time, over every block of texts
        p = weights.take(group)
        x = [
            _layer_jit(p, xb, mask[lo : lo + block], heads=model["num_attention_heads"], eps=eps, quant=quant)
            for xb, lo in zip(x, range(0, len(ids), block))
        ]
    x = jnp.concatenate(x, axis=0)
    live = jnp.asarray(mask[:, :, None], x.dtype)
    pooled = (x * live).sum(1) / jnp.maximum(live.sum(1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def flops(model, token_lengths):
    d, inter = model["hidden_size"], model["intermediate_size"]
    return float(sum(model["num_hidden_layers"] * n * (8 * d * d + 4 * n * d + 4 * d * inter) for n in map(int, token_lengths)))
