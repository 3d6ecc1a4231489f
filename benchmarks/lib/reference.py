"""The plain reference: what the configuration says the system computes,
in straightforward ``jax.numpy`` and float32 at ``highest`` precision.

It imports nothing of the program and takes nothing the program made.
Its inputs are the benchmark's own: the texts, the seed's weights
(``weights.py``) and the seed's noise. Three parts, as the program has
them: the tokenizer (hashed whitespace/punctuation words, as
``models/tokenizer.py`` does without a vocabulary file), the encoder
(post-LN BERT block, masked mean pooling, L2 normalisation) and the
index (a matrix of unit rows by key, cosine top-k by brute force).

Departure from the published model, as the program makes it: GELU is
the tanh approximation (``jax.nn.gelu(approximate=True)`` in
``models/encoder.py``), where all-MiniLM's config says ``gelu``.

``quant`` is the control, the step below the configuration's bfloat16:
every dense matmul of the encoder with its activations (a scale per
token) and its weights (a scale per output channel) rounded to ``fp8``
(e4m3) or ``int8``, and the index rows and the queries rounded the same
way with a scale per row. The careful form of each, so that it reads as
low as that precision can.
"""

from __future__ import annotations

import functools
import math
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np

_WORD = re.compile(r"[A-Za-z0-9]+|[^\sA-Za-z0-9]")
CLS, SEP = 101, 102
HIGHEST = jax.lax.Precision.HIGHEST
NEG = -3.0e38


# ---- tokenizer ---------------------------------------------------------------


def tokenize(texts, max_len: int, vocab_size: int) -> tuple[np.ndarray, np.ndarray]:
    """-> (ids [n, max_len] int32 zero-padded, lens [n]). [CLS] words [SEP],
    each word hashed into the ids above the specials, cut to ``max_len``."""
    memo: dict[str, int] = {}
    ids = np.zeros((len(texts), max_len), np.int32)
    lens = np.zeros((len(texts),), np.int32)
    span = vocab_size - 1000
    for i, text in enumerate(texts):
        row = [CLS]
        for word in _WORD.findall(text.lower()):
            tok = memo.get(word)
            if tok is None:
                tok = memo[word] = 999 + zlib.crc32(word.encode()) % span
            row.append(tok)
            if len(row) >= max_len - 1:
                break
        row = row[: max_len - 1] + [SEP]
        ids[i, : len(row)] = row
        lens[i] = len(row)
    return ids, lens


# ---- encoder -----------------------------------------------------------------


QUANT = {"int8": (127.0, jnp.int8), "fp8": (448.0, jnp.float8_e4m3fn)}


def _to_low(x, axis, quant: str):
    """-> (x in the low type, scale): symmetric, one scale along ``axis``."""
    top, dtype = QUANT[quant]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top, 1e-30)
    y = x / scale
    if quant == "int8":
        y = jnp.clip(jnp.round(y), -top, top)
    return y.astype(dtype), scale


def _quant(x, axis, quant: str):
    """To the low type and back: what a matmul in it would see."""
    y, scale = _to_low(x, axis, quant)
    return y.astype(jnp.float32) * scale


def _dense(x, w, b, quant):
    if quant:
        x, w = _quant(x, -1, quant), _quant(w, 0, quant)
    return jnp.matmul(x, w, precision=HIGHEST) + b


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


LAYER_LEAVES = (
    "attention/qkv/kernel", "attention/qkv/bias", "attention/out/kernel", "attention/out/bias",
    "ln_att/scale", "ln_att/bias", "mlp_in/kernel", "mlp_in/bias", "mlp_out/kernel", "mlp_out/bias",
    "ln_mlp/scale", "ln_mlp/bias",
)  # fmt: skip


@functools.partial(jax.jit, static_argnames=("layers", "heads", "eps", "quant"))
def _forward(w, ids, lens, *, layers: int, heads: int, eps: float, quant):
    b, s = ids.shape
    mask = jnp.arange(s)[None, :] < lens[:, None]
    x = w["tok_embed/embedding"][ids] + w["pos_embed/embedding"][None, :s]
    x = x + w["type_embed/embedding"][0][None, None, :]
    x = _layer_norm(x, w["ln_embed/scale"], w["ln_embed/bias"], eps)
    d = x.shape[-1]
    hd = d // heads

    def layer(x, p):
        qkv = _dense(x, p["attention/qkv/kernel"], p["attention/qkv/bias"], quant)
        q, k, v = (t.reshape(b, s, heads, hd) for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(hd)
        scores = jnp.where(mask[:, None, None, :], scores, NEG)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST).reshape(b, s, d)
        a = _dense(ctx, p["attention/out/kernel"], p["attention/out/bias"], quant)
        x = _layer_norm(x + a, p["ln_att/scale"], p["ln_att/bias"], eps)
        m = _dense(x, p["mlp_in/kernel"], p["mlp_in/bias"], quant)
        m = jax.nn.gelu(m, approximate=True)
        m = _dense(m, p["mlp_out/kernel"], p["mlp_out/bias"], quant)
        return _layer_norm(x + m, p["ln_mlp/scale"], p["ln_mlp/bias"], eps), None

    # one layer's program, run over the layers' weights in turn
    stacked = {leaf: jnp.stack([w[f"layer_{i}/{leaf}"] for i in range(layers)]) for leaf in LAYER_LEAVES}
    x, _ = jax.lax.scan(layer, x, stacked)
    live = mask[:, :, None].astype(x.dtype)
    pooled = (x * live).sum(1) / jnp.maximum(live.sum(1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def encode(weights: dict, model: dict, texts, *, quant: str | None = None, block: int = 256):
    """texts -> [n, hidden] unit rows on the device, in input order. Runs
    length-sorted blocks of ``block`` texts, each padded to its longest
    rounded up to 64, so that it fits beside whatever else is resident."""
    ids, lens = tokenize(texts, model["max_seq_len"], model["vocab_size"])
    order = np.argsort(lens, kind="stable")
    parts = []
    for lo in range(0, len(order), block):
        rows = order[lo : lo + block]
        pad = block - len(rows)
        s = min(-(-int(lens[rows].max()) // 64) * 64, ids.shape[1])
        blk_ids = np.pad(ids[rows, :s], ((0, pad), (0, 0)))
        blk_lens = np.pad(lens[rows], (0, pad), constant_values=1)
        out = _forward(
            weights,
            blk_ids,
            blk_lens,
            layers=model["num_hidden_layers"],
            heads=model["num_attention_heads"],
            eps=model["layer_norm_eps"],
            quant=quant,
        )
        parts.append(out[: len(rows)])
    inverse = np.empty_like(order)
    inverse[order] = np.arange(len(order))
    return jnp.concatenate(parts, axis=0)[jnp.asarray(inverse)]


# ---- standing rows -----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk",))
def standing_chunk(key, index, pool_emb, sigma, *, chunk: int):
    """Rows ``index*chunk ...`` of the standing corpus, not normalised:
    key ``r`` sits at pool document ``r % pool`` plus seeded noise of
    length about ``sigma``, so standing rows lie where embedded text
    lies and compete in every top-k."""
    pool, dim = pool_emb.shape
    rows = index * chunk + jnp.arange(chunk)
    noise = jax.random.normal(jax.random.fold_in(key, index), (chunk, dim), jnp.float32)
    return pool_emb[rows % pool] + (sigma / math.sqrt(dim)) * noise


# ---- index -------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,))
def _put_rows(matrix, at, rows):
    return jax.lax.dynamic_update_slice(matrix, rows, (at, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def _replace(matrix, keys, rows):
    return matrix.at[keys].set(rows)


@jax.jit
def _judge(matrix, dead, q_emb, keys):
    """For each query: the reference's score of every returned key, and
    the best score among all keys that were not returned."""
    scores = jnp.matmul(q_emb, matrix.T, precision=HIGHEST)  # [q, rows]
    scores = jnp.where(dead[None, :], NEG, scores)
    returned = jnp.take_along_axis(scores, keys, axis=1)
    rows = jnp.arange(q_emb.shape[0])[:, None]
    best_out = scores.at[rows, keys].set(NEG).max(axis=1)
    return returned, best_out


@functools.partial(jax.jit, static_argnames=("k", "quant"))
def _low_topk(matrix_q, row_scale, dead, q_emb, k: int, quant: str):
    """Top-k by the low type's own arithmetic: exact products of the
    rounded values (int32 accumulation for int8; fp8 values are exact in
    bfloat16, their products in float32), then the two scales."""
    q_q, q_scale = _to_low(q_emb, 1, quant)
    if quant == "int8":
        acc = jax.lax.dot_general(q_q, matrix_q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
    else:
        acc = jax.lax.dot_general(
            q_q.astype(jnp.bfloat16),
            matrix_q.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    scores = acc.astype(jnp.float32) * q_scale * row_scale[None, :]
    return jax.lax.top_k(jnp.where(dead[None, :], NEG, scores), k)


class ReferenceIndex:
    """The standing corpus by key, under the window's writes. Row ``r``
    is key ``r``; a write replaces rows with pool embeddings."""

    def __init__(self, pool_emb, rows: int, sigma: float, key, chunk: int, quant: str | None = None):
        self.pool_emb = pool_emb
        self.quant = quant
        dim = pool_emb.shape[1]
        n_chunks = -(-rows // chunk)
        dtype = QUANT[quant][1] if quant else jnp.float32
        self.matrix = jnp.zeros((n_chunks * chunk, dim), dtype)
        self.scale = jnp.ones((n_chunks * chunk,), jnp.float32) if quant else None
        self.rows = rows
        for c in range(n_chunks):
            block = standing_chunk(key, c, pool_emb, sigma, chunk=chunk)
            self._write(c * chunk, None, block)
        # rows past the last key are never scored
        self.dead = jnp.arange(n_chunks * chunk) >= rows

    def _write(self, at, keys, block):
        block = block / jnp.maximum(jnp.linalg.norm(block, axis=1, keepdims=True), 1e-12)
        if self.quant:
            block, scale = _to_low(block, 1, self.quant)
            scale = scale[:, 0]
            if keys is None:
                self.scale = jax.lax.dynamic_update_slice(self.scale, scale, (at,))
            else:
                self.scale = self.scale.at[keys].set(scale)
        if keys is None:
            self.matrix = _put_rows(self.matrix, at, block)
        else:
            self.matrix = _replace(self.matrix, keys, block)

    def replace(self, keys, docs) -> None:
        self._write(None, jnp.asarray(keys, jnp.int32), self.pool_emb[jnp.asarray(docs)])

    def judge(self, q_emb, keys):
        returned, best_out = _judge(self.matrix, self.dead, q_emb, jnp.asarray(keys, jnp.int32))
        return np.asarray(returned), np.asarray(best_out)

    def answer(self, q_emb, k: int):
        """The control's own answers: keys and scores, by its own arithmetic."""
        vals, idx = _low_topk(self.matrix, self.scale, self.dead, q_emb, k, self.quant)
        return np.asarray(idx), np.asarray(vals)
