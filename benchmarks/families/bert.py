"""The family ``bert``: a post-LN BERT block encoder with masked mean
pooling, as sentence-transformers' all-MiniLM models are and as the
program's ``SentenceEncoder`` builds them. Everything the benchmark knows
of this block is here: its leaves and how the seed draws them, its
tokenizer and its two specials, its plain reference, its FLOPs.

It imports nothing of the program, and of ``lib/`` only ``lowprec`` (the
control's rounding); the contract it fills is in ``lib/spec.py``.

Leaf names are the paths of the program's parameter tree. Scales are the
configuration's (``weights`` in its file): BERT's 0.02 for every matrix, a
larger table of word vectors and smaller position and type vectors, so
that a mean-pooled embedding depends on the words and not on what every
document shares (PERF.md, Findings, PR 21: seeded flax defaults give
cosine 0.9995 between unrelated texts).

The reference is straightforward ``jax.numpy`` in float32 at ``highest``
precision. Its tokenizer hashes whitespace/punctuation words as
``models/tokenizer.py`` does without a vocabulary file. Departure from
the published model, as the program makes it: GELU is the tanh
approximation (``jax.nn.gelu(approximate=True)`` in ``models/encoder.py``),
where all-MiniLM's config says ``gelu``. ``quant`` is the control, the
step below the configuration's bfloat16: every dense matmul with its
activations (a scale per token) and its weights (a scale per output
channel) rounded to ``fp8`` or ``int8``.
"""

from __future__ import annotations

import functools
import math
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.lowprec import roundtrip

_WORD = re.compile(r"[A-Za-z0-9]+|[^\sA-Za-z0-9]")
CLS, SEP = 101, 102
SPECIALS = 2  # [CLS] and [SEP] around every text
HIGHEST = jax.lax.Precision.HIGHEST
NEG = -3.0e38

LAYER_MATRICES = ("attention/qkv", "attention/out", "mlp_in", "mlp_out")
LAYER_NORMS = ("ln_att", "ln_mlp")


# ---- leaves ------------------------------------------------------------------


def leaves(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind). Kinds: ``word``, ``position``, ``type`` and
    ``matrix`` are normal draws at the configuration's ``<kind>_std``;
    ``one`` and ``zero`` are what they say."""
    d, inter = model["hidden_size"], model["intermediate_size"]
    out = {
        "tok_embed/embedding": ((model["vocab_size"], d), "word"),
        "pos_embed/embedding": ((model["max_position_embeddings"], d), "position"),
        "type_embed/embedding": ((model["type_vocab_size"], d), "type"),
        "ln_embed/scale": ((d,), "one"),
        "ln_embed/bias": ((d,), "zero"),
    }
    matrix_shapes = dict(zip(LAYER_MATRICES, ((d, 3 * d), (d, d), (d, inter), (inter, d))))
    for i in range(model["num_hidden_layers"]):
        p = f"layer_{i}/"
        for name, shape in matrix_shapes.items():
            out[p + name + "/kernel"] = (shape, "matrix")
            out[p + name + "/bias"] = ((shape[1],), "zero")
        for ln in LAYER_NORMS:
            out[p + ln + "/scale"] = ((d,), "one")
            out[p + ln + "/bias"] = ((d,), "zero")
    return out


def take_groups(model: dict) -> list[list[str]]:
    """The leaves in the groups they are made in: 22 to 33 M parameters,
    so one group, one program (a large family gives a group a layer)."""
    return [sorted(leaves(model))]


def make_leaf(kind: str, shape, key, scales: dict):
    """One leaf in float32 from its own key; traced inside the handle's jit."""
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    if kind == "zero":
        return jnp.zeros(shape, jnp.float32)
    return scales[kind + "_std"] * jax.random.normal(key, shape, jnp.float32)


# ---- tokens ------------------------------------------------------------------


def tokens_of(words, model: dict):
    """Token length of a text of ``words`` generated words (a number or an
    array of them): one token a word and the two specials, cut as the
    tokenizer cuts."""
    return np.minimum(np.asarray(words) + SPECIALS, model["max_seq_len"])


def tokenize(texts, model: dict) -> tuple[np.ndarray, np.ndarray]:
    """-> (ids [n, max_seq_len] int32 zero-padded, lens [n]). [CLS] words
    [SEP], each word hashed into the ids above the specials, cut to
    ``max_seq_len``."""
    max_len, vocab_size = model["max_seq_len"], model["vocab_size"]
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


# ---- the plain reference -----------------------------------------------------


def _dense(x, w, b, quant):
    if quant:
        x, w = roundtrip(x, -1, quant), roundtrip(w, 0, quant)
    return jnp.matmul(x, w, precision=HIGHEST) + b


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


LAYER_LEAVES = tuple(f"{m}/{part}" for m in LAYER_MATRICES for part in ("kernel", "bias")) + tuple(
    f"{ln}/{part}" for ln in LAYER_NORMS for part in ("scale", "bias")
)


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


def encode(weights, model: dict, texts, *, quant: str | None = None, block: int = 256):
    """texts -> [n, hidden] unit rows on the device, in input order. Runs
    length-sorted blocks of ``block`` texts, each padded to its longest
    rounded up to 64, so that it fits beside whatever else is resident.
    ``weights`` is the handle of ``lib/weights.py``: this family is small,
    so it takes every leaf in one call and holds them for this call only."""
    w = weights.take(weights.names())
    ids, lens = tokenize(texts, model)
    order = np.argsort(lens, kind="stable")
    parts = []
    for lo in range(0, len(order), block):
        rows = order[lo : lo + block]
        pad = block - len(rows)
        s = min(-(-int(lens[rows].max()) // 64) * 64, ids.shape[1])
        blk_ids = np.pad(ids[rows, :s], ((0, pad), (0, 0)))
        blk_lens = np.pad(lens[rows], (0, pad), constant_values=1)
        out = _forward(
            w,
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


# ---- work --------------------------------------------------------------------


def flops(model: dict, token_lengths) -> float:
    """Forward FLOPs of encoding texts of these token lengths, each
    attending over its own length; multiply-add = 2. A token among ``n``
    costs, a layer: qkv ``2·d·3d``, scores and probs@V ``4·n·d``, output
    projection ``2·d·d``, FFN in and out ``4·d·inter``. Counts are of the
    work, whatever implements it: real tokens, not a batch shape's padding.

    The arithmetic of ``bench.py:_encoder_flops_per_token`` /
    ``ops/fused_layer.py:encoder_flops_per_token`` (PERF.md, Open
    questions: the originals are a later PR's to delete)."""
    d, inter, layers = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    n = np.asarray(token_lengths).astype(np.int64).reshape(-1)
    flat = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * inter  # of a token, whatever its neighbours
    return float(layers * (flat * int(n.sum()) + 2 * 2 * d * int((n * n).sum())))
