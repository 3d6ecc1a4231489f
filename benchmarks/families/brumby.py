"""The family ``brumby``: a causal stack of power-retention layers —
Qwen3-14B's block (grouped query heads with a per-head RMSNorm on queries
and keys, rope, SwiGLU, RMSNorm pre-norm, no biases) in which every
softmax attention is a power retention (Manifest AI, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239; Brumby-14B-Base) —
used as a sentence encoder the way sentence-transformers wraps a plain
causal LM: mean pooling over the last hidden states (after the final
norm), L2. Everything the benchmark knows of this block is here: its
leaves and how the seed draws them, its tokenizer, its plain reference,
its FLOPs, and the operations and bytes of the retention alone.

It imports nothing of the program, and of ``lib/`` only ``lowprec`` (the
control's rounding); the contract it fills is in ``lib/spec.py``.

Per document of ``T`` tokens (``N`` an RMSNorm with its own scale)::

    x = E[ids]
    for layer i:
        u = N_in(x)
        q = u W_q -> [T, heads, head_dim];  k = u W_k, v = u W_v -> [T, kv_heads, head_dim]
        q = rope(N_q(q)),  k = rope(N_k(k))       N_q, N_k over the head_dim dims of each head
        log g = logsigmoid(u W_g + gate_bias) -> [T, kv_heads];   L_t = sum_{s <= t} log g_s
        for query head a of key/value head b = a // (heads / kv_heads), and j <= i:
            A_ij = exp(L_i - L_j) * (q_i . k_j / sqrt(head_dim)) ** degree
            o_i  = sum_j A_ij v_j / (sum_j A_ij + retention_eps)
        x = x + concat_a(o) W_o
        w = N_ff(x)
        x = x + (silu(w W_gate) * (w W_up)) W_down
    row = l2(mean_t N_final(x)_t)

``rope(u)_t = u cos(t a) + [-u_hi | u_lo] sin(t a)`` over the halves of the
``head_dim`` dims, ``a_j = rope_theta ** (-2 j / head_dim)`` repeated for
both halves, ``t`` the token's index in its document.

The reference is straightforward ``jax.numpy`` in float32 at ``highest``
precision: the pair form as written, the whole ``[T, T]`` weights of a
document at once, a key/value head's group of query heads at a time (40
heads' float32 weights of a 4,096-token document are 2.7 GB). No blocks,
no kernel, no packing, no state: documents of one padded length go
through a layer together, long ones one at a time. Layers are outermost,
so a layer's leaves are taken from the handle once (1.32 GB of float32
at the published widths). ``quant`` is the control, the step below the
configuration's bfloat16: every dense matmul with its activations (a
scale per token) and its weights (a scale per output channel) rounded to
``fp8`` or ``int8``; the gate (float32 in the configuration), rope and the
retention's own products stay float32.

The matrix-valued state (``S_t = g_t S_{t-1} + phi(k_t) v_t^T``, ``phi`` the
symmetric square: ``head_dim (head_dim + 1) / 2`` by ``head_dim`` a
key/value head) gives the same function, and is the lesser work only for
a document longer than :func:`state_crossover`. The work functions below
count pairs, so the family refuses a ``max_seq_len`` past that crossover:
a roofline share must not count more work than the cheapest exact form.

Seeded scales (``weights`` in the configuration's file): word vectors
N(0, ``word_std``); a matrix N(0, ``matrix_gain`` / sqrt(fan_in)); the two
matrices that write to the residual stream (``W_o``, ``W_down``) N(0,
``out_gain`` / sqrt(fan_in)), so that 2 x layers additions stand beside
the word vectors and do not drown them; the gate's matrix N(0,
``gate_gain`` / sqrt(fan_in)); norm scales 1.
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
SPECIALS = 2  # the two ids the hash tokenizer puts around every text
HIGHEST = jax.lax.Precision.HIGHEST
#: the reference's block of documents holds at most this many padded tokens
BLOCK_TOKENS = 4096


# ---- leaves ------------------------------------------------------------------


def _layer_leaves(model: dict, layer: int) -> dict[str, tuple[tuple[int, ...], str]]:
    d, hd, inner = model["hidden_size"], model["head_dim"], model["intermediate_size"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    p = f"layer_{layer}/"
    return {
        p + "norm_in/scale": ((d,), "one"),
        p + "norm_ff/scale": ((d,), "one"),
        p + "retention/q/kernel": ((d, heads * hd), "matrix"),
        p + "retention/k/kernel": ((d, kv * hd), "matrix"),
        p + "retention/v/kernel": ((d, kv * hd), "matrix"),
        p + "retention/o/kernel": ((heads * hd, d), "out"),
        p + "retention/gate/kernel": ((d, kv), "gate"),
        p + "retention/q_norm/scale": ((hd,), "one"),
        p + "retention/k_norm/scale": ((hd,), "one"),
        p + "mlp/gate/kernel": ((d, inner), "matrix"),
        p + "mlp/up/kernel": ((d, inner), "matrix"),
        p + "mlp/down/kernel": ((inner, d), "out"),
    }


def leaves(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind). Kinds: ``word``, ``matrix``, ``out``,
    ``gate``, ``one`` (the module's docstring says how each is drawn)."""
    _check(model)
    d = model["hidden_size"]
    out = {"embed/embedding": ((model["vocab_size"], d), "word"), "norm_final/scale": ((d,), "one")}
    for i in range(model["num_hidden_layers"]):
        out.update(_layer_leaves(model, i))
    return out


def take_groups(model: dict) -> list[list[str]]:
    """The embedding, then a layer a group: what is made in float32 and
    cast together beside everything already laid — 1.32 GB a layer at the
    published widths, 3.1 GB the embedding (first, with nothing beside it)."""
    groups = [["embed/embedding", "norm_final/scale"]]
    return groups + [sorted(_layer_leaves(model, i)) for i in range(model["num_hidden_layers"])]


def make_leaf(kind: str, shape, key, scales: dict):
    """One leaf in float32 from its own key; traced inside the handle's jit."""
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    if kind == "word":
        return scales["word_std"] * jax.random.normal(key, shape, jnp.float32)
    gain = {"matrix": "matrix_gain", "out": "out_gain", "gate": "gate_gain"}.get(kind)
    if gain is None:
        raise ValueError(f"no leaf kind {kind!r}")
    return scales[gain] / math.sqrt(shape[0]) * jax.random.normal(key, shape, jnp.float32)


# ---- tokens ------------------------------------------------------------------


def tokens_of(words, model: dict):
    """Token length of a text of ``words`` generated words (a number or an
    array of them): one token a word and the two specials, cut as the
    tokenizer cuts."""
    return np.minimum(np.asarray(words) + SPECIALS, model["max_seq_len"])


def tokenize(texts, model: dict) -> tuple[np.ndarray, np.ndarray]:
    """-> (ids [n, max_seq_len] int32 zero-padded on the right, lens [n]).
    The hash tokenizer at the model's vocabulary: id 101, each word hashed
    into the ids 999 ... vocab_size - 1, id 102, cut to ``max_seq_len``."""
    max_len, span = model["max_seq_len"], model["vocab_size"] - 1000
    memo: dict[str, int] = {}
    ids = np.zeros((len(texts), max_len), np.int32)
    lens = np.zeros((len(texts),), np.int32)
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


def _dense(x, w, quant):
    if quant:
        x, w = roundtrip(x, -1, quant), roundtrip(w, -2, quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(u, theta: float):
    """``u`` ``[b, s, heads, dim]`` rotated by halves, position = axis 1."""
    s, dim = u.shape[1], u.shape[-1]
    a = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = (jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.concatenate([a, a])[None, :])[None, :, None, :]
    turned = jnp.concatenate([-u[..., dim // 2 :], u[..., : dim // 2]], axis=-1)
    return u * jnp.cos(angle) + turned * jnp.sin(angle)


def retention(q, k, v, log_g, *, degree: int, eps: float):
    """The pair form over whole documents: ``q`` ``[b, s, kv, group, dim]``,
    ``k`` and ``v`` ``[b, s, kv, dim]``, ``log_g`` ``[b, s, kv]`` ->
    ``[b, s, kv, group, dim]``. A key/value head at a time."""
    s, dim = q.shape[1], q.shape[-1]
    total = jnp.cumsum(log_g, axis=1)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(args):
        qh, kh, vh, lh = args  # [b, s, group, dim], [b, s, dim], [b, s, dim], [b, s]
        scores = jnp.einsum("bigd,bjd->bgij", qh, kh, precision=HIGHEST) / math.sqrt(dim)
        decay = jnp.exp(jnp.where(causal[None], lh[:, :, None] - lh[:, None, :], -jnp.inf))
        weights = decay[:, None] * scores**degree
        num = jnp.einsum("bgij,bjd->bigd", weights, vh, precision=HIGHEST)
        den = jnp.swapaxes(weights.sum(axis=-1), 1, 2)[..., None]  # [b, s, group, 1]
        return num / (den + eps)

    heads_first = (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0), jnp.moveaxis(total, 2, 0))
    return jnp.moveaxis(jax.lax.map(one_head, heads_first), 0, 2)


_STATIC = ("heads", "kv", "hd", "theta", "eps", "degree", "gate_bias", "ret_eps", "quant")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer(p, x, *, heads, kv, hd, theta, eps, degree, gate_bias, ret_eps, quant):
    """One residual block over a block of documents ``x`` ``[b, s, d]``
    (right-padded: under a causal weight a document's real tokens never
    see its padding); ``p`` holds this layer's leaves by their names
    without the ``layer_{i}/`` prefix."""
    b, s, _ = x.shape
    u = _rmsnorm(x, p["norm_in/scale"], eps)
    q = _dense(u, p["retention/q/kernel"], quant).reshape(b, s, heads, hd)
    k = _dense(u, p["retention/k/kernel"], quant).reshape(b, s, kv, hd)
    v = _dense(u, p["retention/v/kernel"], quant).reshape(b, s, kv, hd)
    q = _rope(_rmsnorm(q, p["retention/q_norm/scale"], eps), theta)
    k = _rope(_rmsnorm(k, p["retention/k_norm/scale"], eps), theta)
    log_g = jax.nn.log_sigmoid(jnp.matmul(u, p["retention/gate/kernel"], precision=HIGHEST) + gate_bias)
    o = retention(q.reshape(b, s, kv, heads // kv, hd), k, v, log_g, degree=degree, eps=ret_eps)
    x = x + _dense(o.reshape(b, s, heads * hd), p["retention/o/kernel"], quant)
    w = _rmsnorm(x, p["norm_ff/scale"], eps)
    act = jax.nn.silu(_dense(w, p["mlp/gate/kernel"], quant)) * _dense(w, p["mlp/up/kernel"], quant)
    return x + _dense(act, p["mlp/down/kernel"], quant)


@functools.partial(jax.jit, static_argnames=("eps",))
def _pool(x, lens, scale, *, eps: float):
    live = (jnp.arange(x.shape[1])[None, :] < lens[:, None])[:, :, None].astype(x.dtype)
    pooled = (_rmsnorm(x, scale, eps) * live).sum(1) / jnp.maximum(live.sum(1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def _padded(length: int) -> int:
    """Few shapes to compile: a document is padded to a power of two, 64
    at least (seven shapes up to 4,096 tokens)."""
    return max(64, 1 << (length - 1).bit_length())


def encode(weights, model: dict, texts, *, quant: str | None = None, block: int = 8):
    """texts -> [n, hidden] unit rows on the device, in input order.
    Length-sorted; documents of one padded length go through a layer
    together, at most ``block`` of them and ``BLOCK_TOKENS`` tokens (so a
    long document goes alone); layers outermost, a layer's leaves taken
    from the handle (``lib/weights.py``) once and dropped before the next."""
    _check(model)
    ids, lens = tokenize(texts, model)
    order = np.argsort(lens, kind="stable")
    top = weights.take(["embed/embedding", "norm_final/scale"])
    blocks = []  # [hidden states [b, s, d], lens [b], real rows]
    lo = 0
    while lo < len(order):
        s = min(_padded(int(lens[order[lo]])), ids.shape[1])
        most = max(1, min(block, BLOCK_TOKENS // s))
        rows = [i for i in order[lo : lo + most] if min(_padded(int(lens[i])), ids.shape[1]) == s]
        pad = most - len(rows)
        blk_ids = np.pad(ids[rows, :s], ((0, pad), (0, 0)))
        blocks.append([top["embed/embedding"][blk_ids], np.pad(lens[rows], (0, pad), constant_values=1), len(rows)])
        lo += len(rows)
    final_scale = top["norm_final/scale"]
    del top
    static = dict(
        heads=model["num_attention_heads"],
        kv=model["num_key_value_heads"],
        hd=model["head_dim"],
        theta=float(model["rope_theta"]),
        eps=model["rms_norm_eps"],
        degree=int(model["degree"]),
        gate_bias=float(model["gate_bias"]),
        ret_eps=float(model["retention_eps"]),
        quant=quant,
    )
    for i in range(model["num_hidden_layers"]):
        prefix = f"layer_{i}/"
        p = {name[len(prefix) :]: leaf for name, leaf in weights.take(sorted(_layer_leaves(model, i))).items()}
        for blk in blocks:
            blk[0] = _layer(p, blk[0], **static)
        del p
    parts = [_pool(x, blk_lens, final_scale, eps=model["rms_norm_eps"])[:n] for x, blk_lens, n in blocks]
    inverse = np.empty_like(order)
    inverse[order] = np.arange(len(order))
    return jnp.concatenate(parts, axis=0)[jnp.asarray(inverse)]


# ---- work --------------------------------------------------------------------


def _layer_matmul_params(model: dict) -> int:
    d, hd = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return 2 * d * heads * hd + 2 * d * kv * hd + d * kv + 3 * d * model["intermediate_size"]


def _pair_flops(model: dict) -> int:
    """A causal pair of one layer: the score and the weighted value of
    every query head, ``2 · head_dim`` each, multiply-add = 2."""
    return model["num_attention_heads"] * 4 * model["head_dim"]


def state_crossover(model: dict) -> float:
    """The document length past which carrying the state is the lesser
    work: a token through the state costs a key/value head its update
    ``2 · features · head_dim`` and each of its query heads a read ``2 ·
    features · (head_dim + 1)``, ``features = head_dim (head_dim + 1) / 2``;
    a document of ``n`` tokens scored pair by pair ``n (n + 1) / 2`` pairs."""
    hd, heads, kv = model["head_dim"], model["num_attention_heads"], model["num_key_value_heads"]
    features = hd * (hd + 1) // 2
    through_state = kv * 2 * features * hd + heads * 2 * features * (hd + 1)
    return 2 * through_state / _pair_flops(model) - 1


def _check(model: dict) -> None:
    if model["degree"] != 2:
        raise SystemExit("the family's state form, and so its crossover, is the symmetric square's: degree 2")
    if model["max_seq_len"] >= state_crossover(model):
        raise SystemExit(
            f"max_seq_len {model['max_seq_len']} is past the {state_crossover(model):.0f} tokens at which the carried "
            "state is the lesser work: the family's work functions count pairs and would overstate a roofline share"
        )


def layer_call_flops(model: dict, tokens, pairs) -> float:
    """FLOPs of layer calls that took ``tokens`` real tokens and
    ``pairs`` causal pairs of real tokens in all (each summed over the
    calls): the matmuls' ``2 · parameters`` a token and the pairs."""
    return float(tokens) * 2 * _layer_matmul_params(model) + float(pairs) * _pair_flops(model)


def flops(model: dict, token_lengths) -> float:
    """Forward FLOPs of encoding texts of these token lengths; multiply-add
    = 2; real tokens, not a batch shape's padding; no output head. In
    every layer a token costs ``2 ·`` the matmul parameters (q, k, v, o, the
    gate, the SwiGLU's three) and a document of ``l`` tokens its ``l (l +
    1) / 2`` causal pairs, ``heads · 4 · head_dim`` each."""
    lengths = np.asarray(token_lengths).astype(np.int64).reshape(-1)
    pairs = int((lengths * (lengths + 1) // 2).sum())
    return model["num_hidden_layers"] * layer_call_flops(model, int(lengths.sum()), pairs)


def retention_flops(model: dict, pairs) -> float:
    """FLOPs of the retention alone for ``pairs`` causal pairs (summed
    over layer calls)."""
    return float(pairs) * _pair_flops(model)


def retention_bytes(model: dict, layer_calls) -> float:
    """The least bytes ``layer_calls`` retention calls move that their
    number alone tells: one token's q, k, v and o rows each, in the
    configuration's bfloat16. A lower bound far under the pairs' FLOPs at
    any length this family admits; the reader takes the larger of the two."""
    row = 2 * (model["num_attention_heads"] + model["num_key_value_heads"]) * model["head_dim"]
    return float(layer_calls) * row * 2
