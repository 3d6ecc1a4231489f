"""The family ``jamba``: a causal hybrid of Mamba-1 state-space layers and
attention layers (AI21's Jamba: ``attn_layer_period`` / ``attn_layer_offset``
say which layer is which), every feed-forward a dense SwiGLU
(``num_experts`` 1), pre-norm RMSNorm residual blocks, used as a sentence
encoder the way sentence-transformers wraps a plain causal LM: masked
mean pooling over the last hidden states (after the final norm), L2.
Everything the benchmark knows of this block is here: its leaves and how
the seed draws them, its tokenizer, its plain reference, its FLOPs and
the least bytes its selective scan moves.

It imports nothing of the program, and of ``lib/`` only ``lowprec`` (the
control's rounding); the contract it fills is in ``lib/spec.py``.

Per text of ``L`` tokens (``m`` the mask of real tokens)::

    x  = E[ids]
    for each layer i:
        h  = rmsnorm(x; g_in_i)
        x += attn_i(h) if i % period == offset else mamba_i(h)
        h  = rmsnorm(x; g_ff_i)
        x += (silu(h W_gate) * (h W_up)) W_down
    row = l2(sum_t m_t rmsnorm(x; g_final)_t / sum_t m_t)

    attn(h):  q, k, v = h W_q, h W_k, h W_v  (no bias, no rotary, no position signal)
              softmax over keys s <= t, s real, of q_t . k_s / sqrt(head_dim); heads concatenated; W_o
    mamba(h): u, z = split(h W_in);  u = silu(conv(u) + b_conv)   (depthwise, causal, d_conv - 1 zeros on the left)
              dt, B, C = split(u W_x);  each through its own rmsnorm
              D_t = softplus(dt W_dt + b_dt);  A = -exp(A_log)
              s_t = exp(D_t (x) A) * s_{t-1} + (D_t * u_t) (x) B_t,  s_{-1} = 0
              y_t = s_t . C_t + D_skip * u_t;  return (y * silu(z)) W_out

Leaf names are the paths of the program's parameter tree. The reference is
straightforward ``jax.numpy`` in float32 at ``highest`` precision, the
recurrence a ``lax.scan`` over time whose carry is the ``[docs, d_inner,
d_state]`` state; no kernel. Layers are outermost — a layer's leaves are
taken from the handle once, 0.42 GB of float32 — and documents go through
it in blocks, so nothing larger than ``[block, L, 2 * d_inner]`` float32
is alive beside the hidden states. ``quant`` is the control, the step
below the configuration's bfloat16: every dense matmul with its
activations (a scale per token) and its weights (a scale per output
channel) rounded to ``fp8`` or ``int8``; the recurrence, the convolution
and the attention products stay float32.

Seeded scales (``weights`` in the configuration's file): a matrix is
N(0, ``matrix_gain`` / sqrt(fan_in)) — 0.0198 at the published width, the
0.02 such models are initialised with — and the three matrices that write
to the residual stream (``out_proj``, ``o``, ``down``) N(0, ``out_gain`` /
sqrt(fan_in)), the depth-scaled form of the same initialisation, so that
28 layers' additions stand beside the word vectors (N(0, ``word_std``))
instead of drowning them; the convolution's taps and bias uniform in
+-``conv_bound`` (1 / sqrt(d_conv), as published Mamba draws them);
``A_log`` = log 1..d_state along the state axis; ``b_dt`` the inverse
softplus of a step drawn log-uniform in [1e-3, 1e-1].
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
NEG = -3.0e38


# ---- leaves ------------------------------------------------------------------


def _is_attention(model: dict, layer: int) -> bool:
    return layer % model["attn_layer_period"] == model["attn_layer_offset"]


def _layer_leaves(model: dict, layer: int) -> dict[str, tuple[tuple[int, ...], str]]:
    d, inter = model["hidden_size"], model["intermediate_size"]
    di, n, r = model["mamba_expand"] * d, model["mamba_d_state"], model["mamba_dt_rank"]
    hd = d // model["num_attention_heads"]
    heads, kv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    p = f"layer_{layer}/"
    out = {
        p + "norm_in/scale": ((d,), "one"),
        p + "norm_ff/scale": ((d,), "one"),
        p + "mlp/gate/kernel": ((d, inter), "matrix"),
        p + "mlp/up/kernel": ((d, inter), "matrix"),
        p + "mlp/down/kernel": ((inter, d), "matrix_out"),
    }
    if _is_attention(model, layer):
        out.update(
            {
                p + "attn/q/kernel": ((d, heads), "matrix"),
                p + "attn/k/kernel": ((d, kv), "matrix"),
                p + "attn/v/kernel": ((d, kv), "matrix"),
                p + "attn/o/kernel": ((heads, d), "matrix_out"),
            }
        )
    else:
        out.update(
            {
                p + "mamba/in_proj/kernel": ((d, 2 * di), "matrix"),
                p + "mamba/conv/kernel": ((model["mamba_d_conv"], di), "conv"),
                p + "mamba/conv/bias": ((di,), "conv"),
                p + "mamba/x_proj/kernel": ((di, r + 2 * n), "matrix"),
                p + "mamba/dt_norm/scale": ((r,), "one"),
                p + "mamba/b_norm/scale": ((n,), "one"),
                p + "mamba/c_norm/scale": ((n,), "one"),
                p + "mamba/dt_proj/kernel": ((r, di), "matrix"),
                p + "mamba/dt_proj/bias": ((di,), "dt_bias"),
                p + "mamba/a_log": ((di, n), "a_log"),
                p + "mamba/d_skip": ((di,), "one"),
                p + "mamba/out_proj/kernel": ((di, d), "matrix_out"),
            }
        )
    return out


def leaves(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind). Kinds: ``word``, ``matrix``, ``matrix_out``,
    ``conv``, ``a_log``, ``dt_bias``, ``one`` (the module's docstring
    says how each is drawn)."""
    d = model["hidden_size"]
    out = {"embed/embedding": ((model["vocab_size"], d), "word"), "norm_final/scale": ((d,), "one")}
    for i in range(model["num_hidden_layers"]):
        out.update(_layer_leaves(model, i))
    return out


def take_groups(model: dict) -> list[list[str]]:
    """The embedding (0.67 GB of float32 at the published size), then a
    layer each (0.42 GB at most): what is made and cast together."""
    groups = [["embed/embedding", "norm_final/scale"]]
    groups += [sorted(_layer_leaves(model, i)) for i in range(model["num_hidden_layers"])]
    return groups


def make_leaf(kind: str, shape, key, scales: dict):
    """One leaf in float32 from its own key; traced inside the handle's jit."""
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    if kind == "word":
        return scales["word_std"] * jax.random.normal(key, shape, jnp.float32)
    if kind in ("matrix", "matrix_out"):
        gain = scales["matrix_gain" if kind == "matrix" else "out_gain"]
        return gain / math.sqrt(shape[0]) * jax.random.normal(key, shape, jnp.float32)
    if kind == "conv":
        bound = scales["conv_bound"]
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if kind == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    if kind == "dt_bias":
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))  # softplus(this) == step
    raise ValueError(f"no leaf kind {kind!r}")


# ---- tokens ------------------------------------------------------------------


def tokens_of(words, model: dict):
    """Token length of a text of ``words`` generated words (a number or an
    array of them): one token a word and the two specials, cut as the
    tokenizer cuts."""
    return np.minimum(np.asarray(words) + SPECIALS, model["max_seq_len"])


def tokenize(texts, model: dict) -> tuple[np.ndarray, np.ndarray]:
    """-> (ids [n, max_seq_len] int32 zero-padded on the right, lens [n]).
    The hash tokenizer at the model's vocabulary size: id 101, each word
    hashed into the ids 999 ... vocab_size - 1, id 102, cut to
    ``max_seq_len``."""
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
        x, w = roundtrip(x, -1, quant), roundtrip(w, 0, quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _attention(p, h, mask, *, heads: int, kv_heads: int, quant):
    b, s, _ = h.shape
    q = _dense(h, p["attn/q/kernel"], quant)
    k = _dense(h, p["attn/k/kernel"], quant)
    v = _dense(h, p["attn/v/kernel"], quant)
    hd = q.shape[-1] // heads
    q = q.reshape(b, s, kv_heads, heads // kv_heads, hd)
    k, v = k.reshape(b, s, kv_heads, hd), v.reshape(b, s, kv_heads, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    keep = causal[None, None, None] & mask[:, None, None, None, :]
    probs = jax.nn.softmax(jnp.where(keep, scores, NEG), axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs, v, precision=HIGHEST)
    return _dense(ctx.reshape(b, s, heads * hd), p["attn/o/kernel"], quant)


def _mamba(p, h, *, d_state: int, dt_rank: int, eps: float, quant):
    b, s, _ = h.shape
    uz = _dense(h, p["mamba/in_proj/kernel"], quant)
    di = uz.shape[-1] // 2
    u, z = uz[..., :di], uz[..., di:]
    taps = p["mamba/conv/kernel"]  # [d_conv, d_inner]
    width = taps.shape[0]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[:, j : j + s, :] * taps[j] for j in range(width)) + p["mamba/conv/bias"])
    proj = _dense(u, p["mamba/x_proj/kernel"], quant)
    dt = _rmsnorm(proj[..., :dt_rank], p["mamba/dt_norm/scale"], eps)
    bmat = _rmsnorm(proj[..., dt_rank : dt_rank + d_state], p["mamba/b_norm/scale"], eps)
    cmat = _rmsnorm(proj[..., dt_rank + d_state :], p["mamba/c_norm/scale"], eps)
    dt = jax.nn.softplus(_dense(dt, p["mamba/dt_proj/kernel"], quant) + p["mamba/dt_proj/bias"])
    a = -jnp.exp(p["mamba/a_log"])  # [d_inner, d_state]

    def step(state, x):
        u_t, dt_t, b_t, c_t = x
        state = jnp.exp(dt_t[:, :, None] * a[None]) * state + (dt_t * u_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("bdn,bn->bd", state, c_t, precision=HIGHEST)

    over_time = tuple(jnp.swapaxes(t, 0, 1) for t in (u, dt, bmat, cmat))
    _, ys = jax.lax.scan(step, jnp.zeros((b, di, d_state), jnp.float32), over_time)
    y = jnp.swapaxes(ys, 0, 1) + p["mamba/d_skip"] * u
    return _dense(y * jax.nn.silu(z), p["mamba/out_proj/kernel"], quant)


@functools.partial(jax.jit, static_argnames=("attention", "heads", "kv_heads", "d_state", "dt_rank", "eps", "quant"))
def _layer(p, x, lens, *, attention: bool, heads: int, kv_heads: int, d_state: int, dt_rank: int, eps: float, quant):
    """One residual block over a block of documents; ``p`` holds this
    layer's leaves by their names without the ``layer_{i}/`` prefix."""
    mask = jnp.arange(x.shape[1])[None, :] < lens[:, None]
    h = _rmsnorm(x, p["norm_in/scale"], eps)
    if attention:
        x = x + _attention(p, h, mask, heads=heads, kv_heads=kv_heads, quant=quant)
    else:
        x = x + _mamba(p, h, d_state=d_state, dt_rank=dt_rank, eps=eps, quant=quant)
    h = _rmsnorm(x, p["norm_ff/scale"], eps)
    act = jax.nn.silu(_dense(h, p["mlp/gate/kernel"], quant)) * _dense(h, p["mlp/up/kernel"], quant)
    return x + _dense(act, p["mlp/down/kernel"], quant)


@functools.partial(jax.jit, static_argnames=("eps",))
def _pool(x, lens, scale, *, eps: float):
    live = (jnp.arange(x.shape[1])[None, :] < lens[:, None])[:, :, None].astype(x.dtype)
    pooled = (_rmsnorm(x, scale, eps) * live).sum(1) / jnp.maximum(live.sum(1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def encode(weights, model: dict, texts, *, quant: str | None = None, block: int = 32):
    """texts -> [n, hidden] unit rows on the device, in input order.
    Length-sorted blocks of ``block`` texts, each padded to its longest
    rounded up to 64; layers outermost, so a layer's leaves are taken
    from the handle (``lib/weights.py``) once and dropped before the next."""
    ids, lens = tokenize(texts, model)
    order = np.argsort(lens, kind="stable")
    top = weights.take(["embed/embedding", "norm_final/scale"])
    blocks = []  # [hidden states [block, s, d], lens [block], real rows]
    for lo in range(0, len(order), block):
        rows = order[lo : lo + block]
        pad = block - len(rows)
        s = min(-(-int(lens[rows].max()) // 64) * 64, ids.shape[1])
        blk_ids = np.pad(ids[rows, :s], ((0, pad), (0, 0)))
        blocks.append([top["embed/embedding"][blk_ids], np.pad(lens[rows], (0, pad), constant_values=1), len(rows)])
    final_scale = top["norm_final/scale"]
    del top
    static = dict(
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"],
        d_state=model["mamba_d_state"],
        dt_rank=model["mamba_dt_rank"],
        eps=model["rms_norm_eps"],
        quant=quant,
    )
    for i in range(model["num_hidden_layers"]):
        prefix = f"layer_{i}/"
        p = {name[len(prefix) :]: leaf for name, leaf in weights.take(sorted(_layer_leaves(model, i))).items()}
        for blk in blocks:
            blk[0] = _layer(p, blk[0], blk[1], attention=_is_attention(model, i), **static)
        del p
    parts = [_pool(x, blk_lens, final_scale, eps=model["rms_norm_eps"])[:n] for x, blk_lens, n in blocks]
    inverse = np.empty_like(order)
    inverse[order] = np.arange(len(order))
    return jnp.concatenate(parts, axis=0)[jnp.asarray(inverse)]


# ---- work --------------------------------------------------------------------


def _ssm_layers(model: dict) -> int:
    return sum(not _is_attention(model, i) for i in range(model["num_hidden_layers"]))


def flops(model: dict, token_lengths) -> float:
    """Forward FLOPs of encoding texts of these token lengths; multiply-add
    = 2; real tokens, not a batch shape's padding; no output head. A token
    costs, in every layer, the SwiGLU ``6·d·inter``; in a state-space layer
    the four projections ``2·(d·2di + di·(r + 2n) + r·di + di·d)``, the
    convolution ``2·d_conv·di`` and the scan's elementwise work — 7 a state
    element (``dt·A``, exp, ``·s``, ``·B``, add, ``·C``, the sum over the
    state) and 6 a channel (``dt·u``, ``D·u``, its add, the gate's sigmoid
    and two products): ``7·di·n + 6·di``; in an attention layer the
    projections ``2·(2·d·d + 2·d·kv)`` and, token ``t`` of a text attending
    over its ``t + 1`` predecessors, scores and probs@V ``4·d·(t + 1)``."""
    d, inter = model["hidden_size"], model["intermediate_size"]
    di, n, r = model["mamba_expand"] * d, model["mamba_d_state"], model["mamba_dt_rank"]
    kv = model["num_key_value_heads"] * (d // model["num_attention_heads"])
    lengths = np.asarray(token_lengths).astype(np.int64).reshape(-1)
    tokens = int(lengths.sum())
    ssm, attn = _ssm_layers(model), model["num_hidden_layers"] - _ssm_layers(model)
    mlp = 6 * d * inter
    mamba = 2 * (d * 2 * di + di * (r + 2 * n) + r * di + di * d) + 2 * model["mamba_d_conv"] * di + 7 * di * n + 6 * di
    attention = 2 * (2 * d * d + 2 * d * kv)
    causal = 4 * d * int((lengths * (lengths + 1) // 2).sum())
    return float(tokens * (model["num_hidden_layers"] * mlp + ssm * mamba + attn * attention) + attn * causal)


def ssm_scan_bytes(model: dict, tokens) -> float:
    """The least bytes any implementation of the selective scan moves for
    ``tokens`` real tokens through the state-space layers: read ``u`` and
    ``z``, write ``y``, each ``d_inner`` values of 2 bytes (the
    configuration's bfloat16) a token a layer. The step ``dt``, ``B`` and
    ``C`` can be made inside from what is read; the state never leaves."""
    di = model["mamba_expand"] * model["hidden_size"]
    return float(tokens) * _ssm_layers(model) * 3 * di * 2
