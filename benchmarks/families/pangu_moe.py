"""The family ``pangu_moe``: one expert-parallel rank of a causal stack of
latent-attention (MLA) layers with sandwich norms, leading dense SwiGLU
feed-forwards and then sparse ones — a shared expert plus the routed
experts this rank holds, chosen by a sigmoid router of the published
width (openPangu-Ultra-MoE, the DeepSeek-V3 lineage) — used as a sentence
encoder the way sentence-transformers wraps a plain causal LM: masked
mean pooling over the last hidden states (after the final norm), L2.
Everything the benchmark knows of this block is here: its leaves and how
the seed draws them, its tokenizer, its plain reference, its FLOPs, and
the operations and bytes of the routed experts' products.

It imports nothing of the program, and of ``lib/`` only ``lowprec`` (the
control's rounding); the contract it fills is in ``lib/spec.py``.

``model`` (the configuration's): the published keys, of which
``n_routed_experts`` counts the experts **held here** (``experts_first``
says from which on) and ``router_experts`` is the router's published
width. Per text of ``L`` tokens (``m`` the mask of real tokens, ``N`` an
RMSNorm with its own scale)::

    x = E[ids]
    for layer i:
        h  = N_in(x)
        cq = N_qa(h W_qa);  q = cq W_qb                  -> heads x (nope | rope)
        [ckv | kr] = h W_kva;  ckv = N_kva(ckv)
        [k_nope | v] = ckv W_kvb                         -> heads x (nope | v)
        s  = (q_nope . k_nope + rope(q_rope) . rope(kr)) / sqrt(nope + rope)
             softmax over keys s <= t, s real; kr is one vector shared by all heads
        x += N_post_attn(concat_heads(softmax(s) v) W_o)
        h  = N_pre_mlp(x)
        f  = (silu(h W_gate) * (h W_up)) W_down                       if i < first_k_dense_replace
           = swiglu_shared(h) + sum_{e held} w_e swiglu_e(h)           otherwise
             g = sigmoid(h W_g);  top = the num_experts_per_tok largest of g over all router_experts
             w_e = routed_scaling_factor * g_e / sum_{e' in top} g_e'  if e in top, else 0
        x += N_post_mlp(f)
    row = l2(sum_t m_t N_final(x)_t / sum_t m_t)

``rope(u)_t = u cos(t a) + [-u_hi | u_lo] sin(t a)`` over the halves of
the ``qk_rope_head_dim`` dims, ``a_j = rope_theta ** (-2 j / dim)``
repeated for both halves, ``t`` the token's index in its text. ``w`` is
normalised over all chosen experts, held or not: what the experts that
are not held would have added is left out, and that partial result goes
on to the next layer; nothing stands in for the other ranks.

The reference is straightforward ``jax.numpy`` in float32 at ``highest``
precision: every held expert is applied to every token and masked by
``w`` — no dispatch, no grouped product, no kernel, no cache. Layers are
outermost — a layer's leaves are taken from the handle once, at most 4 GB
of float32 — and documents go through it in blocks of 8, so nothing
larger than a block's ``[8, heads, L, L]`` scores is alive beside the
hidden states. ``quant`` is the control, the step below the
configuration's bfloat16: every dense matmul with its activations (a
scale per token) and its weights (a scale per output channel) rounded to
``fp8`` or ``int8``; the router (float32 in the configuration), rope and
the attention products stay float32.

Seeded scales (``weights`` in the configuration's file): word vectors
N(0, ``word_std``); a matrix N(0, ``matrix_gain`` / sqrt(fan_in)); the
router N(0, ``router_gain`` / sqrt(fan_in)), so a logit is N(0,
``router_gain``²) on a unit-RMS input; norm scales 1, but for the two
norms that stand after a sub-block, ``post_norm_scale``: under sandwich
norms that scale, and not the size of ``W_o`` or ``W_down``, is what a
sub-block adds to the residual stream, and at 1 ten additions of unit
RMS drown the word vectors (a query then ranks its own document first
39 times of 64 at a width-256 proxy; at 0.15, 64 of 64).
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


def _is_dense(model: dict, layer: int) -> bool:
    return layer < model["first_k_dense_replace"]


def _qk_dim(model: dict) -> int:
    return model["qk_nope_head_dim"] + model["qk_rope_head_dim"]


def _swiglu_leaves(prefix: str, d: int, width: int) -> dict:
    return {
        prefix + "gate/kernel": ((d, width), "matrix"),
        prefix + "up/kernel": ((d, width), "matrix"),
        prefix + "down/kernel": ((width, d), "matrix"),
    }


def _layer_leaves(model: dict, layer: int) -> dict[str, tuple[tuple[int, ...], str]]:
    d, heads = model["hidden_size"], model["num_attention_heads"]
    q_rank, kv_rank = model["q_lora_rank"], model["kv_lora_rank"]
    p = f"layer_{layer}/"
    out = {p + f"norm_{n}/scale": ((d,), "one") for n in ("in", "pre_mlp")}
    out.update({p + f"norm_{n}/scale": ((d,), "post") for n in ("post_attn", "post_mlp")})
    out.update(
        {
            p + "attn/q_a/kernel": ((d, q_rank), "matrix"),
            p + "attn/q_a_norm/scale": ((q_rank,), "one"),
            p + "attn/q_b/kernel": ((q_rank, heads * _qk_dim(model)), "matrix"),
            p + "attn/kv_a/kernel": ((d, kv_rank + model["qk_rope_head_dim"]), "matrix"),
            p + "attn/kv_a_norm/scale": ((kv_rank,), "one"),
            p + "attn/kv_b/kernel": ((kv_rank, heads * (model["qk_nope_head_dim"] + model["v_head_dim"])), "matrix"),
            p + "attn/o/kernel": ((heads * model["v_head_dim"], d), "matrix"),
        }
    )
    if _is_dense(model, layer):
        out.update(_swiglu_leaves(p + "mlp/", d, model["intermediate_size"]))
    else:
        inner, held = model["moe_intermediate_size"], model["n_routed_experts"]
        out.update(_swiglu_leaves(p + "moe/shared/", d, model["n_shared_experts"] * inner))
        out[p + "moe/router/kernel"] = ((d, model["router_experts"]), "router")
        out[p + "moe/experts/gate"] = ((held, d, inner), "expert")
        out[p + "moe/experts/up"] = ((held, d, inner), "expert")
        out[p + "moe/experts/down"] = ((held, inner, d), "expert")
    return out


def leaves(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind). Kinds: ``word``, ``matrix``, ``router``,
    ``expert`` (stacked ``[held, fan_in, fan_out]``), ``one``, ``post``
    (the module's docstring says how each is drawn)."""
    d = model["hidden_size"]
    out = {"embed/embedding": ((model["vocab_size"], d), "word"), "norm_final/scale": ((d,), "one")}
    for i in range(model["num_hidden_layers"]):
        out.update(_layer_leaves(model, i))
    return out


def _layer_groups(model: dict, layer: int) -> list[list[str]]:
    """A layer's leaves in four groups: each of the three large matrices
    of its feed-forward (the stacked experts' gate, up and down: 0.25 B
    parameters each at the published widths, 1 GB of float32; a dense
    layer's 0.14 B each) on its own, and the rest — the attention, the
    norms, the router and the shared expert, 0.25 B — together."""
    names = sorted(_layer_leaves(model, layer))
    alone = [n for n in names if "/moe/experts/" in n or "/mlp/" in n]
    return [[n for n in names if n not in alone]] + [[n] for n in alone]


def take_groups(model: dict) -> list[list[str]]:
    """The embedding, then each layer in its four groups: what is made in
    float32 and cast together, beside everything already laid — a quarter
    of a sparse layer at most."""
    groups = [["embed/embedding", "norm_final/scale"]]
    for i in range(model["num_hidden_layers"]):
        groups += _layer_groups(model, i)
    return groups


def make_leaf(kind: str, shape, key, scales: dict):
    """One leaf in float32 from its own key; traced inside the handle's jit."""
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    if kind == "post":
        return jnp.full(shape, scales["post_norm_scale"], jnp.float32)
    if kind == "word":
        return scales["word_std"] * jax.random.normal(key, shape, jnp.float32)
    gain = {"matrix": "matrix_gain", "expert": "matrix_gain", "router": "router_gain"}.get(kind)
    if gain is None:
        raise ValueError(f"no leaf kind {kind!r}")
    fan_in = shape[-2]
    return scales[gain] / math.sqrt(fan_in) * jax.random.normal(key, shape, jnp.float32)


# ---- tokens ------------------------------------------------------------------


def tokens_of(words, model: dict):
    """Token length of a text of ``words`` generated words (a number or an
    array of them): one token a word and the two specials, cut as the
    tokenizer cuts."""
    return np.minimum(np.asarray(words) + SPECIALS, model["max_seq_len"])


def tokenize(texts, model: dict) -> tuple[np.ndarray, np.ndarray]:
    """-> (ids [n, max_seq_len] int32 zero-padded on the right, lens [n]).
    The hash tokenizer at the vocabulary held here: id 101, each word
    hashed into the ids 999 ... vocab_size - 1 (the slice), id 102, cut
    to ``max_seq_len``."""
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


def _swiglu(p, prefix, h, quant):
    act = jax.nn.silu(_dense(h, p[prefix + "gate/kernel"], quant)) * _dense(h, p[prefix + "up/kernel"], quant)
    return _dense(act, p[prefix + "down/kernel"], quant)


def _rope(u, theta: float):
    """``u`` ``[b, s, ..., dim]`` rotated by halves, position = axis 1."""
    s, dim = u.shape[1], u.shape[-1]
    a = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.concatenate([a, a])[None, :]
    angle = angle.reshape((1, s) + (1,) * (u.ndim - 3) + (dim,))
    turned = jnp.concatenate([-u[..., dim // 2 :], u[..., : dim // 2]], axis=-1)
    return u * jnp.cos(angle) + turned * jnp.sin(angle)


def _attention(p, h, mask, *, heads, nope, rope, vdim, kv_rank, theta, eps, quant):
    b, s, _ = h.shape
    cq = _rmsnorm(_dense(h, p["attn/q_a/kernel"], quant), p["attn/q_a_norm/scale"], eps)
    q = _dense(cq, p["attn/q_b/kernel"], quant).reshape(b, s, heads, nope + rope)
    kva = _dense(h, p["attn/kv_a/kernel"], quant)
    ckv = _rmsnorm(kva[..., :kv_rank], p["attn/kv_a_norm/scale"], eps)
    k_rope = _rope(kva[..., kv_rank:], theta)
    kv = _dense(ckv, p["attn/kv_b/kernel"], quant).reshape(b, s, heads, nope + vdim)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope], precision=HIGHEST)
    scores = scores + jnp.einsum("bqhd,bkd->bhqk", _rope(q[..., nope:], theta), k_rope, precision=HIGHEST)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    keep = causal[None, None] & mask[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(keep, scores / math.sqrt(nope + rope), NEG), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., nope:], precision=HIGHEST)
    return _dense(ctx.reshape(b, s, heads * vdim), p["attn/o/kernel"], quant)


def expert_weights(scores, *, first: int, held: int, top_k: int, scale: float, norm: bool):
    """``scores`` ``[..., router_experts]`` -> ``[..., held]``: each held
    expert's weight for each token — its score over the sum of the
    ``top_k`` chosen ones (all of them, held or not) times ``scale`` where
    it is among them, 0 where it is not."""
    top, _ = jax.lax.top_k(scores, top_k)
    chosen = scores >= top[..., -1:]
    total = jnp.sum(top, axis=-1, keepdims=True) if norm else 1.0
    return jnp.where(chosen, scale * scores / total, 0.0)[..., first : first + held]


def _moe(p, h, *, first, top_k, scale, norm, quant):
    g = jax.nn.sigmoid(jnp.matmul(h, p["moe/router/kernel"], precision=HIGHEST))
    held = p["moe/experts/gate"].shape[0]
    w = expert_weights(g, first=first, held=held, top_k=top_k, scale=scale, norm=norm)
    out = _swiglu(p, "moe/shared/", h, quant)
    for e in range(held):  # every held expert over every token, masked by its weight
        act = jax.nn.silu(_dense(h, p["moe/experts/gate"][e], quant)) * _dense(h, p["moe/experts/up"][e], quant)
        out = out + w[..., e : e + 1] * _dense(act, p["moe/experts/down"][e], quant)
    return out


_STATIC = ("dense", "heads", "nope", "rope", "vdim", "kv_rank", "theta", "eps", "first", "top_k", "scale", "norm", "quant")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer(p, x, lens, *, dense, heads, nope, rope, vdim, kv_rank, theta, eps, first, top_k, scale, norm, quant):
    """One residual block over a block of documents; ``p`` holds this
    layer's leaves by their names without the ``layer_{i}/`` prefix."""
    mask = jnp.arange(x.shape[1])[None, :] < lens[:, None]
    a = _attention(
        p, _rmsnorm(x, p["norm_in/scale"], eps), mask,
        heads=heads, nope=nope, rope=rope, vdim=vdim, kv_rank=kv_rank, theta=theta, eps=eps, quant=quant,
    )  # fmt: skip
    x = x + _rmsnorm(a, p["norm_post_attn/scale"], eps)
    h = _rmsnorm(x, p["norm_pre_mlp/scale"], eps)
    if dense:
        f = _swiglu(p, "mlp/", h, quant)
    else:
        f = _moe(p, h, first=first, top_k=top_k, scale=scale, norm=norm, quant=quant)
    return x + _rmsnorm(f, p["norm_post_mlp/scale"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _pool(x, lens, scale, *, eps: float):
    live = (jnp.arange(x.shape[1])[None, :] < lens[:, None])[:, :, None].astype(x.dtype)
    pooled = (_rmsnorm(x, scale, eps) * live).sum(1) / jnp.maximum(live.sum(1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def encode(weights, model: dict, texts, *, quant: str | None = None, block: int = 8):
    """texts -> [n, hidden] unit rows on the device, in input order.
    Length-sorted blocks of ``block`` texts, each padded to its longest
    rounded up to 64; layers outermost, so a layer's leaves are taken
    from the handle (``lib/weights.py``) once, in the groups it makes them
    in, and dropped before the next."""
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
        nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"],
        vdim=model["v_head_dim"],
        kv_rank=model["kv_lora_rank"],
        theta=float(model["rope_theta"]),
        eps=model["rms_norm_eps"],
        first=model["experts_first"],
        top_k=model["num_experts_per_tok"],
        scale=float(model["routed_scaling_factor"]),
        norm=bool(model["norm_topk_prob"]),
        quant=quant,
    )
    for i in range(model["num_hidden_layers"]):
        prefix = f"layer_{i}/"
        p = {}
        for group in _layer_groups(model, i):
            p.update({name[len(prefix) :]: leaf for name, leaf in weights.take(group).items()})
        for blk in blocks:
            blk[0] = _layer(p, blk[0], blk[1], dense=_is_dense(model, i), **static)
        del p
    parts = [_pool(x, blk_lens, final_scale, eps=model["rms_norm_eps"])[:n] for x, blk_lens, n in blocks]
    inverse = np.empty_like(order)
    inverse[order] = np.arange(len(order))
    return jnp.concatenate(parts, axis=0)[jnp.asarray(inverse)]


# ---- work --------------------------------------------------------------------


def _expert_matmul_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def flops(model: dict, token_lengths) -> float:
    """Forward FLOPs of encoding texts of these token lengths; multiply-add
    = 2; real tokens, not a batch shape's padding; no output head. A token
    costs, in every layer, the five attention projections ``2·(d·q_rank +
    q_rank·heads·(nope + rope) + d·(kv_rank + rope) + kv_rank·heads·(nope +
    v) + heads·v·d)`` and, token ``t`` of a text attending over its ``t +
    1`` predecessors, scores and probs@V ``2·heads·(nope + rope + v)·(t +
    1)``; in a dense layer the SwiGLU ``6·d·inter``; in a sparse layer the
    router ``2·d·router_experts``, the shared expert ``6·d·moe_inter`` and
    the routed experts held here at the expected ``top_k · held /
    router_experts`` assignments a token, ``6·d·moe_inter`` each."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    nope, rope, v = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    q_rank, kv_rank = model["q_lora_rank"], model["kv_lora_rank"]
    layers = model["num_hidden_layers"]
    n_dense = min(model["first_k_dense_replace"], layers)
    lengths = np.asarray(token_lengths).astype(np.int64).reshape(-1)
    tokens = int(lengths.sum())
    proj = 2 * (d * q_rank + q_rank * heads * (nope + rope) + d * (kv_rank + rope) + kv_rank * heads * (nope + v) + heads * v * d)
    causal = 2 * heads * (nope + rope + v) * int((lengths * (lengths + 1) // 2).sum())
    expert = 2 * _expert_matmul_params(model)
    assigned = model["num_experts_per_tok"] * model["n_routed_experts"] / model["router_experts"]
    sparse = 2 * d * model["router_experts"] + (model["n_shared_experts"] + assigned) * expert
    per_token = layers * proj + n_dense * 6 * d * model["intermediate_size"] + (layers - n_dense) * sparse
    return float(tokens * per_token + layers * causal)


def expert_flops(model: dict, assignments) -> float:
    """FLOPs of the routed experts' three products for ``assignments``
    (token, held expert) pairs: ``6·d·moe_inter`` each."""
    return float(assignments) * 2 * _expert_matmul_params(model)


def expert_bytes(model: dict, layer_calls) -> float:
    """The least bytes the routed experts' products move in
    ``layer_calls`` sparse-layer calls: every held expert's three matrices
    once a call, in the configuration's bfloat16 (2 bytes). The token rows
    are a few per cent of that at this cell's batch and are left out."""
    return float(layer_calls) * model["n_routed_experts"] * _expert_matmul_params(model) * 2
