"""A causal stack of latent-attention (MLA) layers with dense and
sparse-expert feed-forwards as a sentence encoder: what
``SentenceTransformerEmbedder(model=<a causal LM>)`` gives — the
backbone's last hidden states, masked mean pooling, L2 — behind the same
``module.apply(params, ids, mask) -> unit rows`` the other encoders have.
The block is openPangu-Ultra-MoE's (the DeepSeek-V3 lineage with
``sandwich_norm``), and the module is **one expert-parallel rank** of it:
``experts_held = (first, count)`` says which routed experts' weights are
here; the router keeps its published width.

Per text (right-padded; ``m`` the mask of real tokens; ``N`` an RMSNorm
with its own scale)::

    x = E[ids]                                          # float32 residual stream
    for layer i:
        h  = N_in(x)
        cq = N_qa(W_qa h);  q = W_qb cq                 -> heads x (nope | rope)
        [ckv | kr] = W_kva h;  ckv = N_kva(ckv)
        [k_nope | v] = W_kvb ckv                        -> heads x (nope | v)
        s  = (q_nope . k_nope + rope(q_rope) . rope(kr)) / sqrt(nope + rope), causal & m
        x  = x + N_post_attn(W_o concat_heads(softmax(s) v))
        h  = N_pre_mlp(x)
        f  = swiglu(h)                                              if i < first_k_dense_replace
           = swiglu_shared(h) + sum_{e in top_k(g), e held} w_e swiglu_e(h)   otherwise
             g = sigmoid(W_g h);  w_e = routed_scaling_factor * g_e / sum_{e' in top_k(g)} g_e'
        x  = x + N_post_mlp(f)
    row = l2(sum_t m_t N_final(x)_t / sum_t m_t)

``rope`` rotates halves of the ``qk_rope_head_dim`` dims by the token's
index in its text (theta ``rope_theta``, no scaling); ``kr`` is one
vector shared by every head. ``w`` is normalised over all the chosen
experts, held or not: what the absent ones would have added is left out
and the partial result goes on to the next layer — nothing stands in for
the other ranks or their exchange (``ops/expert_dispatch.py``).

The attention — the queries' rope, scores, causal and padding mask,
softmax, values — is the Pallas kernel of ``ops/mla_attention.py`` where
a text is whole tiles of it (the write batches' 256, set-up's 128 and
256), an XLA chain elsewhere (the query program's 16 tokens); both
compute the same thing.

Precision: bfloat16 parameters and matmul inputs, float32 accumulation;
the residual stream, every norm's statistics, rope, softmax, the pool,
and the router — its weights, its logits (``highest``), the sigmoid and
the top-k — in float32, as the published gate computes it.

Departures from the published model: sigmoid scoring with no expert
groups and no selection bias (the config has none of ``scoring_func``,
``n_group``, ``topk_group``: the lineage's gate); rope over halves, not
interleaved pairs (a fixed permutation of the rope dims of ``W_qb`` and
``W_kva``); no multi-token-prediction module and no output head (neither
is on an embedding path); mean pooling, as sentence-transformers gives a
plain causal LM.

The parameter tree is named by layer (``layer_{i}/attn/q_a/kernel``) and
its leaves are created in their final types.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..ops import mla_attention
from ..ops.expert_dispatch import held_expert_sum, route
from .hybrid_ssm import _matmul, _rmsnorm  # float32 statistics; bfloat16 in, float32 out

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: the float32 attention scores of one block of texts: half a GiB
_SCORE_BYTES = 1 << 29


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """The published ``config.json`` keys, letter for letter, then what
    this program adds (``experts_held`` ... ``attention_impl``)."""

    attention_bias: bool = False
    first_k_dense_replace: int = 3
    hidden_act: str = "silu"
    hidden_size: int = 7680
    intermediate_size: int = 18432
    kv_lora_rank: int = 512
    max_position_embeddings: int = 131072
    model_type: str = "pangu_ultra_moe"
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    num_attention_heads: int = 128
    num_experts_per_tok: int = 8
    num_hidden_layers: int = 61
    num_key_value_heads: int = 128
    num_nextn_predict_layers: int = 1
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    routed_scaling_factor: float = 2.5
    sandwich_norm: bool = True
    tie_word_embeddings: bool = False
    v_head_dim: int = 128
    vocab_size: int = 153600

    #: (first, count): the routed experts whose weights this rank holds
    experts_held: tuple[int, int] = (0, 256)
    dtype: Any = jnp.bfloat16
    pooling: str = "mean"
    normalize: bool = True
    # "kernel", or "interpret" for the Pallas interpreter (CPU tests)
    expert_impl: str = "kernel"
    # "kernel", or "interpret" for the Pallas interpreter (CPU tests)
    attention_impl: str = "kernel"

    #: the whole-layer kernel of ``ops/fused_layer.py`` is the BERT
    #: block's; ``use_fused_encoder`` reads this and stays out
    layer_impl = "xla"
    #: a sequence bucket is a compiled program of the whole stack
    seq_buckets = (16, 32, 64, 128, 256, 512)

    @classmethod
    def pangu_ultra_moe_ep16_l5(cls, **kw) -> "LatentMoEConfig":
        """One chip's share of openPangu-Ultra-MoE-718B: each layer
        shared by 16 chips (the attention and the shared expert on each,
        16 of the 256 routed experts here), one leading dense layer and
        four sparse ones of the 3 + 58, an eighth of the vocabulary."""
        base = dict(num_hidden_layers=5, first_k_dense_replace=1, experts_held=(0, 16), vocab_size=19200)
        return cls(**{**base, **kw})

    @classmethod
    def tiny_for_tests(cls, **kw) -> "LatentMoEConfig":
        """Both kinds of layer, 8 experts top-2, at widths a CPU test
        runs in seconds; no published model."""
        base = dict(
            first_k_dense_replace=1,
            hidden_size=64,
            intermediate_size=128,
            kv_lora_rank=16,
            moe_intermediate_size=32,
            n_routed_experts=8,
            num_attention_heads=4,
            num_experts_per_tok=2,
            num_hidden_layers=3,
            num_key_value_heads=4,
            q_lora_rank=32,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=16,
            vocab_size=2048,
            experts_held=(0, 8),
        )
        return cls(**{**base, **kw})

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def max_group_tokens(self) -> int:
        """Tokens of one dispatch group: the power of two whose float32
        residual stream stays under a quarter GiB — 8,192 at the
        published width, 32 texts of 256."""
        return 1 << (((1 << 28) // (4 * self.hidden_size)).bit_length() - 1)

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def group_counts(self, seq: int, lens) -> tuple[str, int, dict] | None:
        """What a forward of texts padded to ``seq`` with ``lens`` real
        tokens adds to the program's counters where its attention takes
        the kernel: the stage, its calls (one a layer), a call's units —
        the real tokens and the token rows the kernel computes. ``None``
        on the XLA route."""
        if mla_attention.route(seq, self.attention_impl) != "kernel":
            return None
        return "embed_attention", self.num_hidden_layers, {"tokens": int(lens.sum()), "computed_tokens": len(lens) * seq}

    def flops_per_token(self, seq: int) -> float:
        """Forward FLOPs of one token in a text padded to ``seq``,
        multiply-add = 2: the projections, causal attention over half the
        padded length, the dense feed-forward or the router, the shared
        expert and the routed experts held here at an even router's
        ``num_experts_per_tok * held / n_routed_experts`` a token."""
        d, heads = self.hidden_size, self.num_attention_heads
        proj = d * self.q_lora_rank + self.q_lora_rank * heads * self.qk_head_dim
        proj += d * (self.kv_lora_rank + self.qk_rope_head_dim)
        proj += self.kv_lora_rank * heads * (self.qk_nope_head_dim + self.v_head_dim)
        proj += heads * self.v_head_dim * d
        attn = 2 * proj + 2 * heads * (self.qk_head_dim + self.v_head_dim) * seq / 2
        expert = 6 * d * self.moe_intermediate_size
        routed = self.num_experts_per_tok * self.experts_held[1] / self.n_routed_experts
        sparse = 2 * d * self.n_routed_experts + (self.n_shared_experts + routed) * expert
        n_dense = min(self.first_k_dense_replace, self.num_hidden_layers)
        dense = 6 * d * self.intermediate_size
        return float(self.num_hidden_layers * attn + n_dense * dense + (self.num_hidden_layers - n_dense) * sparse)


def _swiglu(p, h):
    act = jax.nn.silu(_matmul(h, p["gate"]["kernel"])) * _matmul(h, p["up"]["kernel"])
    return _matmul(act, p["down"]["kernel"])


def _rope(x, cos, sin):
    """``x`` ``[..., s, (heads,) rope]`` float32 rotated by halves;
    ``cos``/``sin`` ``[s, rope]`` broadcast over what lies between."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    if x.ndim == 4:
        cos, sin = cos[:, None, :], sin[:, None, :]
    return x * cos + turned * sin


def _heads_product(x, w):
    """``x`` ``[b, s, r]`` times ``w`` ``[r, heads, d]`` -> float32 ``[b, s,
    heads * d]``, as one product of the tokens' rows."""
    b, s, r = x.shape
    return jnp.matmul(x.reshape(b * s, r), w.reshape(r, -1), preferred_element_type=F32).reshape(b, s, -1)


def _rope_table(seq: int, dim: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    angle = jnp.arange(seq, dtype=F32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def _texts_per_block(batch: int, seq: int, heads: int) -> int:
    """The most texts (a divisor of ``batch``) whose float32 attention
    scores ``[texts, heads, seq, seq]`` stay under ``_SCORE_BYTES``."""
    most = max(1, _SCORE_BYTES // (4 * heads * seq * seq))
    return max(b for b in range(1, batch + 1) if batch % b == 0 and b <= most)


def _context_xla(q_nope, q_rope, k_nope, k_rope, v, mask):
    """The attention as an XLA chain: ``[b, s, heads, nope | rope | v]``
    queries, keys and values, ``k_rope`` ``[b, s, rope]``, ``mask`` ``[b,
    s]`` -> the float32 context ``[b, s, heads * v]``. The scores are
    written out, ``[b, heads, s, s]`` float32."""
    b, s, heads, vd = v.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, preferred_element_type=F32)
    scores = scores + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope, preferred_element_type=F32)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    keep = causal[None, None] & mask[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(keep, scores / math.sqrt(q_nope.shape[-1] + k_rope.shape[-1]), -1e30), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v, preferred_element_type=F32)
    return ctx.reshape(b, s, heads * vd)


class LatentMoEEncoder:
    """``apply(params, ids, mask) -> [n, hidden]`` unit rows."""

    def __init__(self, cfg: LatentMoEConfig):
        first, count = cfg.experts_held
        if not (0 <= first and count >= 1 and first + count <= cfg.n_routed_experts):
            raise ValueError(f"experts_held {cfg.experts_held} is not a range of the router's {cfg.n_routed_experts}")
        self.cfg = cfg

    # ---- parameters ----------------------------------------------------------

    def param_kinds(self) -> dict:
        """The tree of ``(shape, dtype, kind)``; kinds: ``normal`` N(0,
        0.02), ``one``."""
        c = self.cfg
        d, w, heads = c.hidden_size, c.dtype, c.num_attention_heads
        inner, held = c.moe_intermediate_size, c.experts_held[1]

        def dense(i, o, dtype=w):
            return {"kernel": ((i, o), dtype, "normal")}

        def norm(size):
            return {"scale": ((size,), F32, "one")}

        def swiglu(width):
            return {"gate": dense(d, width), "up": dense(d, width), "down": dense(width, d)}

        tree = {"embed": {"embedding": ((c.vocab_size, d), w, "normal")}, "norm_final": norm(d)}
        for i in range(c.num_hidden_layers):
            layer = {
                "norm_in": norm(d),
                "norm_post_attn": norm(d),
                "norm_pre_mlp": norm(d),
                "norm_post_mlp": norm(d),
                "attn": {
                    "q_a": dense(d, c.q_lora_rank),
                    "q_a_norm": norm(c.q_lora_rank),
                    "q_b": dense(c.q_lora_rank, heads * c.qk_head_dim),
                    "kv_a": dense(d, c.kv_lora_rank + c.qk_rope_head_dim),
                    "kv_a_norm": norm(c.kv_lora_rank),
                    "kv_b": dense(c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim)),
                    "o": dense(heads * c.v_head_dim, d),
                },
            }
            if c.is_dense(i):
                layer["mlp"] = swiglu(c.intermediate_size)
            else:
                layer["moe"] = {
                    "router": dense(d, c.n_routed_experts, F32),
                    "shared": swiglu(c.n_shared_experts * inner),
                    "experts": {
                        "gate": ((held, d, inner), w, "normal"),
                        "up": ((held, d, inner), w, "normal"),
                        "down": ((held, inner, d), w, "normal"),
                    },
                }
            tree[f"layer_{i}"] = layer
        return tree

    def init(self, seed: int = 0):
        """Seeded parameters, each leaf made on the device in its own
        type by its own small program."""
        leaves, treedef = jax.tree_util.tree_flatten(self.param_kinds(), is_leaf=lambda x: isinstance(x, tuple))
        key = jax.random.PRNGKey(seed)
        made = [
            _make_leaf(jax.random.fold_in(key, i), shape, jnp.dtype(dtype), kind)
            for i, (shape, dtype, kind) in enumerate(leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, made)

    # ---- forward -------------------------------------------------------------

    def apply(self, params, ids, mask):
        return self.apply_with_loads(params, ids, mask)[0]

    def apply_with_loads(self, params, ids, mask):
        """-> (unit rows ``[n, hidden]``, ``[sparse layers, held]`` int32:
        the real tokens assigned to each held expert in each sparse
        layer)."""
        c = self.cfg
        b, s = ids.shape
        eps = c.rms_norm_eps
        x = params["embed"]["embedding"][ids].astype(F32)
        rope = _rope_table(s, c.qk_rope_head_dim, c.rope_theta)
        per = _texts_per_block(b, s, c.num_attention_heads)
        loads = []
        for i in range(c.num_hidden_layers):
            p = params[f"layer_{i}"]

            def block(args, p=p, dense=c.is_dense(i)):
                xb, mb = args
                a = self._attention(p["attn"], _rmsnorm(xb, p["norm_in"]["scale"], eps), mb, rope)
                xb = xb + _rmsnorm(a, p["norm_post_attn"]["scale"], eps)
                if dense:
                    with jax.named_scope("pw.encode.mlp"):
                        f = _swiglu(p["mlp"], _rmsnorm(xb, p["norm_pre_mlp"]["scale"], eps))
                    xb = xb + _rmsnorm(f, p["norm_post_mlp"]["scale"], eps)
                return xb

            if per == b:
                x = block((x, mask))
            else:
                # a block of texts at a time: the float32 scores, and the
                # dense feed-forward's gate and up, of all of them at once
                # would be gigabytes
                blocks = (x.reshape(b // per, per, s, -1), mask.reshape(b // per, per, s))
                x = jax.lax.map(block, blocks).reshape(b, s, -1)
            if not c.is_dense(i):
                f, load = self._moe(p["moe"], _rmsnorm(x, p["norm_pre_mlp"]["scale"], eps), mask)
                x = x + _rmsnorm(f, p["norm_post_mlp"]["scale"], eps)
                loads.append(load)
        with jax.named_scope("pw.encode.pool"):
            x = _rmsnorm(x, params["norm_final"]["scale"], eps)
            live = mask[:, :, None].astype(F32)
            pooled = (x * live).sum(axis=1) / jnp.maximum(live.sum(axis=1), 1.0)
            if c.normalize:
                pooled = pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
        held = c.experts_held[1]
        return pooled, jnp.stack(loads) if loads else jnp.zeros((0, held), jnp.int32)

    def _attention(self, p, h, mask, rope):
        c = self.cfg
        b, s, _ = h.shape
        heads, nope, rot, vd = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        cos, sin = rope
        # the halves of W_qb and W_kvb as two products each: a slice of
        # the weights is tens of MB, a slice of their float32 product
        # hundreds a block of texts
        w_qb = p["q_b"]["kernel"].reshape(-1, heads, nope + rot)
        w_kvb = p["kv_b"]["kernel"].reshape(-1, heads, nope + vd)
        with jax.named_scope("pw.encode.mla_q"):
            cq = _rmsnorm(_matmul(h, p["q_a"]["kernel"]), p["q_a_norm"]["scale"], c.rms_norm_eps).astype(c.dtype)
            q_nope = _heads_product(cq, w_qb[..., :nope]).astype(c.dtype)
            q_rope = _heads_product(cq, w_qb[..., nope:])  # float32, turned by the attention
        with jax.named_scope("pw.encode.mla_kv"):
            kva = _matmul(h, p["kv_a"]["kernel"])
            ckv = _rmsnorm(kva[..., : c.kv_lora_rank], p["kv_a_norm"]["scale"], c.rms_norm_eps).astype(c.dtype)
            k_rope = _rope(kva[..., c.kv_lora_rank :], cos, sin).astype(c.dtype)  # one vector for every head
            k_nope = _heads_product(ckv, w_kvb[..., :nope]).astype(c.dtype)
            v = _heads_product(ckv, w_kvb[..., nope:]).astype(c.dtype)
        with jax.named_scope("pw.encode.attn"):
            if mla_attention.route(s, c.attention_impl) == "kernel":
                lens = mask.sum(axis=1, dtype=jnp.int32)  # right-padded texts
                ctx = mla_attention.mla_attention(
                    q_nope, q_rope, k_nope, k_rope, v, lens, cos, sin, interpret=c.attention_impl == "interpret"
                )
            else:
                q_rope = _rope(q_rope.reshape(b, s, heads, rot), cos, sin).astype(c.dtype)
                ctx = _context_xla(
                    q_nope.reshape(b, s, heads, nope), q_rope, k_nope.reshape(b, s, heads, nope), k_rope,
                    v.reshape(b, s, heads, vd), mask,
                )  # fmt: skip
            return _matmul(ctx, p["o"]["kernel"])

    def _moe(self, p, h, mask):
        c = self.cfg
        b, s, d = h.shape
        tokens = h.reshape(b * s, d)
        with jax.named_scope("pw.encode.moe_route"):
            logits = jnp.matmul(tokens, p["router"]["kernel"], precision=HIGHEST)
            expert_ids, weights = route(
                jax.nn.sigmoid(logits), c.num_experts_per_tok, scale=c.routed_scaling_factor, norm_topk=c.norm_topk_prob
            )
        with jax.named_scope("pw.encode.moe_shared"):
            shared = _swiglu(p["shared"], tokens)
        e = p["experts"]
        routed, loads = held_expert_sum(
            tokens.astype(c.dtype),
            expert_ids,
            weights,
            mask.reshape(b * s),
            e["gate"],
            e["up"],
            e["down"],
            first=c.experts_held[0],
            experts=c.n_routed_experts,
            interpret=c.expert_impl == "interpret",
        )
        return (shared + routed).reshape(b, s, d), loads


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make_leaf(key, shape, dtype, kind):
    if kind == "one":
        return jnp.ones(shape, dtype)
    return (0.02 * jax.random.normal(key, shape, dtype)).astype(dtype)
