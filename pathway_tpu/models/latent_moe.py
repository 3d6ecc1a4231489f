"""A causal stack of latent-attention (MLA) layers with dense and
sparse-expert feed-forwards as a sentence encoder: what
``SentenceTransformerEmbedder(model=<a causal LM>)`` gives — the
backbone's last hidden states, masked mean pooling, L2 — behind the same
``module.apply(params, ids, mask) -> unit rows`` the other encoders have.
The block is openPangu-Ultra-MoE's (the DeepSeek-V3 lineage with
``sandwich_norm``), and the module is **one expert-parallel rank** of it:
``experts_held = (first, count)`` says which routed experts' weights are
here; the router keeps its published width.

Per document (``m`` the mask of its real tokens; ``N`` an RMSNorm with
its own scale)::

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
index in its document (theta ``rope_theta``, no scaling); ``kr`` is one
vector shared by every head. ``w`` is normalised over all the chosen
experts, held or not: what the absent ones would have added is left out
and the partial result goes on to the next layer — nothing stands in for
the other ranks or their exchange (``ops/expert_dispatch.py``).

The module **packs** a write batch: one stream of tokens, each document
padded to ``doc_align`` only, told where each starts
(:meth:`LatentMoEEncoder.apply_stream`); ``rope`` takes a token's place
in its own document, and the attention masks by document as well as by
order, so packing changes which rows share a product and not what a row
computes. Everything that works on a token alone — the norms, the
projections, ``W_o``, the feed-forwards, the router — runs in loops over
``[token_chunk, hidden]`` slices whose trip count is the stream's live
length: one compiled program serves every batch up to
``max_group_tokens`` and computes its live chunks only. A layer's first
loop writes the stream's keys, values and the queries' latent; its
second makes a chunk's queries and runs the attention for them against
the whole stream. ``apply(params, ids, mask)`` is the padded forward, a
text a row: the query program's.

The attention — the queries' rope, scores, the mask, softmax, values —
is the Pallas kernel of ``ops/mla_attention.py`` where a stream is whole
tiles of it (every write batch; a padded batch of 128 or 256 is the
stream whose text ``i`` starts at ``i * s``), an XLA chain elsewhere (the
query program's 16 tokens, and off the chip); both compute the same
thing.

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
from .token_stream import TokenStream

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_put = jax.lax.dynamic_update_slice_in_dim
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
    #: the most tokens of one stream, 32 documents of 256: its float32
    #: residual stream is a quarter GiB at the published width
    max_group_tokens: int = 8192
    #: tokens of one turn of the loops over the per-token work
    token_chunk: int = 1024
    #: a document's tokens in a stream are padded to a multiple of this
    doc_align: int = 16
    #: a document is cut to this many tokens (of ``max_position_embeddings``):
    #: how many key tiles of the attention kernel a query tile meets
    max_seq_len: int = 256

    #: the whole-layer kernel of ``ops/fused_layer.py`` is the BERT
    #: block's; ``use_fused_encoder`` reads this and stays out
    layer_impl = "xla"
    #: a sequence bucket is a compiled program of the whole stack (the
    #: query program's; a write batch is one stream)
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
            max_group_tokens=1024,
            token_chunk=256,
        )
        return cls(**{**base, **kw})

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def stream_counts(self, lens, computed: int) -> tuple[str, int, dict]:
        """What a stream of documents of ``lens`` tokens, ``computed`` token
        rows of it run, adds to the program's counters: the stage, its
        calls, a call's units."""
        return "embed_latent", 1, {"tokens": int(lens.sum()), "computed_tokens": computed}

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
    """``x`` ``[..., r]`` times ``w`` ``[r, heads * d]`` -> float32 ``[...,
    heads * d]``, as one product of the tokens' rows."""
    r = x.shape[-1]
    return jnp.matmul(x.reshape(-1, r), w, preferred_element_type=F32).reshape(*x.shape[:-1], -1)


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


def _context_xla(q_nope, q_rope, k_nope, k_rope, v, keep):
    """The attention as an XLA chain: ``[b, q, heads, nope | rope]`` queries
    (their rope turned), ``[b, k, heads, nope | v]`` keys and values,
    ``k_rope`` ``[b, k, rope]``, ``keep`` ``[b, q, k]`` the pairs that
    count -> the float32 context ``[b, q, heads * v]``. The scores are
    written out, ``[b, heads, q, k]`` float32."""
    b, q, heads, _ = q_nope.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, preferred_element_type=F32)
    scores = scores + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope, preferred_element_type=F32)
    probs = jax.nn.softmax(jnp.where(keep[:, None], scores / math.sqrt(q_nope.shape[-1] + k_rope.shape[-1]), -1e30), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v, preferred_element_type=F32)
    return ctx.reshape(b, q, -1)


class LatentMoEEncoder:
    """``apply(params, ids, mask) -> [n, hidden]`` unit rows;
    ``apply_stream`` the same for a packed stream, with the experts' loads."""

    def __init__(self, cfg: LatentMoEConfig):
        first, count = cfg.experts_held
        if not (0 <= first and count >= 1 and first + count <= cfg.n_routed_experts):
            raise ValueError(f"experts_held {cfg.experts_held} is not a range of the router's {cfg.n_routed_experts}")
        if cfg.token_chunk % cfg.doc_align or cfg.max_group_tokens % cfg.token_chunk:
            raise ValueError("a stream is whole chunks, and a chunk whole aligned documents")
        if cfg.max_seq_len > cfg.max_group_tokens:
            raise ValueError("a document has to fit one stream")
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
        """``ids`` ``[n, s]`` right-padded, ``mask`` its real tokens -> ``[n,
        hidden]`` unit rows: a text a row, as the query program runs it."""
        c = self.cfg
        b, s = ids.shape
        eps = c.rms_norm_eps
        x = params["embed"]["embedding"][ids].astype(F32)
        rope = _rope_table(s, c.qk_rope_head_dim, c.rope_theta)
        per = _texts_per_block(b, s, c.num_attention_heads)
        for i in range(c.num_hidden_layers):
            p = params[f"layer_{i}"]

            def block(args, p=p, w=self._heads_weights(p["attn"]), dense=c.is_dense(i)):
                xb, mb = args
                a = self._attention(p["attn"], w, _rmsnorm(xb, p["norm_in"]["scale"], eps), mb, rope)
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
                f, _ = self._moe(p["moe"], _rmsnorm(x, p["norm_pre_mlp"]["scale"], eps), mask)
                x = x + _rmsnorm(f, p["norm_post_mlp"]["scale"], eps)
        with jax.named_scope("pw.encode.pool"):
            x = _rmsnorm(x, params["norm_final"]["scale"], eps)
            live = mask[:, :, None].astype(F32)
            pooled = (x * live).sum(axis=1) / jnp.maximum(live.sum(axis=1), 1.0)
            if c.normalize:
                pooled = pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
        return pooled

    def apply_stream(self, params, ids, starts, lens):
        """``ids`` ``[t]``: the documents' tokens one after another,
        document ``i`` at ``starts[i] ... starts[i] + lens[i] - 1``
        (``starts`` ascending multiples of ``doc_align``; a document that
        is not there has length 0 and starts at ``t``), anything between
        them padding; ``t`` whole chunks of ``token_chunk``.
        -> (``[docs, hidden]`` unit rows, zeros for a document that is not
        there; ``[sparse layers, held]`` int32: the real tokens assigned to
        each held expert in each sparse layer)."""
        c = self.cfg
        eps, dtype = c.rms_norm_eps, c.dtype
        t, docs = ids.shape[0], starts.shape[0]
        heads, nope, rot, vd = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        st = TokenStream.of(c.token_chunk, t, starts, lens)
        real = st.seg >= 0
        table = _rope_table(c.max_seq_len, rot, c.rope_theta)
        cos, sin = (rows[st.pos] for rows in table)  # by the token's place in its document
        x = params["embed"]["embedding"][ids].astype(F32)
        # what a layer's first loop hands to its second — the queries'
        # latent, keys and values — and a sparse layer's second loop to its
        # experts: written a live chunk at a time; past the live chunks they
        # stay what they are here
        latents = tuple(jnp.zeros((t, width), dtype) for width in (c.q_lora_rank, heads * nope, rot, heads * vd))
        experts_in = (jnp.zeros((t, c.hidden_size), dtype), jnp.zeros((t, c.n_routed_experts), F32))
        loads = []
        for i in range(c.num_hidden_layers):
            p = params[f"layer_{i}"]
            w = self._heads_weights(p["attn"])

            def project(lo, bufs, x=x, p=p, w=w):
                h = _rmsnorm(st.rows(x, lo), p["norm_in"]["scale"], eps)
                cq = self._q_latent(p["attn"], h)
                k_nope, k_rope, v = self._kv(p["attn"], w, h, st.rows(cos, lo), st.rows(sin, lo))
                # a padding token's value would be a product with zero: nothing is kept of it
                v = jnp.where(st.rows(real, lo)[:, None], v, 0).astype(dtype)
                return tuple(_put(buf, rows, lo, 0) for buf, rows in zip(bufs, (cq, k_nope, k_rope, v)))

            latents = st.over(project, latents)

            def mix(lo, carry, p=p, w=w, latents=latents, dense=c.is_dense(i)):
                x, (tokens, scores) = carry
                q_nope, q_rope = self._q_heads(w, st.rows(latents[0], lo))
                with jax.named_scope("pw.encode.attn"):
                    ctx = self._stream_context(q_nope, q_rope, *latents[1:], st, lo, table, cos, sin)
                    a = _matmul(ctx, p["attn"]["o"]["kernel"])
                xc = st.rows(x, lo) + _rmsnorm(a, p["norm_post_attn"]["scale"], eps)
                if dense:
                    with jax.named_scope("pw.encode.mlp"):
                        f = _swiglu(p["mlp"], _rmsnorm(xc, p["norm_pre_mlp"]["scale"], eps))
                    return _put(x, xc + _rmsnorm(f, p["norm_post_mlp"]["scale"], eps), lo, 0), (tokens, scores)
                h = _rmsnorm(xc, p["norm_pre_mlp"]["scale"], eps)
                with jax.named_scope("pw.encode.moe_route"):
                    g = jax.nn.sigmoid(jnp.matmul(h, p["moe"]["router"]["kernel"], precision=HIGHEST))
                return _put(x, xc, lo, 0), (_put(tokens, h.astype(dtype), lo, 0), _put(scores, g, lo, 0))

            x, experts_in = st.over(mix, (x, experts_in))
            if c.is_dense(i):
                continue
            tokens, scores = experts_in
            with jax.named_scope("pw.encode.moe_route"):
                expert_ids, weights = route(
                    scores, c.num_experts_per_tok, scale=c.routed_scaling_factor, norm_topk=c.norm_topk_prob
                )
            e = p["moe"]["experts"]
            routed, load = held_expert_sum(
                tokens, expert_ids, weights, real, e["gate"], e["up"], e["down"],
                first=c.experts_held[0], experts=c.n_routed_experts, interpret=c.expert_impl == "interpret",
            )  # fmt: skip
            loads.append(load)

            def add(lo, x, p=p, tokens=tokens, routed=routed):
                with jax.named_scope("pw.encode.moe_shared"):
                    f = _swiglu(p["moe"]["shared"], st.rows(tokens, lo)) + st.rows(routed, lo)
                return _put(x, st.rows(x, lo) + _rmsnorm(f, p["norm_post_mlp"]["scale"], eps), lo, 0)

            x = st.over(add, x)

        def pool(lo, total):
            at = st.rows(st.seg, lo)
            member = at[None, :] == jnp.arange(docs, dtype=jnp.int32)[:, None]
            # a padding token's row may hold anything: it is not summed
            xc = jnp.where(at[:, None] >= 0, _rmsnorm(st.rows(x, lo), params["norm_final"]["scale"], eps), 0.0)
            return total + jnp.matmul(member.astype(F32), xc, precision=HIGHEST)

        with jax.named_scope("pw.encode.pool"):
            pooled = st.over(pool, jnp.zeros((docs, c.hidden_size), F32))
            pooled = pooled / jnp.maximum(lens, 1).astype(F32)[:, None]
            if c.normalize:
                pooled = pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
        return pooled, jnp.stack(loads) if loads else jnp.zeros((0, c.experts_held[1]), jnp.int32)

    def _q_latent(self, p, h):
        """The queries' latent ``[..., q_lora_rank]`` of ``h`` (after the
        layer's first norm), in the parameters' type."""
        c = self.cfg
        with jax.named_scope("pw.encode.mla_q"):
            return _rmsnorm(_matmul(h, p["q_a"]["kernel"]), p["q_a_norm"]["scale"], c.rms_norm_eps).astype(c.dtype)

    def _heads_weights(self, p):
        """The halves of ``W_qb`` (nope | rope) and of ``W_kvb`` (nope | v),
        each ``[rank, heads * d]``: two products each, as a slice of the
        weights is tens of MB and a slice of their float32 product hundreds.
        Made once a layer, outside the loops over the chunks: a slice taken
        inside them is a copy of the weights every turn."""
        c = self.cfg
        heads, nope = c.num_attention_heads, c.qk_nope_head_dim
        w_qb = p["q_b"]["kernel"].reshape(-1, heads, c.qk_head_dim)
        w_kvb = p["kv_b"]["kernel"].reshape(-1, heads, nope + c.v_head_dim)
        halves = (w_qb[..., :nope], w_qb[..., nope:], w_kvb[..., :nope], w_kvb[..., nope:])
        return tuple(half.reshape(half.shape[0], -1) for half in halves)

    def _q_heads(self, w, cq):
        """The queries from their latent (``w`` the layer's
        :meth:`_heads_weights`): ``q_nope`` ``[..., heads * nope]`` in the
        parameters' type, ``q_rope`` ``[..., heads * rope]`` float32, turned
        by the attention."""
        with jax.named_scope("pw.encode.mla_q"):
            return _heads_product(cq, w[0]).astype(self.cfg.dtype), _heads_product(cq, w[1])

    def _kv(self, p, w, h, cos, sin):
        """Keys and values of ``h`` ``[..., s, hidden]`` (after the layer's
        first norm) at positions whose rope rows are ``cos``/``sin`` ``[s,
        rope]``: ``k_nope``, ``v`` ``[..., s, heads * dim]``, ``k_rope``
        ``[..., s, rope]`` turned, all in the parameters' type."""
        c = self.cfg
        with jax.named_scope("pw.encode.mla_kv"):
            kva = _matmul(h, p["kv_a"]["kernel"])
            ckv = _rmsnorm(kva[..., : c.kv_lora_rank], p["kv_a_norm"]["scale"], c.rms_norm_eps).astype(c.dtype)
            k_rope = _rope(kva[..., c.kv_lora_rank :], cos, sin).astype(c.dtype)  # one vector for every head
            k_nope = _heads_product(ckv, w[2]).astype(c.dtype)
            v = _heads_product(ckv, w[3]).astype(c.dtype)
        return k_nope, k_rope, v

    def _stream_context(self, q_nope, q_rope, k_nope, k_rope, v, st, lo, table, cos, sin):
        """The context of the chunk of queries from row ``lo`` of the stream
        ``st`` against the whole stream's keys and values."""
        c = self.cfg
        t, chunk = st.t, st.chunk
        heads, nope, rot, vd = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        if mla_attention.route(t, c.attention_impl) == "kernel":
            return mla_attention.mla_attention(
                q_nope, q_rope, k_nope, k_rope, v, st.seg, st.pos, *table, span=mla_attention.span_of(c.max_seq_len),
                first=lo, live=st.live, interpret=c.attention_impl == "interpret",
            )  # fmt: skip
        # off the chip: against every key of the stream, masked by document and by order
        rows = lo + jnp.arange(chunk)
        keep = (st.rows(st.seg, lo)[:, None] == st.seg[None, :]) & (rows[:, None] >= jnp.arange(t)[None, :])
        turned = _rope(q_rope.reshape(1, chunk, heads, rot), st.rows(cos, lo), st.rows(sin, lo)).astype(c.dtype)
        return _context_xla(
            q_nope.reshape(1, chunk, heads, nope), turned, k_nope.reshape(1, t, heads, nope), k_rope[None],
            v.reshape(1, t, heads, vd), keep[None],
        )[0]  # fmt: skip

    def _attention(self, p, w, h, mask, rope):
        c = self.cfg
        b, s, _ = h.shape
        heads, nope, rot, vd = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        cos, sin = rope
        q_nope, q_rope = self._q_heads(w, self._q_latent(p, h))
        k_nope, k_rope, v = self._kv(p, w, h, cos, sin)
        seg = jnp.where(mask, jnp.arange(b, dtype=jnp.int32)[:, None], -1)
        with jax.named_scope("pw.encode.attn"):
            if mla_attention.route(s, c.attention_impl) == "kernel":
                # the texts as a stream whose text i starts at i * s
                pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
                ctx = mla_attention.mla_attention(
                    *(a.reshape(b * s, -1) for a in (q_nope, q_rope, k_nope, k_rope, v)), seg.reshape(-1),
                    pos.reshape(-1), cos, sin, span=mla_attention.span_of(s), interpret=c.attention_impl == "interpret",
                ).reshape(b, s, -1)  # fmt: skip
            else:
                q_rope = _rope(q_rope.reshape(b, s, heads, rot), cos, sin).astype(c.dtype)
                causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
                ctx = _context_xla(
                    q_nope.reshape(b, s, heads, nope), q_rope, k_nope.reshape(b, s, heads, nope), k_rope,
                    v.reshape(b, s, heads, vd), causal[None] & (seg[:, :, None] == seg[:, None, :]),
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
