"""A causal stack of power-retention layers as a sentence encoder: what
``SentenceTransformerEmbedder(model=<a causal LM>)`` gives — the
backbone's last hidden states, masked mean pooling, L2. The block is
Brumby-14B-Base's: Qwen3-14B's (grouped heads with a per-head RMSNorm on
queries and keys, rope, SwiGLU, RMSNorm pre-norm, no biases) with every
softmax attention a power retention (``ops/power_retention.py``).

Unlike the other encoders this one **packs**: a batch of documents is
one stream of tokens, each document padded to ``doc_align`` only, and
the module is told where each starts (:meth:`PowerRetentionEncoder.apply_stream`).
A document boundary is a restart of the positions, of the gate's running
sum and of the causal mask — no other document's token is ever scored —
so packing changes no row. The projections and the feed-forward run on
``[tokens, hidden]`` a ``token_chunk`` at a time, in loops whose trip
count is the stream's live length: one compiled program serves every
batch up to ``max_group_tokens`` and computes its live chunks only.
``apply(params, ids, mask)`` is the same stream with a row a document.

Per document of ``T`` tokens (``N`` an RMSNorm with its own scale)::

    x = E[ids]                                   # float32 residual stream
    for layer i:
        u = N_in(x)
        q = N_q(u W_q)  per head over head_dim;   k = N_k(u W_k);   v = u W_v
        q, k <- rope(theta, over halves, positions 0..T-1)
        log g = logsigmoid(u W_g + gate_bias)     # [T, kv_heads], float32
        x = x + retention(q / sqrt(head_dim), k, v, log g) W_o
        w = N_ff(x)
        x = x + (silu(w W_gate) * (w W_up)) W_down
    row = l2(mean_t N_final(x)_t)

Precision: bfloat16 parameters and matmul inputs, float32 accumulation;
the residual stream, every norm's statistics, rope, the gate (its
weights, its product at ``highest``, its running sum), the scores' power
and decay, numerator and denominator, and the pool in float32.

Departures from the published model, none of them in ``config.json``:
the degree (2), the gate as ``logsigmoid`` of a bias-free projection plus
a constant ``gate_bias`` (a trained gate sits near 1; a seeded one would
sit at 1/2), that Qwen3's per-head norms and rope stay, the normaliser's
``retention_eps`` (1e-2: a mid-document token's weights sum to ~100, an
early token's may sum to less than their own rounding); no output head (nothing on an embedding path reads
logits); mean pooling, as sentence-transformers gives a plain causal LM.

The parameter tree is named by layer (``layer_{i}/retention/q/kernel``)
and its leaves are created in their final types.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.power_retention import power_retention
from .hybrid_ssm import _matmul, _rmsnorm  # float32 statistics; bfloat16 in, float32 out
from .token_stream import TokenStream, stream_length

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class PowerRetentionConfig:
    """The published ``config.json`` keys, letter for letter, then what
    this program adds (``dtype`` ... ``blocks``)."""

    attention_bias: bool = False
    head_dim: int = 128
    hidden_act: str = "silu"
    hidden_size: int = 5120
    intermediate_size: int = 17408
    max_position_embeddings: int = 32768
    max_window_layers: int = 40
    model_type: str = "brumby"
    num_attention_heads: int = 40
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    rms_norm_eps: float = 1e-6
    rope_scaling: None = None
    rope_theta: float = 1000000.0
    sliding_window: None = None
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    vocab_size: int = 151936

    dtype: Any = jnp.bfloat16
    pooling: str = "mean"
    normalize: bool = True
    degree: int = 2
    gate_bias: float = 5.0
    #: the normaliser's: above the bfloat16 rounding of a unit score's square,
    #: so that a token whose few causal scores are all near zero (the early
    #: tokens of every text) does not divide rounding by rounding
    retention_eps: float = 1e-2
    # "kernel", or "interpret" for the Pallas interpreter (CPU tests)
    retention_impl: str = "kernel"
    #: a document is cut to this many tokens (of ``max_position_embeddings``)
    max_seq_len: int = 4096
    #: the most tokens of one stream: its float32 residual stream is a third of a GiB
    max_group_tokens: int = 16384
    #: a document's tokens in a stream are padded to a multiple of this
    doc_align: int = 128
    #: tokens of one turn of the loops over the projections and the feed-forward
    token_chunk: int = 1024
    #: the retention kernel's query and key blocks
    blocks: tuple[int, int] = (256, 512)

    #: the whole-layer kernel of ``ops/fused_layer.py`` is the BERT
    #: block's; ``use_fused_encoder`` reads this and stays out
    layer_impl = "xla"

    @classmethod
    def brumby_14b_base_l8(cls, **kw) -> "PowerRetentionConfig":
        """The first pipeline stage of Brumby-14B-Base: the embedding and
        layers 0-7 of the 40, every width as published."""
        return cls(**{"num_hidden_layers": 8, **kw})

    @classmethod
    def tiny_for_tests(cls, **kw) -> "PowerRetentionConfig":
        """Grouped heads 5 : 1 at widths, blocks and chunks a CPU test
        runs in seconds; no published model."""
        base = dict(
            head_dim=32,
            hidden_size=64,
            intermediate_size=128,
            num_attention_heads=10,
            num_hidden_layers=2,
            num_key_value_heads=2,
            vocab_size=2048,
            gate_bias=2.0,
            max_seq_len=256,
            max_group_tokens=256,
            doc_align=8,
            token_chunk=64,
            blocks=(16, 32),
        )
        return cls(**{**base, **kw})

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.hidden_size, self.head_dim
        heads, kv = self.num_attention_heads, self.num_key_value_heads
        return 2 * d * heads * hd + 2 * d * kv * hd + d * kv + 3 * d * self.intermediate_size

    def flops_per_token(self, seq: int) -> float:
        """Forward FLOPs of one token of a document of ``seq`` tokens,
        multiply-add = 2: the matmuls and the causal pairs' score and
        value products over half the length."""
        pairs = 4 * self.num_attention_heads * self.head_dim * seq / 2
        return float(self.num_hidden_layers * (2 * self.layer_matmul_params + pairs))

    def stream_counts(self, lens, computed: int) -> tuple[str, int, dict]:
        """What a stream of documents of ``lens`` tokens, ``computed`` token
        rows of it run, adds to the program's counters: the stage, its
        calls (a retention a layer), a call's units — the stream's real
        tokens and their causal pairs."""
        pairs = int((lens.astype("int64") * (lens + 1) // 2).sum())
        return "embed_retention", self.num_hidden_layers, {"tokens": int(lens.sum()), "rows": pairs, "computed_tokens": computed}


def _rope(x, cos, sin):
    """``x`` ``[t, heads, dim]`` float32 rotated by halves; ``cos`` and
    ``sin`` ``[t, dim]``."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


class PowerRetentionEncoder:
    """``apply(params, ids, mask) -> [n, hidden]`` unit rows;
    ``apply_stream`` the same for a packed stream."""

    def __init__(self, cfg: PowerRetentionConfig):
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("query heads are no whole number of groups of the key/value heads")
        if cfg.token_chunk % cfg.blocks[0] or cfg.token_chunk % cfg.blocks[1] or cfg.max_group_tokens % cfg.token_chunk:
            raise ValueError("a stream is whole chunks, and a chunk whole blocks of the retention kernel")
        if cfg.max_seq_len > min(cfg.max_group_tokens, cfg.max_position_embeddings):
            raise ValueError("a document has to fit one stream, and the model's positions")
        self.cfg = cfg

    # ---- parameters ----------------------------------------------------------

    def param_kinds(self) -> dict:
        """The tree of ``(shape, dtype, kind)``; kinds: ``normal`` N(0,
        0.02), ``one``."""
        c = self.cfg
        d, w, hd = c.hidden_size, c.dtype, c.head_dim
        heads, kv = c.num_attention_heads, c.num_key_value_heads

        def dense(i, o, dtype=w):
            return {"kernel": ((i, o), dtype, "normal")}

        def norm(size):
            return {"scale": ((size,), F32, "one")}

        tree = {"embed": {"embedding": ((c.vocab_size, d), w, "normal")}, "norm_final": norm(d)}
        for i in range(c.num_hidden_layers):
            tree[f"layer_{i}"] = {
                "norm_in": norm(d),
                "norm_ff": norm(d),
                "retention": {
                    "q": dense(d, heads * hd),
                    "k": dense(d, kv * hd),
                    "v": dense(d, kv * hd),
                    "o": dense(heads * hd, d),
                    "gate": dense(d, kv, F32),
                    "q_norm": norm(hd),
                    "k_norm": norm(hd),
                },
                "mlp": {
                    "gate": dense(d, c.intermediate_size),
                    "up": dense(d, c.intermediate_size),
                    "down": dense(c.intermediate_size, d),
                },
            }
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
        """``ids`` ``[n, s]`` right-padded, ``mask`` its real tokens: the
        stream of ``n * s`` tokens with a row a document."""
        n, s = ids.shape
        t = stream_length(self.cfg.token_chunk, n * s)
        flat = jnp.pad(ids.reshape(n * s), (0, t - n * s))
        starts = jnp.arange(n, dtype=jnp.int32) * s
        return self.apply_stream(params, flat, starts, mask.sum(axis=1).astype(jnp.int32))

    def apply_stream(self, params, ids, starts, lens):
        """``ids`` ``[t]``: the documents' tokens one after another,
        document ``i`` at ``starts[i] ... starts[i] + lens[i] - 1``
        (``starts`` ascending; a document that is not there has length 0
        and starts at ``t``), anything between them padding. ``t`` is a
        :func:`token_stream.stream_length` of ``token_chunk``. ->
        ``[docs, hidden]`` unit rows, zeros for a document that is not
        there."""
        c = self.cfg
        t, docs = ids.shape[0], starts.shape[0]
        heads, kv, hd, eps = c.num_attention_heads, c.num_key_value_heads, c.head_dim, c.rms_norm_eps
        st = TokenStream.of(c.token_chunk, t, starts, lens)
        chunk, seg, pos, over_chunks, rows = st.chunk, st.seg, st.pos, st.over, st.rows

        inv = 1.0 / (c.rope_theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
        angle = pos.astype(F32)[:, None] * jnp.concatenate([inv, inv])[None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x = params["embed"]["embedding"][ids].astype(F32)
        q = jnp.zeros((t, heads * hd), c.dtype)
        k = v = jnp.zeros((t, kv * hd), c.dtype)
        log_g = jnp.zeros((t, kv), F32)
        for i in range(c.num_hidden_layers):
            p = params[f"layer_{i}"]
            r, m = p["retention"], p["mlp"]

            def project(lo, bufs, x=x, p=p, r=r):
                q, k, v, log_g = bufs
                u = _rmsnorm(rows(x, lo), p["norm_in"]["scale"], eps)
                cs, sn = rows(cos, lo), rows(sin, lo)
                qc = _rmsnorm(_matmul(u, r["q"]["kernel"]).reshape(chunk, heads, hd), r["q_norm"]["scale"], eps)
                kc = _rmsnorm(_matmul(u, r["k"]["kernel"]).reshape(chunk, kv, hd), r["k_norm"]["scale"], eps)
                qc = (_rope(qc, cs, sn) * hd**-0.5).reshape(chunk, heads * hd).astype(c.dtype)
                kc = _rope(kc, cs, sn).reshape(chunk, kv * hd).astype(c.dtype)
                vc = _matmul(u, r["v"]["kernel"]).astype(c.dtype)
                gc = jax.nn.log_sigmoid(jnp.matmul(u, r["gate"]["kernel"], precision=HIGHEST) + c.gate_bias)
                put = jax.lax.dynamic_update_slice_in_dim
                return put(q, qc, lo, 0), put(k, kc, lo, 0), put(v, vc, lo, 0), put(log_g, gc, lo, 0)

            with jax.named_scope("pw.encode.ret_qkv"):
                q, k, v, log_g = over_chunks(project, (q, k, v, log_g))
            with jax.named_scope("pw.encode.retention"):
                o = power_retention(
                    q, k, v, log_g, seg, pos,
                    live=st.live_chunks * chunk, degree=c.degree, eps=c.retention_eps,
                    block_q=c.blocks[0], block_k=c.blocks[1], interpret=c.retention_impl == "interpret",
                )  # fmt: skip

            def mix(lo, x, o=o, p=p, r=r, m=m):
                with jax.named_scope("pw.encode.ret_out"):
                    h = rows(x, lo) + _matmul(rows(o, lo), r["o"]["kernel"])
                with jax.named_scope("pw.encode.mlp"):
                    w = _rmsnorm(h, p["norm_ff"]["scale"], eps)
                    act = jax.nn.silu(_matmul(w, m["gate"]["kernel"])) * _matmul(w, m["up"]["kernel"])
                    h = h + _matmul(act, m["down"]["kernel"])
                return jax.lax.dynamic_update_slice_in_dim(x, h, lo, 0)

            x = over_chunks(mix, x)

        def pool(lo, total, x=x):
            member = rows(seg, lo)[None, :] == jnp.arange(docs, dtype=jnp.int32)[:, None]
            # a padding token's row may hold anything: it is not summed
            xc = jnp.where(rows(seg, lo)[:, None] >= 0, _rmsnorm(rows(x, lo), params["norm_final"]["scale"], eps), 0.0)
            return total + jnp.matmul(member.astype(F32), xc, precision=HIGHEST)

        with jax.named_scope("pw.encode.pool"):
            pooled = over_chunks(pool, jnp.zeros((docs, c.hidden_size), F32))
            pooled = pooled / jnp.maximum(lens, 1).astype(F32)[:, None]
            if c.normalize:
                pooled = pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
        return pooled


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make_leaf(key, shape, dtype, kind):
    if kind == "one":
        return jnp.ones(shape, dtype)
    return (0.02 * jax.random.normal(key, shape, dtype)).astype(dtype)
