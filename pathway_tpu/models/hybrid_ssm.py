"""A causal hybrid of state-space (Mamba-1) and attention layers as a
sentence encoder: what ``SentenceTransformerEmbedder(model=<a causal
LM>)`` gives — the backbone's last hidden states, masked mean pooling,
L2.

The module **packs**: a batch of documents is one stream of tokens, each
document padded to ``doc_align`` only, and the module is told where each
starts (:meth:`HybridSSMEncoder.apply_stream`). A document boundary is a
restart of the convolution's window, of the scan's state and of the
causal mask — no other document's token is ever read — so packing changes
no row. Everything that works on a token alone (the norms, the
projections, the feed-forward) runs on ``[token_chunk, hidden]`` slices in
loops whose trip count is the stream's live length: one compiled program
serves every batch up to ``max_group_tokens`` and computes its live chunks
only. ``apply(params, ids, mask)`` is the same stream with a row a
document.

Per document (``T`` tokens)::

    x  = E[ids]
    for each layer i:
        x += attn_i(rmsnorm(x))  if i % period == offset  else  mamba_i(rmsnorm(x))
        x += swiglu_i(rmsnorm(x))
    row = l2(mean_t rmsnorm(x)_t)

Attention is causal with grouped key/value heads and no position signal
(the state-space layers carry order); a document is at most
``max_seq_len`` tokens, so a token's keys lie in the ``max_seq_len`` rows
before it and a block of queries is scored against that band of the
stream, masked by document and by order. The Mamba mixer's recurrence
runs in ``ops/selective_scan.py``, its state in VMEM, cleared where a
document starts; a tap of its depthwise convolution that reaches before a
document's first token contributes zero. Every choice between a real
token's value and nothing is a ``where``, never a product: what a padding
token's row holds (it may be anything) reaches no document's row.
Precision: bfloat16 parameters and matmul inputs, float32 accumulation;
the residual stream, every RMSNorm's statistics, the convolution, ``A``,
``D``, the step ``dt`` with its bias and softplus, the scan's state,
softmax and the pool in float32.

The parameter tree is named by layer (``layer_{i}/mamba/in_proj/kernel``)
and its leaves are created in their final types: a bfloat16 leaf never
exists in float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.selective_scan import TIME_CHUNK, selective_scan
from .token_stream import TokenStream, stream_length

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
    """The published ``config.json`` keys, letter for letter, then what
    this program adds (``dtype`` ... ``max_seq_len``)."""

    attn_layer_offset: int = 7
    attn_layer_period: int = 14
    expert_layer_offset: int = 1
    expert_layer_period: int = 2
    hidden_act: str = "silu"
    hidden_size: int = 2560
    intermediate_size: int = 8192
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 262144
    model_type: str = "jamba"
    num_attention_heads: int = 20
    num_experts: int = 1
    num_experts_per_tok: int = 1
    num_hidden_layers: int = 28
    num_key_value_heads: int = 1
    num_logits_to_keep: int = 1
    rms_norm_eps: float = 1e-6
    sliding_window: int | None = None
    tie_word_embeddings: bool = True
    use_mamba_kernels: bool = True
    vocab_size: int = 65536

    dtype: Any = jnp.bfloat16
    pooling: str = "mean"
    normalize: bool = True
    # "kernel", or "interpret" for the Pallas interpreter (CPU tests)
    scan_impl: str = "kernel"
    #: the most tokens of one stream, 32 documents of 256: its widest buffers
    #: (the mixer's float32 step, the residual stream) are a quarter of a GiB
    max_group_tokens: int = 8192
    #: tokens of one turn of the loops over the projections and the feed-forward
    token_chunk: int = 1024
    #: a document is cut to this many tokens (of ``max_position_embeddings``):
    #: how far back an attention layer looks for a token's keys
    max_seq_len: int = 256

    #: the whole-layer kernel of ``ops/fused_layer.py`` is the BERT
    #: block's; ``use_fused_encoder`` reads this and stays out
    layer_impl = "xla"
    #: a document's tokens in a stream are padded to a multiple of this:
    #: the scan kernel clears its state once a chunk of time steps
    doc_align = TIME_CHUNK

    @classmethod
    def jamba2_3b(cls, **kw) -> "HybridSSMConfig":
        """AI21-Jamba2-3B as published."""
        return cls(**kw)

    @classmethod
    def tiny_for_tests(cls, **kw) -> "HybridSSMConfig":
        """Both kinds of layer at widths a CPU test runs in seconds; no
        published model."""
        base = dict(
            attn_layer_offset=2,
            attn_layer_period=4,
            hidden_size=64,
            intermediate_size=128,
            mamba_dt_rank=4,
            num_attention_heads=4,
            num_hidden_layers=4,
            vocab_size=2048,
            max_group_tokens=1024,
            token_chunk=64,
        )
        return cls(**{**base, **kw})

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset

    def flops_per_token(self, seq: int) -> float:
        """Forward FLOPs of one token of a document of ``seq`` tokens,
        multiply-add = 2: the matmuls, the convolution, the scan's
        elementwise work (7 a state element, 6 a channel) and causal
        attention over half the length."""
        d, di, n, r = self.hidden_size, self.d_inner, self.mamba_d_state, self.mamba_dt_rank
        kv = self.num_key_value_heads * self.head_dim
        mlp = 6 * d * self.intermediate_size
        mamba = 2 * (d * 2 * di + di * (r + 2 * n) + r * di + di * d)
        mamba += 2 * self.mamba_d_conv * di + 7 * di * n + 6 * di
        attn = 2 * (2 * d * d + 2 * d * kv) + 4 * d * seq / 2
        n_attn = sum(self.is_attention(i) for i in range(self.num_hidden_layers))
        return float(self.num_hidden_layers * mlp + n_attn * attn + (self.num_hidden_layers - n_attn) * mamba)

    def stream_counts(self, lens, computed: int) -> tuple[str, int, dict]:
        """What a stream of documents of ``lens`` tokens, ``computed`` token
        rows of it run, adds to the program's counters: the stage, its
        calls, a call's units."""
        return "embed_ssm", 1, {"tokens": int(lens.sum()), "computed_tokens": computed}


def _rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _matmul(x, w):
    """bfloat16 (the parameters' type) in, float32 out."""
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=F32)


_put = jax.lax.dynamic_update_slice_in_dim


class HybridSSMEncoder:
    """``apply(params, ids, mask) -> [n, hidden]`` unit rows;
    ``apply_stream`` the same for a packed stream."""

    def __init__(self, cfg: HybridSSMConfig):
        if cfg.token_chunk % cfg.doc_align or cfg.max_group_tokens % cfg.token_chunk:
            raise ValueError("a stream is whole chunks, and a chunk whole aligned documents")
        if cfg.max_seq_len > cfg.max_group_tokens:
            raise ValueError("a document has to fit one stream")
        self.cfg = cfg

    # ---- parameters ----------------------------------------------------------

    def param_kinds(self) -> dict:
        """The tree of ``(shape, dtype, kind)``; kinds: ``normal`` N(0,
        0.02), ``conv`` uniform +-1/sqrt(d_conv), ``a_log`` log 1..N,
        ``dt_bias`` inverse softplus of a log-uniform step in [1e-3,
        1e-1], ``one``, ``zero``."""
        c = self.cfg
        d, di, n, r, w = c.hidden_size, c.d_inner, c.mamba_d_state, c.mamba_dt_rank, c.dtype
        hd = c.head_dim

        def dense(i, o):
            return {"kernel": ((i, o), w, "normal")}

        def norm(size):
            return {"scale": ((size,), F32, "one")}

        tree = {"embed": {"embedding": ((c.vocab_size, d), w, "normal")}, "norm_final": norm(d)}
        for i in range(c.num_hidden_layers):
            layer = {
                "norm_in": norm(d),
                "norm_ff": norm(d),
                "mlp": {"gate": dense(d, c.intermediate_size), "up": dense(d, c.intermediate_size), "down": dense(c.intermediate_size, d)},
            }
            if c.is_attention(i):
                layer["attn"] = {
                    "q": dense(d, c.num_attention_heads * hd),
                    "k": dense(d, c.num_key_value_heads * hd),
                    "v": dense(d, c.num_key_value_heads * hd),
                    "o": dense(c.num_attention_heads * hd, d),
                }
            else:
                layer["mamba"] = {
                    "in_proj": dense(d, 2 * di),
                    "conv": {"kernel": ((c.mamba_d_conv, di), w, "conv"), "bias": ((di,), w, "zero")},
                    "x_proj": dense(di, r + 2 * n),
                    "dt_norm": norm(r),
                    "b_norm": norm(n),
                    "c_norm": norm(n),
                    "dt_proj": {"kernel": ((r, di), w, "normal"), "bias": ((di,), F32, "dt_bias")},
                    "a_log": ((di, n), F32, "a_log"),
                    "d_skip": ((di,), F32, "one"),
                    "out_proj": dense(di, d),
                }
            tree[f"layer_{i}"] = layer
        return tree

    def init(self, seed: int = 0):
        """Seeded parameters, each leaf made on the device in its own
        type by its own small program."""
        leaves, treedef = jax.tree_util.tree_flatten(self.param_kinds(), is_leaf=lambda x: isinstance(x, tuple))
        key = jax.random.PRNGKey(seed)
        made = [
            _make_leaf(jax.random.fold_in(key, i), shape, jnp.dtype(dtype), kind, self.cfg.mamba_d_conv)
            for i, (shape, dtype, kind) in enumerate(leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, made)

    # ---- forward -------------------------------------------------------------

    def apply(self, params, ids, mask):
        """``ids`` ``[n, s]`` right-padded, ``mask`` its real tokens: the
        stream of ``n`` rows of ``s`` tokens (padded to ``doc_align``)
        with a row a document."""
        c = self.cfg
        n, s = ids.shape
        if s > c.max_seq_len:
            raise ValueError(f"a document of {s} tokens is longer than the {c.max_seq_len} an attention layer looks back")
        width = -(-s // c.doc_align) * c.doc_align
        t = stream_length(c.token_chunk, n * width)
        flat = jnp.pad(jnp.pad(ids, ((0, 0), (0, width - s))).reshape(n * width), (0, t - n * width))
        starts = jnp.arange(n, dtype=jnp.int32) * width
        return self.apply_stream(params, flat, starts, mask.sum(axis=1).astype(jnp.int32))

    def apply_stream(self, params, ids, starts, lens):
        """``ids`` ``[t]``: the documents' tokens one after another,
        document ``i`` at ``starts[i] ... starts[i] + lens[i] - 1``
        (``starts`` ascending multiples of ``doc_align``; a document that
        is not there has length 0 and starts at ``t``), anything between
        them padding. ``t`` is a :func:`token_stream.stream_length` of
        ``token_chunk``.
        -> ``[docs, hidden]`` unit rows, zeros for a document that is not
        there."""
        c = self.cfg
        t, docs = ids.shape[0], starts.shape[0]
        di, n, window = c.d_inner, c.mamba_d_state, c.max_seq_len
        st = TokenStream.of(c.token_chunk, t, starts, lens)
        x = params["embed"]["embedding"][ids].astype(F32)
        # what a layer hands from its first loop to its second: written a
        # live chunk at a time; past the live chunks it stays what it is here
        mixer = (
            jnp.zeros((t, di), c.dtype),  # u after the convolution
            jnp.zeros((t, di), F32),  # dt
            jnp.zeros((t, di), c.dtype),  # z
            jnp.zeros((t, n), F32),  # B
            jnp.zeros((t, n), F32),  # C
        )
        # keys and values, ``window`` rows of nothing in front of the stream's
        kv = (jnp.zeros((window + t, c.num_key_value_heads * c.head_dim), c.dtype),) * 2
        for i in range(c.num_hidden_layers):
            p = params[f"layer_{i}"]
            if c.is_attention(i):
                x, kv = self._attention(p, x, st, kv)
            else:
                x, mixer = self._mamba(p, x, st, mixer)

        def pool(lo, total, x=x):
            at = st.rows(st.seg, lo)
            member = at[None, :] == jnp.arange(docs, dtype=jnp.int32)[:, None]
            # a padding token's row may hold anything: it is not summed
            xc = jnp.where(at[:, None] >= 0, _rmsnorm(st.rows(x, lo), params["norm_final"]["scale"], c.rms_norm_eps), 0.0)
            return total + jnp.matmul(member.astype(F32), xc, precision=HIGHEST)

        with jax.named_scope("pw.encode.pool"):
            pooled = st.over(pool, jnp.zeros((docs, c.hidden_size), F32))
            pooled = pooled / jnp.maximum(lens, 1).astype(F32)[:, None]
            if c.normalize:
                pooled = pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
        return pooled

    def _mlp(self, p, h):
        """``h`` ``[chunk, hidden]``, the stream after the mixer -> after the feed-forward."""
        with jax.named_scope("pw.encode.mlp"):
            w = _rmsnorm(h, p["norm_ff"]["scale"], self.cfg.rms_norm_eps)
            m = p["mlp"]
            act = jax.nn.silu(_matmul(w, m["gate"]["kernel"])) * _matmul(w, m["up"]["kernel"])
            return h + _matmul(act, m["down"]["kernel"])

    def _attention(self, p, x, st, kv):
        """An attention layer and its feed-forward over the stream ``x``;
        ``kv`` the key and value buffers -> (``x``, ``kv``)."""
        c = self.cfg
        heads, groups, hd, window = c.num_attention_heads, c.num_key_value_heads, c.head_dim, c.max_seq_len
        a = p["attn"]
        # a block of queries meets the band of ``window`` rows before its
        # first and its own: a chunk is whole blocks, of ``window`` rows where
        # that divides it (the lesser of the two, for powers of two)
        block = math.gcd(st.chunk, window)
        blocks, band = st.chunk // block, window + block
        at = (jnp.arange(blocks) * block)[:, None] + jnp.arange(band)[None, :]  # a block's band, in the chunk's
        before = jnp.arange(band)[None, :] <= window + jnp.arange(block)[:, None]  # [query, key] of a block
        seg_keys = jnp.concatenate([jnp.full((window,), -2, jnp.int32), st.seg])

        def project(lo, kv):
            k, v = kv
            h = _rmsnorm(st.rows(x, lo), p["norm_in"]["scale"], c.rms_norm_eps)
            # a padding token's key is masked; its value would be a product with zero: nothing is kept of it
            real = st.rows(st.seg, lo)[:, None] >= 0
            kc = _matmul(h, a["k"]["kernel"]).astype(c.dtype)
            vc = jnp.where(real, _matmul(h, a["v"]["kernel"]), 0.0).astype(c.dtype)
            return _put(k, kc, window + lo, 0), _put(v, vc, window + lo, 0)

        def mix(lo, x):
            xc = st.rows(x, lo)
            with jax.named_scope("pw.encode.attn"):
                h = _rmsnorm(xc, p["norm_in"]["scale"], c.rms_norm_eps)
                q = _matmul(h, a["q"]["kernel"]).reshape(blocks, block, groups, heads // groups, hd).astype(c.dtype)
                kb, vb = (
                    jax.lax.dynamic_slice_in_dim(buf, lo, window + st.chunk, axis=0)[at].reshape(blocks, band, groups, hd)
                    for buf in kv
                )
                scores = jnp.einsum("bqkgd,bskd->bkgqs", q, kb, preferred_element_type=F32) / math.sqrt(hd)
                doc_q = st.rows(st.seg, lo).reshape(blocks, block, 1)
                doc_k = jax.lax.dynamic_slice_in_dim(seg_keys, lo, window + st.chunk)[at][:, None, :]
                keep = ((doc_q == doc_k) & before[None])[:, None, None]
                probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
                ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(c.dtype), vb, preferred_element_type=F32)
                h = xc + _matmul(ctx.reshape(st.chunk, heads * hd), a["o"]["kernel"])
            return _put(x, self._mlp(p, h), lo, 0)

        with jax.named_scope("pw.encode.attn"):
            kv = st.over(project, kv)
        return st.over(mix, x), kv

    def _mamba(self, p, x, st, mixer):
        """A Mamba layer and its feed-forward over the stream ``x``;
        ``mixer`` the buffers between its loops -> (``x``, ``mixer``)."""
        c = self.cfg
        di, n, r, taps_n = c.d_inner, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv
        m = p["mamba"]
        taps = m["conv"]["kernel"].astype(F32)

        def project(lo, carry):
            bufs, tail = carry
            h = _rmsnorm(st.rows(x, lo), p["norm_in"]["scale"], c.rms_norm_eps)
            with jax.named_scope("pw.encode.ssm_in"):
                uz = _matmul(h, m["in_proj"]["kernel"])
                u, z = uz[:, :di], uz[:, di:].astype(c.dtype)
            with jax.named_scope("pw.encode.ssm_conv"):
                # depthwise, causal: tap j reads the token d_conv - 1 - j before,
                # the last rows of the chunk before among them (``tail``), and
                # nothing where that is before the document's first
                window = jnp.concatenate([tail, u])
                place = st.rows(st.pos, lo)[:, None]
                conv = sum(
                    jnp.where(place >= taps_n - 1 - j, window[j : j + st.chunk] * taps[j], 0.0) for j in range(taps_n)
                )
                tail = window[st.chunk :]
                u = jax.nn.silu(conv + m["conv"]["bias"].astype(F32)).astype(c.dtype)
                proj = _matmul(u, m["x_proj"]["kernel"])
                dt = _rmsnorm(proj[..., :r], m["dt_norm"]["scale"], c.rms_norm_eps)
                bmat = _rmsnorm(proj[..., r : r + n], m["b_norm"]["scale"], c.rms_norm_eps)
                cmat = _rmsnorm(proj[..., r + n :], m["c_norm"]["scale"], c.rms_norm_eps)
                dt = jax.nn.softplus(_matmul(dt, m["dt_proj"]["kernel"]) + m["dt_proj"]["bias"])
            made = (u, dt, z, bmat, cmat)
            return tuple(_put(buf, rows, lo, 0) for buf, rows in zip(bufs, made)), tail

        mixer, _ = st.over(project, (mixer, jnp.zeros((taps_n - 1, di), F32)))
        with jax.named_scope("pw.encode.ssm_scan"):
            y = selective_scan(*mixer, -jnp.exp(m["a_log"]), m["d_skip"], st.starts, live=st.live, interpret=c.scan_impl == "interpret")

        def mix(lo, x):
            with jax.named_scope("pw.encode.ssm_out"):
                h = st.rows(x, lo) + _matmul(st.rows(y, lo), m["out_proj"]["kernel"])
            return _put(x, self._mlp(p, h), lo, 0)

        return st.over(mix, x), mixer


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make_leaf(key, shape, dtype, kind, d_conv):
    if kind == "one":
        return jnp.ones(shape, dtype)
    if kind == "zero":
        return jnp.zeros(shape, dtype)
    if kind == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=F32)), shape).astype(dtype)
    if kind == "dt_bias":
        step = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3), math.log(1e-1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    if kind == "conv":
        bound = 1.0 / math.sqrt(d_conv)
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return (0.02 * jax.random.normal(key, shape, dtype)).astype(dtype)
