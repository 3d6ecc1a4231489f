"""A causal hybrid of state-space (Mamba-1) and attention layers as a
sentence encoder: what ``SentenceTransformerEmbedder(model=<a causal
LM>)`` gives — the backbone's last hidden states, masked mean pooling,
L2 — behind the same ``module.apply(params, ids, mask) -> unit rows`` the
BERT-block encoders have.

Per text (right-padded; ``m`` the mask of real tokens)::

    x  = E[ids]
    for each layer i:
        x += attn_i(rmsnorm(x))  if i % period == offset  else  mamba_i(rmsnorm(x))
        x += swiglu_i(rmsnorm(x))
    row = l2(sum_t m_t rmsnorm(x)_t / sum_t m_t)

Attention is causal with grouped key/value heads and no position signal
(the state-space layers carry order). The Mamba mixer's recurrence runs
in ``ops/selective_scan.py``, its state in VMEM. Precision: bfloat16
parameters and matmul inputs, float32 accumulation; the residual stream,
every RMSNorm's statistics, the convolution, ``A``, ``D``, the step
``dt`` with its bias and softplus, the scan's state, softmax and the pool
in float32.

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

from ..ops.selective_scan import selective_scan

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
    """The published ``config.json`` keys, letter for letter, then what
    this program adds (``dtype`` ... ``scan_impl``)."""

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

    #: the whole-layer kernel of ``ops/fused_layer.py`` is the BERT
    #: block's; ``use_fused_encoder`` reads this and stays out
    layer_impl = "xla"
    #: a sequence bucket is a 28-layer program (~10 s to compile on the
    #: v5e): powers of two, not the BERT blocks' fourteen
    seq_buckets = (16, 32, 64, 128, 256, 512)

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
        )
        return cls(**{**base, **kw})

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def max_group_tokens(self) -> int:
        """Tokens of one dispatch group: its widest float32 activation
        (the feed-forward's gate and up, or the mixer's in_proj) held to
        half a GiB — 8,192 at the published widths, 32 texts of 256."""
        return (1 << 29) // (4 * 2 * max(self.d_inner, self.intermediate_size))

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset

    def flops_per_token(self, seq: int) -> float:
        """Forward FLOPs of one token in a text padded to ``seq``,
        multiply-add = 2: the matmuls, the convolution, the scan's
        elementwise work (7 a state element, 6 a channel) and causal
        attention over half the padded length."""
        d, di, n, r = self.hidden_size, self.d_inner, self.mamba_d_state, self.mamba_dt_rank
        kv = self.num_key_value_heads * self.head_dim
        mlp = 6 * d * self.intermediate_size
        mamba = 2 * (d * 2 * di + di * (r + 2 * n) + r * di + di * d)
        mamba += 2 * self.mamba_d_conv * di + 7 * di * n + 6 * di
        attn = 2 * (2 * d * d + 2 * d * kv) + 4 * d * seq / 2
        n_attn = sum(self.is_attention(i) for i in range(self.num_hidden_layers))
        return float(self.num_hidden_layers * mlp + n_attn * attn + (self.num_hidden_layers - n_attn) * mamba)


def _rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _matmul(x, w):
    """bfloat16 (the parameters' type) in, float32 out."""
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=F32)


class HybridSSMEncoder:
    """``apply(params, ids, mask) -> [n, hidden]`` unit rows."""

    def __init__(self, cfg: HybridSSMConfig):
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
        c = self.cfg
        x = params["embed"]["embedding"][ids].astype(F32)
        for i in range(c.num_hidden_layers):
            p = params[f"layer_{i}"]
            h = _rmsnorm(x, p["norm_in"]["scale"], c.rms_norm_eps)
            x = x + (self._attention(p["attn"], h, mask) if c.is_attention(i) else self._mamba(p["mamba"], h))
            with jax.named_scope("pw.encode.mlp"):
                h = _rmsnorm(x, p["norm_ff"]["scale"], c.rms_norm_eps)
                m = p["mlp"]
                act = jax.nn.silu(_matmul(h, m["gate"]["kernel"])) * _matmul(h, m["up"]["kernel"])
                x = x + _matmul(act, m["down"]["kernel"])
        with jax.named_scope("pw.encode.pool"):
            x = _rmsnorm(x, params["norm_final"]["scale"], c.rms_norm_eps)
            live = mask[:, :, None].astype(F32)
            pooled = (x * live).sum(axis=1) / jnp.maximum(live.sum(axis=1), 1.0)
            if c.normalize:
                pooled = pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
            return pooled

    def _attention(self, p, h, mask):
        c = self.cfg
        b, s, _ = h.shape
        heads, kv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        with jax.named_scope("pw.encode.attn"):
            q = _matmul(h, p["q"]["kernel"]).reshape(b, s, kv, heads // kv, hd).astype(c.dtype)
            k = _matmul(h, p["k"]["kernel"]).reshape(b, s, kv, hd).astype(c.dtype)
            v = _matmul(h, p["v"]["kernel"]).reshape(b, s, kv, hd).astype(c.dtype)
            scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=F32) / math.sqrt(hd)
            causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
            keep = causal[None, None, None] & mask[:, None, None, None, :]
            probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
            ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(c.dtype), v, preferred_element_type=F32)
            return _matmul(ctx.reshape(b, s, heads * hd), p["o"]["kernel"])

    def _mamba(self, p, h):
        c = self.cfg
        di, n, r = c.d_inner, c.mamba_d_state, c.mamba_dt_rank
        with jax.named_scope("pw.encode.ssm_in"):
            # the two halves of W_in as two products: a slice of the
            # weights is 26 MB, a slice of their product 250 MB a layer
            w_in = p["in_proj"]["kernel"]
            u = _matmul(h, w_in[:, :di])
            z = _matmul(h, w_in[:, di:]).astype(c.dtype)
        with jax.named_scope("pw.encode.ssm_conv"):
            # depthwise, causal: d_conv - 1 zeros on the left
            taps = p["conv"]["kernel"].astype(F32)
            padded = jnp.pad(u, ((0, 0), (c.mamba_d_conv - 1, 0), (0, 0)))
            conv = sum(padded[:, j : j + u.shape[1], :] * taps[j] for j in range(c.mamba_d_conv))
            u = jax.nn.silu(conv + p["conv"]["bias"].astype(F32)).astype(c.dtype)
            proj = _matmul(u, p["x_proj"]["kernel"])
            dt = _rmsnorm(proj[..., :r], p["dt_norm"]["scale"], c.rms_norm_eps)
            bmat = _rmsnorm(proj[..., r : r + n], p["b_norm"]["scale"], c.rms_norm_eps)
            cmat = _rmsnorm(proj[..., r + n :], p["c_norm"]["scale"], c.rms_norm_eps)
            dt = jax.nn.softplus(_matmul(dt, p["dt_proj"]["kernel"]) + p["dt_proj"]["bias"])
        with jax.named_scope("pw.encode.ssm_scan"):
            y = selective_scan(u, dt, z, bmat, cmat, -jnp.exp(p["a_log"]), p["d_skip"], interpret=c.scan_impl == "interpret")
        with jax.named_scope("pw.encode.ssm_out"):
            return _matmul(y, p["out_proj"]["kernel"])


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
