"""BERT-style transformer encoder in flax — the shared trunk for the
xpack's ML hot paths.

The reference runs sentence-transformers (torch, per-row ``model.encode``
— /root/reference/python/pathway/xpacks/llm/embedders.py:270-329) and
CrossEncoder (rerankers.py:186). Here the encoder is a jit-compiled,
bf16, batched flax module designed for the MXU: fixed (bucketed) shapes,
fused attention via dot products XLA tiles onto the systolic array, and
parameter layouts annotated for tensor-parallel sharding over a
``jax.sharding.Mesh`` (see :mod:`pathway_tpu.parallel.sharding`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

# Logical axis names used for pjit sharding rules. Mapped to mesh axes in
# pathway_tpu.parallel.sharding (embed -> None, heads/mlp -> "model",
# batch -> "data").
EMBED = "embed"
HEADS = "heads"
MLP = "mlp"
VOCAB = "vocab"


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    vocab_size: int = 30522
    hidden_size: int = 384
    num_layers: int = 6
    num_heads: int = 12
    intermediate_size: int = 1536
    max_position: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16
    # pooling: "mean" (sentence-transformers MiniLM), "cls" (cross-encoder)
    pooling: str = "mean"
    normalize: bool = True
    # attention path: "auto" (pallas fused kernel on TPU — see
    # ops/fused_attention.py), "xla", "fused", "interpret"
    attention_impl: str = "auto"
    # whole-layer path for the inference encode jits: "auto" (one pallas
    # dispatch per layer on TPU — ops/fused_layer.py), "fused", "xla"
    layer_impl: str = "auto"

    @classmethod
    def minilm_l6(cls, **kw) -> "EncoderConfig":
        """all-MiniLM-L6-v2 geometry (the reference's default embedder)."""
        return cls(**kw)

    @classmethod
    def minilm_l12(cls, **kw) -> "EncoderConfig":
        return cls(num_layers=12, **kw)

    @classmethod
    def cross_encoder_l6(cls, **kw) -> "EncoderConfig":
        kw.setdefault("pooling", "cls")
        kw.setdefault("normalize", False)
        return cls(**kw)


def _dense(features, name, kernel_axes, dtype):
    return nn.Dense(
        features,
        name=name,
        dtype=dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02), kernel_axes
        ),
    )


class SelfAttention(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.cfg
        d = cfg.hidden_size
        h = cfg.num_heads
        hd = d // h
        # QKV fused into one projection: one big matmul for the MXU.
        qkv = _dense(3 * d, "qkv", (EMBED, HEADS), cfg.dtype)(x)
        from ..ops.fused_attention import attention

        ctx = attention(qkv, mask, n_heads=h, impl=cfg.attention_impl)
        return _dense(d, "out", (HEADS, EMBED), cfg.dtype)(ctx)


class EncoderLayer(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.cfg
        a = SelfAttention(cfg, name="attention")(x, mask)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="ln_att")(x + a)
        m = _dense(cfg.intermediate_size, "mlp_in", (EMBED, MLP), cfg.dtype)(x)
        m = jax.nn.gelu(m, approximate=True)
        m = _dense(cfg.hidden_size, "mlp_out", (MLP, EMBED), cfg.dtype)(m)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="ln_mlp")(x + m)
        return x


class TextEncoder(nn.Module):
    """Token ids -> pooled sentence embedding (or token states)."""

    cfg: EncoderConfig

    @nn.compact
    def __call__(self, ids, mask, token_type_ids=None, return_tokens=False):
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (VOCAB, EMBED)
            ),
            name="tok_embed",
        )(ids)
        pos = nn.Embed(
            cfg.max_position, cfg.hidden_size, dtype=cfg.dtype, name="pos_embed"
        )(jnp.arange(ids.shape[1])[None, :])
        typ = 0
        if cfg.type_vocab_size:
            tt = token_type_ids if token_type_ids is not None else jnp.zeros_like(ids)
            typ = nn.Embed(
                cfg.type_vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="type_embed"
            )(tt)
        x = embed + pos + typ
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="ln_embed")(x)
        for i in range(cfg.num_layers):
            x = EncoderLayer(cfg, name=f"layer_{i}")(x, mask)
        if return_tokens:
            return x
        if cfg.pooling == "cls":
            pooled = x[:, 0]
        else:  # masked mean pooling (sentence-transformers default)
            m = mask[:, :, None].astype(x.dtype)
            pooled = (x * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)
        pooled = pooled.astype(jnp.float32)
        if cfg.normalize:
            pooled = pooled / jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12
            )
        return pooled


class CrossEncoderHead(nn.Module):
    """(query, doc) pair -> relevance score. Reference:
    sentence_transformers.CrossEncoder used at rerankers.py:186."""

    cfg: EncoderConfig

    @nn.compact
    def __call__(self, ids, mask, token_type_ids):
        x = TextEncoder(self.cfg, name="encoder")(
            ids, mask, token_type_ids, return_tokens=True
        )
        cls = x[:, 0].astype(jnp.float32)
        return nn.Dense(1, name="classifier", dtype=jnp.float32)(cls)[:, 0]


def init_params(model: nn.Module, cfg: EncoderConfig, seed: int = 0, seq_len: int = 16):
    ids = jnp.zeros((1, seq_len), jnp.int32)
    mask = jnp.ones((1, seq_len), bool)
    if isinstance(model, CrossEncoderHead):
        return model.init(jax.random.PRNGKey(seed), ids, mask, jnp.zeros_like(ids))
    return model.init(jax.random.PRNGKey(seed), ids, mask)


def param_logical_axes(model: nn.Module, cfg: EncoderConfig, seq_len: int = 16):
    """Logical-axis pytree for pjit sharding (flax partitioning metadata)."""
    ids = jnp.zeros((1, seq_len), jnp.int32)
    mask = jnp.ones((1, seq_len), bool)
    if isinstance(model, CrossEncoderHead):
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), ids, mask, jnp.zeros_like(ids))
        )
    else:
        variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, mask))
    return nn.get_partition_spec(variables)


def load_hf_weights(params, checkpoint_dir: str):
    """Map a HuggingFace BERT-style state dict (pytorch_model.bin /
    model.safetensors in ``checkpoint_dir``) onto our param tree. Only
    used when a local checkpoint exists — this image has no network
    egress, so tests/benches run on random-init weights (throughput is
    weight-independent)."""
    import os

    state = None
    st_path = os.path.join(checkpoint_dir, "model.safetensors")
    pt_path = os.path.join(checkpoint_dir, "pytorch_model.bin")
    if os.path.exists(st_path):
        from safetensors import safe_open  # type: ignore

        state = {}
        with safe_open(st_path, framework="np") as f:
            for k in f.keys():
                state[k] = f.get_tensor(k)
    elif os.path.exists(pt_path):
        import torch

        state = {
            k: v.numpy() for k, v in torch.load(pt_path, map_location="cpu").items()
        }
    if state is None:
        raise FileNotFoundError(f"no checkpoint in {checkpoint_dir}")

    def g(name):
        for pfx in ("", "bert.", "model."):
            if pfx + name in state:
                return np.asarray(state[pfx + name])
        raise KeyError(name)

    p = jax.tree_util.tree_map(np.asarray, params)["params"]
    enc = p.get("encoder", p)
    enc["tok_embed"]["embedding"] = g("embeddings.word_embeddings.weight")
    enc["pos_embed"]["embedding"] = g("embeddings.position_embeddings.weight")
    if "type_embed" in enc:
        enc["type_embed"]["embedding"] = g("embeddings.token_type_embeddings.weight")
    enc["ln_embed"]["scale"] = g("embeddings.LayerNorm.weight")
    enc["ln_embed"]["bias"] = g("embeddings.LayerNorm.bias")
    i = 0
    while f"layer_{i}" in enc:
        L = enc[f"layer_{i}"]
        pre = f"encoder.layer.{i}."
        qw = g(pre + "attention.self.query.weight").T
        kw = g(pre + "attention.self.key.weight").T
        vw = g(pre + "attention.self.value.weight").T
        L["attention"]["qkv"]["kernel"] = np.concatenate([qw, kw, vw], axis=1)
        L["attention"]["qkv"]["bias"] = np.concatenate(
            [
                g(pre + "attention.self.query.bias"),
                g(pre + "attention.self.key.bias"),
                g(pre + "attention.self.value.bias"),
            ]
        )
        L["attention"]["out"]["kernel"] = g(pre + "attention.output.dense.weight").T
        L["attention"]["out"]["bias"] = g(pre + "attention.output.dense.bias")
        L["ln_att"]["scale"] = g(pre + "attention.output.LayerNorm.weight")
        L["ln_att"]["bias"] = g(pre + "attention.output.LayerNorm.bias")
        L["mlp_in"]["kernel"] = g(pre + "intermediate.dense.weight").T
        L["mlp_in"]["bias"] = g(pre + "intermediate.dense.bias")
        L["mlp_out"]["kernel"] = g(pre + "output.dense.weight").T
        L["mlp_out"]["bias"] = g(pre + "output.dense.bias")
        L["ln_mlp"]["scale"] = g(pre + "output.LayerNorm.weight")
        L["ln_mlp"]["bias"] = g(pre + "output.LayerNorm.bias")
        i += 1
    if "classifier" in p:
        p["classifier"]["kernel"] = g("classifier.weight").T
        p["classifier"]["bias"] = g("classifier.bias")
    return {"params": p}
