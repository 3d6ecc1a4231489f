"""Ring attention / sequence-parallel long-context encoding on the
virtual 8-device mesh: must match single-device full attention exactly
(same math, online-softmax accumulation)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models.encoder import EncoderConfig, TextEncoder, init_params
from pathway_tpu.models.long_context import ring_attention, ring_encode
from pathway_tpu.parallel.sharding import make_mesh


def _cfg():
    return EncoderConfig(
        vocab_size=512,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        intermediate_size=128,
        max_position=128,
        dtype=jnp.float32,
        pooling="mean",
    )


def test_ring_attention_matches_full_attention():
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(model_parallel=1)  # 8-way sequence ring
    rng = np.random.default_rng(0)
    B, H, S, D = 2, 4, 64, 16
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    mask = np.ones((B, S), bool)
    mask[:, 50:] = False  # ragged tail
    mask = jnp.asarray(mask)

    ringed = jax.jit(
        jax.shard_map(
            lambda q, k, v, m: ring_attention(q, k, v, m, "data"),
            mesh=mesh,
            in_specs=(P(None, None, "data"), P(None, None, "data"), P(None, None, "data"), P(None, "data")),
            out_specs=P(None, None, "data"),
            check_vma=False,
        )
    )(q, k, v, mask)

    # reference: plain full attention
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    scores = jnp.where(mask[:, None, None, :], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    full = jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    np.testing.assert_allclose(np.asarray(ringed), np.asarray(full), rtol=2e-5, atol=2e-5)


def test_ring_encode_matches_single_device():
    cfg = _cfg()
    module = TextEncoder(cfg)
    params = init_params(module, cfg)
    mesh = make_mesh(model_parallel=1)

    rng = np.random.default_rng(1)
    B, S = 2, 64  # 8 tokens per shard
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    mask = np.ones((B, S), bool)
    mask[1, 40:] = False
    mask = jnp.asarray(mask)

    ringed = ring_encode(params, cfg, ids, mask, mesh, axis="data")
    direct = module.apply(params, ids, mask)
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(direct), rtol=3e-4, atol=3e-4)


def test_ring_encode_long_sequence_beyond_single_block():
    """S=128 over 8 shards: positions are global, pooling is psum'd."""
    cfg = _cfg()
    module = TextEncoder(cfg)
    params = init_params(module, cfg)
    mesh = make_mesh(model_parallel=1)
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 128)), jnp.int32)
    mask = jnp.ones((1, 128), bool)
    ringed = ring_encode(params, cfg, ids, mask, mesh)
    direct = module.apply(params, ids, mask)
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(direct), rtol=3e-4, atol=3e-4)
