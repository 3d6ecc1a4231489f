"""Whole-layer pallas kernel (ops/fused_layer.py): numerics vs the flax
module, gradient path, packing round-trip, and the CLIP YUV420 wire
format.  Kernels run in interpret mode on the CPU mesh."""

from __future__ import annotations

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from pathway_tpu.models.batching import DEFAULT_SEQ_BUCKETS
from pathway_tpu.models.encoder import EncoderConfig, TextEncoder, init_params
from pathway_tpu.ops.fused_layer import (
    ROW_TILE,
    computed_tokens,
    encoder_forward,
    fused_layer_tokens,
    live_tiles,
    pack_tokens,
    supports_fused_encoder,
    unpack_tokens,
)


@pytest.fixture(scope="module")
def minilm():
    cfg = EncoderConfig.minilm_l6()
    module = TextEncoder(cfg)
    return cfg, module, init_params(module, cfg)


def _batch(rng, b, s):
    ids = rng.integers(999, 29000, (b, s)).astype(np.int32)
    lens = rng.integers(max(1, s // 2), s + 1, (b,))
    mask = np.arange(s)[None, :] < lens[:, None]
    return jnp.asarray(ids), jnp.asarray(mask)


@pytest.mark.parametrize("b,s", [(8, 32), (5, 96), (3, 160), (2, 224), (2, 256)])
def test_fused_encoder_matches_module(minilm, b, s):
    cfg, module, params = minilm
    ids, mask = _batch(np.random.default_rng(s), b, s)
    ref = np.asarray(module.apply(params, ids, mask))
    got = np.asarray(encoder_forward(params, cfg, ids, mask, interpret=True))
    assert got.shape == ref.shape
    err = np.abs(ref - got).max()
    cos = (ref * got).sum(axis=1).min()
    assert err < 3e-2 and cos > 0.999, (err, cos)


@pytest.fixture(scope="module")
def tiny():
    """Miniature geometry for the full bucket sweep: parity is a
    property of the kernel's (seq, pack-factor) tiling, not the model
    size, so every bucket runs at a width that keeps interpret mode
    cheap."""
    cfg = EncoderConfig(
        vocab_size=1000, hidden_size=64, num_layers=2, num_heads=2,
        intermediate_size=128, max_position=512,
    )
    module = TextEncoder(cfg)
    return cfg, module, init_params(module, cfg)


@pytest.mark.parametrize("s", list(DEFAULT_SEQ_BUCKETS))
def test_every_bucket_parity_with_all_padding_rows(tiny, s):
    """Every seq bucket, every pack factor: the ragged kernel matches
    the per-op XLA module on live rows, and an all-padding row riding in
    the batch (its block may be dead-skipped) comes back exactly zero —
    the batch spills into a second, partly-dead block on purpose."""
    from pathway_tpu.ops.fused_layer import _pack_rows

    cfg, module, params = tiny
    rng = np.random.default_rng(s)
    b = _pack_rows(s) + 2
    ids = rng.integers(5, 999, (b, s)).astype(np.int32)
    lens = rng.integers(1, s + 1, (b,))
    lens[-1] = 0  # all-padding row in the tail (length-sorted contract)
    mask = np.arange(s)[None, :] < lens[:, None]
    ids_j, mask_j = jnp.asarray(ids), jnp.asarray(mask)
    got = np.asarray(encoder_forward(params, cfg, ids_j, mask_j, interpret=True))
    ref = np.asarray(module.apply(params, ids_j, mask_j))
    live = lens > 0
    err = np.abs(ref[live] - got[live]).max()
    assert err < 3e-2, (s, err)
    assert np.all(got[~live] == 0.0), "all-padding row must embed to zero"


# ---- the tile rule: a live sequence computes its live row tiles only --------

TILED_SEQS = (160, 192, 224, 256)


def _computed_rows(lens, s):
    """ceil(len / ROW_TILE) * ROW_TILE, never past the bucket — written
    out here, not taken from the helper under test."""
    return np.minimum(-(-np.asarray(lens) // ROW_TILE) * ROW_TILE, s)


def _edge_lens(s):
    """Lengths that hit every live-tile count of a bucket, both edges of
    every tile, an all-padding row in the middle and at the tail — and,
    after them, one block whose sequences all reach their last tile
    (the kernel takes such a block in one pass)."""
    from pathway_tpu.ops.fused_layer import _pack_rows

    edges = {1, s - 1, s}
    for k in range(1, -(-s // ROW_TILE) + 1):
        edges |= {k * ROW_TILE - 1, k * ROW_TILE, k * ROW_TILE + 1}
    lens = sorted(n for n in edges if 1 <= n <= s)
    lens.insert(len(lens) // 2, 0)
    p = _pack_rows(s)
    lens += [0] * (1 + (-len(lens) - 1) % p)
    return np.asarray(lens + [s - j for j in range(p)], np.int64)


def _edge_batch(s, vocab=999):
    lens = _edge_lens(s)
    ids = np.random.default_rng(s).integers(5, vocab, (len(lens), s)).astype(np.int32)
    return ids, lens, np.arange(s)[None, :] < lens[:, None]


def test_edge_lens_hit_every_live_tile_count():
    for s in TILED_SEQS:
        lens = _edge_lens(s)
        assert set(live_tiles(lens).tolist()) == set(range(-(-s // ROW_TILE) + 1))
        assert {0, 1, ROW_TILE, ROW_TILE + 1, s - 1, s} <= set(lens.tolist())


@pytest.mark.parametrize("s", TILED_SEQS)
def test_tile_rule_matches_module_at_every_live_tile_count(tiny, s):
    cfg, module, params = tiny
    ids, lens, mask = _edge_batch(s)
    got = np.asarray(encoder_forward(params, cfg, jnp.asarray(ids), jnp.asarray(mask), interpret=True))
    ref = np.asarray(module.apply(params, jnp.asarray(ids), jnp.asarray(mask)))
    live = lens > 0
    err = np.abs(ref[live] - got[live]).max()
    cos = (ref[live] * got[live]).sum(axis=1).min()
    assert err < 3e-2 and cos > 0.999, (s, err, cos)
    assert np.all(got[~live] == 0.0), "an all-padding row embeds to zero wherever it rides"


@pytest.mark.parametrize("s", TILED_SEQS)
def test_rows_past_the_last_live_tile_are_zero_after_every_layer(tiny, s):
    """The next layer reads those rows as keys under KEY_OFF and pooling
    multiplies them by the mask: zeros, never stale memory — and the
    rows of the live tiles are what the kernel computed, pad rows of the
    last live tile included."""
    from flax.core import meta

    cfg, _, params = tiny
    layers = meta.unbox(params["params"])
    _, lens, mask = _edge_batch(s)
    rng = np.random.default_rng(s)
    x = jnp.asarray(rng.normal(size=(len(lens), s, cfg.hidden_size)), cfg.dtype)
    tokens, lens_blk, b0 = pack_tokens(x, jnp.asarray(mask))
    past = np.arange(s)[None, :] >= _computed_rows(lens, s)[:, None]
    for i in range(cfg.num_layers):
        tokens = fused_layer_tokens(
            tokens, lens_blk, layers[f"layer_{i}"],
            n_heads=cfg.num_heads, seq=s, eps=cfg.layer_norm_eps, interpret=True,
        )
        states = np.asarray(unpack_tokens(tokens, b0, s), np.float32)
        assert np.isfinite(states).all()
        assert np.all(states[past] == 0.0), f"layer {i}: rows past the last live tile"
        computed = np.abs(states).max(axis=2)[~past]
        assert np.all(computed > 0.0), f"layer {i}: a row of a live tile was left out"


@pytest.mark.parametrize("s", TILED_SEQS)
def test_pooled_output_ignores_the_ids_at_pad_positions(tiny, s):
    cfg, _, params = tiny
    ids, lens, mask = _edge_batch(s)
    noise = np.random.default_rng(s + 1).integers(5, 999, ids.shape).astype(np.int32)
    other = np.where(mask, ids, noise)
    assert (other != ids).any()
    run = lambda i: np.asarray(
        encoder_forward(params, cfg, jnp.asarray(i), jnp.asarray(mask), interpret=True)
    )
    np.testing.assert_array_equal(run(ids), run(other))


@pytest.mark.parametrize("s", (16, 96, 128) + TILED_SEQS + (512,))
def test_computed_tokens_is_the_sum_of_live_tiles(s):
    from pathway_tpu.ops.fused_layer import _pack_rows, tile_rule

    rng = np.random.default_rng(s)
    lens = np.concatenate([rng.integers(1, s + 1, 37), np.zeros(11, np.int64)])
    assert tile_rule(s) == (s > 128)
    if tile_rule(s):
        assert computed_tokens(lens, s) == int(_computed_rows(lens, s).sum())
        assert computed_tokens(_edge_lens(s), s) == int(_computed_rows(_edge_lens(s), s).sum())
        assert computed_tokens(np.full(5, s), s) == 5 * s
    else:
        # no tile rule: every row of a block with one live sequence
        p = _pack_rows(s)
        assert computed_tokens(lens, s) == -(-37 // p) * p * s
    assert computed_tokens(np.zeros(8, np.int64), s) == 0
    assert int(lens.sum()) <= computed_tokens(lens, s) <= len(lens) * s + _pack_rows(s) * s


def test_fused_encoder_cls_pooling(minilm):
    _, _, params = minilm
    cfg = EncoderConfig.cross_encoder_l6()
    module = TextEncoder(cfg)
    p = init_params(module, cfg)
    ids, mask = _batch(np.random.default_rng(0), 4, 32)
    ref = np.asarray(module.apply(p, ids, mask))
    got = np.asarray(encoder_forward(p, cfg, ids, mask, interpret=True))
    # cls outputs are unnormalized (scale ~3), so bound the error
    # relative to the output scale (a few bf16 ulps) plus direction
    err = np.abs(ref - got).max()
    assert err < 3e-2 * max(1.0, np.abs(ref).max()), err
    rn = ref / np.linalg.norm(ref, axis=1, keepdims=True)
    gn = got / np.linalg.norm(got, axis=1, keepdims=True)
    assert (rn * gn).sum(axis=1).min() > 0.999


def test_fused_encoder_gradient_flows(minilm):
    """custom_vjp backward recomputes through the flax path — grads
    must match the module's own within bf16 noise."""
    cfg, module, params = minilm
    ids, mask = _batch(np.random.default_rng(1), 2, 32)

    def loss_fused(p):
        return encoder_forward(p, cfg, ids, mask, interpret=True).sum()

    def loss_ref(p):
        return module.apply(p, ids, mask).sum()

    g_fused = jax.grad(loss_fused)(params)
    g_ref = jax.grad(loss_ref)(params)
    leaf_f = jax.tree_util.tree_leaves(g_fused)
    leaf_r = jax.tree_util.tree_leaves(g_ref)
    assert len(leaf_f) == len(leaf_r)
    for a, b in zip(leaf_f, leaf_r):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-2, rtol=2e-2
        )


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 32, 8)).astype(np.float32))
    mask = jnp.ones((5, 32), bool)
    tokens, lens, b0 = pack_tokens(x, mask)
    assert tokens.shape[0] % (256 // 32 * 32) == 0
    # per-block lengths: one row per packed block, one entry per sequence
    assert lens.shape[1] == 256 // 32 and np.asarray(lens)[0, 0] == 32
    back = unpack_tokens(tokens, b0, 32)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_supports_fused_encoder_gates():
    cfg = EncoderConfig.minilm_l6()
    assert supports_fused_encoder(cfg, 160)
    assert not supports_fused_encoder(cfg, 1024)  # beyond packing range


def test_layer_impl_policy_is_honored():
    import dataclasses

    from pathway_tpu.ops.fused_layer import use_fused_encoder

    cfg = EncoderConfig.minilm_l6()
    assert not use_fused_encoder(dataclasses.replace(cfg, layer_impl="xla"), 160)
    assert use_fused_encoder(dataclasses.replace(cfg, layer_impl="fused"), 160)
    # auto on CPU backend: stays on the XLA path
    assert not use_fused_encoder(cfg, 160)


def test_clip_yuv420_wire_format_close_to_rgb():
    from pathway_tpu.models.clip import CLIPEncoder, CLIPConfig

    cfg = CLIPConfig(
        image_size=32, patch_size=8, vision_layers=1, vision_width=64,
        vision_heads=2, text_layers=1, text_width=64, text_heads=2,
        embed_dim=32,
    )
    enc = CLIPEncoder(cfg, max_batch=8)
    rng = np.random.default_rng(0)
    imgs = (rng.random((4, 32, 32, 3)) * 255).astype(np.uint8)
    enc.transport = "rgb"
    ref = enc.encode_image(imgs)
    enc.transport = "yuv420"
    got = enc.encode_image(imgs)
    cos = (ref * got).sum(axis=1)
    assert cos.min() > 0.99, cos
    # packed wire rows are half the size of RGB rows
    packed = enc._pack_yuv420(imgs)
    assert packed.shape[1] * 2 == imgs[0].size
