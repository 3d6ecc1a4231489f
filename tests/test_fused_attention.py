"""Fused attention kernel + native tokenizer + encode fast paths.

The pallas kernel runs in interpret mode on CPU (tests/conftest.py
forces the CPU platform); numerics must match the XLA reference chain
bit-for-bit up to bf16 rounding, including padding masks and gradients
(the custom_vjp recompute path used by ContrastiveTrainer)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.ops.fused_attention import attention


def _rand_qkv(rng, b, s, d):
    return jnp.asarray(
        rng.standard_normal((b, s, 3 * d)).astype(np.float32)
    ).astype(jnp.bfloat16)


@pytest.mark.parametrize(
    "b,s,h,d",
    [
        (10, 32, 12, 384),  # MiniLM geometry (4 sequences packed per block)
        (7, 32, 12, 384),  # batch not divisible by pack factor
        (33, 64, 4, 128),
        (256, 16, 8, 256),
        (3, 200, 8, 256),  # seq > 128: single-sequence blocks
    ],
)
def test_kernel_matches_xla(b, s, h, d):
    rng = np.random.default_rng(0)
    qkv = _rand_qkv(rng, b, s, d)
    mask = np.ones((b, s), bool)
    mask[0, s // 2 :] = False
    mask[-1, 1:] = False
    mask = jnp.asarray(mask)
    got = attention(qkv, mask, n_heads=h, impl="interpret")
    want = attention(qkv, mask, n_heads=h, impl="xla")
    # compare only unmasked positions: padded query rows are garbage on
    # both paths and excluded by pooling
    m = np.asarray(mask)[:, :, None]
    err = np.max(np.abs(np.float32(got) - np.float32(want)) * m)
    assert err < 0.05, err


def test_kernel_grad_matches_xla():
    rng = np.random.default_rng(1)
    b, s, h, d = 6, 32, 12, 384
    qkv = _rand_qkv(rng, b, s, d)
    mask = jnp.asarray(np.ones((b, s), bool))

    def loss(impl):
        def f(t):
            out = attention(t, mask, n_heads=h, impl=impl).astype(jnp.float32)
            return jnp.sum(out * out)

        return f

    ga = jax.grad(loss("interpret"))(qkv)
    gb = jax.grad(loss("xla"))(qkv)
    assert np.max(np.abs(np.float32(ga) - np.float32(gb))) < 0.2


def test_auto_impl_selects_xla_off_tpu():
    # conftest forces CPU: auto must not route into the TPU kernel
    rng = np.random.default_rng(2)
    qkv = _rand_qkv(rng, 4, 32, 96)
    mask = jnp.asarray(np.ones((4, 32), bool))
    out = attention(qkv, mask, n_heads=4, impl="auto")
    assert out.shape == (4, 32, 96)


def test_native_tokenizer_parity_hash_mode():
    from pathway_tpu import native
    from pathway_tpu.models.tokenizer import WordPieceTokenizer

    if not native.is_available():
        pytest.skip("native lib unavailable")
    tok = WordPieceTokenizer()
    texts = [
        "Hello, World! 123 foo-bar",
        "the quick brown fox",
        "",
        "a" * 300,
        "punct!!! ??? ,,,",
    ] + [f"text {i} borp{i}" for i in range(20)]
    assert tok.batch_encode(texts, max_len=32) == [
        tok.encode(t, max_len=32) for t in texts
    ]


def test_native_tokenizer_parity_vocab_mode(tmp_path):
    from pathway_tpu import native
    from pathway_tpu.models.tokenizer import WordPieceTokenizer

    if not native.is_available():
        pytest.skip("native lib unavailable")
    vf = tmp_path / "vocab.txt"
    vf.write_text(
        "\n".join(
            ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "the", "quick", "brown",
             "fox", "##s", "he", "##llo", "hello", "wor", "##ld", "!", ",",
             "123", "a", "##a"]
        )
        + "\n"
    )
    tok = WordPieceTokenizer(vocab_file=str(vf))
    texts = ["Hello, worlds!", "the quick foxs", "unknownword", "a" * 150]
    assert tok.batch_encode(texts, max_len=16) == [
        tok.encode(t, max_len=16) for t in texts
    ]


def test_native_tokenizer_non_ascii_fallback():
    from pathway_tpu.models.tokenizer import WordPieceTokenizer

    tok = WordPieceTokenizer()
    mix = ["héllo wörld", "plain ascii", "汉字 test"]
    assert tok.batch_encode(mix, 16) == [tok.encode(t, 16) for t in mix]


def test_encode_device_matches_encode():
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.models.sentence_encoder import SentenceEncoder

    cfg = EncoderConfig(
        vocab_size=30000,
        hidden_size=32,
        num_layers=1,
        num_heads=2,
        intermediate_size=64,
        max_position=64,
        pooling="mean",
    )
    enc = SentenceEncoder(
        config=cfg, checkpoint_dir="/nonexistent", max_seq_len=32, max_batch=16
    )
    # 64 rows = 4 whole groups, halved once
    texts = [f"hello world document {i} words" for i in range(64)]
    a = np.asarray(enc.encode_device(texts))
    b = enc.encode(texts)
    np.testing.assert_allclose(a, b, atol=2e-5)
    # a ragged last group
    texts2 = ["short", "a bit longer text here", "x " * 30] * 7
    a2 = np.asarray(enc.encode_device(texts2))
    b2 = enc.encode(texts2)
    np.testing.assert_allclose(a2, b2, atol=2e-5)
