"""The one route from texts to the compiled forward: tokenize ->
``_matrix_groups`` -> ``_run_group`` -> assemble.

Every shape an embed call takes (whole groups, a ragged last group, a
handful of rows, ``pad_to``, the halving of a large batch, a batch that
is mostly padding, the whole-layer kernel, a module that is not a BERT
block) must give the rows ``encode`` gives, and compile exactly the
``(B, L)`` programs ``predict_compile_keys`` names. (A module that packs
takes ``_stream_groups`` -> ``_run_stream`` instead, one program of
``max_group_tokens`` whatever the batch: the latent-attention case here,
``test_hybrid_ssm.py``, ``test_power_retention.py``.)
"""

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models.encoder import EncoderConfig
from pathway_tpu.models.latent_moe import LatentMoEConfig
from pathway_tpu.models.sentence_encoder import SentenceEncoder
from pathway_tpu.models.tokenizer import default_tokenizer

B = 16


def _bert(max_position=64, **kw):
    return EncoderConfig(
        vocab_size=30522,
        hidden_size=32,
        num_layers=1,
        num_heads=2,
        intermediate_size=64,
        max_position=max_position,
        pooling="mean",
        **kw,
    )


def _mixed(rng, n, max_words):
    """Word counts spread over every sequence bucket up to the window."""
    return rng.integers(1, max_words, n)


def _sparse(rng, n, max_words):
    """65 tokens a text and one that fills the window: the real share
    of rows x bucket is 0.39 at 512 texts in groups of 128, with a mean
    over 64 — under the 0.45 at which such a batch once left this route
    for a segment-packing kernel."""
    words = np.full(n, 63)
    words[rng.integers(n)] = max_words
    return words


# name -> (config, n, max_batch, max_seq_len, pad_to, length mix)
CASES = {
    "n=2B": (_bert, 2 * B, B, 32, None, _mixed),
    "n=3B": (_bert, 3 * B, B, 32, None, _mixed),
    "n=4B": (_bert, 4 * B, B, 32, None, _mixed),  # halved once
    "n=8B": (_bert, 8 * B, B, 32, None, _mixed),  # halved twice
    "n=2B,pad_to=64": (_bert, 2 * B, B, 32, 64, _mixed),
    "n=B+3": (_bert, B + 3, B, 32, None, _mixed),  # a ragged last group
    "n=5": (_bert, 5, B, 32, None, _mixed),
    "sparse,n=512": (lambda: _bert(512), 512, 128, 384, 512, _sparse),
    "sparse,n=512,interpret": (
        lambda: _bert(512, layer_impl="interpret"), 512, 128, 384, 512, _sparse,
    ),
    # float32: the packed stream and the padded forward are two programs,
    # and in bfloat16 a probability whose float32 sums differ in the last
    # bit rounds apart (tests/test_latent_moe.py holds them to 2e-6 in float32)
    "latent-moe,n=2B+3": (
        lambda: LatentMoEConfig.tiny_for_tests(expert_impl="interpret", dtype=jnp.float32), 2 * B + 3, B, 32, None, _mixed,
    ),
    "latent-moe,n=B+3,pad_to=64,interpret": (
        lambda: LatentMoEConfig.tiny_for_tests(expert_impl="interpret", attention_impl="interpret"), B + 3, B, 32, 64, _mixed,
    ),
}


def _leaves(n, max_batch):
    """The sizes ``encode_device`` cuts ``n`` texts into before it
    tokenizes, in input order."""
    if n >= 4 * max_batch:
        mid = (n // 2 // max_batch) * max_batch
        if mid and n - mid >= 2 * max_batch:
            return _leaves(mid, max_batch) + _leaves(n - mid, max_batch)
    return [n]


@pytest.mark.parametrize("case", CASES)
def test_encode_device_matches_encode_on_the_predicted_programs(case, monkeypatch):
    config, n, max_batch, max_seq_len, pad_to, mix = CASES[case]
    enc = SentenceEncoder(
        config=config(), checkpoint_dir="/nonexistent", max_seq_len=max_seq_len, max_batch=max_batch
    )
    rng = np.random.default_rng(7)
    texts = [
        " ".join(f"tok{t}" for t in rng.integers(0, 5000, w)) for w in mix(rng, n, max_seq_len)
    ]
    matrix = enc.tokenizer.batch_encode_matrix(texts, max_seq_len)
    if matrix is None:
        pytest.skip("native lib unavailable")
    lens = matrix[1]
    assert lens.max() <= max_seq_len and len(lens) == n

    compiled = set()
    packs = hasattr(enc.module, "apply_stream")  # a module that packs runs streams
    run = enc._run_stream if packs else enc._run_group

    def recording(ids, *rest):
        compiled.add((1,) + ids.shape if packs else ids.shape)
        return run(ids, *rest)

    monkeypatch.setattr(enc, "_run_stream" if packs else "_run_group", recording)
    got = np.asarray(enc.encode_device(texts, pad_to=pad_to))
    monkeypatch.undo()

    # the programs: those predicted for each piece, and no other
    want_keys, start = set(), 0
    for size in [n] if pad_to else _leaves(n, enc.max_batch):
        want_keys |= enc.predict_compile_keys(lens[start : start + size].tolist())
        start += size
    assert compiled == want_keys
    fwd = enc._fwd_stream if packs else enc._fwd_group
    assert fwd.__wrapped__._cache_size() == len(want_keys)
    if packs:  # one program, whatever the batch
        assert want_keys == {(1, enc.cfg.max_group_tokens)} == enc.predict_compile_keys([1, 256, 7])
    # the wire ring staged every group and holds none back
    assert enc._wire_ring.staged > 0 and enc._wire_ring.in_flight() == 0

    # the rows
    want = enc.encode(texts)
    assert got.shape == (pad_to or n, enc.dim)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    np.testing.assert_allclose(got[:n], want, atol=2e-5)
    assert not got[n:].any()
    assert np.allclose(np.linalg.norm(want, axis=1), 1.0, atol=1e-3)
    # and against the dense route, which shares no host code with this
    # one: per-row Python lists, padded by pad_token_batch, through the
    # flax module
    dense = enc.encode_tokens([enc.tokenizer.encode(t, max_seq_len) for t in texts])
    np.testing.assert_allclose(got[:n], dense, atol=3e-2 if "interpret" in case else 1e-4)


def test_tokenizer_is_built_at_the_tables_size(tmp_path):
    """An id past the embedding table embeds to NaN without an error:
    the seeded tokenizer stays inside a table narrower than the
    published 30,522, and a vocabulary file wider than the table is
    refused at construction."""
    cfg = EncoderConfig(
        vocab_size=30000, hidden_size=32, num_layers=1, num_heads=2,
        intermediate_size=64, max_position=64,
    )
    enc = SentenceEncoder(config=cfg, checkpoint_dir="/nonexistent", max_seq_len=32, max_batch=16)
    assert enc.tokenizer.vocab_size == 30000
    texts = [" ".join(f"word{i * 31 + j}" for j in range(28)) for i in range(256)]
    ids, _ = enc.tokenizer.batch_encode_matrix(texts, 32)
    assert 29000 < ids.max() < 30000  # the draw reaches the top of the table
    assert np.isfinite(enc.encode(texts)).all()
    assert np.isfinite(np.asarray(enc.encode_device(texts))).all()
    assert default_tokenizer().vocab_size == 30522

    (tmp_path / "vocab.txt").write_text("\n".join(f"t{i}" for i in range(40)))
    assert len(default_tokenizer(str(tmp_path), 40).vocab) == 40
    with pytest.raises(ValueError, match="40 tokens"):
        default_tokenizer(str(tmp_path), 39)
