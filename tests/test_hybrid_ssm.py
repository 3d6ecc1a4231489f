"""The hybrid state-space / attention encoder (``models/hybrid_ssm.py``),
its selective-scan kernel (``ops/selective_scan.py``) and its place on the
normal embed -> scatter -> search path. CPU, tiny widths; the plain
reference is the benchmark's family ``benchmarks/families/jamba.py``,
which imports nothing of the program."""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec, system as bench_system
from benchmarks.lib.weights import make_weights
from pathway_tpu import tracing
from pathway_tpu.models import sentence_encoder
from pathway_tpu.models.encoder import EncoderConfig
from pathway_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMEncoder
from pathway_tpu.models.sentence_encoder import SentenceEncoder, architecture_of
from pathway_tpu.ops import knn
from pathway_tpu.ops.selective_scan import selective_scan, selective_scan_reference

SCALES = {"word_std": 1.0, "matrix_gain": 1.0, "out_gain": 0.3, "conv_bound": 0.5}
TEXTS = [
    "w0001 w0002 w0003",
    "alpha beta gamma delta " * 9,
    "one",
    "the quick brown fox jumps over the lazy dog " * 4,
    "w0404 " * 60,
]


def tiny_model(cfg: HybridSSMConfig) -> dict:
    """The benchmark's description of a program configuration."""
    keys = [f.name for f in cfg.__dataclass_fields__.values() if f.name not in ("dtype", "scan_impl")]
    return {"family": "jamba", "max_seq_len": 256, **{k: getattr(cfg, k) for k in keys}}


@functools.lru_cache(maxsize=None)
def tiny(dtype: str = "float32"):
    """(SentenceEncoder, family, model, weights) at the tiny preset, the
    seed's weights laid over the program's tree as the benchmark lays them."""
    cfg = HybridSSMConfig.tiny_for_tests(dtype=jnp.dtype(dtype), scan_impl="interpret")
    enc = SentenceEncoder("hybrid-ssm-tiny-for-tests", config=cfg)
    family, model = spec.load_family("jamba"), tiny_model(cfg)
    weights = make_weights(family, model, SCALES, seed=11)
    enc.params = bench_system._lay_over(enc.params, weights)
    return enc, family, model, weights


# ---- the program against the plain reference ----------------------------------


def test_program_equals_reference_in_float32():
    enc, family, model, weights = tiny()
    got = np.asarray(enc.encode_device(TEXTS))
    want = np.asarray(family.encode(weights, model, TEXTS))
    np.testing.assert_allclose(np.linalg.norm(got, axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_program_in_bfloat16_is_near_the_reference():
    # unit rows of width 64 through 4 layers whose matmul inputs are
    # rounded to 8 bits of mantissa (relative 2**-9 each, some 20 matmuls
    # deep): the rows move by a few 1e-3 an element; 2e-2 is ten times
    # that and a hundred times under an unrelated row's distance (~0.18)
    enc, family, model, weights = tiny("bfloat16")
    assert enc.params["layer_0"]["mamba"]["in_proj"]["kernel"].dtype == jnp.bfloat16
    assert enc.params["layer_0"]["mamba"]["a_log"].dtype == jnp.float32
    got = np.asarray(enc.encode_device(TEXTS))
    want = np.asarray(family.encode(weights, model, TEXTS))
    assert np.abs(got - want).max() < 2e-2
    assert (got * want).sum(axis=1).min() > 0.999


def test_fp8_control_is_farther_than_bfloat16():
    enc, family, model, weights = tiny("bfloat16")
    want = np.asarray(family.encode(weights, model, TEXTS))
    bf16 = np.abs(np.asarray(enc.encode_device(TEXTS)) - want).max()
    fp8 = np.abs(np.asarray(family.encode(weights, model, TEXTS, quant="fp8")) - want).max()
    assert fp8 > 2 * bf16


# ---- the kernel against the recurrence ------------------------------------------


def _scan_inputs(batch, length, channels, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (batch, length, channels), jnp.float32)
    z = jax.random.normal(ks[1], (batch, length, channels), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (batch, length, channels)) - 2.0)
    b = jax.random.normal(ks[3], (batch, length, n))
    c = jax.random.normal(ks[4], (batch, length, n))
    a = -jnp.exp(jax.random.uniform(ks[5], (channels, n), jnp.float32, 0.0, 2.5))
    return u, dt, z, b, c, a, jnp.linspace(0.5, 1.5, channels)


@pytest.mark.parametrize("length", [1, 3, 16, 37, 256])
@pytest.mark.parametrize("channels", [128, 200])  # 200 does not divide the block
def test_kernel_equals_recurrence(length, channels):
    args = _scan_inputs(2, length, channels, seed=length)
    got = selective_scan(*args, interpret=True)
    want = selective_scan_reference(*args)
    assert got.shape == want.shape == (2, length, channels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5 * float(jnp.abs(want).max()))


def test_kernel_state_does_not_cross_documents_or_read_the_future():
    u, dt, z, b, c, a, d = _scan_inputs(3, 48, 128, seed=7)
    whole = np.asarray(selective_scan(u, dt, z, b, c, a, d, interpret=True))
    # a document alone gives what it gives in the batch
    alone = np.asarray(selective_scan(u[1:2], dt[1:2], z[1:2], b[1:2], c[1:2], a, d, interpret=True))
    np.testing.assert_array_equal(whole[1:2], alone)
    # what comes after token 20 cannot reach tokens 0..19
    cut = np.asarray(selective_scan(u.at[:, 20:].set(9.0), dt, z, b, c, a, d, interpret=True))
    np.testing.assert_array_equal(whole[:, :20], cut[:, :20])


# ---- padding and batching -----------------------------------------------------


def test_padding_invariance():
    """A text embeds to the same row alone at its own bucket and inside a
    batch padded to 256."""
    enc, *_ = tiny()
    short = "w0007 w0008 w0009 w0010"
    alone = np.asarray(enc.encode_device([short]))[0]
    long = " ".join(f"w{i:04d}" for i in range(250))
    ids, lens = enc.tokenizer.batch_encode_matrix([short, long], enc.max_seq_len)
    assert lens.max() > 224  # the pair pads to the 256 bucket
    both = np.asarray(enc.encode_device([short, long]))
    np.testing.assert_allclose(both[0], alone, atol=2e-6)


def test_document_independence():
    """Permuting the rows of a batch permutes the result."""
    enc, *_ = tiny()
    perm = [3, 0, 4, 2, 1]
    straight = np.asarray(enc.encode_device(TEXTS))
    shuffled = np.asarray(enc.encode_device([TEXTS[i] for i in perm]))
    np.testing.assert_allclose(shuffled, straight[perm], atol=2e-6)


# ---- the published preset, without allocating it --------------------------------


def published_model() -> dict:
    path = os.path.join(spec.ROOT, "benchmarks", "configs", "msmarco-doc-jamba2-3b.json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def test_published_tree_is_the_configuration_files():
    config = published_model()
    model = config["model"]
    cfg = architecture_of(model["name"])
    assert isinstance(cfg, HybridSSMConfig) and architecture_of("ai21labs/AI21-Jamba2-3B") == cfg
    for key, value in model.items():  # every published key, letter for letter
        if key in cfg.__dataclass_fields__ and key != "dtype":
            assert getattr(cfg, key) == value, key
            assert config.get(key, value) == value, key  # the top-level copy agrees
    shapes = jax.eval_shape(lambda: HybridSSMEncoder(cfg).init(0))
    paths, _ = jax.tree_util.tree_flatten_with_path(shapes)
    tree = {"/".join(p.key for p in path): tuple(leaf.shape) for path, leaf in paths}
    family = spec.load_family("jamba")
    assert tree == {name: tuple(shape) for name, (shape, _) in family.leaves(model).items()}
    assert sorted(n for g in family.take_groups(model) for n in g) == sorted(tree)
    assert sum(int(np.prod(s)) for s in tree.values()) == 3_029_337_472
    kernel = shapes["layer_0"]["mamba"]["in_proj"]["kernel"]
    assert kernel.dtype == jnp.bfloat16 and shapes["layer_0"]["mamba"]["a_log"].dtype == jnp.float32
    assert [i for i in range(28) if cfg.is_attention(i)] == [7, 21]
    # the encoder caps its own groups from its configuration: 32 x 256
    assert cfg.max_group_tokens // 256 == 32


def test_family_work_by_hand():
    family, model = spec.load_family("jamba"), published_model()["model"]
    # one token: 2 x the 2.858 B matmul parameters, the conv, the scan, and one key of attention
    matmul = 28 * 3 * 2560 * 8192 + 26 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560) + 2 * (2 * 2560 * 2560 + 2 * 2560 * 128)
    rest = 26 * (2 * 4 * 5120 + 7 * 5120 * 16 + 6 * 5120) + 2 * 4 * 2560
    assert family.flops(model, [1]) == 2 * matmul + rest
    assert family.flops(model, [5, 7]) > 12 * family.flops(model, [1])
    assert family.ssm_scan_bytes(model, 1000) == 1000 * 26 * 3 * 5120 * 2
    ids, lens = family.tokenize(["w0001 w0002", ""], model)
    assert ids.shape == (2, 256) and lens.tolist() == [4, 2] and ids.max() < 65536
    assert ids[0, 0] == 101 and ids[0, 3] == 102 and (ids[0, 1:3] >= 999).all()


# ---- the normal path ------------------------------------------------------------


def test_names_resolve_at_construction(monkeypatch):
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    l12 = SentenceTransformerEmbedder("all-MiniLM-L12-v2")._encoder
    assert isinstance(l12.cfg, EncoderConfig) and l12.cfg.num_layers == 12
    assert architecture_of("sentence-transformers/all-MiniLM-L12-v2").num_layers == 12
    assert architecture_of("all-MiniLM-L6-v2").num_layers == 6
    assert architecture_of("some-unknown-model") == EncoderConfig.minilm_l6()
    monkeypatch.setitem(
        sentence_encoder.ARCHITECTURES,
        "hybrid-ssm-tiny-for-tests",
        functools.partial(HybridSSMConfig.tiny_for_tests, scan_impl="interpret"),
    )
    emb = SentenceTransformerEmbedder("hybrid-ssm-tiny-for-tests")
    enc = emb._encoder
    assert isinstance(enc.module, HybridSSMEncoder)
    assert enc.tokenizer.vocab_size == enc.cfg.vocab_size == 2048
    assert emb.get_embedding_dimension() == 64
    rows = np.asarray(emb.encode_device(TEXTS))
    np.testing.assert_allclose(np.linalg.norm(rows, axis=1), 1.0, atol=1e-3)


def test_tokenizer_runs_at_the_models_vocabulary():
    from pathway_tpu.models.tokenizer import WordPieceTokenizer

    tok = WordPieceTokenizer(vocab_size=65536)
    words = [f"w{i:04d}" for i in range(3000)]
    ids, lens = tok.batch_encode_matrix([" ".join(words[:200]), " ".join(words[200:400])], 256)
    assert ids.dtype == np.int32 and ids.max() > 32767 and ids.max() < 65536
    assert ids[0, : lens[0]].tolist() == tok.encode(" ".join(words[:200]), 256)
    family = spec.load_family("jamba")
    ref_ids, ref_lens = family.tokenize([" ".join(words[:200])], {"max_seq_len": 256, "vocab_size": 65536})
    assert ref_lens[0] == lens[0] and ref_ids[0].tolist() == ids[0].tolist()


def test_checkpoint_directory_is_refused_not_ignored(tmp_path):
    with pytest.raises(NotImplementedError, match="no checkpoint loader"):
        SentenceEncoder("hybrid-ssm-tiny-for-tests", checkpoint_dir=str(tmp_path))


def test_group_is_bounded_by_tokens():
    wide = HybridSSMConfig.tiny_for_tests(hidden_size=2560, intermediate_size=8192, num_hidden_layers=0)
    assert wide.max_group_tokens == 8192
    assert HybridSSMConfig.tiny_for_tests().max_group_tokens // 256 >= 1024  # tiny: the caller's max_batch stands


def test_search_texts_batch_is_encode_plus_brute_force():
    enc, *_ = tiny()
    docs = [" ".join(f"w{(7 * d + j) % 97:04d}" for j in range(5 + d % 9)) for d in range(40)]
    index = knn.DeviceKnnIndex(enc.dim, metric="cos", reserved_space=64)
    index.attach_encoder(enc)
    rows = enc.encode_device(docs)
    index.add_batch_device(list(range(40)), rows, None)
    queries = [docs[3], docs[17], "w0001 w0008"]
    got = index.search_texts_batch(queries, 5)
    scores = np.asarray(enc.encode(queries)) @ np.asarray(rows).T
    for answer, row in zip(got, scores):
        want = np.argsort(-row)[:5]
        assert [key for key, _ in answer] == want.tolist()
        np.testing.assert_allclose([s for _, s in answer], row[want], atol=1e-5)
    assert got[0][0][0] == 3 and got[1][0][0] == 17


# ---- spans and counters (the scope names: tests/test_tracing_device_plane.py) ------------------------------------------------------


def test_embed_dispatch_counts_padded_tokens_and_kernel_stats_are_fed():
    from pathway_tpu.internals.profiler import ENCODER_KERNEL_STATS

    enc, *_ = tiny()
    ENCODER_KERNEL_STATS.reset()
    tracing.set_tracing_enabled(True)
    tracing.TRACING_METRICS.reset()
    try:
        with tracing.span("embed_batch", new_trace=True, rows=len(TEXTS)):  # as the embedder opens it
            enc.encode_device(TEXTS)
        totals = tracing.stage_totals()
    finally:
        tracing.set_tracing_enabled(False)
        tracing.TRACING_METRICS.reset()
    snap = ENCODER_KERNEL_STATS.snapshot()
    ENCODER_KERNEL_STATS.reset()
    real, padded = totals["embed_tokenize"]["tokens"], totals["embed_dispatch"]["tokens"]
    assert padded == 8 * 64  # 5 texts pad to the batch bucket 8, the longest (62 tokens) to 64
    assert 0 < real < padded and snap["real_tokens"] == real
    assert snap["dispatches"] == 1 and snap["model_flops"] == pytest.approx(padded * enc.cfg.flops_per_token(64))
