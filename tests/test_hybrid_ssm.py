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
from pathway_tpu.models.token_stream import stream_length
from pathway_tpu.ops import knn
from pathway_tpu.ops.selective_scan import TIME_BLOCK, TIME_CHUNK, selective_scan, selective_scan_reference

SCALES = {"word_std": 1.0, "matrix_gain": 1.0, "out_gain": 0.3, "conv_bound": 0.5}
PROGRAM_ONLY = ("dtype", "scan_impl", "max_group_tokens", "token_chunk", "max_seq_len")
TEXTS = [
    "w0001 w0002 w0003",
    "alpha beta gamma delta " * 9,
    "one",
    "the quick brown fox jumps over the lazy dog " * 4,
    "w0404 " * 60,
]


def tiny_model(cfg: HybridSSMConfig) -> dict:
    """The benchmark's description of a program configuration."""
    keys = [f.name for f in cfg.__dataclass_fields__.values() if f.name not in PROGRAM_ONLY]
    return {"family": "jamba", "max_seq_len": cfg.max_seq_len, **{k: getattr(cfg, k) for k in keys}}


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


def _scan_inputs(length, channels, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (length, channels), jnp.float32)
    z = jax.random.normal(ks[1], (length, channels), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (length, channels)) - 2.0)
    b = jax.random.normal(ks[3], (length, n))
    c = jax.random.normal(ks[4], (length, n))
    a = -jnp.exp(jax.random.uniform(ks[5], (channels, n), jnp.float32, 0.0, 2.5))
    return u, dt, z, b, c, a, jnp.linspace(0.5, 1.5, channels)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("length", [1, 3, 16, 37, 256, 1040])
@pytest.mark.parametrize("channels", [128, 200])  # 200 does not divide the block
def test_kernel_equals_recurrence(length, channels):
    """One document, and the same tokens as documents that start at 16,
    32, 128, on the edge of the second time block and in the middle of the
    third: the state is carried from block to block and cleared where a
    document starts."""
    args = _scan_inputs(length, channels, seed=length)
    one = jnp.zeros((1,), jnp.int32)
    got = selective_scan(*args, one, interpret=True)
    assert got.shape == (length, channels)
    _close(got, selective_scan_reference(*args, one))
    starts = jnp.asarray([0, 16, 32, 128, TIME_BLOCK, 2 * TIME_BLOCK + 16], jnp.int32)
    _close(selective_scan(*args, starts, interpret=True), selective_scan_reference(*args, starts))


# documents laid out against the kernel's time blocks of 512, each aligned to a
# chunk of 16 -> (lens, starts, stream)
LAYOUTS = {
    # the first ends mid-block, the second starts mid-block and ends on a block's
    # edge, the third is one token long and starts on that edge, the fourth
    # crosses a whole block, the last sits behind it
    "across_blocks": ((630, 384, 1, 600, 23), (0, 640, 1024, 1040, 1648), 1680),
    # every boundary on a block's edge
    "on_block_edges": ((512, 500, 512), (0, 512, 1024), 1536),
    # one block, not a whole one
    "in_one_block": ((40, 16, 1, 64, 23), (0, 48, 64, 80, 144), 176),
    # a document of a single token either side of a long one
    "single_tokens": ((1, 1100, 1, 1), (0, 16, 1120, 1136), 1152),
}
DOC_LENS, DOC_STARTS, STREAM = LAYOUTS["across_blocks"]
assert STREAM > 3 * TIME_BLOCK and DOC_STARTS[1] % TIME_BLOCK and DOC_STARTS[2] % TIME_BLOCK == 0


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_with_starts_equals_the_reference_with_starts(layout):
    _, doc_starts, stream = LAYOUTS[layout]
    args = _scan_inputs(stream, 128, seed=3)
    starts = jnp.asarray(doc_starts + (stream, stream), jnp.int32)  # two documents that are not there
    want = selective_scan_reference(*args, starts)
    _close(selective_scan(*args, starts, interpret=True), want)
    # without the starts it is another function: the state runs on
    assert float(jnp.abs(selective_scan_reference(*args, starts[:1]) - want).max()) > 1e-2


def test_kernel_state_does_not_cross_documents_or_read_the_future():
    u, dt, z, b, c, a, d = _scan_inputs(STREAM, 128, seed=7)
    starts = jnp.asarray(DOC_STARTS, jnp.int32)
    whole = np.asarray(selective_scan(u, dt, z, b, c, a, d, starts, interpret=True))
    # a document alone, a stream of its own, gives what it gives in the stream
    for at, n in zip(DOC_STARTS, DOC_LENS):
        rows = slice(at, at + n)
        alone = selective_scan(u[rows], dt[rows], z[rows], b[rows], c[rows], a, d, starts[:1], interpret=True)
        np.testing.assert_allclose(whole[rows], np.asarray(alone), atol=1e-6 * np.abs(whole).max())  # another shape's program
    # what comes after token 700 (the second block, the second document) cannot reach tokens 0..699
    cut = np.asarray(selective_scan(u.at[700:].set(9.0), dt, z, b, c, a, d, starts, interpret=True))
    np.testing.assert_array_equal(whole[:700], cut[:700])


def test_kernel_padding_poisoned_with_nan_changes_no_real_row():
    args = _scan_inputs(STREAM, 128, seed=5)
    starts = jnp.asarray(DOC_STARTS, jnp.int32)
    real = np.zeros(STREAM, bool)
    for at, n in zip(DOC_STARTS, DOC_LENS):
        real[at : at + n] = True
    want = np.asarray(selective_scan(*args, starts, interpret=True))
    poisoned = [jnp.where(real[:, None], x, jnp.nan) for x in args[:5]]
    got = np.asarray(selective_scan(*poisoned, *args[5:], starts, interpret=True))
    assert np.isnan(got[~real]).all()  # the state was cleared, not scaled: nothing of this reached a document
    np.testing.assert_array_equal(got[real], want[real])


def test_kernel_computes_the_time_blocks_under_the_live_length():
    args = _scan_inputs(STREAM, 128, seed=9)
    starts = jnp.asarray(DOC_STARTS, jnp.int32)
    want = np.asarray(selective_scan(*args, starts, interpret=True))
    # poison past the live tokens: a block that ran over them would write NaN under them too
    ran = 3 * TIME_BLOCK  # 1,030 tokens are three blocks of 512
    u = args[0].at[ran:].set(jnp.nan)
    got = np.asarray(selective_scan(u, *args[1:], starts, live=jnp.int32(2 * TIME_BLOCK + 6), interpret=True))
    np.testing.assert_array_equal(got[:ran], want[:ran])


# ---- packing --------------------------------------------------------------------


def _stream_of(lens, seed=0, t=None):
    """Documents of ``lens`` tokens (seeded ids) laid out as
    ``_stream_groups`` lays them -> (ids, starts, lens), with as many slots
    for documents as the stream can hold."""
    cfg = tiny()[0].cfg
    t = t or cfg.max_group_tokens
    rng = np.random.default_rng(seed)
    padded = -(-np.asarray(lens) // cfg.doc_align) * cfg.doc_align
    begins = np.cumsum(padded) - padded
    most = t // cfg.doc_align
    starts, doc_lens, ids = np.full((most,), t, np.int32), np.zeros((most,), np.int32), np.zeros((t,), np.int32)
    starts[: len(lens)], doc_lens[: len(lens)] = begins, lens
    for at, n in zip(begins, lens):
        ids[at : at + n] = rng.integers(999, cfg.vocab_size, n)
    return ids, starts, doc_lens


# token_chunk is 64, the kernel's time block 512: the second document ends on a
# chunk's edge and the third starts there; the fourth crosses three chunks
# (the conv window, the scan's state and the attention band with it); one
# is a single token; the sixth ends on the time block's edge at 512, the
# seventh starts there, the eighth starts mid-block
PACKED_LENS = (40, 16, 61, 150, 1, 208, 256, 9, 100)


def test_packed_stream_gives_each_document_the_row_it_gets_alone():
    enc, *_ = tiny()
    cfg = enc.cfg
    ids, starts, lens = _stream_of(PACKED_LENS)
    assert starts[2] == cfg.token_chunk and starts[6] == TIME_BLOCK and starts[7] % TIME_BLOCK and lens.sum() > TIME_BLOCK
    fwd = jax.jit(enc.module.apply_stream)
    packed = np.asarray(fwd(enc.live_params(), ids, starts, lens))
    np.testing.assert_allclose(np.linalg.norm(packed[: len(PACKED_LENS)], axis=1), 1.0, atol=1e-5)
    assert not packed[len(PACKED_LENS) :].any()  # a document that is not there
    for i, n in enumerate(PACKED_LENS):
        alone = np.zeros_like(ids)
        alone[:n] = ids[starts[i] : starts[i] + n]
        one_start, one_len = np.full_like(starts, len(ids)), np.zeros_like(lens)
        one_start[0], one_len[0] = 0, n
        np.testing.assert_allclose(np.asarray(fwd(enc.live_params(), alone, one_start, one_len))[0], packed[i], atol=2e-6)


@pytest.mark.parametrize("fault", ["scan_state_runs_on", "conv_reads_across"])
def test_a_boundary_that_is_no_restart_changes_a_row(monkeypatch, fault):
    """The test above sees each restart: without one, a packed document's
    row is not the row it gets alone."""
    from pathway_tpu.models import hybrid_ssm, token_stream

    enc, *_ = tiny()
    ids, starts, lens = _stream_of(PACKED_LENS)
    want = np.asarray(jax.jit(lambda *a: enc.module.apply_stream(*a))(enc.live_params(), ids, starts, lens))
    if fault == "scan_state_runs_on":
        scan = hybrid_ssm.selective_scan
        monkeypatch.setattr(hybrid_ssm, "selective_scan", lambda *a, **kw: scan(*a[:7], a[7][:1], **kw))
    else:  # every tap of the convolution reads, wherever in its document the token is
        layout = token_stream.token_layout
        monkeypatch.setattr(token_stream, "token_layout", lambda *a: (lambda seg, pos, live: (seg, pos + 3, live))(*layout(*a)))
    got = np.asarray(jax.jit(lambda *a: enc.module.apply_stream(*a))(enc.live_params(), ids, starts, lens))
    np.testing.assert_allclose(got[0], want[0], atol=2e-6)  # the first document has nothing before it
    assert np.abs(got[1 : len(PACKED_LENS)] - want[1 : len(PACKED_LENS)]).max(axis=1).min() > 1e-4


def test_padding_rows_poisoned_with_nan_change_no_row():
    """Every padding token embeds to NaN: between documents, behind the
    last, and in the chunks the loops still run."""
    enc, *_ = tiny()
    ids, starts, lens = _stream_of(PACKED_LENS, seed=1)
    assert (ids[ids > 0] >= 999).all()  # id 0 is padding's alone
    fwd = jax.jit(enc.module.apply_stream)
    want = np.asarray(fwd(enc.live_params(), ids, starts, lens))
    params = dict(enc.live_params())
    params["embed"] = {"embedding": params["embed"]["embedding"].at[0].set(jnp.nan)}
    got = np.asarray(fwd(params, ids, starts, lens))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


def test_padding_invariance():
    """A text embeds to the same row alone, beside a neighbour that fills
    the window, and as a row of the padded form at any width."""
    enc, family, model, weights = tiny()
    short = "w0007 w0008 w0009 w0010"
    alone = np.asarray(enc.encode_device([short]))[0]
    long = " ".join(f"w{i:04d}" for i in range(250))
    ids, lens = enc.tokenizer.batch_encode_matrix([short, long], enc.max_seq_len)
    assert lens.max() > 224
    both = np.asarray(enc.encode_device([long, short]))
    np.testing.assert_allclose(both[1], alone, atol=2e-6)
    # the padded form (a row a document) is the same stream function
    apply = jax.jit(enc.module.apply)
    for width in (6, 40, 256):  # 6 and 40 are no multiple of the alignment
        mask = np.arange(width)[None, :] < np.minimum(lens, width)[:, None]
        rows = [i for i in range(2) if lens[i] <= width]
        got = np.asarray(apply(enc.live_params(), ids[rows, :width], mask[rows]))
        np.testing.assert_allclose(got, both[[1, 0]][rows], atol=2e-6)
    with pytest.raises(ValueError, match="longer than"):
        apply(enc.live_params(), np.zeros((1, 272), np.int32), np.ones((1, 272), bool))


def test_document_independence():
    """Permuting the documents of a stream permutes the result: a row does
    not depend on where in the stream its document lies."""
    enc, *_ = tiny()
    perm = [3, 0, 4, 2, 1]
    straight = np.asarray(enc.encode_device(TEXTS))
    shuffled = np.asarray(enc.encode_device([TEXTS[i] for i in perm]))
    np.testing.assert_allclose(shuffled, straight[perm], atol=2e-6)


def test_a_batch_over_the_token_bound_goes_as_several_streams():
    enc, family, model, weights = tiny()
    texts = TEXTS * 7  # 1,344 tokens aligned to 16: two streams of 1,024
    calls = []
    run = enc._run_stream
    enc._run_stream = lambda ids, starts, lens: (calls.append((ids.shape, int((lens > 0).sum()))), run(ids, starts, lens))[1]
    try:
        got = np.asarray(enc.encode_device(texts))
    finally:
        del enc._run_stream
    assert len(calls) == 2 and {shape for shape, _ in calls} == {(1024,)} and sum(n for _, n in calls) == len(texts)
    np.testing.assert_allclose(got, np.asarray(family.encode(weights, model, texts)), atol=2e-5)


def test_one_compiled_program_serves_batches_of_different_token_counts():
    enc, *_ = tiny()
    for texts in (TEXTS[:1], TEXTS, TEXTS * 3, TEXTS[2:3] * 40):  # 16 to 640 aligned tokens, 1 to 40 documents
        assert np.isfinite(np.asarray(enc.encode_device(texts))).all()
    assert enc._fwd_stream.__wrapped__._cache_size() == 1
    assert getattr(enc, "_fwd_group", None) is None  # the padded route is not this module's


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
    cfg = HybridSSMConfig.jamba2_3b()
    # one stream of 8,192 tokens a dispatch, documents of up to 256 aligned to the scan's chunk of 16, loops over 1,024
    assert (cfg.max_seq_len, cfg.max_group_tokens, cfg.doc_align, cfg.token_chunk) == (256, 8192, TIME_CHUNK, 1024)
    assert stream_length(cfg.token_chunk, 8 * 16) == 128 and stream_length(cfg.token_chunk, 4500) == 5120  # the query program's stream; a write batch's
    tiny_cfg = HybridSSMConfig.tiny_for_tests()
    assert (tiny_cfg.max_group_tokens, tiny_cfg.token_chunk) == (1024, 64)
    with pytest.raises(ValueError, match="whole chunks"):
        HybridSSMEncoder(HybridSSMConfig.tiny_for_tests(token_chunk=40))
    with pytest.raises(ValueError, match="whole chunks"):
        HybridSSMEncoder(HybridSSMConfig.tiny_for_tests(token_chunk=384, max_group_tokens=1024))
    with pytest.raises(ValueError, match="fit one stream"):
        HybridSSMEncoder(HybridSSMConfig.tiny_for_tests(max_group_tokens=128))
    with pytest.raises(ValueError, match="fit one stream"):
        SentenceEncoder("hybrid-ssm-tiny-for-tests", max_seq_len=2048)
    # the encoder's document length is the module's: the rows of a group, the band an attention layer scores
    enc = SentenceEncoder("AI21-Jamba2-3B", max_seq_len=512)
    assert (enc.cfg.max_seq_len, enc.module.cfg.max_seq_len, enc.max_batch) == (512, 512, 16)


@pytest.mark.parametrize("window", [512, 384, 100])
def test_documents_as_long_as_the_encoder_is_built_for(window):
    """``max_seq_len`` is how far back an attention layer looks: twice the
    default, and two lengths that are no power of two (a chunk of 64 is
    then blocks of 64 and of 4 queries). A document that fills the window
    lies between two neighbours in one stream."""
    cfg = HybridSSMConfig.tiny_for_tests(scan_impl="interpret", dtype=jnp.dtype("float32"))
    enc = SentenceEncoder("hybrid-ssm-tiny-for-tests", config=cfg, max_seq_len=window)
    family, model = spec.load_family("jamba"), tiny_model(enc.cfg)
    assert model["max_seq_len"] == window
    weights = make_weights(family, model, SCALES, seed=11)
    enc.params = bench_system._lay_over(enc.params, weights)
    texts = [TEXTS[1], " ".join(f"w{i:04d}" for i in range(window + 20)), TEXTS[3]]
    assert family.tokens_of([len(t.split()) for t in texts], model)[1] == window
    np.testing.assert_allclose(np.asarray(enc.encode_device(texts)), np.asarray(family.encode(weights, model, texts)), atol=2e-5)


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


def test_embed_dispatch_counts_padded_tokens_and_kernel_stats_are_fed(monkeypatch):
    from pathway_tpu.internals.profiler import ENCODER_KERNEL_STATS

    enc, family, model, _ = tiny()
    enc.encode_device(TEXTS)  # compiled; tracing off: nothing is counted
    ENCODER_KERNEL_STATS.reset()
    fetched = []
    monkeypatch.setattr(jax.Array, "__array__", lambda self, *a, **k: fetched.append(self.shape) or np.zeros(self.shape), raising=False)
    tracing.set_tracing_enabled(True)
    tracing.TRACING_METRICS.reset()
    try:
        with tracing.span("embed_batch", new_trace=True, rows=len(TEXTS) + 2):  # as the embedder opens it
            enc._dispatch_tokenized(TEXTS, enc._tokenize_matrix(TEXTS))
            enc._dispatch_tokenized(TEXTS[:2], enc._tokenize_matrix(TEXTS[:2]))
        totals = tracing.stage_totals()
    finally:
        tracing.set_tracing_enabled(False)
        tracing.TRACING_METRICS.reset()
    assert not fetched  # the counts are the host's own, from the lengths it has
    snap = ENCODER_KERNEL_STATS.snapshot()
    ENCODER_KERNEL_STATS.reset()
    lens = np.asarray(family.tokens_of([len(t.split()) for t in TEXTS], model))
    # TEXTS: 146 tokens, 192 aligned to 16 = three chunks of 64; its first two: 64 aligned, one chunk
    both = np.concatenate([lens, lens[:2]])
    stage = totals["embed_ssm"]
    assert stage["calls"] == 2  # once a stream
    assert stage["tokens"] == both.sum() == totals["embed_tokenize"]["tokens"]
    assert stage["computed_tokens"] == 192 + 64 == totals["embed_dispatch"]["tokens"]
    assert "embed_retention" not in totals  # another module's stage
    assert totals["embed_dispatch"]["rows"] == len(both) and totals["embed_dispatch"]["calls"] == 2
    assert (snap["dispatches"], snap["real_tokens"], snap["computed_tokens"]) == (2, both.sum(), 256)
    assert snap["model_flops"] == pytest.approx(sum(n * enc.cfg.flops_per_token(n) for n in both.tolist()))
