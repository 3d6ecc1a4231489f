"""The power-retention encoder (``models/power_retention.py``), its kernel
(``ops/power_retention.py``), dispatch bounded by tokens
(``SentenceEncoder._stream_groups``) and its place on the normal embed ->
scatter -> search path. CPU, tiny widths, the kernel in the Pallas
interpreter; the plain reference is the benchmark's family
``benchmarks/families/brumby.py``, which imports nothing of the program."""

from __future__ import annotations

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec, system as bench_system
from benchmarks.lib.weights import make_weights
from pathway_tpu import tracing
from pathway_tpu.internals.ledger import LEDGER, pytree_nbytes
from pathway_tpu.models import power_retention as model_mod, sentence_encoder
from pathway_tpu.models.power_retention import PowerRetentionConfig, PowerRetentionEncoder
from pathway_tpu.models.sentence_encoder import SentenceEncoder, architecture_of
from pathway_tpu.ops import knn
from pathway_tpu.ops.power_retention import (
    block_pairs,
    power_retention,
    power_retention_reference,
    segment_cumsum,
    symmetric_square,
)

SCALES = {"word_std": 1.0, "matrix_gain": 1.0, "out_gain": 0.3, "gate_gain": 1.0}
PRESET = "power-retention-tiny-for-tests"
TEXTS = [
    "w0001 w0002 w0003",
    "alpha beta gamma delta " * 9,
    "one",
    "the quick brown fox jumps over the lazy dog " * 4,
    "w0404 " * 60,
    "a b c d e f g " * 30,
]
PROGRAM_ONLY = ("dtype", "pooling", "normalize", "retention_impl", "max_group_tokens", "doc_align", "token_chunk", "blocks")


def family_model(cfg: PowerRetentionConfig) -> dict:
    """The benchmark's description of a program configuration."""
    keys = [f for f in cfg.__dataclass_fields__ if f not in PROGRAM_ONLY]
    return {"family": "brumby", **{k: getattr(cfg, k) for k in keys}}


@functools.lru_cache(maxsize=None)
def tiny(dtype: str = "float32"):
    """(SentenceEncoder, family, model, weights) at the tiny preset, the
    seed's weights laid over the program's tree as the benchmark lays them."""
    cfg = PowerRetentionConfig.tiny_for_tests(dtype=jnp.dtype(dtype), retention_impl="interpret")
    enc = SentenceEncoder(PRESET, config=cfg)
    family, model = spec.load_family("brumby"), family_model(cfg)
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
    np.testing.assert_allclose(enc.encode(TEXTS), want, atol=2e-5)


def test_bfloat16_is_near_the_reference_and_the_fp8_control_farther():
    # unit rows of width 64 through 2 layers whose matmul inputs are
    # rounded to 8 bits of mantissa move by about 1e-3 an element; the
    # control rounds the same inputs to 3 bits
    enc, family, model, weights = tiny("bfloat16")
    want = np.asarray(family.encode(weights, model, TEXTS))
    near = np.abs(np.asarray(enc.encode_device(TEXTS)) - want).max()
    far = np.abs(np.asarray(family.encode(weights, model, TEXTS, quant="fp8")) - want).max()
    assert near < 4e-3 < far, (near, far)
    assert far > 3 * near


# ---- the three forms of the retention -------------------------------------------


def stream(lens, align, t, seed=0, kv=2, group=5, dim=16):
    """A seeded stream of documents of ``lens`` tokens, each padded to
    ``align``, in ``t`` tokens."""
    rng = np.random.default_rng(seed)
    seg, pos, at = np.full(t, -1, np.int32), np.zeros(t, np.int32), 0
    for i, n in enumerate(lens):
        seg[at : at + n], pos[at : at + n] = i, np.arange(n)
        at += -(-n // align) * align
    assert at <= t
    q = jnp.asarray(rng.normal(size=(t, kv * group * dim)), jnp.float32) * dim**-0.25
    k = jnp.asarray(rng.normal(size=(t, kv * dim)), jnp.float32) * dim**-0.25
    v = jnp.asarray(rng.normal(size=(t, kv * dim)), jnp.float32)
    log_g = jnp.asarray(-0.2 * np.abs(rng.normal(size=(t, kv))), jnp.float32)
    return q, k, v, log_g, jnp.asarray(seg), jnp.asarray(pos)


def pair_form(q, k, v, log_g, seg, pos, eps=1e-6):
    """The equations as written: whole ``[t, t]`` weights."""
    t, kv = log_g.shape
    dim = k.shape[1] // kv
    total = segment_cumsum(log_g, pos == 0).T
    q, k, v = q.reshape(t, kv, -1, dim), k.reshape(t, kv, dim), v.reshape(t, kv, dim)
    keep = (seg[:, None] == seg[None, :]) & (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])
    decay = jnp.exp(jnp.where(keep[None], total[:, :, None] - total[:, None, :], -1e30))
    weights = decay[:, None] * jnp.einsum("ibgd,jbd->bgij", q, k, precision="highest") ** 2
    num = jnp.einsum("bgij,jbd->ibgd", weights, v, precision="highest")
    return (num / (jnp.moveaxis(weights.sum(-1), 2, 0)[..., None] + eps)).reshape(t, -1)


def blocks_with_a_carried_state(q, k, v, log_g, seg, pos, block: int, eps=1e-6):
    """One document from position 0: a block's own pairs scored directly,
    everything before the block through ``S`` and ``z`` carried from block
    to block. The document's length need be no multiple of ``block``."""
    t, kv = log_g.shape
    dim = k.shape[1] // kv
    n = int((np.asarray(seg) == 0).sum())
    total = jnp.cumsum(log_g[:n], axis=0)
    qs, ks, vs = q[:n].reshape(n, kv, -1, dim), k[:n].reshape(n, kv, dim), v[:n].reshape(n, kv, dim)
    features = dim * (dim + 1) // 2
    state, z = jnp.zeros((kv, features, dim)), jnp.zeros((kv, features))
    out = []
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        qb, kb, vb, lb = qs[lo:hi], ks[lo:hi], vs[lo:hi], total[lo:hi]
        before = total[lo - 1] if lo else jnp.zeros((kv,))
        into = jnp.exp(lb - before)  # [b, kv]: the decay from the block's start to each of its tokens
        phi_q = symmetric_square(qb)  # [b, kv, group, features]
        num = into[:, :, None, None] * jnp.einsum("ibgf,bfd->ibgd", phi_q, state, precision="highest")
        den = into[:, :, None] * jnp.einsum("ibgf,bf->ibg", phi_q, z, precision="highest")
        causal = jnp.arange(hi - lo)[None, :] <= jnp.arange(hi - lo)[:, None]
        decay = jnp.exp(jnp.where(causal[None], lb.T[:, :, None] - lb.T[:, None, :], -1e30))
        weights = decay[:, None] * jnp.einsum("ibgd,jbd->bgij", qb, kb, precision="highest") ** 2
        num = num + jnp.einsum("bgij,jbd->ibgd", weights, vb, precision="highest")
        den = den + jnp.moveaxis(weights.sum(-1), 2, 0)
        out.append(num / (den[..., None] + eps))
        left = jnp.exp(lb[-1][None, :] - lb)  # [b, kv]: what is left of each token at the block's end
        phi_k = symmetric_square(kb) * left[:, :, None]
        whole = jnp.exp(lb[-1] - before)
        state = whole[:, None, None] * state + jnp.einsum("jbf,jbd->bfd", phi_k, vb, precision="highest")
        z = whole[:, None] * z + phi_k.sum(axis=0)
    return jnp.concatenate(out).reshape(n, -1)


@pytest.mark.parametrize("blocks", [(16, 32), (32, 16), (8, 8), (96, 96), (16, 48)])
def test_recurrence_pair_form_and_blocked_kernel_agree(blocks):
    # a document of 70 tokens: past four blocks of 16, a multiple of no block here
    # (a normaliser of 1e-2: where a token's weights sum to ~1e-4 the quotient
    # is the rounding of two orders of summation, not the function)
    args = stream([70, 5, 1], align=8, t=96)
    real = np.asarray(args[4]) >= 0
    want = np.asarray(pair_form(*args, eps=1e-2))
    np.testing.assert_allclose(np.asarray(power_retention_reference(*args, eps=1e-2))[real], want[real], atol=2e-4, rtol=2e-4)
    got = power_retention(*args, eps=1e-2, block_q=blocks[0], block_k=blocks[1], interpret=True)
    assert np.isfinite(np.asarray(got)).all()  # padding gets something finite
    np.testing.assert_allclose(np.asarray(got)[real], want[real], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block", [16, 24, 70])
def test_blocks_with_a_carried_state_agree_with_the_pairs(block):
    # the form the kernel does not take: the same function all the same
    args = stream([70], align=8, t=96, seed=3)
    want = np.asarray(pair_form(*args))[:70]
    got = np.asarray(blocks_with_a_carried_state(*args, block=block))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    kernel = np.asarray(power_retention(*args, block_q=16, block_k=32, interpret=True))[:70]
    np.testing.assert_allclose(kernel, want, atol=2e-5, rtol=2e-5)


def test_symmetric_square_is_the_square_of_the_product():
    rng = np.random.default_rng(1)
    x, y = jnp.asarray(rng.normal(size=(2, 16))), jnp.asarray(rng.normal(size=(2, 16)))
    assert symmetric_square(x).shape == (2, 136)
    np.testing.assert_allclose((symmetric_square(x) * symmetric_square(y)).sum(-1), (x * y).sum(-1) ** 2, rtol=1e-5)


def test_grouped_heads_against_a_per_head_loop():
    """Query head ``a`` reads key/value head ``a // 5`` and its gate."""
    q, k, v, log_g, seg, pos = stream([40, 17], align=8, t=64, seed=2)
    got = np.asarray(power_retention(q, k, v, log_g, seg, pos, block_q=16, block_k=32, interpret=True))
    real = np.asarray(seg) >= 0
    for a in range(10):
        b = a // 5
        one = pair_form(
            q[:, 16 * a : 16 * (a + 1)], k[:, 16 * b : 16 * (b + 1)], v[:, 16 * b : 16 * (b + 1)], log_g[:, b : b + 1], seg, pos
        )
        np.testing.assert_allclose(got[real, 16 * a : 16 * (a + 1)], np.asarray(one)[real], atol=2e-5, rtol=2e-5)


def test_only_live_blocks_and_their_documents_pairs_are_visited():
    _, _, _, _, seg, pos = stream([40, 17], align=8, t=128)
    qi, kj, flags, visits = block_pairs(pos, 64, 16, 32)
    visits = int(visits)
    # document 0 is blocks 0-2 of 16 queries (key blocks 0; 0; 0, 1), document 1 sits in query block 2-3 ...
    seen = list(zip(np.asarray(qi)[:visits].tolist(), np.asarray(kj)[:visits].tolist()))
    assert seen == [(0, 0), (1, 0), (2, 0), (2, 1), (3, 1)]
    assert np.asarray(flags)[:visits].tolist() == [3, 3, 1, 2, 3]
    # one document spanning the stream is the most there can be
    assert len(np.asarray(qi)) == sum((i * 16 + 15) // 32 + 1 for i in range(8))


def test_gate_running_sum_and_rope_by_hand():
    x = jnp.asarray([[1.0], [2.0], [4.0], [8.0], [16.0]])
    first = jnp.asarray([True, False, False, True, False])
    assert np.asarray(segment_cumsum(x, first))[:, 0].tolist() == [1.0, 3.0, 7.0, 8.0, 24.0]
    # rope over halves: position 3, head_dim 4, theta 100 -> angles 3 and 0.3
    u = jnp.asarray([[[1.0, 2.0, 3.0, 4.0]]])
    angle = jnp.asarray([[3.0, 0.3, 3.0, 0.3]])
    got = np.asarray(model_mod._rope(u, jnp.cos(angle), jnp.sin(angle)))[0, 0]
    want = [
        1 * math.cos(3) - 3 * math.sin(3),
        2 * math.cos(0.3) - 4 * math.sin(0.3),
        3 * math.cos(3) + 1 * math.sin(3),
        4 * math.cos(0.3) + 2 * math.sin(0.3),
    ]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # and the family's, on its own: the same rotation
    family = spec.load_family("brumby")
    ref = np.asarray(family._rope(jnp.tile(u[None], (1, 4, 1, 1)), 100.0))[0, 3, 0]
    np.testing.assert_allclose(ref, want, rtol=1e-5)


# ---- packing ----------------------------------------------------------------------


def test_packed_stream_is_one_document_at_a_time():
    enc, family, model, weights = tiny()
    together = np.asarray(enc.encode_device(TEXTS))
    for i, text in enumerate(TEXTS):  # a stream of one document
        np.testing.assert_allclose(np.asarray(enc.encode_device([text]))[0], together[i], atol=2e-6)
    # another neighbour, another place in the stream: the same row
    shuffled = [TEXTS[i] for i in (4, 0, 5, 2)]
    np.testing.assert_allclose(np.asarray(enc.encode_device(shuffled)), together[[4, 0, 5, 2]], atol=2e-6)


def test_padding_and_chunk_boundaries_change_no_row():
    enc, family, model, weights = tiny()
    ids, lens = family.tokenize(TEXTS, model)
    # TEXTS[4] (62 tokens) then TEXTS[5] (212): the second document crosses chunks of 64 tokens
    assert lens[4] < enc.cfg.token_chunk < lens[5]
    want = np.asarray(family.encode(weights, model, [TEXTS[4], TEXTS[5]]))
    np.testing.assert_allclose(np.asarray(enc.encode_device([TEXTS[4], TEXTS[5]])), want, atol=2e-5)
    # the padded form (a row a document) is the same stream function
    apply = jax.jit(enc.module.apply)
    for width in (64, 256):
        mask = np.arange(width)[None, :] < np.minimum(lens, width)[:, None]
        short = [i for i in range(len(TEXTS)) if lens[i] <= width]
        got = np.asarray(apply(enc.live_params(), ids[short, :width], mask[short]))
        np.testing.assert_allclose(got, np.asarray(family.encode(weights, model, TEXTS))[short], atol=2e-5)


def test_a_batch_over_the_token_bound_goes_as_several_streams():
    enc, family, model, weights = tiny()
    texts = TEXTS * 3  # 1,098 tokens padded to 8: five streams of 256
    calls = []
    run = enc._run_stream
    enc._run_stream = lambda ids, starts, lens: (calls.append((ids.shape, int((lens > 0).sum()))), run(ids, starts, lens))[1]
    try:
        got = np.asarray(enc.encode_device(texts))
    finally:
        del enc._run_stream
    assert len(calls) > 1 and {shape for shape, _ in calls} == {(256,)} and sum(n for _, n in calls) == len(texts)
    np.testing.assert_allclose(got, np.asarray(family.encode(weights, model, texts)), atol=2e-5)
    assert enc._fwd_stream.__wrapped__._cache_size() == 1  # one compiled program, whatever the batch


# ---- the published tree ------------------------------------------------------------


def published_config() -> dict:
    with open(os.path.join(spec.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "msmarco-doc-brumby-14b")
    with open(os.path.join(spec.ROOT, entry["file"]), encoding="utf-8") as f:
        return json.load(f)


def test_published_tree_is_the_configuration_files():
    config = published_config()
    model = config["model"]
    cfg = architecture_of(model["name"])
    assert isinstance(cfg, PowerRetentionConfig) and architecture_of("manifestai/" + model["name"]) == cfg
    assert family_model(cfg) == {k: v for k, v in model.items() if k not in ("name", "float32", *PROGRAM_ONLY)}
    whole = PowerRetentionConfig()
    published = [k for k in whole.__dataclass_fields__ if k not in PROGRAM_ONLY + ("degree", "gate_bias", "retention_eps", "max_seq_len")]
    assert len(published) == 18
    for key in published:
        assert config[key] == model[key], key  # the top-level copy agrees
        if key in config["reduced"]:
            assert config["published"][key] == getattr(whole, key) != model[key], key
        else:  # every width as published
            assert model[key] == getattr(whole, key), key
    assert config["reduced"] == ["num_hidden_layers", "rows"]
    shapes = sentence_encoder._param_shapes(PowerRetentionEncoder(cfg))  # no array
    paths, _ = jax.tree_util.tree_flatten_with_path(shapes)
    assert all(isinstance(leaf, jax.ShapeDtypeStruct) for _, leaf in paths)
    tree = {"/".join(p.key for p in path): tuple(leaf.shape) for path, leaf in paths}
    family = spec.load_family("brumby")
    assert tree == {name: tuple(shape) for name, (shape, _) in family.leaves(model).items()}
    assert sorted(n for g in family.take_groups(model) for n in g) == sorted(tree)
    count = {name: int(np.prod(s)) for name, s in tree.items()}
    assert sum(count.values()) == 3_420_740_608
    assert sum(n for name, n in count.items() if name.startswith("layer_3/")) == 330_352_896
    assert pytree_nbytes(shapes) == 2 * sum(count.values()) + 2 * sum(n for name, n in count.items() if "norm" in name or "gate/" in name and "mlp" not in name)
    assert shapes["layer_7"]["retention"]["gate"]["kernel"].dtype == jnp.float32
    assert shapes["layer_0"]["mlp"]["down"]["kernel"].shape == (17408, 5120)
    # whole documents of up to 4,096 tokens, one stream of 16,384 a dispatch
    enc_geometry = (cfg.max_seq_len, cfg.max_group_tokens, cfg.doc_align, cfg.token_chunk, cfg.blocks)
    assert enc_geometry == (4096, 16384, 128, 1024, (256, 512))


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setitem(
        sentence_encoder.ARCHITECTURES, PRESET, functools.partial(PowerRetentionConfig.tiny_for_tests, retention_impl="interpret")
    )


def test_names_resolve_at_construction(interpreted):
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    assert architecture_of("Brumby-14B-Base.l8") == PowerRetentionConfig.brumby_14b_base_l8()
    emb = SentenceTransformerEmbedder(PRESET)
    enc = emb._encoder
    assert isinstance(enc.module, PowerRetentionEncoder)
    assert enc.tokenizer.vocab_size == enc.cfg.vocab_size == 2048
    assert (emb.get_embedding_dimension(), enc.max_seq_len) == (64, 256)  # the module says how long a document may be
    rows = np.asarray(emb.encode_device(TEXTS))
    np.testing.assert_allclose(np.linalg.norm(rows, axis=1), 1.0, atol=1e-3)
    with pytest.raises(ValueError, match="whole chunks"):
        PowerRetentionEncoder(PowerRetentionConfig.tiny_for_tests(token_chunk=48))


def test_the_tree_is_shapes_until_a_forward_needs_values():
    LEDGER.reset()
    config = functools.partial(PowerRetentionConfig.tiny_for_tests, retention_impl="interpret")
    enc = SentenceEncoder(PRESET, config=config(), seed=4)
    born = jax.tree_util.tree_leaves(enc.params)
    assert born and all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in born)
    assert LEDGER.snapshot()["accounts"]["weights"]["bytes"] == pytree_nbytes(enc.params) > 0
    first = np.asarray(enc.encode_device(TEXTS[:2]))  # the first forward makes the seeded leaves
    made = jax.tree_util.tree_leaves(enc.params)
    assert all(isinstance(leaf, jax.Array) for leaf in made)
    assert [(m.shape, m.dtype) for m in made] == [(b.shape, b.dtype) for b in born]
    same = SentenceEncoder(PRESET, config=config(), seed=4)
    np.testing.assert_array_equal(np.asarray(same.encode_device(TEXTS[:2])), first)


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


def test_a_fresh_encoder_answers_a_text_query_before_any_embed(interpreted):
    """The fused query program is a forward too: it makes the seeded leaves."""
    enc = SentenceEncoder(PRESET)
    index = knn.DeviceKnnIndex(enc.dim, metric="cos", reserved_space=64)
    index.attach_encoder(enc)
    index.add_batch_device([0, 1], jnp.eye(2, enc.dim), None)
    assert len(index.search_texts_batch(["w0001 w0002"], 2)[0]) == 2
    assert isinstance(jax.tree_util.tree_leaves(enc.params)[0], jax.Array)


# ---- spans and counters (the scope names: tests/test_tracing_device_plane.py) ------


def test_embed_retention_and_computed_tokens_are_fed_without_a_device_fetch(monkeypatch):
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
    # TEXTS: 368 tokens, 424 padded to 8, in two streams (212 is the last to fit 256): 256 + 192 computed
    both = np.concatenate([lens, lens[:2]])
    stage = totals["embed_retention"]
    assert stage["calls"] == 3 * 2  # three streams of two layers
    assert stage["tokens"] == 2 * both.sum() == 2 * totals["embed_tokenize"]["tokens"]
    assert stage["rows"] == 2 * (both * (both + 1) // 2).sum()
    assert stage["computed_tokens"] == 2 * (256 + 192 + 64) == 2 * totals["embed_dispatch"]["tokens"]
    assert totals["embed_dispatch"]["rows"] == len(both) and totals["embed_dispatch"]["calls"] == 3
    assert (snap["dispatches"], snap["real_tokens"], snap["computed_tokens"]) == (3, both.sum(), 512)
    assert snap["model_flops"] == pytest.approx(sum(n * enc.cfg.flops_per_token(n) for n in both.tolist()))
