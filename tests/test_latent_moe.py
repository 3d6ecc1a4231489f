"""The latent-attention / sparse-expert encoder (``models/latent_moe.py``),
its expert layer (``ops/expert_dispatch.py``) and its place on the normal
embed -> scatter -> search path; and the parameter tree that is shapes
until a forward needs values, for this module and the hybrid one. CPU,
tiny widths; the plain reference is the benchmark's family
``benchmarks/families/pangu_moe.py``, which imports nothing of the
program."""

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
from pathway_tpu.models import latent_moe, sentence_encoder
from pathway_tpu.models.hybrid_ssm import HybridSSMConfig
from pathway_tpu.models.latent_moe import LatentMoEConfig, LatentMoEEncoder
from pathway_tpu.models.sentence_encoder import SentenceEncoder, architecture_of
from pathway_tpu.models.tokenizer import WordPieceTokenizer
from pathway_tpu.ops import expert_dispatch, knn, mla_attention

SCALES = {"word_std": 1.0, "matrix_gain": 1.0, "router_gain": 1.0, "post_norm_scale": 0.15}
PRESET = "latent-moe-tiny-for-tests"
TEXTS = [
    "w0001 w0002 w0003",
    "alpha beta gamma delta " * 9,
    "one",
    "the quick brown fox jumps over the lazy dog " * 4,
    "w0404 " * 60,
]
PROGRAM_ONLY = (
    "dtype", "expert_impl", "attention_impl", "experts_held", "pooling", "normalize", "max_group_tokens", "token_chunk",
    "doc_align",
)  # fmt: skip
#: the attention's two routes on the CPU: "kernel" takes the XLA chain off
#: the chip, "interpret" the Pallas kernel in the interpreter
ATTENTION = ("kernel", "interpret")


def family_model(cfg: LatentMoEConfig) -> dict:
    """The benchmark's description of a program configuration."""
    keys = [f for f in cfg.__dataclass_fields__ if f not in PROGRAM_ONLY]
    model = {"family": "pangu_moe", **{k: getattr(cfg, k) for k in keys}}
    model["router_experts"] = cfg.n_routed_experts
    model["experts_first"], model["n_routed_experts"] = cfg.experts_held
    return model


@functools.lru_cache(maxsize=None)
def tiny(dtype: str = "float32", held: tuple[int, int] = (0, 8), attention: str = "kernel"):
    """(SentenceEncoder, family, model, weights) at the tiny preset, the
    seed's weights laid over the program's tree as the benchmark lays them."""
    cfg = LatentMoEConfig.tiny_for_tests(
        dtype=jnp.dtype(dtype), expert_impl="interpret", attention_impl=attention, experts_held=held
    )
    enc = SentenceEncoder(PRESET, config=cfg)
    family, model = spec.load_family("pangu_moe"), family_model(cfg)
    weights = make_weights(family, model, SCALES, seed=11)
    enc.params = bench_system._lay_over(enc.params, weights)
    return enc, family, model, weights


# ---- the program against the plain reference ----------------------------------


@pytest.mark.parametrize("held", [(0, 8), (2, 3)])
def test_program_equals_reference_in_float32(held):
    enc, family, model, weights = tiny(held=held)
    got = np.asarray(enc.encode_device(TEXTS))
    want = np.asarray(family.encode(weights, model, TEXTS))
    np.testing.assert_allclose(np.linalg.norm(got, axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_bfloat16_is_near_the_reference_and_the_fp8_control_farther():
    # unit rows of width 64 through 3 layers whose matmul inputs are
    # rounded to 8 bits of mantissa: the rows move by a few 1e-3 an
    # element, and a token whose eighth and ninth scores swap moves its
    # text's row by about as much; 3e-2 is ten times that
    enc, family, model, weights = tiny("bfloat16")
    assert enc.params["layer_1"]["moe"]["experts"]["gate"].dtype == jnp.bfloat16
    assert enc.params["layer_1"]["moe"]["router"]["kernel"].dtype == jnp.float32
    want = np.asarray(family.encode(weights, model, TEXTS))
    got = np.asarray(enc.encode_device(TEXTS))
    assert np.abs(got - want).max() < 3e-2
    assert (got * want).sum(axis=1).min() > 0.998
    fp8 = np.abs(np.asarray(family.encode(weights, model, TEXTS, quant="fp8")) - want).max()
    assert fp8 > 2 * np.abs(got - want).max()


# ---- the expert layer -----------------------------------------------------------


def _loop_gmm(x, w, sizes):
    out, lo = np.zeros((x.shape[0], w.shape[2]), np.float32), 0
    for g, size in enumerate(sizes):
        out[lo : lo + size] = np.asarray(x[lo : lo + size]) @ np.asarray(w[g])
        lo += size
    return out, lo


@pytest.mark.parametrize("sizes", [[5, 0, 17, 3], [0, 0, 0, 40], [16, 16, 16, 16], [1, 1, 1, 1], [64, 0, 0, 0], [0, 0, 0, 0], [15, 17, 1, 31]])
def test_grouped_product_equals_a_per_expert_loop(sizes):
    """Groups that start and end inside a row tile of 16."""
    rng = np.random.default_rng(sum(sizes))
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 32, 48)), jnp.float32)
    got = np.asarray(expert_dispatch.grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32), tile=16, interpret=True))
    want, rows = _loop_gmm(x, w, sizes)
    np.testing.assert_allclose(got[:rows], want[:rows], atol=1e-5)


def _dense_expert_sum(x, scores, real, w_gate, w_up, w_down, *, first, top_k, scale):
    """Every held expert over every token, masked by its weight."""
    x, scores = np.asarray(x, np.float64), np.asarray(scores, np.float64)
    out = np.zeros_like(x)
    loads = np.zeros(w_gate.shape[0], np.int64)
    for t in range(x.shape[0]):
        if not real[t]:
            continue
        top = np.argsort(-scores[t], kind="stable")[:top_k]
        total = scores[t, top].sum()
        for e in top:
            if first <= e < first + w_gate.shape[0]:
                g = x[t] @ np.asarray(w_gate[e - first], np.float64)
                act = g / (1 + np.exp(-g)) * (x[t] @ np.asarray(w_up[e - first], np.float64))
                out[t] += scale * scores[t, e] / total * (act @ np.asarray(w_down[e - first], np.float64))
                loads[e - first] += 1
    return out, loads


def _expert_case(tokens=96, d=16, inner=24, experts=8, held=(2, 4), seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32)
    scores = jnp.asarray(rng.uniform(0.05, 0.95, size=(tokens, experts)), jnp.float32)
    real = np.arange(tokens) % 7 != 6
    ws = [jnp.asarray(rng.normal(size=shape) / 4, jnp.float32) for shape in [(held[1], d, inner), (held[1], d, inner), (held[1], inner, d)]]
    return x, scores, real, ws


def _held_sum(x, scores, real, ws, *, first, experts, top_k=2, scale=2.5):
    ids, weights = expert_dispatch.route(scores, top_k, scale=scale)
    out, loads = jax.jit(
        functools.partial(expert_dispatch.held_expert_sum, first=first, experts=experts, tile=16, interpret=True)
    )(x, ids, weights, jnp.asarray(real), *ws)
    return np.asarray(out), np.asarray(loads)


@pytest.mark.parametrize(
    "skew",
    ["even", "all_on_one_held_expert", "all_on_held_experts", "one_held_expert_gets_nothing", "none_held"],
)
def test_expert_layer_is_dropless_whatever_the_router_does(skew):
    x, scores, real, ws = _expert_case()
    first, held, experts = 2, 4, 8
    if skew == "all_on_one_held_expert":  # nearly every token's first choice is expert 3
        scores = scores.at[:, 3].set(jnp.where(jnp.arange(96) % 11 == 0, 0.01, 0.99))
    elif skew == "all_on_held_experts":  # both choices held here: 2 x the tokens, a second round
        scores = scores.at[:, :2].set(0.01).at[:, 6:].set(0.01)
    elif skew == "one_held_expert_gets_nothing":
        scores = scores.at[:, 4].set(0.001)
    elif skew == "none_held":
        scores = scores.at[:, 2:6].set(0.001)
    got, loads = _held_sum(x, scores, real, ws, first=first, experts=experts)
    want, want_loads = _dense_expert_sum(x, scores, real, *ws, first=first, top_k=2, scale=2.5)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert loads.tolist() == want_loads.tolist()
    assert (got[~real] == 0).all()  # pad tokens are not routed
    cap = expert_dispatch.capacity_of(96, 2, held, experts, tile=16)
    if skew == "all_on_held_experts":
        assert loads.sum() == 2 * real.sum() > cap  # more than a round's worth, none dropped
    if skew == "one_held_expert_gets_nothing":
        assert loads[2] == 0 and loads.sum() > 0
    if skew == "none_held":
        assert loads.sum() == 0 and not got.any()


def test_the_shares_add_up():
    """8 experts over 4 ranks of 2: the ranks' routed parts, and the
    shared expert counted once, are the uncut reference layer."""
    enc, family, model, weights = tiny()
    prefix = "layer_1/"
    leaves = {n[len(prefix) :]: leaf for n, leaf in weights.take(sorted(family._layer_leaves(model, 1))).items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (4, 16, enc.cfg.hidden_size), jnp.float32)
    mask = jnp.arange(16)[None, :] < jnp.asarray([16, 9, 1, 12])[:, None]
    whole = family._moe(leaves, h, first=0, top_k=2, scale=2.5, norm=True, quant=None)
    moe = enc.params["layer_1"]["moe"]
    shared = np.asarray(latent_moe._swiglu(moe["shared"], h.reshape(64, -1))).reshape(h.shape)
    total = np.zeros(h.shape, np.float32)
    all_loads = []
    for rank in range(4):
        cfg = LatentMoEConfig.tiny_for_tests(dtype=jnp.float32, expert_impl="interpret", experts_held=(2 * rank, 2))
        part = {**moe, "experts": {k: v[2 * rank : 2 * rank + 2] for k, v in moe["experts"].items()}}
        out, loads = LatentMoEEncoder(cfg)._moe(part, h, mask)
        total += np.asarray(out) - shared  # this rank's routed part
        all_loads += np.asarray(loads).tolist()
    live = np.asarray(mask)[:, :, None]
    np.testing.assert_allclose((total + shared) * live, np.asarray(whole) * live, atol=2e-5)
    assert sum(all_loads) == 2 * int(np.asarray(mask).sum())  # every real token's two choices, once each


# ---- rope, padding and batching -------------------------------------------------


def test_rope_by_hand_at_two_positions():
    cos, sin = latent_moe._rope_table(6, 8, 10000.0)
    u = jnp.arange(1.0, 9.0)[None, None, :] * jnp.ones((1, 6, 1))
    got = np.asarray(latent_moe._rope(u, cos, sin))[0]
    np.testing.assert_allclose(got[0], np.arange(1.0, 9.0), atol=1e-6)  # position 0: unturned
    for pos in (1, 5):
        for j in range(4):  # dims j and j + 4 turn together by pos * theta^(-2j/8)
            a = pos * 10000.0 ** (-2 * j / 8)
            lo, hi = j + 1.0, j + 5.0
            np.testing.assert_allclose(got[pos, j], lo * math.cos(a) - hi * math.sin(a), atol=1e-5)
            np.testing.assert_allclose(got[pos, j + 4], hi * math.cos(a) + lo * math.sin(a), atol=1e-5)
    family = spec.load_family("pangu_moe")
    np.testing.assert_allclose(np.asarray(family._rope(u, 10000.0))[0], got, atol=1e-5)
    heads = jnp.stack([u, 2 * u], axis=2)  # [b, s, heads, dim]: every head turns alike
    np.testing.assert_allclose(np.asarray(latent_moe._rope(heads, cos, sin))[0, :, 1], 2 * got, atol=1e-5)


@pytest.mark.parametrize("attention", ATTENTION)
def test_padding_invariance(attention):
    """A text embeds to the same row alone at its own bucket and inside a
    batch padded to 256 (position = index in the text; pads not routed)."""
    enc, *_ = tiny(attention=attention)
    short = "w0007 w0008 w0009 w0010"
    alone = np.asarray(enc.encode_device([short]))[0]
    long = " ".join(f"w{i:04d}" for i in range(250))
    both = np.asarray(enc.encode_device([short, long]))
    np.testing.assert_allclose(both[0], alone, atol=2e-6)


@pytest.mark.parametrize("attention, texts", [("kernel", TEXTS), ("interpret", TEXTS[:4] + ["w0404 " * 100])])
def test_document_independence(attention, texts):
    """Permuting the rows of a batch permutes the result (through the
    kernel at the bucket of 128)."""
    enc, *_ = tiny(attention=attention)
    perm = [3, 0, 4, 2, 1]
    straight = np.asarray(enc.encode_device(texts))
    shuffled = np.asarray(enc.encode_device([texts[i] for i in perm]))
    np.testing.assert_allclose(shuffled, straight[perm], atol=2e-6)


@pytest.mark.parametrize("attention, texts, seq", [("kernel", TEXTS, 64), ("interpret", TEXTS[:4] + ["w0404 " * 100], 128)])
def test_texts_go_through_attention_in_blocks(attention, texts, seq):
    """The padded forward (the query program's) takes its texts in blocks
    whose float32 scores fit; a write batch is one stream and has none."""
    assert latent_moe._texts_per_block(32, 256, 128) == 16  # [16, 128, 256, 256] float32 = half a GiB
    assert latent_moe._texts_per_block(8, 16, 128) == 8
    assert latent_moe._texts_per_block(6, 256, 128) == 6
    enc, *_ = tiny(attention=attention)
    ids, lens = enc.tokenizer.batch_encode_matrix(texts, 256)
    ids = np.pad(ids, ((0, 8 - len(texts)), (0, 0)))[:, :seq]
    mask = np.arange(seq)[None, :] < np.pad(lens, (0, 8 - len(texts)))[:, None]
    assert lens.max() <= seq
    whole = np.asarray(jax.jit(enc.module.apply)(enc.params, ids, mask))
    blocked = latent_moe._SCORE_BYTES
    try:  # two texts a block of the 8 x seq
        latent_moe._SCORE_BYTES = 2 * 4 * enc.cfg.num_attention_heads * seq * seq
        assert latent_moe._texts_per_block(8, seq, enc.cfg.num_attention_heads) == 2
        got = np.asarray(jax.jit(enc.module.apply)(enc.params, ids, mask))
    finally:
        latent_moe._SCORE_BYTES = blocked
    np.testing.assert_allclose(got, whole, atol=2e-6)
    np.testing.assert_allclose(got[: len(texts)], np.asarray(enc.encode_device(texts)), atol=2e-6)


# ---- the attention kernel (ops/mla_attention.py) ------------------------------------


def _heads_of(t: int, seed: int):
    """Queries, keys and values of ``t`` tokens at the tiny preset's heads:
    ``q_nope``, ``k_nope``, ``v`` bfloat16, ``q_rope`` float32 not yet
    turned, ``k_rope`` bfloat16."""
    cfg = LatentMoEConfig.tiny_for_tests()
    heads, nope, rot, vd = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rng = np.random.default_rng(seed)

    def draw(width, dtype=jnp.bfloat16):
        return jnp.asarray(rng.normal(size=(t, width)), dtype)

    return cfg, (draw(heads * nope), draw(heads * rot, jnp.float32), draw(heads * nope), draw(rot), draw(heads * vd))


def _chain(cfg, q_nope, q_rope, k_nope, k_rope, v, seg, cos, sin):
    """The XLA chain over ``[b, s]`` texts: the queries' rope turned by the
    rows ``cos``/``sin`` ``[s, rope]``, masked by ``seg`` ``[b, s]`` and by
    order -> float32 ``[b * s, heads * v]``."""
    b, s = seg.shape
    heads, nope, rot, vd = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    turned = latent_moe._rope(q_rope.reshape(b, s, heads, rot), cos, sin).astype(q_nope.dtype)
    keep = (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]) & (seg[:, :, None] == seg[:, None, :])
    ctx = latent_moe._context_xla(
        q_nope.reshape(b, s, heads, nope), turned, k_nope.reshape(b, s, heads, nope), k_rope.reshape(b, s, rot),
        v.reshape(b, s, heads, vd), keep,
    )  # fmt: skip
    return np.asarray(ctx).reshape(b * s, -1)


@pytest.mark.parametrize("lens", ["ones", "full", "mixed"])
@pytest.mark.parametrize("seq", [128, 256])
def test_attention_kernel_equals_the_xla_chain(seq, lens):
    """The kernel in the interpreter against the XLA chain of the padded
    forward — rope of the queries, scores, masks, softmax, values — at the
    tiny preset's heads, on 4 texts padded to ``seq`` as the stream whose
    text ``i`` starts at ``i * seq``: every text one token (every key after
    the first is padding), every text full, and a mix."""
    lens = jnp.asarray({"ones": [1, 1, 1, 1], "full": [seq] * 4, "mixed": [1, seq, 77, seq - 1]}[lens])
    cfg, heads_in = _heads_of(4 * seq, seq)
    mask = jnp.arange(seq)[None, :] < lens[:, None]
    seg = jnp.where(mask, jnp.arange(4)[:, None], -1)
    pos = jnp.broadcast_to(jnp.arange(seq), (4, seq))
    cos, sin = latent_moe._rope_table(seq, cfg.qk_rope_head_dim, cfg.rope_theta)
    want = _chain(cfg, *heads_in, seg, cos, sin)
    args = (*heads_in, seg.reshape(-1), pos.reshape(-1), cos, sin)
    span = mla_attention.span_of(seq)
    got = mla_attention.mla_attention(*args, span=span, out_dtype=jnp.float32, interpret=True)
    # float32 on both sides up to the bfloat16 rounding of a probability
    # whose float32 value differs in its last bit
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    same = mla_attention.mla_attention(*args, span=span, interpret=True)
    assert same.dtype == jnp.bfloat16  # what W_o reads, on the normal path
    np.testing.assert_allclose(np.asarray(same, np.float32), want, rtol=1e-2, atol=1e-2)


#: a stream of the tiny preset (1,024 tokens, chunks of 256, documents
#: aligned to 16): one token; 37, no multiple of 16; a whole 256 across two
#: tiles and a chunk's end; 130 across a tile's end; 16; 200 across a tile's
#: and a chunk's end; then nothing — the rest of its chunk and a whole dead
#: chunk, and two documents that are not there
STREAM_LENS = [1, 37, 256, 130, 16, 200, 0, 0]


def _stream_of(lens, t: int, align: int = 16):
    starts = np.full((len(lens),), t, np.int32)
    at = 0
    for i, n in enumerate(lens):
        if n:
            starts[i] = at
            at += -(-n // align) * align
    return starts, np.asarray(lens, np.int32)


def test_the_kernel_visits_the_live_tiles_and_equals_a_document_and_order_mask():
    """The visit form over a packed stream: real rows against the XLA
    chain over the stream as one text, masked by document and by order;
    query tile ``i`` meets key tiles back to its first document's first,
    and no tile past the live length is visited."""
    from pathway_tpu.models.token_stream import token_layout

    t = 1024
    starts, lens = _stream_of(STREAM_LENS, t)
    seg, pos, live = token_layout(jnp.asarray(starts), jnp.asarray(lens), t)
    assert int(live) == 680
    cfg, heads_in = _heads_of(t, 3)
    cos, sin = latent_moe._rope_table(256, cfg.qk_rope_head_dim, cfg.rope_theta)
    span = mla_attention.span_of(256)
    assert span == 3  # a document of 256 starting a row past a tile's start
    qi, kj, nth, count, steps = mla_attention.visits(pos, live, span)
    steps = int(steps)
    visits = sorted(zip(np.asarray(qi)[:steps].tolist(), np.asarray(kj)[:steps].tolist()))
    # tile 0: itself; 1, 2: back to the 256's tile 0; 3: back to the 130's tile 2; 4, 5: back to the 200's tile 3
    assert visits == [(0, 0), (1, 0), (1, 1), (2, 0), (2, 1), (2, 2), (3, 2), (3, 3), (4, 3), (4, 4), (5, 3), (5, 4), (5, 5)]
    assert np.asarray(count)[:steps].tolist() == [1, 2, 2, 3, 3, 3, 2, 2, 2, 2, 3, 3, 3]
    assert np.asarray(nth)[:steps].tolist() == [0, 0, 1, 0, 1, 2, 0, 1, 0, 1, 0, 1, 2]
    got = np.asarray(mla_attention.mla_attention(*heads_in, seg, pos, cos, sin, span=span, live=live, out_dtype=jnp.float32, interpret=True))
    want = _chain(cfg, *heads_in, seg[None], cos[pos], sin[pos])
    real = np.asarray(seg) >= 0
    np.testing.assert_allclose(got[real], want[real], rtol=1e-4, atol=1e-4)
    assert np.isfinite(got[: 6 * mla_attention.BLOCK]).all()  # a padding row of a visited tile too


def _stream_against_alone(enc):
    """The rows of ``STREAM_LENS``' documents (seeded ids) as one packed
    stream, and each alone through the padded forward at 256."""
    t = enc.cfg.max_group_tokens
    starts, lens = _stream_of(STREAM_LENS, t)
    rng = np.random.default_rng(5)
    ids = np.zeros((t,), np.int32)
    alone = []
    for start, n in zip(starts, lens):
        if not n:
            continue
        doc = rng.integers(999, enc.cfg.vocab_size, size=n).astype(np.int32)
        ids[start : start + n] = doc
        row = np.zeros((1, 256), np.int32)
        row[0, :n] = doc
        alone.append(np.asarray(jax.jit(enc.module.apply)(enc.params, row, jnp.asarray(np.arange(256) < n)[None]))[0])
    # traced anew: a kernel a test patches is what the program calls
    rows, loads = jax.jit(lambda *args: enc.module.apply_stream(*args))(enc.params, ids, starts, lens)
    return np.asarray(rows), np.asarray(alone), np.asarray(loads)


@pytest.mark.parametrize("attention", ATTENTION)
def test_the_stream_equals_its_documents_one_by_one(attention):
    """In float32, the packed stream's rows are its documents' rows alone
    through the padded forward (the kernel at 256 in the interpreter, the
    XLA chain otherwise); a document that is not there gets zeros, and
    the loads count every real token's choices of the held experts."""
    enc, *_ = tiny(attention=attention)
    rows, alone, loads = _stream_against_alone(enc)
    present = np.asarray(STREAM_LENS) > 0
    np.testing.assert_allclose(rows[present], alone, atol=2e-6)
    assert not rows[~present].any()
    sparse = enc.cfg.num_hidden_layers - enc.cfg.first_k_dense_replace
    assert loads.shape == (sparse, 8) and loads.sum(axis=1).tolist() == [2 * sum(STREAM_LENS)] * sparse


def test_a_cross_document_leak_is_caught(monkeypatch):
    """The kernel told every real token is of one document: each attends
    the documents before it, and their rows move far past the tolerance
    the stream is held to."""
    enc, *_ = tiny(attention="interpret")
    kernel = mla_attention.mla_attention

    def leaking(*args, **kw):
        seg = args[5]
        return kernel(*args[:5], jnp.where(seg >= 0, 0, seg), *args[6:], **kw)

    monkeypatch.setattr(mla_attention, "mla_attention", leaking)
    rows, alone, _ = _stream_against_alone(enc)
    moved = np.abs(rows[np.asarray(STREAM_LENS) > 0] - alone).max(axis=1)
    assert moved[0] < 2e-6  # the first document has nothing before it
    assert moved[1:].min() > 1e-3


@pytest.mark.parametrize("seq, route", [(16, "xla"), (160, "xla"), (128, "kernel"), (256, "kernel")])
def test_attention_route_by_shape_and_its_counter(seq, route):
    """In the padded forward (the query program's) texts of whole
    128-token tiles take the kernel and every other shape (the query
    program's 16) the XLA chain; a write batch is a stream of whole tiles
    and takes it in every layer, counted on the host as ``embed_latent``:
    a call a stream, its real tokens and the token rows of its live
    chunks. Off the chip the kernel runs only where it is asked for in the
    interpreter."""
    assert mla_attention.route(seq, "interpret") == route
    assert mla_attention.route(seq, "kernel") == "xla"  # the CPU
    enc, *_ = tiny(attention="interpret")
    layers, t, chunk = enc.cfg.num_hidden_layers, enc.cfg.max_group_tokens, enc.cfg.token_chunk
    lens = np.array([seq, 1, seq // 2, 3], np.int32)
    ids = np.where(np.arange(seq)[None, :] < lens[:, None], 7, 0).astype(np.int32)
    program = str(jax.make_jaxpr(enc.module.apply)(enc.params, ids, jnp.asarray(ids > 0)))
    assert program.count("mla_attention") == (layers if route == "kernel" else 0)
    stream = (jax.ShapeDtypeStruct((t,), jnp.int32),) + (jax.ShapeDtypeStruct((t // 16,), jnp.int32),) * 2
    assert str(jax.make_jaxpr(enc.module.apply_stream)(enc.params, *stream)).count("mla_attention") == layers
    tracing.set_tracing_enabled(True)
    tracing.TRACING_METRICS.reset()
    try:
        for _ in range(2):
            enc._matrix_groups(ids, lens)
        totals = tracing.stage_totals()
    finally:
        tracing.set_tracing_enabled(False)
        tracing.TRACING_METRICS.reset()
    assert "embed_attention" not in totals
    starts, _ = _stream_of(lens, t)
    live = int(starts[-1] + lens[-1])
    stage = totals["embed_latent"]
    assert stage["calls"] == 2
    assert stage["tokens"] == 2 * int(lens.sum())
    assert stage["computed_tokens"] == 2 * -(-live // chunk) * chunk


# ---- the published preset, without allocating it --------------------------------


def published_config() -> dict:
    path = os.path.join(spec.ROOT, "benchmarks", "configs", "msmarco-doc-pangu-ultra-moe.json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def test_published_tree_is_the_configuration_files():
    config = published_config()
    model = config["model"]
    cfg = architecture_of(model["name"])
    assert isinstance(cfg, LatentMoEConfig) and architecture_of("FreedomIntelligence/" + model["name"]) == cfg
    assert family_model(cfg) == {k: v for k, v in model.items() if k not in ("name", "float32", *PROGRAM_ONLY)}
    whole = LatentMoEConfig()
    assert model["max_seq_len"] == cfg.max_seq_len == 256  # the harness's geometry, not a published key
    for key in whole.__dataclass_fields__:
        if key in PROGRAM_ONLY or key == "max_seq_len":
            continue
        assert config[key] == model[key], key  # the top-level copy agrees
        if key in config["reduced"]:
            assert config["published"][key] == getattr(whole, key) != model[key], key
        else:  # every width as published
            assert model[key] == getattr(whole, key), key
    assert config["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size", "rows"]
    assert (model["router_experts"], cfg.experts_held) == (256, (0, 16))
    shapes = sentence_encoder._param_shapes(LatentMoEEncoder(cfg))  # no array
    paths, _ = jax.tree_util.tree_flatten_with_path(shapes)
    assert all(isinstance(leaf, jax.ShapeDtypeStruct) for _, leaf in paths)
    tree = {"/".join(p.key for p in path): tuple(leaf.shape) for path, leaf in paths}
    family = spec.load_family("pangu_moe")
    assert tree == {name: tuple(shape) for name, (shape, _) in family.leaves(model).items()}
    assert sorted(n for g in family.take_groups(model) for n in g) == sorted(tree)
    count = {name: int(np.prod(s)) for name, s in tree.items()}
    assert sum(count.values()) == 4_771_683_840  # 4,771.7 M
    assert max(sum(count[n] for n in g) for g in family.take_groups(model)) < 0.26e9  # a group: a quarter of a sparse layer
    assert pytree_nbytes(shapes) == 2 * sum(count.values()) + 2 * sum(n for name, n in count.items() if "norm" in name or "router" in name)
    assert shapes["layer_1"]["moe"]["experts"]["down"].shape == (16, 2048, 7680)
    assert shapes["layer_1"]["moe"]["router"]["kernel"].dtype == jnp.float32
    assert [cfg.is_dense(i) for i in range(5)] == [True, False, False, False, False]
    # the encoder caps its own groups from its configuration: 32 x 256
    assert cfg.max_group_tokens == 8192
    assert expert_dispatch.capacity_of(8192, 8, 16, 256) == 4096


def test_family_work_by_hand():
    family, model = spec.load_family("pangu_moe"), published_config()["model"]
    attn = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256 + 128 * 128 * 7680
    assert attn == 196_575_232
    expert = 3 * 7680 * 2048
    # one token: the projections of 5 layers, one key of attention in each, the dense
    # feed-forward, and in 4 layers the router, the shared expert and half an assignment
    one = 2 * (5 * attn + 3 * 7680 * 18432 + 4 * (7680 * 256 + expert + 0.5 * expert)) + 5 * 2 * 128 * (192 + 128)
    assert family.flops(model, [1]) == one
    assert family.flops(model, [5, 7]) > 12 * family.flops(model, [1])
    cfg = architecture_of(model["name"])  # the program counts a padded token the same way
    # ... but for attention over half the padded length: 256 x 128 keys a text, not 256 x 257 / 2
    assert 256 * cfg.flops_per_token(256) == pytest.approx(family.flops(model, [256]) - 5 * 2 * 128 * 320 * 128)
    assert family.expert_flops(model, 1000) == 1000 * 2 * expert
    assert family.expert_bytes(model, 4) == 4 * 16 * expert * 2
    ids, lens = family.tokenize(["w0001 w0002", ""], model)
    assert ids.shape == (2, 256) and lens.tolist() == [4, 2] and ids.max() < 19200
    enc_ids, enc_lens = WordPieceTokenizer(vocab_size=19200).batch_encode_matrix(["w0001 w0002", ""], 256)
    assert enc_ids[0, :4].tolist() == ids[0, :4].tolist() and enc_lens.tolist() == lens.tolist()


# ---- the normal path ------------------------------------------------------------


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setitem(
        sentence_encoder.ARCHITECTURES, PRESET, functools.partial(LatentMoEConfig.tiny_for_tests, expert_impl="interpret")
    )


def test_names_resolve_at_construction(interpreted):
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    assert architecture_of("openPangu-Ultra-MoE-718B.ep16-l5") == LatentMoEConfig.pangu_ultra_moe_ep16_l5()
    emb = SentenceTransformerEmbedder(PRESET)
    enc = emb._encoder
    assert isinstance(enc.module, LatentMoEEncoder)
    assert enc.tokenizer.vocab_size == enc.cfg.vocab_size == 2048
    assert emb.get_embedding_dimension() == 64
    rows = np.asarray(emb.encode_device(TEXTS))
    np.testing.assert_allclose(np.linalg.norm(rows, axis=1), 1.0, atol=1e-3)
    with pytest.raises(ValueError, match="experts_held"):
        LatentMoEEncoder(LatentMoEConfig.tiny_for_tests(experts_held=(6, 4)))


@pytest.mark.parametrize("preset, config", [(PRESET, LatentMoEConfig), ("hybrid-ssm-tiny-for-tests", HybridSSMConfig)])
def test_the_tree_is_shapes_until_a_forward_needs_values(preset, config):
    impl = {"expert_impl": "interpret"} if config is LatentMoEConfig else {"scan_impl": "interpret"}
    LEDGER.reset()
    enc = SentenceEncoder(preset, config=config.tiny_for_tests(**impl), seed=4)
    born = jax.tree_util.tree_leaves(enc.params)
    assert born and all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in born)
    booked = LEDGER.snapshot()["accounts"]["weights"]["bytes"]
    assert booked == pytree_nbytes(enc.params) > 0
    first = np.asarray(enc.encode_device(TEXTS[:2]))  # the first forward makes the seeded leaves
    made = jax.tree_util.tree_leaves(enc.params)
    assert all(isinstance(leaf, jax.Array) for leaf in made)
    assert [(m.shape, m.dtype) for m in made] == [(b.shape, b.dtype) for b in born]
    assert pytree_nbytes(enc.params) == booked  # the ledger's bytes agree either way
    assert enc.live_params() is enc.params
    same = SentenceEncoder(preset, config=config.tiny_for_tests(**impl), seed=4)
    np.testing.assert_array_equal(np.asarray(same.encode_device(TEXTS[:2])), first)
    # a tree somebody assigned is used as it is, and never overwritten
    other = SentenceEncoder(preset, config=config.tiny_for_tests(**impl), seed=5)
    assigned = jax.tree_util.tree_map(lambda leaf: leaf, enc.params)
    other.params = assigned
    np.testing.assert_array_equal(np.asarray(other.encode_device(TEXTS[:2])), first)
    assert other.params is assigned


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


def test_embed_experts_is_fed_from_the_device_without_a_fetch_on_the_dispatch_path(monkeypatch):
    from pathway_tpu.internals.profiler import ENCODER_KERNEL_STATS

    enc, *_ = tiny()
    ENCODER_KERNEL_STATS.reset()
    folds = []
    fold = tracing.TRACING_METRICS._fold
    monkeypatch.setattr(tracing.TRACING_METRICS, "_fold", lambda owed: (folds.append(len(owed)), fold(owed))[1])
    enc.encode_device(TEXTS)  # tracing off: nothing is kept
    assert not tracing.TRACING_METRICS._owed
    tracing.set_tracing_enabled(True)
    tracing.TRACING_METRICS.reset()
    try:
        with tracing.span("embed_batch", new_trace=True, rows=len(TEXTS) + 2):  # as the embedder opens it
            enc.encode_device(TEXTS)
            enc.encode_device(TEXTS[:2])
        owed = list(tracing.TRACING_METRICS._owed)
        assert [stage for stage, _, _ in owed] == ["embed_experts"] * 2
        assert all(isinstance(loads, jax.Array) and loads.shape == (2, 8) for _, _, loads in owed)
        assert not any(folds)  # nothing was fetched while dispatching
        totals = tracing.stage_totals()  # the reader pays
        assert tracing.stage_totals() == totals and not tracing.TRACING_METRICS._owed
    finally:
        tracing.set_tracing_enabled(False)
        tracing.TRACING_METRICS.reset()
    snap = ENCODER_KERNEL_STATS.snapshot()
    ENCODER_KERNEL_STATS.reset()
    experts, real = totals["embed_experts"], totals["embed_tokenize"]["tokens"]
    assert experts["calls"] == 2 * 2  # two dispatches of two sparse layers
    assert experts["rows"] == 2 * 2 * real  # top-2 of 8, all held: every real token twice a layer
    assert experts["mean_load"] == pytest.approx(experts["rows"] / 8)
    assert experts["max_load"] >= experts["mean_load"]
    padded = totals["embed_dispatch"]["tokens"]
    lens = enc.tokenizer.batch_encode_matrix(TEXTS, 256)[1]
    chunk, t = enc.cfg.token_chunk, enc.cfg.max_group_tokens

    def computed(ls):  # the live chunks of the one stream these documents are
        starts, ls = _stream_of(ls, t)
        return -(-int(starts[-1] + ls[-1]) // chunk) * chunk

    def flops(ls):
        return sum(int(n) * enc.cfg.flops_per_token(int(n)) for n in ls)

    assert padded == computed(lens) + computed(lens[:2]) and snap["dispatches"] == 3  # one more ran untraced
    assert snap["model_flops"] == pytest.approx(2 * flops(lens) + flops(lens[:2]))


def test_a_stream_without_experts_owes_no_loads():
    """A packing module whose stream forward returns its rows alone (the
    hybrid) goes through ``_run_stream`` as it did: nothing is owed to
    ``embed_experts``."""
    enc = SentenceEncoder("hybrid-ssm-tiny-for-tests", config=HybridSSMConfig.tiny_for_tests(scan_impl="interpret"))
    tracing.set_tracing_enabled(True)
    tracing.TRACING_METRICS.reset()
    try:
        rows = np.asarray(enc.encode_device(TEXTS))
        assert not tracing.TRACING_METRICS._owed
        totals = tracing.stage_totals()
    finally:
        tracing.set_tracing_enabled(False)
        tracing.TRACING_METRICS.reset()
    assert "embed_experts" not in totals and totals["embed_ssm"]["calls"] == 1
    np.testing.assert_allclose(np.linalg.norm(rows, axis=1), 1.0, atol=1e-3)
