"""The query programs' exact two-stage top-k (``ops/knn.py``
``_select_topk``): a maximum per block of columns, then the k winning
blocks only.

It returns what ``jax.lax.top_k`` returns — values and indices, ties
broken towards the lower slot — and takes ``lax.top_k`` itself where
the shape rule (``_topk_route``: a function of N and k alone) says the
row is too short, or no multiple of a block. End to end, a vector
search and a text search over an index large enough to engage the rule
answer what they answer with the helper forced to ``lax.top_k``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models.encoder import EncoderConfig
from pathway_tpu.models.sentence_encoder import SentenceEncoder
from pathway_tpu.ops import knn
from pathway_tpu.tracing import TRACE_STORE, TRACING_METRICS, set_tracing_enabled, stage_totals

B = knn._TOPK_BLOCK
N = 64 * knn._TOPK_MIN_BLOCKS_PER_K * B  # the shortest row on which k = 64 takes the block route


def _random(rng, q, n):
    return rng.normal(size=(q, n)).astype(np.float32)


def _winners_in_one_block(rng, q, n, k):
    s = _random(rng, q, n)
    for row in range(q):
        block = int(rng.integers(n // B))
        s[row, block * B + rng.choice(B, k, replace=False)] = 10.0 + rng.random(k).astype(np.float32)
    return s


def _one_winner_a_block(rng, q, n, k):
    s = _random(rng, q, n)
    for row in range(q):
        blocks = rng.choice(n // B, k, replace=False)
        s[row, blocks * B + rng.integers(B, size=k)] = 10.0 + rng.random(k).astype(np.float32)
    return s


def _ties_inside_a_block(rng, q, n, k):
    s = _random(rng, q, n)
    s[:, 5 * B + 3 : 5 * B + 3 + 2 * k] = 7.0  # 2k equal winners, side by side
    return s


def _ties_across_blocks(rng, q, n, k):
    s = _random(rng, q, n)
    s[:, 17::B] = 7.0  # one equal winner in every block: more tied blocks than k
    s[:, 2 * B + 40] = 9.0
    return s


def _all_equal(rng, q, n, k):
    return np.full((q, n), 0.25, np.float32)


def _masked(rng, q, n, k):
    s = _random(rng, q, n)
    s[:, rng.random(n) < 0.9] = knn._NEG  # nine rows in ten are not live
    return s


def _fewer_than_k_valid(rng, q, n, k):
    s = np.full((q, n), knn._NEG, np.float32)
    live = rng.choice(n, max(1, k // 2), replace=False)
    s[:, live] = _random(rng, q, len(live))
    return s


CASES = {
    "random": lambda rng, q, n, k: _random(rng, q, n),
    "winners_in_one_block": _winners_in_one_block,
    "one_winner_a_block": _one_winner_a_block,
    "ties_inside_a_block": _ties_inside_a_block,
    "ties_across_blocks": _ties_across_blocks,
    "all_equal": _all_equal,
    "masked_to_neg": _masked,
    "fewer_than_k_valid": _fewer_than_k_valid,
}


@pytest.mark.parametrize("q", [8, 3])
@pytest.mark.parametrize("k", [8, 16, 64])
@pytest.mark.parametrize("case", sorted(CASES))
def test_select_is_lax_top_k(case, k, q):
    """Values and indices, ties included: blocks are runs of
    consecutive slots and candidates keep slot order, so the lower slot
    wins a tie in both stages as it does in ``lax.top_k``."""
    scores = jnp.asarray(CASES[case](np.random.default_rng(k * 100 + q), q, N, k))
    assert knn._topk_route(N, k) == "blocks"
    vals, idx = jax.jit(knn._select_topk, static_argnames="k")(scores, k=k)
    want_vals, want_idx = jax.lax.top_k(scores, k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    # a valid top-k on its own terms: distinct slots that hold those values
    idx = np.asarray(idx)
    assert all(len(set(row)) == k for row in idx.tolist())
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(scores), idx, axis=1), np.asarray(vals))


@pytest.mark.parametrize(
    "n, k",
    [
        (N + 64, 8),  # no multiple of a block
        (N - B // 2, 8),
        (knn._TOPK_MIN_BLOCKS_PER_K * 8 * B - B, 8),  # one block short of the rule
        (N, 2 * (N // B)),  # a deep refetch: k has grown towards N
        (64, 8),  # a toy index
    ],
)
def test_below_the_rule_it_is_lax_top_k_itself(n, k, monkeypatch):
    assert knn._topk_route(n, k) == "full"
    k = min(k, n)

    def refuse(*a, **kw):
        raise AssertionError("the block route ran below the rule")

    monkeypatch.setattr(knn, "_select_blocks", refuse)
    scores = jnp.asarray(_random(np.random.default_rng(n), 4, n))
    vals, idx = knn._select_topk(scores, k)
    want_vals, want_idx = jax.lax.top_k(scores, k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))


def test_the_rule_reads_n_and_k_only():
    assert knn._topk_route(3_276_800, 16) == "blocks"  # the MiniLM cells' slab
    assert knn._topk_route(409_600, 16) == "blocks"
    assert knn._topk_route(3_276_800, 3_276_800) == "full"
    assert knn._topk_route(3_276_800 + 1, 16) == "full"


# -- end to end -----------------------------------------------------------


@pytest.fixture()
def fresh_programs():
    """The route is read when a program is traced: a test that patches
    it starts from no compiled search program and leaves none behind."""
    saved = dict(knn._JIT)
    knn._JIT.clear()
    yield
    knn._JIT.clear()
    knn._JIT.update(saved)


def _force_full(monkeypatch):
    monkeypatch.setattr(knn, "_topk_route", lambda n, k: "full")


ROWS, DIM = 8 * knn._TOPK_MIN_BLOCKS_PER_K * B, 16


@pytest.mark.parametrize("metric", ["cos", "l2", "ip"])
def test_vector_search_answers_as_with_lax_top_k(metric, monkeypatch, fresh_programs):
    rng = np.random.default_rng(5)
    vecs = rng.normal(size=(ROWS - 100, DIM)).astype(np.float32)
    vecs[1000:1040] = vecs[7]  # forty exact duplicates of one row: ties
    queries = vecs[[7, 100, 4321]] + 0.01 * rng.normal(size=(3, DIM)).astype(np.float32)

    def answers():
        idx = knn.DeviceKnnIndex(dim=DIM, metric=metric, reserved_space=ROWS)
        idx.add_batch_arrays(list(range(len(vecs))), vecs)
        for key in range(0, 2000, 3):
            idx.remove(key)
        assert idx.capacity == ROWS
        return idx.search_batch(queries, 8)

    assert knn._topk_route(ROWS, 8) == "blocks"
    got = answers()
    knn._JIT.clear()
    _force_full(monkeypatch)
    assert got == answers()
    if metric != "ip":  # the largest inner product need not be the row itself
        assert [row[0][0] for row in got][1:] == [100, 4321]


def test_mesh_search_answers_as_with_lax_top_k(monkeypatch):
    """Each shard's ``local_topk`` selects through the blocks where its
    own slab is long enough; the merge over shards is untouched."""
    from pathway_tpu.parallel.mesh import resolve_mesh

    mesh = resolve_mesh(2)
    rng = np.random.default_rng(9)
    vecs = rng.normal(size=(ROWS + ROWS // 2, DIM)).astype(np.float32)
    queries = vecs[[3, 20_000, 40_000]] + 0.01 * rng.normal(size=(3, DIM)).astype(np.float32)

    def answers():
        knn._MESH_JIT.pop(mesh, None)
        idx = knn.DeviceKnnIndex(dim=DIM, metric="cos", reserved_space=2 * ROWS, mesh=mesh)
        idx.add_batch_arrays(list(range(len(vecs))), vecs)
        assert idx.shard_capacity == ROWS
        return idx.search_batch(queries, 8)

    got = answers()
    _force_full(monkeypatch)
    assert got == answers()
    knn._MESH_JIT.pop(mesh, None)
    assert [row[0][0] for row in got] == [3, 20_000, 40_000]


@pytest.fixture(scope="module")
def enc():
    return SentenceEncoder(config=EncoderConfig(num_layers=1), max_seq_len=32, max_batch=8)


@pytest.fixture()
def tracing_on():
    prev = set_tracing_enabled(True)
    TRACE_STORE.reset()
    TRACING_METRICS.reset()
    yield
    set_tracing_enabled(prev)
    TRACE_STORE.reset()
    TRACING_METRICS.reset()


def _text_index(enc, rows):
    texts = [f"document {i} speaks of subject {i % 7} at length" for i in range(24)]
    idx = knn.DeviceKnnIndex(dim=enc.dim, metric="cos", reserved_space=rows)
    idx.attach_encoder(enc)
    rng = np.random.default_rng(11)
    idx.add_batch_arrays(
        [f"noise{i}" for i in range(rows - 64)],
        rng.normal(size=(rows - 64, enc.dim)).astype(np.float32),
    )
    idx.add_batch_arrays(list(range(24)), np.asarray(enc.encode(texts)))
    return idx, texts


def _device_spans():
    return [s for s in TRACE_STORE.recent_spans(limit=4096) if s["stage"] == "query_device"]


def test_text_search_answers_as_with_lax_top_k_and_says_which_route(enc, monkeypatch, tracing_on):
    idx, texts = _text_index(enc, ROWS)
    asked = [texts[4], texts[9], texts[11]]
    got = idx.search_texts_batch(asked, 8)
    assert [row[0][0] for row in got] == [4, 9, 11]
    totals = stage_totals()
    assert totals["query_topk_blocks"]["queries"] == totals["query_batch"]["queries"] == 3
    assert [s["attrs"]["topk"] for s in _device_spans()] == ["blocks"]

    _force_full(monkeypatch)
    enc._pw_fused_query_jit = idx._fused_jit = None  # traced with the route it was given
    TRACING_METRICS.reset()
    TRACE_STORE.reset()
    assert idx.search_texts_batch(asked, 8) == got
    assert "query_topk_blocks" not in stage_totals()
    assert [s["attrs"]["topk"] for s in _device_spans()] == ["full"]
    enc._pw_fused_query_jit = None  # the next user traces its own


def test_a_toy_index_takes_lax_top_k(enc, tracing_on):
    idx, texts = _text_index(enc, 128)
    got = idx.search_texts_batch([texts[4]], 3)
    assert got[0][0][0] == 4
    totals = stage_totals()
    assert totals["query_batch"]["queries"] == 1 and "query_topk_blocks" not in totals
    assert [s["attrs"]["topk"] for s in _device_spans()] == ["full"]
