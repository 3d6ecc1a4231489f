"""Fused single-dispatch RAG pipeline (ops/fused_rag.py) — the TPU
replacement for the reference's 3-stage query path (embedders.py:270 ->
usearch_integration.rs:53 -> rerankers.py:186)."""

import numpy as np
import pytest

from pathway_tpu.models.sentence_encoder import CrossEncoderScorer, SentenceEncoder
from pathway_tpu.ops.fused_rag import FusedRagPipeline


@pytest.fixture(scope="module")
def enc():
    return SentenceEncoder(max_batch=64)


def test_retrieval_only_exact_match(enc):
    p = FusedRagPipeline(enc, None, reserved_space=128)
    docs = [f"passage {i} about topic {i % 7}" for i in range(40)]
    p.add_docs(list(range(40)), docs)
    r = p.query("passage 3 about topic 3", k=1, k_retrieve=8)
    assert r[0][0] == 3


def test_rerank_returns_k(enc):
    p = FusedRagPipeline(enc, CrossEncoderScorer(), reserved_space=128, doc_seq_len=48)
    docs = [f"passage {i} about topic {i % 7}" for i in range(30)]
    p.add_docs(list(range(30)), docs)
    r = p.query("passage 12 about topic 5", k=5, k_retrieve=16)
    assert len(r) == 5
    assert len({k for k, _ in r}) == 5  # distinct docs


def test_incremental_adds_and_removes(enc):
    p = FusedRagPipeline(enc, None, reserved_space=64)
    p.add_docs(list(range(20)), [f"doc number {i}" for i in range(20)])
    p.query("doc number 1", k=1)  # resident
    p.add_docs([100], ["an unmistakably unique zebra document"])
    r = p.query("an unmistakably unique zebra document", k=1)
    assert r[0][0] == 100
    p.remove_docs([100])
    r = p.query("an unmistakably unique zebra document", k=1)
    assert r[0][0] != 100


def test_growth_past_reserved_space(enc):
    p = FusedRagPipeline(enc, None, reserved_space=64)
    docs = [f"growing corpus item {i} flavor {i % 11}" for i in range(300)]
    p.add_docs(list(range(300)), docs)
    r = p.query("growing corpus item 250 flavor 8", k=1, k_retrieve=8)
    assert r[0][0] == 250


def test_query_async_matches_sync(enc):
    p = FusedRagPipeline(enc, None, reserved_space=64)
    p.add_docs(list(range(10)), [f"async path doc {i}" for i in range(10)])
    sync = p.query("async path doc 4", k=3, k_retrieve=8)
    hits = p.resolve(*p.query_async("async path doc 4", k=3, k_retrieve=8), k=3)
    assert [k for k, _ in sync] == [k for k, _ in hits]


def test_empty_and_missing(enc):
    p = FusedRagPipeline(enc, None, reserved_space=64)
    assert p.query_batch([], 3) == []
    assert p.query("anything", 3) == []  # empty index
    p.add_docs([1], ["only doc"])
    r = p.query("only doc", k=5, k_retrieve=8)
    assert [k for k, _ in r] == [1]  # padding slots filtered out


def test_index_text_queries_match_embed_then_search(enc):
    """DeviceKnnIndex.search_texts_batch (one fused dispatch; two over a
    mesh) against enc.encode + search_batch. The fused program ships
    scores and slots in one int32 array: slots bitcast to f32 are
    denormals, which a TPU flushes to zero (every hit became slot 0)."""
    import jax
    from jax.sharding import Mesh

    from pathway_tpu.ops.knn import DeviceKnnIndex

    docs = [f"text query doc {i} of kind {i % 5}" for i in range(24)]
    queries = [docs[7], docs[19], "kind 3"]
    vecs = enc.encode(docs)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1), ("data", "model"))
    for m in (None, mesh):
        idx = DeviceKnnIndex(dim=enc.dim, metric="cos", mesh=m)
        idx.add_batch_arrays(list(range(len(docs))), vecs)
        idx.attach_encoder(enc)
        got = idx.search_texts_batch(queries, 3)
        want = idx.search_batch(enc.encode(queries), 3)
        assert [[k for k, _ in row] for row in got] == [[k for k, _ in row] for row in want]
        assert got[0][0][0] == 7 and got[1][0][0] == 19
        np.testing.assert_allclose(
            [s for row in got for _, s in row], [s for row in want for _, s in row], atol=1e-5
        )
    packed = enc._pw_fused_query_jit(
        enc.params,
        np.zeros((8, 16), np.int32),
        np.full((8,), 4, np.int32),
        np.zeros((64, enc.dim), np.float32),
        np.ones((64,), bool),
        k=8,
        l2=False,
    )
    assert packed.dtype == np.int32 and packed.shape == (8, 16)
