"""Every Pallas entry point lowers for the TPU platform at production
shapes — on the CPU, in seconds, with no chip.

``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
Pallas -> Mosaic lowering without a TPU backend, which is where block
shapes the hardware cannot tile are refused. The ``slow`` sibling runs
the real Mosaic / XLA-TPU compiler over the same list against a
compile-only v5e topology (needs an importable ``libtpu``; no chip).
Whether a kernel computes the right thing on hardware is
``chip_smoke.py``'s job, not this file's.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from pathway_tpu.decode.config import DecodeConfig
from pathway_tpu.decode.engine import DecoderConfig
from pathway_tpu.models.batching import DEFAULT_SEQ_BUCKETS
from pathway_tpu.models.encoder import EncoderConfig, TextEncoder, init_params
from pathway_tpu.models.sentence_encoder import SentenceEncoder
from pathway_tpu.ops.expert_dispatch import capacity_of, grouped_matmul
from pathway_tpu.ops.fused_attention import attention
from pathway_tpu.ops.fused_layer import _pack_rows, encoder_forward
from pathway_tpu.ops.mla_attention import mla_attention
from pathway_tpu.ops.paged_attention import paged_decode_attention
from pathway_tpu.ops.pallas_knn import knn_topk, knn_topk_sharded
from pathway_tpu.ops.power_retention import power_retention
from pathway_tpu.ops.selective_scan import selective_scan

# MiniLM-L6 at its published width; depth cut to one layer, since every
# layer lowers the same kernel and lowering time is linear in depth
MINILM = dataclasses.replace(EncoderConfig.minilm_l6(), num_layers=1)
N_MESH = 4  # the four-chip host


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@functools.lru_cache(maxsize=None)
def _minilm_params():
    # shapes only: lowering never reads a weight
    return jax.eval_shape(lambda: init_params(TextEncoder(MINILM), MINILM))


def _encoder_case(seq: int):
    # three blocks of packed sequences: the old per-block SMEM operand
    # lowered only when the whole batch was one block
    batch = 3 * _pack_rows(seq)

    def fwd(params, ids, lens):
        mask = jnp.arange(seq)[None, :] < lens[:, None]
        return encoder_forward(params, MINILM, ids, mask, lens=lens)

    return fwd, (
        _minilm_params(),
        _spec((batch, seq), jnp.int32),
        _spec((batch,), jnp.int32),
    )


def _attention_case(seq: int):
    d, heads, batch = MINILM.hidden_size, MINILM.num_heads, 64
    qkv = _spec((batch, seq, 3 * d), jnp.bfloat16)
    fn = lambda qkv, mask: attention(qkv, mask, n_heads=heads, impl="fused")
    return fn, (qkv, _spec((batch, seq), jnp.bool_))


def _knn_case(n_queries: int, n_docs: int, k: int):
    dim = MINILM.hidden_size
    fn = lambda q, docs, bias: knn_topk(q, docs, k=k, bias=bias)
    return fn, (
        _spec((n_queries, dim), jnp.float32),
        _spec((n_docs, dim), jnp.float32),
        _spec((n_docs,), jnp.float32),
    )


def _paged_case(page_size: int):
    model, dec = DecoderConfig(), DecodeConfig(page_size=page_size)
    d = model.hidden_size
    fn = lambda q, kp, vp, pt, ln: paged_decode_attention(
        q, kp, vp, pt, ln, n_heads=model.num_heads
    )
    return fn, (
        _spec((dec.lanes, d), jnp.float32),
        _spec((dec.pages, page_size, d), jnp.float32),
        _spec((dec.pages, page_size, d), jnp.float32),
        _spec((dec.lanes, dec.pages_per_seq()), jnp.int32),
        _spec((dec.lanes,), jnp.int32),
    )


def _scan_case(tokens: int):
    # the hybrid embedder's mixer at its published width: 5,120 channels,
    # a state of 16, over a packed stream — a write batch's 8,192 tokens
    # (as many documents as it can hold, its live length known on the
    # device only) and the query program's 8 x 16
    d, n = 5120, 16
    seqs = _spec((tokens, d), jnp.bfloat16)
    cols = _spec((tokens, n), jnp.float32)

    def scan(u, dt, z, b, c, a, d_skip, starts, live):
        return selective_scan(u, dt, z, b, c, a, d_skip, starts, live=live)

    return scan, (
        seqs,
        _spec((tokens, d), jnp.float32),
        seqs,
        cols,
        cols,
        _spec((d, n), jnp.float32),
        _spec((d,), jnp.float32),
        _spec((tokens // 16,), jnp.int32),
        _spec((), jnp.int32),
    )


def _experts_case(k: int, n: int):
    # one round of a write batch of the expert-parallel embedder: the
    # even share of 8,192 tokens' top-8 of 256 on 16 held experts, at
    # its published widths (gate / up: 7,680 -> 2,048; down: back)
    rows, held = capacity_of(8192, 8, 16, 256), 16
    return grouped_matmul, (_spec((rows, k), jnp.bfloat16), _spec((held, k, n), jnp.bfloat16), _spec((held,), jnp.int32))


def _retention_case(tokens: int):
    # the power-retention embedder at its published heads: 40 query
    # heads on 8 key/value heads of 128, a stream of whole documents
    return power_retention, (
        _spec((tokens, 40 * 128), jnp.bfloat16),
        _spec((tokens, 8 * 128), jnp.bfloat16),
        _spec((tokens, 8 * 128), jnp.bfloat16),
        _spec((tokens, 8), jnp.float32),
        _spec((tokens,), jnp.int32),
        _spec((tokens,), jnp.int32),
    )


def _mla_case(texts: int, seq: int, heads: int = 128, nope: int = 128, rot: int = 64, vd: int = 128):
    # the latent-attention embedder's attention: a block of 16 texts of a
    # write batch at the published heads (128 of 128 + 64 rope, values
    # of 128), and the test preset's 4 heads of 16 + 8
    def rows(width, dtype=jnp.bfloat16):
        return _spec((texts, seq, width), dtype)

    table = _spec((seq, rot), jnp.float32)
    return mla_attention, (
        rows(heads * nope), rows(heads * rot, jnp.float32), rows(heads * nope), rows(rot), rows(heads * vd),
        _spec((texts,), jnp.int32), table, table,
    )  # fmt: skip


SINGLE_DEVICE_CASES = {
    **{
        f"encoder_forward[S={s}]": functools.partial(_encoder_case, s)
        for s in DEFAULT_SEQ_BUCKETS
    },
    **{
        f"attention[fused,S={s}]": functools.partial(_attention_case, s)
        for s in (32, 160, 256, 512)
    },
    "knn_topk[Q=1,N=10k,k=16]": functools.partial(_knn_case, 1, 10_000, 16),
    "knn_topk[Q=100,N=625k,k=64]": functools.partial(_knn_case, 100, 625_000, 64),
    **{
        f"paged_decode_attention[page={p}]": functools.partial(_paged_case, p)
        for p in (8, 16, 32)
    },
    "selective_scan[T=8192]": functools.partial(_scan_case, 8192),
    "selective_scan[T=128]": functools.partial(_scan_case, 128),
    "expert_grouped_matmul[4096x7680x2048]": functools.partial(_experts_case, 7680, 2048),
    "expert_grouped_matmul[4096x2048x7680]": functools.partial(_experts_case, 2048, 7680),
    "power_retention[T=8192,40|8x128]": functools.partial(_retention_case, 8192),
    "power_retention[T=128,40|8x128]": functools.partial(_retention_case, 128),
    "mla_attention[16x256,128x(128+64|128)]": functools.partial(_mla_case, 16, 256),
    "mla_attention[16x128,128x(128+64|128)]": functools.partial(_mla_case, 16, 128),
    "mla_attention[8x128,4x(16+8|16)]": functools.partial(_mla_case, 8, 128, 4, 16, 8, 16),
}


def _lower_for_tpu(fn, args):
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the lowered program"
    return text


@pytest.mark.parametrize("name", SINGLE_DEVICE_CASES)
def test_lowers_for_tpu(name):
    _lower_for_tpu(*SINGLE_DEVICE_CASES[name]())


def test_paged_kernel_refuses_a_page_mosaic_cannot_place():
    """page_size=4 at the default decoder lowers but does not compile
    ("cannot statically prove that index in dimension 0 is a multiple of
    8"), so the entry point refuses it up front."""
    fn, args = _paged_case(4)
    with pytest.raises(ValueError, match="multiple of 8"):
        jax.jit(fn).trace(*args)


def _sharded_knn_case(mesh):
    dim, n_docs = MINILM.hidden_size, N_MESH * 8192
    place = lambda shape, dtype, spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec)
    )
    fn = lambda q, docs, bias: knn_topk_sharded(q, docs, bias, k=16, mesh=mesh)
    return fn, (
        place((16, dim), jnp.float32, P()),
        place((n_docs, dim), jnp.float32, P("data", None)),
        place((n_docs,), jnp.float32, P("data")),
    )


def _mesh_embedder(mesh, monkeypatch):
    """The embedder over ``mesh`` — with the kernel choice a TPU backend
    makes forced for everything traced afterwards: on the CPU "auto"
    picks the XLA chain and the partitioning question never comes up."""
    enc = SentenceEncoder(config=MINILM, checkpoint_dir="/nonexistent", mesh=mesh)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return enc


def test_sharded_topk_lowers_for_tpu():
    mesh = Mesh(np.array(jax.devices()[:N_MESH]), ("data",))
    _lower_for_tpu(*_sharded_knn_case(mesh))


def test_mesh_embedder_kernel_is_inside_shard_map(monkeypatch):
    """XLA cannot partition a Mosaic kernel across a mesh by itself
    ("Mosaic kernels cannot be automatically partitioned"), and says so
    only at compile time on real chips. What can be seen at lowering:
    the kernel must sit inside a manual (shard_map) region."""
    from pathway_tpu.parallel.sharding import make_mesh

    mesh = make_mesh(N_MESH, model_parallel=1)
    enc = _mesh_embedder(mesh, monkeypatch)
    ids = jax.ShapeDtypeStruct((4 * N_MESH, 160), jnp.int32, sharding=enc._data_sharding)
    mask = jax.ShapeDtypeStruct((4 * N_MESH, 160), jnp.bool_, sharding=enc._data_sharding)
    text = _lower_for_tpu(enc._fwd.__wrapped__, (enc.params, ids, mask))
    manual = text.find("sdy.manual_computation")
    assert manual >= 0, "mesh embedder forward has no shard_map region"
    assert manual < text.index("tpu_custom_call") < text.index("sdy.return", manual)


# ---- the real compiler, against a compile-only v5e topology ---------------


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no importable libtpu on this machine
        pytest.skip(f"no compile-only TPU topology: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


@pytest.mark.slow
@pytest.mark.parametrize("name", SINGLE_DEVICE_CASES)
def test_compiles_for_v5e(name, v5e):
    fn, args = SINGLE_DEVICE_CASES[name]()
    jax.jit(fn).lower(*_on(SingleDeviceSharding(v5e[0]), args)).compile()


@pytest.mark.slow
def test_sharded_topk_compiles_for_v5e(v5e):
    fn, args = _sharded_knn_case(Mesh(np.array(v5e), ("data",)))
    jax.jit(fn).lower(*args).compile()


@pytest.mark.slow
def test_mesh_embedder_compiles_for_v5e(v5e, monkeypatch):
    # the encoder's weights live on the CPU mesh it was built over; the
    # same forward is re-wrapped over the topology's mesh for the compile
    cpu_mesh = Mesh(np.array(jax.devices()[:N_MESH]).reshape(N_MESH, 1), ("data", "model"))
    enc = _mesh_embedder(cpu_mesh, monkeypatch)
    mesh = Mesh(np.array(v5e).reshape(N_MESH, 1), ("data", "model"))
    fwd = jax.shard_map(
        enc.module.apply,
        mesh=mesh,
        in_specs=(P(), P("data"), P("data")),
        out_specs=P("data"),
        check_vma=False,
    )
    data = NamedSharding(mesh, P("data"))
    jax.jit(fwd).lower(
        _on(NamedSharding(mesh, P()), enc.params),
        jax.ShapeDtypeStruct((4 * N_MESH, 160), jnp.int32, sharding=data),
        jax.ShapeDtypeStruct((4 * N_MESH, 160), jnp.bool_, sharding=data),
    ).compile()


@pytest.mark.slow
def test_block_topk_leaves_the_scores_where_the_scan_wrote_them(v5e):
    """The serve cells' scan + selection, compiled for the v5e: the two
    stages read the 210 MB of scores in the matmul's own tiles. A form
    that makes XLA lay the matrix out anew shows as a second 210 MB of
    temporaries (and cost 0.5 ms a dispatch on the chip: PERF.md, PR 34)."""
    from pathway_tpu.ops import knn

    q, rows, dim, k = 16, 3_276_800, 384, 16
    assert knn._topk_route(rows, k) == "blocks"

    def scan_and_select(emb, matrix, valid):
        scores = jnp.where(valid[None, :], emb @ matrix.T, knn._NEG)
        return knn._select_topk(scores, k)

    args = (
        jax.ShapeDtypeStruct((q, dim), jnp.float32),
        jax.ShapeDtypeStruct((rows, dim), jnp.float32),
        jax.ShapeDtypeStruct((rows,), jnp.bool_),
    )
    compiled = jax.jit(scan_and_select).lower(*_on(SingleDeviceSharding(v5e[0]), args)).compile()
    scores_bytes = q * rows * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * scores_bytes
