"""Fused pallas KNN top-k kernel vs the unfused XLA reference.

Every call here asks for interpret mode by argument (the CPU has no
Mosaic); the kernel's run on a real TPU is ``chip_smoke.py``'s."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.ops.pallas_knn import NEG, knn_topk


def _ref(q, d, k, bias=None, factor=1.0):
    s = factor * (q @ d.T)
    if bias is not None:
        s = s + bias[None, :]
    return jax.lax.top_k(jnp.asarray(s), k)


def test_dot_topk_matches_xla():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(13, 32)).astype(np.float32)
    d = rng.normal(size=(700, 32)).astype(np.float32)
    vals, idx = knn_topk(q, d, k=5, block_q=8, block_n=256, interpret=True)
    rv, ri = _ref(jnp.asarray(q), jnp.asarray(d), 5)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))


def test_bias_masks_invalid_slots():
    rng = np.random.default_rng(1)
    q = rng.normal(size=(4, 16)).astype(np.float32)
    d = rng.normal(size=(100, 16)).astype(np.float32)
    valid = np.ones(100, bool)
    valid[::3] = False  # a third of the slots are dead
    bias = np.where(valid, 0.0, NEG).astype(np.float32)
    vals, idx = knn_topk(q, d, k=8, bias=bias, block_q=8, block_n=64, interpret=True)
    assert not set(np.asarray(idx).ravel().tolist()) & set(np.nonzero(~valid)[0].tolist())


def test_l2_bias_and_factor():
    rng = np.random.default_rng(2)
    q = rng.normal(size=(6, 24)).astype(np.float32)
    d = rng.normal(size=(300, 24)).astype(np.float32)
    bias = -(d * d).sum(axis=1).astype(np.float32)
    vals, idx = knn_topk(q, d, k=4, bias=bias, factor=2.0, block_q=8, block_n=128, interpret=True)
    # nearest by L2 == argmax of 2q.d - |d|^2
    full = 2.0 * (q @ d.T) - (d * d).sum(axis=1)[None, :]
    ri = np.argsort(-full, axis=1)[:, :4]
    np.testing.assert_array_equal(np.asarray(idx), ri)


def test_padding_never_surfaces():
    rng = np.random.default_rng(3)
    q = rng.normal(size=(3, 8)).astype(np.float32)
    d = -np.abs(rng.normal(size=(37, 8))).astype(np.float32)  # all-negative scores likely
    vals, idx = knn_topk(q, d, k=40, block_q=8, block_n=64, interpret=True)
    got = np.asarray(idx)
    assert got.max() < 37  # padded rows (zero vectors, score 0) excluded
    # only 37 real docs: the tail of k=40 is sentinel
    assert (np.asarray(vals)[:, 37:] <= NEG / 2).all()


def test_device_index_parity_with_pallas_formula():
    """DeviceKnnIndex result parity: the pallas path computes the same
    (key, score) lists as the unfused path (CPU uses unfused; this
    pins the shared formula via _pallas_topk in interpret mode)."""
    from pathway_tpu.ops import knn as knn_mod

    rng = np.random.default_rng(4)
    idx = knn_mod.DeviceKnnIndex(dim=16, metric="cos")
    for i in range(50):
        idx.add(f"k{i}", rng.normal(size=16).astype(np.float32))
    idx.remove("k7")
    q = rng.normal(size=(2, 16)).astype(np.float32)
    expected = idx.search_batch(q, 5)

    idx._sync()
    qn = q / np.linalg.norm(q, axis=1, keepdims=True)
    vals, ids = knn_mod._pallas_topk(
        "cos", idx._dev_matrix, idx._dev_valid, qn, 8, interpret=True
    )
    got = []
    for row_v, row_i in zip(np.asarray(vals), np.asarray(ids)):
        out = []
        for s, slot in zip(row_v, row_i):
            if s <= NEG / 2 or idx._keys[slot] is None:
                continue
            out.append((idx._keys[slot], float(s)))
            if len(out) == 5:
                break
        got.append(out)
    for e_row, g_row in zip(expected, got):
        assert [k for k, _ in e_row] == [k for k, _ in g_row]
        np.testing.assert_allclose(
            [s for _, s in e_row], [s for _, s in g_row], rtol=1e-5
        )


def test_large_k_fori_merge_matches_xla():
    """k > 64 takes the fori_loop extraction merge (flat compile time)."""
    rng = np.random.default_rng(5)
    q = rng.normal(size=(5, 16)).astype(np.float32)
    d = rng.normal(size=(900, 16)).astype(np.float32)
    vals, idx = knn_topk(q, d, k=128, block_q=8, block_n=256, interpret=True)
    rv, ri = _ref(jnp.asarray(q), jnp.asarray(d), 128)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))


def test_sharded_kernel_cross_device_merge():
    """Shard-local kernels + ICI candidate merge == global top-k
    (virtual 8-device CPU mesh, kernel in interpret mode)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pathway_tpu.ops.pallas_knn import knn_topk_sharded

    devices = np.array(jax.devices()[:8])
    mesh = Mesh(devices, ("data",))
    rng = np.random.default_rng(6)
    q = rng.normal(size=(7, 32)).astype(np.float32)
    d = rng.normal(size=(1024, 32)).astype(np.float32)
    valid = np.ones(1024, bool)
    valid[5] = valid[700] = False
    bias = np.where(valid, 0.0, NEG).astype(np.float32)
    dd = jax.device_put(d, NamedSharding(mesh, P("data", None)))
    bb = jax.device_put(bias, NamedSharding(mesh, P("data")))
    vals, idx = knn_topk_sharded(
        jnp.asarray(q), dd, bb, k=9, mesh=mesh, block_q=8, block_n=64,
        interpret=True,
    )
    rv, ri = _ref(jnp.asarray(q), jnp.asarray(d), 9, bias=jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))


def test_device_index_sharded_pallas_parity(monkeypatch):
    """DeviceKnnIndex on a mesh with the pallas path forced: results
    match the unsharded unfused reference."""
    from jax.sharding import Mesh

    from pathway_tpu.ops import knn as knn_mod

    rng = np.random.default_rng(7)
    vecs = [rng.normal(size=24).astype(np.float32) for _ in range(200)]
    q = rng.normal(size=(3, 24)).astype(np.float32)

    ref_idx = knn_mod.DeviceKnnIndex(dim=24, metric="l2")
    for i, v in enumerate(vecs):
        ref_idx.add(f"k{i}", v)
    ref_idx.remove("k11")
    expected = ref_idx.search_batch(q, 6)

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    import functools

    monkeypatch.setattr(knn_mod, "_pallas_eligible", lambda metric, k, mesh: True)
    monkeypatch.setattr(
        knn_mod,
        "_pallas_topk",
        functools.partial(knn_mod._pallas_topk, interpret=True),
    )
    sh_idx = knn_mod.DeviceKnnIndex(dim=24, metric="l2", mesh=mesh)
    for i, v in enumerate(vecs):
        sh_idx.add(f"k{i}", v)
    sh_idx.remove("k11")
    got = sh_idx.search_batch(q, 6)
    for e_row, g_row in zip(expected, got):
        assert [k for k, _ in e_row] == [k for k, _ in g_row]
        np.testing.assert_allclose(
            [s for _, s in e_row], [s for _, s in g_row], rtol=1e-4
        )
