"""What a write publishes comes from state the write already updated.

``DeviceKnnIndex._docs_shard`` is the per-shard count of set flags in
``_valid_host`` after every public call, on every kind of index, and the
index gauges hold the same numbers: so a publish reads the counts and
never the mask. The first test drives seeded random write sequences and
sums the mask itself after each call; the second puts a mask in place
whose reductions raise, and counts the publishes.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.ops.index_metrics import INDEX_METRICS
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.ops.tiered_knn import TierConfig, TieredKnnIndex
from pathway_tpu.parallel.mesh import resolve_mesh
from pathway_tpu.tenancy.packed import TenantPackedIndex
from pathway_tpu.tracing import TRACE_STORE, TRACING_METRICS, set_tracing_enabled, stage_totals

DIM = 8
TENANTS = ("a", "b", "c")


@pytest.fixture(autouse=True)
def _reset_planes():
    prev = set_tracing_enabled(False)
    yield
    set_tracing_enabled(prev)
    TRACE_STORE.reset()
    TRACING_METRICS.reset()
    INDEX_METRICS.reset()


def _assert_counts_are_the_mask(slab: DeviceKnnIndex) -> None:
    mask = slab._valid_host.reshape(slab.n_shards, slab.shard_capacity)
    live = [int(n) for n in mask.sum(axis=1)]
    assert slab._docs_shard == live
    assert all(type(n) is int for n in slab._docs_shard)
    assert INDEX_METRICS.indexes[slab.name]["docs_shard"] == live
    assert INDEX_METRICS.indexes[slab.name]["shard_capacity"] == slab.shard_capacity
    assert sum(live) == len(slab._slot_of)


class _Flat:
    """A flat or mesh index through one of its two bulk entry points."""

    def __init__(self, entry: str, mesh_n: int = 0, forced_fallback: bool = False):
        mesh = resolve_mesh(mesh_n) if mesh_n else None
        self.slab = DeviceKnnIndex(DIM, reserved_space=64, mesh=mesh)
        self.entry = entry
        if forced_fallback:
            # ``_grow`` keeps the arrays resident, so the host re-upload
            # branch of ``_add_batch_device`` is not reached by any call
            # sequence today; put the index in the state that branch is
            # written for, by hand, so its hand-back of slots is held to
            # the invariant as well
            grow = self.slab._grow

            def grow_then_drop_resident():
                grow()
                self.slab._refresh_host()
                self.slab._dev_matrix = None
                self.slab._full = True
                self.slab._pending.clear()

            self.slab._grow = grow_then_drop_resident

    def live(self) -> list:
        return list(self.slab._slot_of)

    def new_key(self, n: int):
        return n

    def add(self, keys, vecs) -> None:
        if self.entry == "device":
            pad = np.zeros((3, DIM), np.float32)  # producers pad to a bucket
            self.slab.add_batch_device(keys, jnp.asarray(np.concatenate([vecs, pad])), None)
        else:
            self.slab.add_batch_arrays(keys, vecs)

    def remove(self, key) -> None:
        self.slab.remove(key)

    def extra(self, rng) -> None:
        self.slab.search_batch(rng.standard_normal((2, DIM)).astype(np.float32), 3)  # syncs


class _Packed:
    def __init__(self):
        self.slab = TenantPackedIndex(DIM, reserved_space=64)

    def live(self) -> list:
        return list(self.slab._slot_of)

    def new_key(self, n: int):
        return (TENANTS[n % len(TENANTS)], n)

    def add(self, keys, vecs) -> None:
        # a batch of one tenant, as ``add_tenant_batch`` takes it; a
        # cold tenant is promoted on the way in
        tenant = keys[0][0]
        mine = [i for i, k in enumerate(keys) if k[0] == tenant]
        self.slab.add_tenant_batch(tenant, [keys[i][1] for i in mine], vecs[mine])

    def remove(self, key) -> None:
        if isinstance(key, tuple):
            self.slab.remove_tenant(*key)
        else:
            self.slab.remove(("a", key))

    def extra(self, rng) -> None:
        tenant = TENANTS[int(rng.integers(len(TENANTS)))]
        if tenant in self.slab._cold:
            self.slab._promote(tenant)
        elif tenant in self.slab._tid:
            self.slab._demote(tenant)


class _TieredHot:
    def __init__(self):
        tiers = TierConfig(hot_rows=64, n_clusters=8, n_probe=8, cold_dtype="f32")
        self.tier = TieredKnnIndex(dim=DIM, metric="cos", reserved_space=64, tiers=tiers)
        self.slab = self.tier.hot

    def live(self) -> list:
        return list(self.tier._cluster_of)

    def new_key(self, n: int):
        return n

    def add(self, keys, vecs) -> None:
        self.tier.add_batch_arrays(keys, vecs)

    def remove(self, key) -> None:
        self.tier.remove(key)

    def extra(self, rng) -> None:
        c = int(rng.integers(max(1, self.tier._n_centroids)))
        if self.tier._cold_keys[c]:
            self.tier._promote_cluster(c)
        else:
            self.tier.force_demote([c])


KINDS = {
    "flat-add_batch_arrays": lambda: _Flat("arrays"),
    "flat-add_batch_device": lambda: _Flat("device"),
    "flat-add_batch_device-growth_fallback": lambda: _Flat("device", forced_fallback=True),
    "mesh8-add_batch_arrays": lambda: _Flat("arrays", mesh_n=8),
    "mesh8-add_batch_device": lambda: _Flat("device", mesh_n=8),
    "tenant_packed": _Packed,
    "tiered_hot": _TieredHot,
}


@pytest.mark.parametrize("seed", [27, 2700001])
@pytest.mark.parametrize("kind", list(KINDS))
def test_docs_shard_is_the_mask_sum_after_every_call(kind, seed):
    """add / replace / remove / remove of a missing key / grow from 64
    rows, in a seeded random order; after every call the counts, the
    mask and the gauges agree shard by shard."""
    rng = np.random.default_rng(seed)
    drv = KINDS[kind]()
    slab, start_capacity = drv.slab, drv.slab.capacity
    next_key = 0
    for step in range(60):
        op = "add" if step == 0 else rng.choice(["add", "add", "replace", "remove", "missing", "extra"])
        live = drv.live()
        if op == "add":
            n = int(rng.integers(1, 24))
            keys = [drv.new_key(next_key + i) for i in range(n)]
            next_key += n
            drv.add(keys, rng.standard_normal((n, DIM)).astype(np.float32))
        elif op == "replace" and live:
            picked = rng.choice(len(live), size=min(len(live), int(rng.integers(1, 9))), replace=False)
            keys = [live[int(i)] for i in picked] + [drv.new_key(next_key)]
            next_key += 1
            drv.add(keys, rng.standard_normal((len(keys), DIM)).astype(np.float32))
        elif op == "remove" and live:
            drv.remove(live[int(rng.integers(len(live)))])
        elif op == "missing":
            drv.remove(drv.new_key(10**9 + step))
        else:
            drv.extra(rng)
        _assert_counts_are_the_mask(slab)
    if kind != "tiered_hot":  # the hot tier is sized once and never grows
        assert slab.capacity > start_capacity
    if slab.n_shards > 1:
        assert min(slab._docs_shard) < max(slab._docs_shard)  # shard by shard, not in sum only


class _NeverReduced(np.ndarray):
    """A validity mask that can be indexed and written, and that fails
    any call which reduces, copies or otherwise reads it whole."""

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        raise AssertionError(f"the write path ran {ufunc.__name__}.{method} over the validity mask")

    def __array_function__(self, func, types, args, kwargs):
        raise AssertionError(f"the write path ran numpy.{func.__name__} over the validity mask")


@pytest.mark.parametrize("replaced", [1, 6])
@pytest.mark.parametrize("entry", ["add_batch_arrays", "add_batch_device"])
def test_the_write_path_never_reduces_the_slab_and_publishes_per_call(entry, replaced):
    idx = DeviceKnnIndex(DIM, reserved_space=256)
    rng = np.random.default_rng(5)

    def add(keys):
        vecs = rng.standard_normal((len(keys), DIM)).astype(np.float32)
        if entry == "add_batch_device":
            idx.add_batch_device(keys, jnp.asarray(vecs), None)
        else:
            idx.add_batch_arrays(keys, vecs)

    add(list(range(40)))
    idx.search_batch(rng.standard_normal((1, DIM)).astype(np.float32), 3)  # resident: writes go by scatter
    idx._valid_host = idx._valid_host.view(_NeverReduced)
    for whole_read in (idx._valid_host.sum, idx._valid_host.any, lambda: np.count_nonzero(idx._valid_host)):
        with pytest.raises(AssertionError, match="validity mask"):
            whole_read()

    set_tracing_enabled(True)

    def publishes(call) -> int:
        TRACING_METRICS.reset()
        call()
        return stage_totals().get("index_publish", {"calls": 0})["calls"]

    assert publishes(lambda: idx.remove(0)) == 1
    assert publishes(lambda: idx.remove(0)) == 0  # no row, nothing to tell
    assert publishes(lambda: add([100, 101, 102])) == 1
    # n keys added again: one publish for the rows that went, whatever
    # n, and the add's own
    keys = list(range(1, 1 + replaced)) + [200]
    assert publishes(lambda: add(keys)) == 2
    assert stage_totals()["index_replace"]["rows"] == replaced
    assert stage_totals()["index_remove"]["calls"] == replaced  # nested, each timed, none publishing

    mask = idx._valid_host.view(np.ndarray)
    assert idx._docs_shard == [int(mask.sum())] == [len(idx)]
    assert INDEX_METRICS.indexes[idx.name]["docs_shard"] == idx._docs_shard
