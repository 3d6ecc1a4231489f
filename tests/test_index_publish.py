"""What a write publishes comes from state the write already updated.

``DeviceKnnIndex._docs_shard`` is the per-shard count of set flags in
``_valid_host`` after every public call, on every kind of index, and the
index gauges hold the same numbers: so a publish reads the counts and
never the mask. The first test drives seeded random write sequences and
sums the mask itself after each call; the second puts a mask in place
whose reductions raise, and counts the publishes.

``remove`` publishes nothing itself: the index owes the planes a publish
and pays it once — at its next publish, at its next ``_sync``, or when a
plane is read — so the gauges are read through ``snapshot()``, as a
scrape reads them. The tests after those two hold that contract.
"""

from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.freshness.plane import FRESHNESS
from pathway_tpu.internals.ledger import LEDGER
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
    INDEX_METRICS.reset()  # an index an earlier test left owing pays here, uncounted
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
    gauges = INDEX_METRICS.snapshot()["indexes"][slab.name]
    assert gauges["docs_shard"] == live
    assert gauges["shard_capacity"] == slab.shard_capacity
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

    def search(self, rng) -> None:
        self.slab.search_batch(rng.standard_normal((2, DIM)).astype(np.float32), 3)  # syncs

    extra = search


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

    def search(self, rng) -> None:
        self.tier.search_batch(rng.standard_normal((2, DIM)).astype(np.float32), 3)

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

    assert publishes(lambda: idx.remove(0)) == 0 and idx._owed == {0}  # owed, not told
    assert publishes(lambda: idx.remove(0)) == 0 and idx._owed == {0}  # no row, nothing more to tell
    assert publishes(lambda: add([100, 101, 102])) == 1 and not idx._owed  # the add's pays the remove's
    # n keys added again: one publish for the rows that went, whatever
    # n, and the add's own
    keys = list(range(1, 1 + replaced)) + [200]
    assert publishes(lambda: add(keys)) == 2
    assert stage_totals()["index_replace"]["rows"] == replaced
    assert stage_totals()["index_remove"]["calls"] == replaced  # nested, each timed, none publishing

    mask = idx._valid_host.view(np.ndarray)
    assert idx._docs_shard == [int(mask.sum())] == [len(idx)]
    assert INDEX_METRICS.snapshot()["indexes"][idx.name]["docs_shard"] == idx._docs_shard


# -- a run of removes publishes once ---------------------------------------

OWING_KINDS = {
    "flat": lambda: _Flat("arrays"),
    "mesh8": lambda: _Flat("device", mesh_n=8),
    "tiered_hot": _TieredHot,
}


@pytest.fixture()
def freshness():
    LEDGER.reset()
    FRESHNESS.reset()
    FRESHNESS.set_enabled(True)
    yield FRESHNESS
    FRESHNESS.set_enabled(None)
    FRESHNESS.reset()
    LEDGER.reset()


def _publish_calls() -> int:
    return stage_totals().get("index_publish", {"calls": 0})["calls"]


def _resident_with_removes(kind: str, n_removed: int = 9):
    """40 rows resident on the device, the planes told, then
    ``n_removed`` of them removed with the publishes counted from the
    first remove; the shards those rows lay in and the time before the
    first of them come back too."""
    rng = np.random.default_rng(32)
    drv = OWING_KINDS[kind]()
    drv.add(list(range(40)), rng.standard_normal((40, DIM)).astype(np.float32))
    drv.search(rng)
    slab = drv.slab
    assert slab._dev_matrix is not None and not slab._owed
    time.sleep(0.002)  # the watermarks so far lie before ``before``
    before = time.time()
    set_tracing_enabled(True)
    TRACING_METRICS.reset()
    touched = set()
    for key in range(n_removed):
        touched.add(slab._slot_of[key] // slab.shard_capacity)
        drv.remove(key)
        assert slab._docs_shard == [
            int(n) for n in slab._valid_host.reshape(slab.n_shards, -1).sum(axis=1)
        ]
    assert _publish_calls() == 0 and slab._owed == touched
    assert stage_totals().get("index_remove", {"rows": 0})["rows"] == n_removed
    return drv, rng, touched, before


def _assert_planes_are_exact(slab: DeviceKnnIndex, touched, before) -> None:
    _assert_counts_are_the_mask(slab)
    alloc = sum(int(a.nbytes) for a in (slab._dev_matrix, slab._dev_valid, slab._dev_bias))
    hot = LEDGER.accounts()["index.hot"]
    assert (hot["bytes"], hot["used_bytes"]) == (alloc, int(alloc * len(slab._slot_of) / slab.capacity))
    for shard in range(slab.n_shards):
        wm = FRESHNESS.visible_wm(slab, [shard])
        if shard in touched:
            assert wm[1] >= before
        else:
            assert wm is None or wm[1] < before  # nothing was removed there


@pytest.mark.parametrize("then", ["add", "search"])
@pytest.mark.parametrize("kind", list(OWING_KINDS))
def test_removes_then_a_call_on_the_index_publish_once(kind, then, freshness):
    drv, rng, touched, before = _resident_with_removes(kind)
    if then == "add":
        drv.add([100, 101], rng.standard_normal((2, DIM)).astype(np.float32))
        touched |= {drv.slab._slot_of[k] // drv.slab.shard_capacity for k in (100, 101)}
    else:
        drv.search(rng)
    assert _publish_calls() == 1 and not drv.slab._owed
    _assert_planes_are_exact(drv.slab, touched, before)
    assert _publish_calls() == 1  # the reads found nothing owed


@pytest.mark.parametrize(
    "read",
    [
        lambda slab: INDEX_METRICS.snapshot(),
        lambda slab: INDEX_METRICS.active(),
        lambda slab: LEDGER.accounts(),
        lambda slab: LEDGER.snapshot(),
        lambda slab: LEDGER.total_bytes(),
        lambda slab: FRESHNESS.visible_wm(slab),
        lambda slab: FRESHNESS.answer_bound(slab),
        lambda slab: FRESHNESS.snapshot(),
    ],
    ids=[
        "index_snapshot", "index_active", "ledger_accounts", "ledger_snapshot",
        "ledger_total_bytes", "visible_wm", "answer_bound", "freshness_snapshot",
    ],
)
@pytest.mark.parametrize("kind", list(OWING_KINDS))
def test_removes_then_nothing_are_exact_on_the_first_read_of_any_plane(kind, read, freshness):
    drv, rng, touched, before = _resident_with_removes(kind)
    read(drv.slab)
    assert _publish_calls() == 1 and not drv.slab._owed
    _assert_planes_are_exact(drv.slab, touched, before)
    read(drv.slab)
    assert _publish_calls() == 1  # no second publish on the next


@pytest.mark.parametrize("kind", list(OWING_KINDS))
def test_removes_inside_an_epoch_move_its_watermark_when_it_commits(kind, freshness):
    """The engine's turn: removes while an epoch executes, then the
    commit. The owed shards are the epoch's, and read its drain cutoff."""
    rng = np.random.default_rng(33)
    drv = OWING_KINDS[kind]()
    drv.add(list(range(40)), rng.standard_normal((40, DIM)).astype(np.float32))
    slab = drv.slab
    stale = slab._slot_of[0] // slab.shard_capacity
    drv.remove(0)  # before the epoch: not the epoch's
    FRESHNESS.begin_epoch(7)
    FRESHNESS.epoch_staged(7)
    FRESHNESS.epoch_exec(7)
    assert not slab._owed and FRESHNESS.visible_wm(slab, [stale])[0] == -1
    touched = set()
    for key in range(1, 6):
        touched.add(slab._slot_of[key] // slab.shard_capacity)
        drv.remove(key)
    assert slab._owed == touched
    FRESHNESS.epoch_committed(7)
    assert not slab._owed
    for shard in touched:
        assert FRESHNESS.visible_wm(slab, [shard])[0] == 7


@pytest.mark.parametrize("kind", list(OWING_KINDS))
def test_a_remove_of_a_key_with_no_row_owes_nothing(kind, freshness):
    drv, rng, touched, before = _resident_with_removes(kind, n_removed=0)
    wm = FRESHNESS.visible_wm(drv.slab)
    drv.remove(10**9)
    drv.remove(10**9 + 1)
    assert not drv.slab._owed and _publish_calls() == 0
    _assert_planes_are_exact(drv.slab, touched, before)
    assert _publish_calls() == 0 and FRESHNESS.visible_wm(drv.slab) == wm


def test_reset_settles_what_is_owed_and_forgets_it(freshness):
    drv, rng, touched, before = _resident_with_removes("flat")
    INDEX_METRICS.reset()
    assert not drv.slab._owed and INDEX_METRICS.snapshot()["indexes"] == {}
    assert _publish_calls() == 1  # the other planes were told before the gauges went
    assert FRESHNESS.visible_wm(drv.slab)[1] >= before
    drv.remove(20)
    _assert_planes_are_exact(drv.slab, touched, before)  # and a later remove owes again


def test_an_index_that_dies_owing_takes_its_debt_with_it():
    from pathway_tpu.ops import index_metrics

    drv, *_ = _resident_with_removes("flat")
    name = drv.slab.name
    told = INDEX_METRICS.snapshot()["indexes"][name]["docs"]
    drv.remove(30)
    assert drv.slab in index_metrics._OWING
    del drv
    assert len(index_metrics._OWING) == 0
    assert INDEX_METRICS.snapshot()["indexes"][name]["docs"] == told


@pytest.mark.parametrize("kind", ["flat", "mesh8"])
def test_a_reader_draining_while_a_writer_removes_loses_no_shard(kind, freshness):
    """A scrape thread reads every plane in a loop while the writer
    removes key by key and adds now and then: both finish, and what the
    planes hold at the end is what the index holds."""
    rng = np.random.default_rng(34)
    drv = OWING_KINDS[kind]()
    n = 600
    drv.add(list(range(n)), rng.standard_normal((n, DIM)).astype(np.float32))
    drv.search(rng)
    slab = drv.slab
    time.sleep(0.002)
    before = time.time()
    stop = threading.Event()
    failures: list = []

    def scrape():
        try:
            while not stop.is_set():
                INDEX_METRICS.snapshot()
                LEDGER.accounts()
                FRESHNESS.visible_wm(slab)
                FRESHNESS.snapshot()
        except Exception as exc:  # noqa: BLE001 - the test reports it
            failures.append(exc)

    reader = threading.Thread(target=scrape, daemon=True)
    reader.start()
    touched = set()
    try:
        for key in range(n - 40):
            touched.add(slab._slot_of[key] // slab.shard_capacity)
            drv.remove(key)
            if key % 97 == 0:
                fresh = [n + key, n + key + 1]
                drv.add(fresh, rng.standard_normal((2, DIM)).astype(np.float32))
                touched |= {slab._slot_of[k] // slab.shard_capacity for k in fresh}
    finally:
        stop.set()
        reader.join(timeout=60)
    assert not reader.is_alive() and not failures
    _assert_planes_are_exact(slab, touched, before)
    assert not slab._owed
