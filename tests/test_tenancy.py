"""Multi-tenant serving plane: spec parsing, tenant-packed device
slabs (segments, growth, mask bit-identity, ledger reconciliation,
cold demotion), per-tenant admission gates, weighted deficit
round-robin batching, and the activity-gated per-tenant metric
surfaces (including the PATHWAY_METRIC_TENANTS cardinality fold)."""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from pathway_tpu.freshness.plane import FRESHNESS
from pathway_tpu.internals.ledger import LEDGER, hot_row_bytes, parse_bytes
from pathway_tpu.ops.index_metrics import INDEX_METRICS
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.tenancy import TenancyConfig, TenantQuotas
from pathway_tpu.tenancy.config import (
    TENANT_HEADER,
    active_tenancy,
    parse_quota_spec,
    parse_tenancy_spec,
    set_active_tenancy,
    use_tenancy,
)
from pathway_tpu.tenancy.metrics import OTHER, TENANCY_METRICS, metric_tenants
from pathway_tpu.tenancy.packed import (
    _MIN_EXTENT,
    TenantOverBudget,
    TenantPackedIndex,
    reset_slabs,
    shared_slab,
)


@pytest.fixture(autouse=True)
def _clean_registries(monkeypatch):
    monkeypatch.delenv("PATHWAY_TENANCY", raising=False)
    monkeypatch.delenv("PATHWAY_METRIC_TENANTS", raising=False)
    set_active_tenancy(None)
    TENANCY_METRICS.reset()
    LEDGER.reset()
    INDEX_METRICS.reset()
    reset_slabs()
    yield
    set_active_tenancy(None)
    TENANCY_METRICS.reset()
    LEDGER.reset()
    INDEX_METRICS.reset()
    reset_slabs()


def _rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# config / spec parsing


def test_parse_tenancy_spec_forms():
    assert parse_tenancy_spec(None) is None
    assert parse_tenancy_spec(False) is None
    assert parse_tenancy_spec("off") is None
    assert parse_tenancy_spec("") is None
    on = parse_tenancy_spec(True)
    assert isinstance(on, TenancyConfig) and on.quotas == {} and on.default is None
    assert isinstance(parse_tenancy_spec("on"), TenancyConfig)
    cfg = parse_tenancy_spec(
        "qps=50,burst=4,inflight=2,hbm=64M,weight=2,floor_k=3,"
        "demote_every=16,decay=0.25,demote_below=0.1"
    )
    assert cfg.default == TenantQuotas(
        qps=50.0,
        burst=4,
        max_inflight=2,
        hbm_bytes=parse_bytes("64M"),
        weight=2.0,
        min_top_k=3,
    )
    assert cfg.demote_every == 16
    assert cfg.decay == 0.25 and cfg.demote_below == 0.1
    # dict form: named quotas + default + cfg knobs
    cfg = parse_tenancy_spec(
        {
            "quotas": {"acme": {"qps": 5, "rate": 5}, "big": "weight=3"},
            "default": {"inflight": 4},
            "demote_every": 8,
        }
    )
    assert cfg.quotas["acme"].qps == 5.0
    assert cfg.quotas["big"].weight == 3.0
    assert cfg.default.max_inflight == 4
    assert cfg.demote_every == 8
    # flat dict knobs become the default quota
    cfg = parse_tenancy_spec({"qps": 9})
    assert cfg.default.qps == 9.0
    # passthrough
    assert parse_tenancy_spec(cfg) is cfg
    q = TenantQuotas(weight=2.0)
    assert parse_quota_spec(q) is q
    assert parse_quota_spec(None) is None


def test_parse_tenancy_spec_rejects_malformed():
    with pytest.raises(ValueError):
        parse_tenancy_spec("zps=1")
    with pytest.raises(ValueError):
        parse_tenancy_spec("qps")  # no '='
    with pytest.raises(ValueError):
        parse_tenancy_spec({"default": {"qps": 1}, "qps": 2})  # both forms
    with pytest.raises(ValueError):
        parse_tenancy_spec(3.5)
    with pytest.raises(ValueError):
        parse_quota_spec({"nope": 1})
    with pytest.raises(ValueError):
        parse_quota_spec("inflight=many")


def test_quota_validation():
    for bad in (
        dict(qps=0.0),
        dict(qps=-1.0),
        dict(burst=0),
        dict(max_inflight=0),
        dict(hbm_bytes=0),
        dict(weight=0.0),
        dict(min_top_k=0),
    ):
        with pytest.raises(ValueError):
            TenantQuotas(**bad)
    with pytest.raises(ValueError):
        TenancyConfig(demote_every=-1)
    with pytest.raises(ValueError):
        TenancyConfig(decay=0.0)
    with pytest.raises(ValueError):
        TenancyConfig(decay=1.5)


def test_quota_for_falls_back_to_default():
    named = TenantQuotas(qps=1.0)
    dflt = TenantQuotas(weight=2.0)
    cfg = TenancyConfig(quotas={"acme": named}, default=dflt)
    assert cfg.quota_for("acme") is named
    assert cfg.quota_for("anyone") is dflt
    assert TenancyConfig().quota_for("anyone") is None
    assert TENANT_HEADER == "X-Pathway-Tenant"


def test_active_tenancy_precedence(monkeypatch):
    assert active_tenancy() is None
    monkeypatch.setenv("PATHWAY_TENANCY", "qps=7,weight=2")
    env_cfg = active_tenancy()
    assert env_cfg is not None and env_cfg.default.qps == 7.0
    # the run-scoped config wins over the env var
    run_cfg = TenancyConfig(default=TenantQuotas(qps=3.0))
    set_active_tenancy(run_cfg)
    assert active_tenancy() is run_cfg
    set_active_tenancy(None)
    assert active_tenancy().default.qps == 7.0
    # malformed env spec reads as "no tenancy", not a crash
    monkeypatch.setenv("PATHWAY_TENANCY", "zps=1")
    assert active_tenancy() is None


def test_use_tenancy_context_manager():
    with use_tenancy("inflight=3"):
        assert active_tenancy().default.max_inflight == 3
        with use_tenancy(None):
            assert active_tenancy() is None
        assert active_tenancy().default.max_inflight == 3
    assert active_tenancy() is None


# ---------------------------------------------------------------------------
# tenant-packed device slab


def test_packed_segments_grant_min_extent_and_count_live_docs():
    idx = TenantPackedIndex(8, reserved_space=64)
    rng = _rng(1)
    idx.add_tenant_batch("a", [0, 1, 2], rng.standard_normal((3, 8)))
    # the grant is the 8-row floor extent, but only live rows count
    assert idx._tenant_rows["a"] == _MIN_EXTENT
    assert idx.tenant_docs("a") == 3
    assert idx._live_docs_shard() == [3]
    (start, size), = idx._segments["a"]
    assert size == _MIN_EXTENT
    # only occupied slots carry the tenant id; granted-but-free rows
    # stay -1 (masked like empty rows)
    extent = [int(t) for t in idx._tenant_host[start : start + size]]
    assert extent.count(idx._tid["a"]) == 3
    assert extent.count(-1) == _MIN_EXTENT - 3


def test_packed_remove_returns_slot_to_tenant_segment():
    idx = TenantPackedIndex(8, reserved_space=64)
    rng = _rng(2)
    idx.add_tenant_batch("a", [0, 1, 2], rng.standard_normal((3, 8)))
    idx.remove_tenant("a", 1)
    assert idx.tenant_docs("a") == 2
    assert idx._live_docs_shard() == [2]
    rows_before = idx._tenant_rows["a"]
    idx.add_tenant("a", 9, rng.standard_normal(8))
    # the freed slot is reused: no new extent granted
    assert idx._tenant_rows["a"] == rows_before
    assert idx.tenant_docs("a") == 3


def test_packed_growth_remaps_segments_and_keeps_results():
    idx = TenantPackedIndex(8, reserved_space=16)
    rng = _rng(3)
    vecs = {t: rng.standard_normal((20, 8)).astype(np.float32) for t in ("a", "b")}
    for i in range(20):
        for t in ("a", "b"):
            idx.add_tenant(t, i, vecs[t][i])
    assert idx.capacity >= 40
    for t in ("a", "b"):
        assert idx.tenant_docs(t) == 20
        # segments stay in-bounds and disjoint after the remap
        rows = []
        for start, size in idx._segments[t]:
            assert 0 <= start and start + size <= idx.capacity
            rows.extend(range(start, start + size))
        assert len(rows) == len(set(rows))
        hits = idx.search_tenant_batch(t, vecs[t][:4], 1)
        assert [row[0][0] for row in hits] == [0, 1, 2, 3]


def test_masked_search_bit_identical_to_private_index():
    dim, res, k = 16, 128, 5
    rng = _rng(4)
    slab = TenantPackedIndex(dim, reserved_space=res)
    solo = DeviceKnnIndex(dim, reserved_space=res)
    corpora = {t: rng.standard_normal((20, dim)).astype(np.float32) for t in ("a", "b", "c")}
    for i in range(20):  # interleaved adds: tenants' rows mix in the slab
        for t in ("a", "b", "c"):
            idx_key = f"{t}{i}"
            slab.add_tenant(t, idx_key, corpora[t][i])
    solo.add_batch_arrays([f"b{i}" for i in range(20)], corpora["b"])
    q = rng.standard_normal((6, dim)).astype(np.float32)
    got = slab.search_tenant_batch("b", q, k)
    want = solo.search_batch(q, k)
    assert got == want  # keys AND scores, bit-for-bit


def test_search_never_crosses_tenants():
    idx = TenantPackedIndex(8, reserved_space=64)
    rng = _rng(5)
    for t in ("a", "b"):
        idx.add_tenant_batch(
            t, [f"{t}{i}" for i in range(10)], rng.standard_normal((10, 8))
        )
    rows = idx.search_tenant_batch("a", rng.standard_normal((4, 8)), 10)
    keys = {key for row in rows for key, _ in row}
    assert keys and all(k.startswith("a") for k in keys)
    # an empty tenant gets empty rows, not other tenants' docs
    assert idx.search_tenant_batch("ghost", rng.standard_normal((2, 8)), 3) == [[], []]


def test_hbm_quota_enforced_at_grant_time():
    budget = 10 * hot_row_bytes(8)  # 10 rows
    cfg = TenancyConfig(quotas={"small": TenantQuotas(hbm_bytes=budget)})
    idx = TenantPackedIndex(8, reserved_space=64, config=cfg)
    rng = _rng(6)
    idx.add_tenant_batch("small", list(range(8)), rng.standard_normal((8, 8)))
    with pytest.raises(TenantOverBudget) as exc:
        idx.add_tenant_batch("small", [100, 101, 102], rng.standard_normal((3, 8)))
    assert exc.value.tenant == "small"
    assert exc.value.budget_bytes == budget
    assert exc.value.need_bytes > budget
    # unquota'd tenants are unaffected
    idx.add_tenant_batch("big", list(range(20)), rng.standard_normal((20, 8)))
    assert idx.tenant_docs("big") == 20


def test_cold_demotion_and_promotion_cycle():
    # idle's single warm-up hit decays 1.0 -> 0.5 on the first sweep,
    # so a 0.6 threshold demotes it there
    cfg = TenancyConfig(demote_every=2, demote_below=0.6)
    idx = TenantPackedIndex(8, reserved_space=64, config=cfg)
    rng = _rng(7)
    vecs = {t: rng.standard_normal((6, 8)).astype(np.float32) for t in ("hot", "idle")}
    for t in ("hot", "idle"):
        idx.add_tenant_batch(t, list(range(6)), vecs[t])
    q = rng.standard_normal((1, 8)).astype(np.float32)
    want_idle = idx.search_tenant_batch("idle", q, 3)
    # two "hot" searches trigger the sweep; "idle" never hit -> demoted
    idx.search_tenant_batch("hot", q, 3)
    idx.search_tenant_batch("hot", q, 3)
    assert idx.tenant_is_cold("idle")
    assert idx.tenant_docs("idle") == 6
    assert idx._tenant_rows["idle"] == 0  # extents freed for reuse
    assert idx._free_extents
    # cold host scan returns the same keys in the same order
    cold = idx.search_tenant_batch("idle", q, 3)
    assert [k for k, _ in cold[0]] == [k for k, _ in want_idle[0]]
    # a second hit while cold promotes the tenant back into the slab
    idx.search_tenant_batch("idle", q, 3)
    assert not idx.tenant_is_cold("idle")
    back = idx.search_tenant_batch("idle", q, 3)
    assert [k for k, _ in back[0]] == [k for k, _ in want_idle[0]]


def test_packed_keys_must_be_tenant_namespaced():
    idx = TenantPackedIndex(8, reserved_space=64)
    with pytest.raises(TypeError):
        idx.add_batch_arrays(["bare-key"], np.zeros((1, 8), np.float32))


def test_ledger_reconciles_tenant_account_with_hot_under_churn():
    idx = TenantPackedIndex(16, reserved_space=64)
    rng = _rng(8)
    for t in ("a", "b", "c"):
        idx.add_tenant_batch(
            t, [f"{t}{i}" for i in range(12)], rng.standard_normal((12, 16))
        )
    q = rng.standard_normal((1, 16)).astype(np.float32)
    idx.search_tenant_batch("a", q, 3)  # materialize the device slab
    # churn: removals, a wholesale demotion, growth from new adds
    for i in range(6):
        idx.remove_tenant("a", f"a{i}")
    idx._demote("b")
    idx.add_tenant_batch(
        "c", [f"c{i}" for i in range(12, 40)], rng.standard_normal((28, 16))
    )
    idx.search_tenant_batch("c", q, 3)  # re-sync after growth
    idx._publish_metrics()
    acc = LEDGER.accounts()
    row_b = hot_row_bytes(idx.dim)
    # the per-tenant account (named owners + __unassigned__) sums
    # exactly to the slab's hot allocation
    assert acc["index.tenant"]["bytes"] == acc["index.hot"]["bytes"]
    assert acc["index.hot"]["bytes"] == idx.capacity * row_b
    named = sum(idx._tenant_rows.values()) * row_b
    rows = LEDGER._rows
    spare = rows[("index.tenant", f"{idx.name}/__unassigned__")][0]
    assert named + spare == acc["index.tenant"]["bytes"]
    # demoted tenant holds no slab bytes; its row dropped
    assert ("index.tenant", f"{idx.name}/b") not in rows
    # per-tenant registry mirrors the slab occupancy
    snap = TENANCY_METRICS.snapshot()["tenants"]
    assert snap["b"]["cold"] and snap["b"]["hbm_bytes"] == 0
    assert snap["a"]["docs"] == idx.tenant_docs("a") == 6
    assert snap["c"]["hbm_bytes"] == idx._tenant_rows["c"] * row_b


def test_shared_slab_registry_is_per_geometry():
    a = shared_slab(16, metric="cos")
    b = shared_slab(16, metric="cos", reserved_space=4096)
    c = shared_slab(16, metric="dot")
    assert a is b
    assert a is not c
    reset_slabs()
    assert shared_slab(16, metric="cos") is not a


def test_tenant_view_strips_namespacing():
    idx = TenantPackedIndex(8, reserved_space=64)
    rng = _rng(9)
    view = idx.view("acme")
    view.add("k0", rng.standard_normal(8))
    view.add_batch([(f"k{i}", rng.standard_normal(8), {"i": i}) for i in (1, 2)])
    assert len(view) == 3
    assert view.dim == 8 and view.metric == idx.metric
    row = view.search_one(rng.standard_normal(8), 3)
    assert {k for k, _ in row} == {"k0", "k1", "k2"}
    view.remove("k1")
    assert len(view) == 2 and idx.tenant_docs("acme") == 2


def test_stdlib_tenant_kwarg_routes_to_shared_slab():
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        BruteForceKnn,
        _TenantPayloadView,
    )

    ia = BruteForceKnn(None, dimensions=8, reserved_space=64, tenant="a")._index_factory()()
    ib = BruteForceKnn(None, dimensions=8, reserved_space=64, tenant="b")._index_factory()()
    assert isinstance(ia, _TenantPayloadView)
    assert ia._view.packed is ib._view.packed  # one slab, one compile
    rng = _rng(10)
    ia.add("x", rng.standard_normal(8))
    ib.add("y", rng.standard_normal(8))
    assert len(ia) == 1 and len(ib) == 1
    hits = ia.search_batch(rng.standard_normal((1, 8)), 5)
    assert [k for k, _ in hits[0]] == ["x"]
    spec = BruteForceKnn(None, dimensions=8, tenant="a")._index_spec()
    assert spec["tenant"] == "a"


# ---------------------------------------------------------------------------
# fair-share admission


def _controller(**cfg_kw):
    from pathway_tpu.serving.admission import AdmissionController, ServingConfig
    from pathway_tpu.serving.metrics import ServingMetrics

    cfg_kw.setdefault("max_queue", 100)
    return AdmissionController(ServingConfig(**cfg_kw), metrics=ServingMetrics())


def test_tenant_qps_bucket_sheds_typed_429():
    from pathway_tpu.serving.admission import RateLimited, TenantRateLimited
    from pathway_tpu.serving.deadline import Deadline

    with use_tenancy({"quotas": {"noisy": {"qps": 1000, "burst": 2}}}):
        ctl = _controller()
        for _ in range(2):
            ctl.admit(Deadline(60_000), tenant="noisy")
        with pytest.raises(TenantRateLimited) as exc:
            ctl.admit(Deadline(60_000), tenant="noisy")
        assert isinstance(exc.value, RateLimited)
        assert exc.value.status == 429
        assert exc.value.reason == "tenant_rate_limited"
        assert exc.value.tenant == "noisy"
        assert exc.value.retry_after_s >= 0.0
        # other tenants ride the default (unquota'd) path untouched
        ctl.admit(Deadline(60_000), tenant="quiet")
        snap = TENANCY_METRICS.snapshot()["tenants"]
        assert snap["noisy"]["shed"] == {"tenant_rate_limited": 1}
        assert snap["noisy"]["admitted"] == 2
        assert snap["quiet"]["admitted"] == 1


def test_tenant_inflight_cap_and_release():
    from pathway_tpu.serving.admission import TenantRateLimited
    from pathway_tpu.serving.deadline import Deadline

    with use_tenancy({"default": {"inflight": 2}}):
        ctl = _controller()
        t1 = ctl.admit(Deadline(60_000), tenant="acme")
        ctl.admit(Deadline(60_000), tenant="acme")
        with pytest.raises(TenantRateLimited):
            ctl.admit(Deadline(60_000), tenant="acme")
        assert TENANCY_METRICS.snapshot()["tenants"]["acme"]["inflight"] == 2
        ctl.release(t1)
        ctl.admit(Deadline(60_000), tenant="acme")  # slot freed
        assert TENANCY_METRICS.snapshot()["tenants"]["acme"]["inflight"] == 2


def test_untenanted_admission_ignores_tenancy_state():
    from pathway_tpu.serving.deadline import Deadline

    with use_tenancy({"default": {"qps": 0.001, "burst": 1, "inflight": 1}}):
        ctl = _controller()
        for _ in range(5):
            ctl.release(ctl.admit(Deadline(60_000)))
    assert not TENANCY_METRICS.active()


# ---------------------------------------------------------------------------
# weighted deficit round-robin batching


def _batcher(batch_max=8):
    from pathway_tpu.serving.admission import ServingConfig
    from pathway_tpu.serving.batching import AdaptiveBatcher
    from pathway_tpu.serving.metrics import ServingMetrics

    b = AdaptiveBatcher(
        lambda items: None,
        config=ServingConfig(batch_max=batch_max, batch_window_ms=0.0),
        metrics=ServingMetrics(),
    )
    # pin a sentinel worker so submit() never spawns the drain thread:
    # these tests drive _take_batch() directly for determinism
    b._thread = threading.current_thread()
    return b


def test_wdrr_drains_tenants_by_quota_weight():
    from pathway_tpu.serving.deadline import Deadline

    b = _batcher(batch_max=8)
    with use_tenancy({"quotas": {"heavy": {"weight": 3}, "light": {"weight": 1}}}):
        for i in range(12):
            b.submit(("heavy", i), Deadline(60_000), tenant="heavy")
        for i in range(12):
            b.submit(("light", i), Deadline(60_000), tenant="light")
        assert b.pending() == 24
        items, _, _, tenants = b._take_batch()
    assert len(items) == 8
    assert tenants.count("heavy") == 6  # 3:1 deficit credit
    assert tenants.count("light") == 2
    # each tenant's own items stay in deadline (submit) order
    assert [i for t, i in items if t == "heavy"] == list(range(6))
    assert [i for t, i in items if t == "light"] == [0, 1]
    assert b.pending() == 16


def test_wdrr_interleaves_legacy_heap_as_anonymous_tenant():
    from pathway_tpu.serving.deadline import Deadline

    b = _batcher(batch_max=8)
    for i in range(4):
        b.submit(("none", i), Deadline(60_000))
    for i in range(4):
        b.submit(("t", i), Deadline(60_000), tenant="t")
    items, _, _, tenants = b._take_batch()
    assert len(items) == 8
    assert tenants.count(None) == 4 and tenants.count("t") == 4


def test_untenanted_batcher_keeps_legacy_single_heap():
    from pathway_tpu.serving.deadline import Deadline

    b = _batcher(batch_max=4)
    for i in range(4):
        b.submit(i, Deadline(60_000))
    assert not b._tenant_heaps and not b._rr
    items, _, _, tenants = b._take_batch()
    assert items == [0, 1, 2, 3]
    assert tenants == [None, None, None, None]


def test_wdrr_drops_expired_without_charging_deficit():
    from pathway_tpu.serving.deadline import Deadline

    b = _batcher(batch_max=8)
    with use_tenancy(True):
        for i in range(3):
            b.submit(("dead", i), Deadline(-1.0), tenant="dead")
        b.submit(("live", 0), Deadline(60_000), tenant="live")
        items, _, _, tenants = b._take_batch()
    assert items == [("live", 0)] and tenants == ["live"]
    assert b.dropped_expired_total == 3


# ---------------------------------------------------------------------------
# metric surfaces: cardinality fold + activity gating (satellite 1)


def test_metric_tenants_knob(monkeypatch):
    assert metric_tenants() == 50
    monkeypatch.setenv("PATHWAY_METRIC_TENANTS", "3")
    assert metric_tenants() == 3
    monkeypatch.setenv("PATHWAY_METRIC_TENANTS", "garbage")
    assert metric_tenants() == 50
    monkeypatch.setenv("PATHWAY_METRIC_TENANTS", "0")
    assert metric_tenants() == 50


def test_snapshot_folds_overflow_tenants_into_other(monkeypatch):
    monkeypatch.setenv("PATHWAY_METRIC_TENANTS", "2")
    for i in range(4):
        TENANCY_METRICS.record_admit(f"t{i}")
    TENANCY_METRICS.record_shed("t2", "tenant_rate_limited")
    TENANCY_METRICS.record_shed("t3", "tenant_rate_limited")
    TENANCY_METRICS.add_chip_seconds("t3", 0.5)
    TENANCY_METRICS.set_index("t2", docs=7, hbm_bytes=100)
    snap = TENANCY_METRICS.snapshot()
    assert set(snap["tenants"]) == {"t0", "t1", OTHER}
    assert snap["tenant_count"] == 4 and snap["folded"] == 2
    other = snap["tenants"][OTHER]
    assert other["admitted"] == 2
    assert other["shed"] == {"tenant_rate_limited": 2}
    assert other["chip_seconds"] == 0.5
    assert other["docs"] == 7 and other["hbm_bytes"] == 100
    # first-seen tenants keep their named series (stable label sets)
    assert snap["tenants"]["t0"]["admitted"] == 1


def test_prometheus_renders_folded_other_series(monkeypatch):
    from pathway_tpu.internals.http_monitoring import MonitoringHttpServer

    monkeypatch.setenv("PATHWAY_METRIC_TENANTS", "2")
    for i in range(5):
        TENANCY_METRICS.record_admit(f"t{i}")
    TENANCY_METRICS.record_shed("t4", "tenant_rate_limited")
    text = "\n".join(MonitoringHttpServer._tenancy_lines())
    assert 'pathway_serving_tenant_admitted_total{tenant="t0"} 1' in text
    assert 'pathway_serving_tenant_admitted_total{tenant="other"} 3' in text
    assert 'tenant="t4"' not in text  # folded, never named
    assert 'pathway_serving_tenant_shed_total{tenant="other",reason="tenant_rate_limited"} 1' in text
    assert "pathway_tenant_count 5" in text
    assert "pathway_tenant_folded 3" in text


def test_tenancy_off_scrape_and_status_byte_identical():
    from pathway_tpu.internals.http_monitoring import MonitoringHttpServer
    from pathway_tpu.internals.monitoring import StatsMonitor

    mon = StatsMonitor()
    # the input/output latency gauges are wall-clock relative; pin them
    # so scrape-to-scrape equality tests the tenancy plane, not time
    mon.input_latency_ms = lambda now=None: 0
    mon.output_latency_ms = lambda now=None: 0
    srv = MonitoringHttpServer(mon, port=0)
    quiet_prom = srv._prometheus()
    quiet_status = srv._status()
    assert "pathway_tenant" not in quiet_prom
    assert "tenants" not in json.loads(quiet_status)
    TENANCY_METRICS.record_admit("acme")
    loud = srv._prometheus()
    assert "pathway_tenant_count" in loud
    assert json.loads(srv._status())["tenants"]["tenants"]["acme"]["admitted"] == 1
    # back to never-named: the scrape is byte-identical again
    TENANCY_METRICS.reset()
    assert srv._prometheus() == quiet_prom
    assert srv._status() == quiet_status


def test_doctor_verdict_carries_tenant_rows():
    from pathway_tpu.internals.ledger import HealthWatchdog, render_verdict

    wd = HealthWatchdog()
    assert wd.verdict()["tenants"] is None  # inactive: nothing rendered
    assert "tenants:" not in render_verdict(wd.verdict())
    TENANCY_METRICS.record_admit("acme")
    TENANCY_METRICS.set_index("acme", docs=3, hbm_bytes=2048)
    v = wd.verdict()
    assert v["tenants"]["tenants"]["acme"]["docs"] == 3
    text = render_verdict(v)
    assert "tenants: 1 active" in text
    assert "acme" in text


# ---------------------------------------------------------------------------
# live-row imbalance (satellite 2)


def test_imbalance_counts_live_rows_not_granted_extents():
    idx = TenantPackedIndex(8, reserved_space=64)
    rng = _rng(11)
    idx.add_tenant_batch("a", [0, 1, 2], rng.standard_normal((3, 8)))
    assert idx._tenant_rows["a"] == _MIN_EXTENT  # 8 rows reserved
    entry = INDEX_METRICS.snapshot()["indexes"][idx.name]
    assert entry["docs_shard"] == [3]  # live rows, not the 8-row grant
    idx.remove_tenant("a", 0)
    assert INDEX_METRICS.snapshot()["indexes"][idx.name]["docs_shard"] == [2]


def test_a_run_of_tenant_removes_publishes_once_and_moves_the_watermark():
    """``TenantPackedIndex.remove`` owes its publish as the flat index's
    does: no plane is told key by key, every plane is exact when read,
    and the freshness watermark moves, which the per-key publish of the
    gauges alone never did."""
    FRESHNESS.reset()
    FRESHNESS.set_enabled(True)
    try:
        idx = TenantPackedIndex(8, reserved_space=64)
        rng = _rng(32)
        idx.add_tenant_batch("a", list(range(6)), rng.standard_normal((6, 8)))
        idx.add_tenant_batch("b", [0, 1], rng.standard_normal((2, 8)))
        idx.search_tenant_batch("a", rng.standard_normal((1, 8)), 2)  # resident: the ledger has its rows
        time.sleep(0.002)
        before = time.time()
        told = []
        publish = idx._publish_metrics
        idx._publish_metrics = lambda: (told.append(1), publish())[1]
        for key in range(4):
            idx.remove_tenant("a", key)
        idx.remove_tenant("a", 99)  # no row, hot or cold
        assert not told and idx._owed == {0}
        assert FRESHNESS.visible_wm(idx)[1] >= before and len(told) == 1 and not idx._owed
        assert INDEX_METRICS.snapshot()["indexes"][idx.name]["docs_shard"] == [4]
        assert TENANCY_METRICS.snapshot()["tenants"]["a"]["docs"] == 2
        seg = LEDGER.accounts()["index.tenant"]
        assert seg["used_bytes"] == 4 * hot_row_bytes(8) and len(told) == 1
        idx.remove_tenant("b", 0)
        assert TENANCY_METRICS.snapshot()["tenants"]["b"]["docs"] == 1 and len(told) == 2
    finally:
        FRESHNESS.set_enabled(None)
        FRESHNESS.reset()


def test_live_docs_shard_matches_valid_mask_on_plain_index():
    idx = DeviceKnnIndex(8, reserved_space=32)
    rng = _rng(12)
    idx.add_batch_arrays(list(range(5)), rng.standard_normal((5, 8)))
    assert idx._live_docs_shard() == [5]
    idx.remove(3)
    assert idx._live_docs_shard() == [4]
    # the published counts against the mask itself, never against
    # the counter they are read from
    assert idx._live_docs_shard() == [int(idx._valid_host.sum())]
