"""Chip-time attribution plane: the device-seconds ledger
(internals/chip_ledger.py), the persistent metrics journal + perf
snapshot/diff (pathway_tpu/perf/), and their surfaces (/metrics,
/status, `pathway top`, watchdog rule, flight-recorder ride-along).

House rules under test: accounting is opt-in and byte-identical-off
(scrapes must not change a byte until the first booking), booked
device-seconds must reconcile with wall time, nested dispatches must
never double-count, and per-tenant sub-accounts must reconcile with
the DRR weights."""

from __future__ import annotations

import json
import os
import time

import pytest

from pathway_tpu.internals.chip_ledger import (
    CHIP_LEDGER,
    PLANE_ACCOUNTS,
    STRANDED_CAUSES,
    chip_ledger_enabled,
    chip_peak_tflops,
)


@pytest.fixture()
def _chip(monkeypatch):
    """Ledger on for the test body, pristine before and after."""
    monkeypatch.delenv("PATHWAY_CHIP_LEDGER", raising=False)
    CHIP_LEDGER.reset()
    CHIP_LEDGER.set_enabled(True)
    yield CHIP_LEDGER
    CHIP_LEDGER.set_enabled(None)
    CHIP_LEDGER.reset()


@pytest.fixture()
def _chip_off(monkeypatch):
    monkeypatch.delenv("PATHWAY_CHIP_LEDGER", raising=False)
    CHIP_LEDGER.reset()
    CHIP_LEDGER.set_enabled(None)
    yield CHIP_LEDGER
    CHIP_LEDGER.set_enabled(None)
    CHIP_LEDGER.reset()


# ---------------------------------------------------------------------------
# gating
# ---------------------------------------------------------------------------


def test_default_off_and_env_opt_in(monkeypatch):
    monkeypatch.delenv("PATHWAY_CHIP_LEDGER", raising=False)
    assert chip_ledger_enabled() is False
    for v in ("1", "true", "on", "yes"):
        monkeypatch.setenv("PATHWAY_CHIP_LEDGER", v)
        assert chip_ledger_enabled() is True
    monkeypatch.setenv("PATHWAY_CHIP_LEDGER", "0")
    assert chip_ledger_enabled() is False


def test_override_wins_over_env(monkeypatch, _chip_off):
    monkeypatch.setenv("PATHWAY_CHIP_LEDGER", "1")
    assert CHIP_LEDGER.on() is True
    CHIP_LEDGER.set_enabled(False)  # pw.run(chip_ledger=False)
    assert CHIP_LEDGER.on() is False
    CHIP_LEDGER.set_enabled(None)
    assert CHIP_LEDGER.on() is True


def test_off_booking_is_noop(_chip_off):
    CHIP_LEDGER.book("encode", 1.0)
    CHIP_LEDGER.book_tenant("a", 1.0)
    CHIP_LEDGER.note_stall("host_prep", 1.0)
    with CHIP_LEDGER.timed("rerank"):
        pass
    assert CHIP_LEDGER.active() is False
    snap = CHIP_LEDGER.snapshot()
    assert snap["accounts"] == {} and snap["busy_seconds"] == 0.0


def test_run_kwarg_sets_and_restores_override(monkeypatch):
    import pathway_tpu as pw

    monkeypatch.delenv("PATHWAY_CHIP_LEDGER", raising=False)
    CHIP_LEDGER.reset()
    t = pw.debug.table_from_markdown("""
        | x
      1 | 1
    """)
    pw.io.null.write(t.select(pw.this.x))
    result = pw.run(monitoring_level="none", chip_ledger=True)
    assert result is not None
    from pathway_tpu.internals.parse_graph import G

    assert G.run_context["chip_ledger"] is True
    assert CHIP_LEDGER.on() is False  # restored to the env default
    CHIP_LEDGER.reset()


# ---------------------------------------------------------------------------
# booking model: sums-to-wall, nested dedup, stranded causes
# ---------------------------------------------------------------------------


def test_accounts_sum_to_wall_within_tolerance(_chip):
    """A staged run whose every phase books must reconcile: busy equals
    the sum of accounts, and accounted_fraction >= 0.95 of the measured
    wall (the bench gate, asserted here without jax). Best-of-3 windows:
    the window is only ~70ms, so a single scheduler stall between the
    staged blocks on a loaded CI box must not fail the claim."""
    best = 0.0
    for _ in range(3):
        CHIP_LEDGER.reset()
        t0 = time.perf_counter()
        for account, dur in (
            ("encode", 0.03),
            ("index.search", 0.02),
            ("index.merge", 0.01),
            ("rerank", 0.01),
        ):
            with CHIP_LEDGER.timed(account):
                time.sleep(dur)
        wall = time.perf_counter() - t0
        snap = CHIP_LEDGER.snapshot(wall)
        # snapshot rounds each figure to 6 decimals, so the sum of
        # rounded account rows can drift a few microseconds from busy
        assert snap["busy_seconds"] == pytest.approx(
            sum(a["seconds"] for a in snap["accounts"].values()), abs=5e-6
        )
        assert snap["wall_seconds"] == pytest.approx(wall, abs=1e-6)
        shares = sum(a["share"] for a in snap["accounts"].values())
        assert shares == pytest.approx(1.0, abs=0.01)
        best = max(best, snap["accounted_fraction"])
        if best >= 0.95:
            break
    assert best >= 0.95, best


def test_nested_booking_never_double_counts(_chip):
    """wrap_jit books `compile` inside an encode timed window: the
    window must book its wall MINUS the nested seconds, so the two
    accounts sum to the window wall, not above it."""
    with CHIP_LEDGER.timed("encode"):
        time.sleep(0.02)
        CHIP_LEDGER.book("compile", 0.015)  # what wrap_jit does
        time.sleep(0.01)
    snap = CHIP_LEDGER.snapshot()
    enc = snap["accounts"]["encode"]["seconds"]
    comp = snap["accounts"]["compile"]["seconds"]
    assert comp == pytest.approx(0.015, abs=1e-9)
    # encode booked ~0.03 of sleep, never the full 0.045 window
    assert enc == pytest.approx(0.03, abs=0.02)
    assert enc + comp <= snap["wall_seconds"] + 1e-6


def test_account_render_order_is_plane_order(_chip):
    CHIP_LEDGER.book("compile", 0.01)
    CHIP_LEDGER.book("decode", 0.01)
    CHIP_LEDGER.book("encode", 0.01)
    CHIP_LEDGER.book("zz_custom", 0.01)
    names = list(CHIP_LEDGER.snapshot()["accounts"])
    assert names == ["encode", "decode", "compile", "zz_custom"]
    assert [a for a in names if a in PLANE_ACCOUNTS] == [
        a for a in PLANE_ACCOUNTS if a in names
    ]


def test_stranded_residual_attributed_to_causes(_chip):
    """busy=0.05 against wall=0.2: 0.15 stranded; explicit stall notes
    claim their share in STRANDED_CAUSES order, remainder is
    unattributed — and causes never claim more than the residual."""
    CHIP_LEDGER.book("encode", 0.05)
    CHIP_LEDGER.note_stall("host_prep", 0.04)
    CHIP_LEDGER.note_stall("barrier", 0.02)
    snap = CHIP_LEDGER.snapshot(0.2)
    assert snap["stranded_seconds"] == pytest.approx(0.15, abs=1e-6)
    causes = snap["stranded_causes"]
    assert causes["host_prep"] == pytest.approx(0.04, abs=1e-6)
    assert causes["barrier"] == pytest.approx(0.02, abs=1e-6)
    assert causes["unattributed"] == pytest.approx(0.09, abs=1e-6)
    assert sum(causes.values()) == pytest.approx(0.15, abs=1e-6)
    assert list(causes)[:2] == [
        c for c in STRANDED_CAUSES if c in ("host_prep", "barrier")
    ]


def test_stranded_causes_capped_at_residual(_chip):
    CHIP_LEDGER.book("encode", 0.09)
    CHIP_LEDGER.note_stall("host_prep", 5.0)  # wildly over-reported
    snap = CHIP_LEDGER.snapshot(0.1)
    causes = snap["stranded_causes"]
    assert causes["host_prep"] == pytest.approx(0.01, abs=1e-6)
    assert "unattributed" not in causes


def test_chip_peak_tflops_env(monkeypatch):
    """No peak is assumed for an unknown device: unset (or unusable)
    means no MFU, rendered as "n/a"."""
    from pathway_tpu.internals.chip_ledger import format_mfu

    monkeypatch.delenv("PATHWAY_CHIP_PEAK_TFLOPS", raising=False)
    assert chip_peak_tflops() is None
    monkeypatch.setenv("PATHWAY_CHIP_PEAK_TFLOPS", "130.7")
    assert chip_peak_tflops() == 130.7
    monkeypatch.setenv("PATHWAY_CHIP_PEAK_TFLOPS", "bogus")
    assert chip_peak_tflops() is None
    unknown = {"achieved_tflops": 73.4, "peak_tflops": None, "mfu": None}
    assert format_mfu(unknown).startswith("encode MFU n/a (73.4 TFLOPs")
    known = {"achieved_tflops": 73.4, "peak_tflops": 146.8, "mfu": 0.5, "pad_fraction": 0.25}
    assert format_mfu(known, pad=True) == "encode MFU 50.00% (73.4 / 146.8 TFLOPs, pad 25.0%)"


# ---------------------------------------------------------------------------
# per-tenant reconciliation with the DRR weights
# ---------------------------------------------------------------------------


def test_tenant_share_reconciles_with_drr_weights(_chip):
    from pathway_tpu.tenancy import TenancyConfig, TenantQuotas, set_active_tenancy

    set_active_tenancy(
        TenancyConfig(
            quotas={
                "gold": TenantQuotas(weight=3.0),
                "free": TenantQuotas(weight=1.0),
            }
        )
    )
    try:
        # chip time delivered exactly at the configured 3:1 split
        CHIP_LEDGER.book("encode", 0.09, tenant="gold")
        CHIP_LEDGER.book("encode", 0.03, tenant="free")
        tenants = CHIP_LEDGER.snapshot()["tenants"]
    finally:
        set_active_tenancy(None)
    assert tenants["gold"]["share"] == pytest.approx(0.75, abs=1e-3)
    assert tenants["free"]["share"] == pytest.approx(0.25, abs=1e-3)
    assert tenants["gold"]["weight_share"] == pytest.approx(0.75, abs=1e-3)
    assert tenants["free"]["weight_share"] == pytest.approx(0.25, abs=1e-3)
    # delivered share matches entitled share when work arrives at the
    # weight ratio — the reconciliation the snapshot exists to expose
    for t in ("gold", "free"):
        assert tenants[t]["share"] == pytest.approx(
            tenants[t]["weight_share"], abs=1e-3
        )


def test_tenant_overflow_folds_to_other(_chip):
    for i in range(60):
        CHIP_LEDGER.book_tenant(f"t{i:02d}", 0.001 * (i + 1))
    tenants = CHIP_LEDGER.snapshot()["tenants"]
    assert len(tenants) == 51  # 50 + "other"
    assert "other" in tenants
    assert sum(r["share"] for r in tenants.values()) == pytest.approx(
        1.0, abs=0.01
    )


# ---------------------------------------------------------------------------
# metrics journal: rotation, crash recovery, sampler
# ---------------------------------------------------------------------------


def test_journal_rotates_and_prunes_segments(tmp_path):
    from pathway_tpu.perf.journal import MetricsJournal

    j = MetricsJournal(str(tmp_path), seg_bytes=4096, segments=3)
    try:
        for i in range(400):
            j.append("sample", {"i": i, "pad": "x" * 64})
    finally:
        j.close()
    segs = j.segments()
    assert 1 < len(segs) <= 3
    # the newest record survived pruning; the oldest did not
    recs = j.read_all()
    assert recs[-1]["i"] == 399
    assert recs[0]["i"] > 0
    assert all(r["kind"] == "sample" for r in recs)


def test_journal_crash_recovery_skips_torn_line(tmp_path):
    """A crash mid-append leaves a torn trailing line; readers must
    return every intact record and drop the torn one."""
    from pathway_tpu.perf.journal import MetricsJournal

    j = MetricsJournal(str(tmp_path))
    j.append("sample", {"i": 1})
    j.append("sample", {"i": 2})
    j.close()
    seg = j.segments()[-1]
    with open(seg, "a", encoding="utf-8") as fh:
        fh.write('{"t": 3, "kind": "sample", "i": 3')  # no closing brace
    recs = j.read_all()
    assert [r["i"] for r in recs] == [1, 2]
    assert j.tail(1)[-1]["i"] == 2


def test_journal_sampler_writes_samples(tmp_path, monkeypatch, _chip):
    from pathway_tpu.perf.journal import JournalSampler, MetricsJournal

    CHIP_LEDGER.book("encode", 0.01)
    j = MetricsJournal(str(tmp_path))
    s = JournalSampler(j, interval_s=0.05)
    s.start()
    time.sleep(0.18)
    s.stop()
    j.close()
    recs = [r for r in j.read_all() if r["kind"] == "sample"]
    assert len(recs) >= 2  # ticks plus the final stop() sample
    assert recs[-1]["chip"]["accounts"]["encode"]["seconds"] > 0


def test_journal_inactive_without_dir(monkeypatch):
    from pathway_tpu.perf.journal import append_record, journal_active

    monkeypatch.delenv("PATHWAY_JOURNAL_DIR", raising=False)
    assert journal_active() is False
    assert append_record("bench", {"x": 1}) is False


# ---------------------------------------------------------------------------
# perf snapshot + diff gate math
# ---------------------------------------------------------------------------


def _snap(metrics):
    """BENCH_r*-shaped snapshot from (metric, value, unit[, extra])."""
    lines = []
    for m in metrics:
        rec = {"metric": m[0], "value": m[1], "unit": m[2]}
        if len(m) > 3:
            rec.update(m[3])
        lines.append(json.dumps(rec))
    return {
        "n": 1,
        "cmd": "test",
        "rc": 0,
        "tail": "=== FINAL SUMMARY (one line per metric) ===\n"
        + "\n".join(lines),
        "parsed": {},
    }


def test_perf_diff_direction_heuristics():
    from pathway_tpu.perf.snapshot import diff_snapshots

    a = _snap([
        ("ingest_eps", 1000.0, "rows/s"),
        ("p50_ms", 10.0, "ms"),
    ])
    b = _snap([
        ("ingest_eps", 800.0, "rows/s"),  # -20% on higher-better: regression
        ("p50_ms", 9.0, "ms"),  # lower-better improved
    ])
    result = diff_snapshots(a, b, gate=0.10)
    by_metric = {r["metric"]: r for r in result["rows"]}
    assert by_metric["ingest_eps"]["status"] == "regression"
    assert by_metric["ingest_eps"]["direction"] == "higher"
    assert by_metric["p50_ms"]["status"] in ("ok", "improved")
    assert result["rc"] == 1
    assert [r["metric"] for r in result["regressions"]] == ["ingest_eps"]


def test_perf_diff_within_gate_passes():
    from pathway_tpu.perf.snapshot import diff_snapshots

    a = _snap([("ingest_eps", 1000.0, "rows/s")])
    b = _snap([("ingest_eps", 950.0, "rows/s")])  # -5% within the 10% gate
    result = diff_snapshots(a, b, gate=0.10)
    assert result["rc"] == 0 and not result["regressions"]


def test_perf_diff_absolute_gate_field_wins():
    """A record carrying its own absolute `gate` (like
    chip_time_accounted_fraction's 0.95) fails when the candidate value
    drops below it, regardless of the relative gate."""
    from pathway_tpu.perf.snapshot import diff_snapshots

    a = _snap([("chip_time_accounted_fraction", 0.99, "fraction", {"gate": 0.95})])
    b = _snap([("chip_time_accounted_fraction", 0.93, "fraction", {"gate": 0.95})])
    result = diff_snapshots(a, b, gate=0.5)
    (row,) = result["regressions"]
    assert row["metric"] == "chip_time_accounted_fraction"
    assert result["rc"] == 1


def test_perf_diff_one_sided_metrics_reported_not_fatal():
    """A metric present in only one snapshot must not crash the diff:
    it reports as `new` (candidate only) / `removed` (baseline only)
    with the missing side None, and never fails the gate (rc 0)."""
    from pathway_tpu.perf.snapshot import diff_snapshots, render_diff

    a = _snap([("ingest_eps", 1000.0, "rows/s"), ("old_only_ms", 5.0, "ms")])
    b = _snap([("ingest_eps", 1000.0, "rows/s"), ("brand_new_qps", 50.0, "qps")])
    result = diff_snapshots(a, b, gate=0.10)
    by_metric = {r["metric"]: r for r in result["rows"]}
    assert by_metric["brand_new_qps"]["status"] == "new"
    assert by_metric["brand_new_qps"]["a"] is None
    assert by_metric["brand_new_qps"]["b"] == 50.0
    assert by_metric["old_only_ms"]["status"] == "removed"
    assert by_metric["old_only_ms"]["a"] == 5.0
    assert by_metric["old_only_ms"]["b"] is None
    assert by_metric["brand_new_qps"]["rel_change"] is None
    assert result["rc"] == 0 and not result["regressions"]
    # the rendered table must survive the None sides
    text = render_diff(result)
    assert "brand_new_qps" in text and "removed" in text and "new" in text


def test_perf_diff_disjoint_snapshots_exit_zero():
    from pathway_tpu.perf.snapshot import diff_snapshots, render_diff

    a = _snap([("alpha_ms", 1.0, "ms")])
    b = _snap([("beta_ms", 2.0, "ms")])
    result = diff_snapshots(a, b, gate=0.10)
    assert result["rc"] == 0
    assert {r["status"] for r in result["rows"]} == {"new", "removed"}
    assert "0 regression(s)" in render_diff(result)


def test_perf_snapshot_builds_from_journal(tmp_path, monkeypatch):
    from pathway_tpu.perf.snapshot import SUMMARY_MARKER, build_snapshot
    from pathway_tpu.perf.journal import MetricsJournal

    j = MetricsJournal(str(tmp_path))
    j.append(
        "bench",
        {
            "records": [{"metric": "ingest_eps", "value": 1234.5, "unit": "rows/s"}],
            "headline": {"metric": "rag_p50_ms", "value": 42.0, "unit": "ms"},
        },
    )
    j.close()
    snap = build_snapshot(str(tmp_path))
    assert SUMMARY_MARKER in snap["tail"]
    assert snap["parsed"]["metric"] == "rag_p50_ms"
    assert '"ingest_eps"' in snap["tail"]
    assert snap["rc"] == 0


def test_perf_snapshot_empty_journal_raises(tmp_path):
    from pathway_tpu.perf.snapshot import build_snapshot

    with pytest.raises(ValueError):
        build_snapshot(str(tmp_path))


# ---------------------------------------------------------------------------
# surfaces: /metrics + /status byte-identity both ways, pathway top
# ---------------------------------------------------------------------------


def test_chip_off_scrape_byte_identical_both_ways(_chip_off, monkeypatch):
    """Until the first booking, /metrics and /status must not change a
    single byte — in both directions: booking attempts while off leave
    the scrape at baseline, and turning accounting on without booking
    still leaves it at baseline (activity-gated, not config-gated)."""
    from pathway_tpu.internals.http_monitoring import MonitoringHttpServer
    from pathway_tpu.internals.monitoring import StatsMonitor

    server = MonitoringHttpServer(StatsMonitor(), port=0)

    def scrape():
        return "\n".join(
            line
            for line in server._prometheus().splitlines()
            if not line.startswith(
                ("pathway_input_latency_ms", "pathway_output_latency_ms")
            )
        )

    baseline_metrics = scrape()
    baseline_status = server._status()
    assert "pathway_chip_" not in baseline_metrics
    assert '"chip"' not in baseline_status

    monkeypatch.setenv("PATHWAY_CHIP_LEDGER", "0")
    CHIP_LEDGER.book("encode", 0.5)  # kill switch: booking is a no-op
    with CHIP_LEDGER.timed("rerank"):
        pass
    assert scrape() == baseline_metrics
    assert server._status() == baseline_status

    monkeypatch.setenv("PATHWAY_CHIP_LEDGER", "1")
    assert scrape() == baseline_metrics  # on but untouched: still silent
    assert server._status() == baseline_status

    CHIP_LEDGER.book("encode", 0.5)
    body = server._prometheus()
    assert 'pathway_chip_seconds_total{account="encode"} 0.500000' in body
    assert "pathway_chip_busy_seconds_total" in body
    assert '"chip"' in server._status()


def test_top_renders_empty_and_populated(_chip):
    from pathway_tpu.perf.top import render_top, verdict_state

    text, state = render_top({})
    assert state == "empty" and "no chip-time samples" in text

    CHIP_LEDGER.book("encode", 0.08, tenant="gold")
    CHIP_LEDGER.book("index.search", 0.02)
    snap = CHIP_LEDGER.snapshot(0.2)
    text, state = render_top({"chip": snap})
    assert state == verdict_state(snap)
    assert "encode" in text and "index.search" in text
    assert "stranded" in text and "gold" in text


def test_top_verdict_thresholds():
    from pathway_tpu.perf.top import verdict_state

    assert verdict_state(None) == "empty"
    assert verdict_state({"stranded_fraction": 0.1}) == "green"
    assert verdict_state({"stranded_fraction": 0.6}) == "yellow"
    assert verdict_state({"stranded_fraction": 0.85}) == "red"


def test_top_handles_both_hbm_shapes(_chip):
    """Journal samples store the flat LEDGER.accounts() dict; /status
    nests under snapshot()["accounts"] — both must render."""
    from pathway_tpu.perf.top import render_top

    CHIP_LEDGER.book("encode", 0.01)
    chip = CHIP_LEDGER.snapshot()
    flat = {"index.hot": {"bytes": 4096, "high_water_bytes": 8192}}
    nested = {"accounts": flat, "total_bytes": 4096}
    for hbm in (flat, nested):
        text, _ = render_top({"chip": chip, "hbm": hbm})
        assert "index.hot" in text and "4,096" in text


# ---------------------------------------------------------------------------
# watchdog rule + flight-recorder ride-along
# ---------------------------------------------------------------------------


def test_watchdog_stranded_rule_breach_and_clear(_chip):
    from pathway_tpu.internals.ledger import HealthWatchdog

    wd = HealthWatchdog(interval_s=0.01)
    # hysteresis: one bad sample is not a breach
    v = wd.evaluate_once({"t": 0.0, "stranded_fraction": 0.9})
    chip_rule = [r for r in v["rules"] if r["name"] == "stranded_chip_time"][0]
    assert chip_rule["level"] == "ok"
    v = wd.evaluate_once({"t": 1.0, "stranded_fraction": 0.9})
    chip_rule = [r for r in v["rules"] if r["name"] == "stranded_chip_time"][0]
    assert chip_rule["level"] == "critical"
    assert v["planes"]["chip"]["status"] == "red"
    # two good samples clear it
    wd.evaluate_once({"t": 2.0, "stranded_fraction": 0.1})
    v = wd.evaluate_once({"t": 3.0, "stranded_fraction": 0.1})
    chip_rule = [r for r in v["rules"] if r["name"] == "stranded_chip_time"][0]
    assert chip_rule["level"] == "ok"


def test_watchdog_spec_overrides_stranded_thresholds():
    from pathway_tpu.internals.ledger import parse_watchdog_spec

    cfg = parse_watchdog_spec("stranded_warn=0.3,stranded_critical=0.6")
    (rule,) = [r for r in cfg["rules"] if r.name == "stranded_chip_time"]
    assert rule.warn == 0.3 and rule.critical == 0.6


def test_watchdog_live_sample_carries_chip_fraction(_chip):
    from pathway_tpu.internals.ledger import HealthWatchdog

    CHIP_LEDGER.book("encode", 0.01)
    sample = HealthWatchdog(interval_s=0.01)._live_sample()
    assert "stranded_fraction" in sample
    assert 0.0 <= sample["stranded_fraction"] <= 1.0
    assert "chip_accounted_fraction" in sample


def test_doctor_verdict_renders_chip_rows(_chip):
    from pathway_tpu.internals.ledger import HealthWatchdog, render_verdict

    CHIP_LEDGER.book("encode", 0.05)
    CHIP_LEDGER.book("decode", 0.01)
    v = HealthWatchdog(interval_s=0.01).evaluate_once({"t": 0.0})
    assert v["chip"] is not None
    text = render_verdict(v)
    assert "chip-time:" in text
    assert "encode" in text and "decode" in text


def test_flight_recorder_dump_embeds_chip_and_journal(
    _chip, tmp_path, monkeypatch
):
    from pathway_tpu.internals import flight_recorder as fr
    from pathway_tpu.perf import journal as pj

    monkeypatch.setenv("PATHWAY_JOURNAL_DIR", str(tmp_path / "journal"))
    pj._JOURNALS.clear()
    CHIP_LEDGER.book("encode", 0.04)
    pj.get_journal().sample()
    fr.record("epoch.commit", epoch=7)
    path = fr.dump("test.chip", None)
    try:
        assert path is not None
        data = fr.load_dump(path)
        assert data["chip"]["accounts"]["encode"]["seconds"] > 0
        assert data["journal_tail"], "journal samples must ride along"
        text = fr.render(data)
        assert "chip time at dump:" in text
        assert "journal samples before dump" in text
    finally:
        pj._JOURNALS.clear()
        if path:
            os.unlink(path)
