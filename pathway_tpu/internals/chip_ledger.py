"""Chip-time attribution ledger: device-seconds per plane account.

The HBM ledger (:mod:`pathway_tpu.internals.ledger`) answers "who holds
the bytes"; this module answers "who got the chip". A process-wide
:class:`ChipTimeLedger` lets every device dispatch book its measured
device-seconds under a named plane account:

====================  =================================================
account               booked by
====================  =================================================
``encode``            fused sentence-encoder forward dispatch
``index.search``      KNN per-shard local top-k (phase 1)
``index.merge``       KNN cross-shard merge collective (phase 2)
``index.tier``        tiered-index cold fetch → rescore
``rerank``            device cross-encoder scoring
``decode``            decode prefill + per-tick step dispatch
``decode.draft``      speculative tick: draft proposal scan
``decode.verify``     speculative tick: target verification scan
``ingest.stage``      DeviceRing host→device staging copies
``compile``           jit cache misses (trace + compile wall)
====================  =================================================

Speculative decode splits its tick across ``decode.draft`` and
``decode.verify`` (never plain ``decode``), so the draft model's cost —
the overhead speculation pays for its acceptance rate — reads directly
off the ledger instead of hiding inside the decode plane's total.

The residual between booked device-seconds and wall time is the
**stranded** chip time — the VectorLiteRAG-style static-partition waste
the SLO autopilot needs to see. :meth:`ChipTimeLedger.snapshot`
attributes the stranded residual to its cause from the hooks that
already measure each one: host-bound prep (``PipelineStats`` prep
windows), ring stalls (``DeviceRing.stage_stall_s``), admission-queue
wait (the serving ``queue`` stage histogram), and barrier waits; the
remainder is reported ``unattributed``.

Per-tenant sub-accounts mirror the DRR scheduler's chip-seconds
bookkeeping so the snapshot can reconcile observed chip-time share
against configured DRR weight ("tenant X got 31% of chip time against
a 40% weight").

Accounting is **off by default** — booking sites block on the dispatch
result to read the clock (the same trade the index merge timing makes
when ``INDEX_METRICS`` is live), which a latency-critical run must opt
into. Enable with ``pw.run(chip_ledger=True)`` or
``PATHWAY_CHIP_LEDGER=1``; when off, every hook is a no-op and all
surfaces (``/metrics``, ``/status``, ``pathway top``) render nothing,
keeping scrapes byte-identical per the house rule.

Deliberately import-light (stdlib only at module level) so analyze-only
runs and the CLI can reason about the configuration without JAX.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator

#: Canonical plane accounts (booking is open-vocabulary; these are the
#: ones the built-in dispatch sites use, in render order).
PLANE_ACCOUNTS: tuple[str, ...] = (
    "encode",
    "index.search",
    "index.merge",
    "index.tier",
    "rerank",
    "decode",
    "decode.draft",
    "decode.verify",
    "ingest.stage",
    "compile",
)

#: Stranded-time causes, in attribution order (first claim wins; the
#: remainder is ``unattributed``).
STRANDED_CAUSES: tuple[str, ...] = (
    "host_prep",
    "ring_stall",
    "admission_queue",
    "barrier",
)

_TRUE = {"1", "true", "on", "yes"}

#: Cap on tenants carried in a snapshot (mirrors the tenancy registry's
#: cardinality guard); overflow folds into ``"other"``.
_SNAPSHOT_TENANTS = 50


def chip_ledger_enabled() -> bool:
    """Environment default for chip-time accounting: **off** unless
    ``PATHWAY_CHIP_LEDGER`` opts in (``1``/``true``/``on``/``yes``).
    ``pw.run(chip_ledger=...)`` overrides via :meth:`ChipTimeLedger.set_enabled`."""
    return os.environ.get("PATHWAY_CHIP_LEDGER", "").strip().lower() in _TRUE


def chip_peak_tflops() -> float | None:
    """Roofline peak for the encode MFU column, from
    ``PATHWAY_CHIP_PEAK_TFLOPS``. There is no default: a peak assumed
    for whatever device happens to be attached makes every MFU a guess,
    so unset (or not a positive number) means the column reads "n/a"."""
    try:
        v = float(os.environ["PATHWAY_CHIP_PEAK_TFLOPS"])
    except (KeyError, ValueError):
        return None
    return v if v > 0 else None


def format_mfu(mfu: dict, pad: bool = False) -> str:
    """One line for an ``encode_mfu`` snapshot block (``pathway top``,
    ``pathway doctor``): "n/a" where no peak is configured."""
    achieved = float(mfu.get("achieved_tflops") or 0.0)
    if mfu.get("mfu") is None:
        text = f"encode MFU n/a ({achieved:.1f} TFLOPs; PATHWAY_CHIP_PEAK_TFLOPS unset"
    else:
        text = (
            f"encode MFU {100 * float(mfu['mfu']):.2f}% "
            f"({achieved:.1f} / {float(mfu['peak_tflops']):.1f} TFLOPs"
        )
    if pad:
        text += f", pad {100 * float(mfu.get('pad_fraction') or 0.0):.1f}%"
    return text + ")"


class ChipTimeLedger:
    """Thread-safe device-seconds accounting per plane account and
    per tenant, with a stranded-residual model.

    Only :meth:`book` / :meth:`timed` / :meth:`note_stall` run on hot
    paths; each is a guarded dict update under one lock (and a no-op
    when accounting is off). Aggregation happens in :meth:`snapshot`.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # account -> [seconds, dispatches]
        self._accounts: dict[str, list] = {}
        # tenant -> seconds (the DRR per-item mirror)
        self._tenants: dict[str, float] = {}
        # cause -> seconds contributed by explicit stall notes
        self._stalls: dict[str, float] = {}
        self._touched = False
        self._override: bool | None = None
        self._window_t0: float | None = None
        self._window_last: float | None = None
        # per-thread nested-booking counter: ``timed`` subtracts seconds
        # booked *inside* its window (e.g. a jit compile booked by
        # ``wrap_jit`` while the encode site times the same call) so a
        # dispatch's wall is never double-counted across accounts.
        self._tl = threading.local()

    # -- gating --

    def set_enabled(self, on: bool | None) -> None:
        """Runtime override from ``pw.run(chip_ledger=...)``; ``None``
        restores the :func:`chip_ledger_enabled` environment default."""
        self._override = None if on is None else bool(on)

    def on(self) -> bool:
        """True when booking sites should measure (and sync) dispatches."""
        ov = self._override
        return chip_ledger_enabled() if ov is None else ov

    def active(self) -> bool:
        """Anything to render? False until the first booking, keeping
        ``/metrics`` and ``/status`` byte-identical for runs that never
        account chip time."""
        return self._touched

    # -- hot path --

    def book(
        self,
        account: str,
        seconds: float,
        *,
        tenant: str | None = None,
        dispatches: int = 1,
        t0: float | None = None,
    ) -> None:
        """Book ``seconds`` of device time under ``account`` (and
        optionally mirror them into ``tenant``'s sub-account). ``t0``
        is the perf-counter start of the measured span when the caller
        knows it (:meth:`timed` does) — it anchors the booking window
        so wall never under-spans busy."""
        if not self.on():
            return
        seconds = max(0.0, float(seconds))
        now = time.perf_counter()
        start = now - seconds if t0 is None else float(t0)
        with self._lock:
            self._touched = True
            if self._window_t0 is None or start < self._window_t0:
                self._window_t0 = start
            self._window_last = now
            row = self._accounts.get(account)
            if row is None:
                row = self._accounts[account] = [0.0, 0]
            row[0] += seconds
            row[1] += int(dispatches)
            if tenant is not None:
                self._tenants[tenant] = self._tenants.get(tenant, 0.0) + seconds
        tl = self._tl
        tl.nested = getattr(tl, "nested", 0.0) + seconds

    def book_tenant(self, tenant: str, seconds: float) -> None:
        """Tenant-dimension-only booking (the plane work was already
        booked at its own dispatch site; the batcher mirrors the DRR
        per-item chip-seconds split here)."""
        if not self.on():
            return
        with self._lock:
            self._touched = True
            self._tenants[tenant] = self._tenants.get(tenant, 0.0) + max(
                0.0, float(seconds)
            )

    @contextmanager
    def timed(self, account: str, *, tenant: str | None = None) -> Iterator[None]:
        """Book the wall of the enclosed block, minus any seconds booked
        to other accounts from inside it (nested-dispatch dedup)."""
        if not self.on():
            yield
            return
        tl = self._tl
        n0 = getattr(tl, "nested", 0.0)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            inner = getattr(tl, "nested", 0.0) - n0
            self.book(account, max(0.0, dt - inner), tenant=tenant, t0=t0)

    def note_stall(self, cause: str, seconds: float) -> None:
        """Accumulate wall seconds a known cause kept the chip idle
        (``host_prep`` from PipelineStats prep windows, ``barrier`` from
        cluster waits). Ring stalls and admission-queue wait are read
        live from their own registries at snapshot time."""
        if not self.on():
            return
        with self._lock:
            self._touched = True
            self._stalls[cause] = self._stalls.get(cause, 0.0) + max(
                0.0, float(seconds)
            )

    # -- aggregation --

    def wall_seconds(self) -> float:
        """Wall span of the booking window (first booking → now)."""
        with self._lock:
            t0 = self._window_t0
        return 0.0 if t0 is None else max(0.0, time.perf_counter() - t0)

    def _live_stalls(self) -> dict[str, float]:
        """Merge explicit stall notes with the registries that already
        measure their own stall walls. Defensive: accounting must never
        take a run down with it."""
        stalls: dict[str, float]
        with self._lock:
            stalls = dict(self._stalls)
        try:
            from ..engine.device_ring import active_rings

            ring = sum(r.stage_stall_s for r in active_rings())
            if ring > 0:
                stalls["ring_stall"] = stalls.get("ring_stall", 0.0) + ring
        except Exception:
            pass
        try:
            from ..serving.metrics import SERVING_METRICS

            if SERVING_METRICS.active():
                q = SERVING_METRICS.stages.get("queue")
                if q is not None and q.total > 0:
                    stalls["admission_queue"] = (
                        stalls.get("admission_queue", 0.0) + q.total
                    )
        except Exception:
            pass
        return stalls

    def _mfu(self) -> dict[str, Any] | None:
        """Encode-plane MFU vs the configured roofline peak (``None``
        fields when no peak is configured), from the encoder kernel
        stats window (dispatch-clock achieved TFLOPs)."""
        try:
            from .profiler import ENCODER_KERNEL_STATS

            if not ENCODER_KERNEL_STATS.dispatches:
                return None
            enc = ENCODER_KERNEL_STATS.snapshot()
            peak = chip_peak_tflops()
            achieved = float(enc.get("achieved_tflops", 0.0))
            return {
                "achieved_tflops": round(achieved, 3),
                "peak_tflops": round(peak, 3) if peak else None,
                "mfu": round(achieved / peak, 6) if peak else None,
                "pad_fraction": enc.get("pad_fraction", 0.0),
            }
        except Exception:
            return None

    def _tenant_block(self, tenants: dict[str, float]) -> dict[str, dict]:
        """Per-tenant chip-time share reconciled against DRR weights."""
        if not tenants:
            return {}
        ranked = sorted(tenants.items(), key=lambda kv: (-kv[1], kv[0]))
        if len(ranked) > _SNAPSHOT_TENANTS:
            head = ranked[:_SNAPSHOT_TENANTS]
            other = sum(s for _, s in ranked[_SNAPSHOT_TENANTS:])
            ranked = head + [("other", other)]
        total = sum(s for _, s in ranked) or 1.0
        weights: dict[str, float] = {}
        try:
            from ..tenancy import active_tenancy

            plane = active_tenancy()
            if plane is not None:
                for t, _ in ranked:
                    if t == "other":
                        continue
                    q = plane.quota_for(t)
                    w = getattr(q, "weight", None) if q is not None else None
                    if w is not None:
                        weights[t] = float(w)
        except Exception:
            weights = {}
        wsum = sum(weights.values())
        out: dict[str, dict] = {}
        for t, s in ranked:
            row: dict[str, Any] = {
                "seconds": round(s, 6),
                "share": round(s / total, 4),
            }
            if t in weights and wsum > 0:
                row["weight"] = weights[t]
                row["weight_share"] = round(weights[t] / wsum, 4)
            out[t] = row
        return out

    def snapshot(self, wall_s: float | None = None) -> dict:
        """Aggregate view: per-account seconds/dispatches/share, the
        stranded residual vs ``wall_s`` (default: the booking window)
        attributed to its causes, encode MFU, and the per-tenant
        share-vs-weight reconciliation."""
        now = time.perf_counter()
        with self._lock:
            accounts = {a: (row[0], row[1]) for a, row in self._accounts.items()}
            tenants = dict(self._tenants)
            t0 = self._window_t0
        busy = sum(s for s, _ in accounts.values())
        if wall_s is None:
            wall = max(0.0, now - t0) if t0 is not None else 0.0
        else:
            wall = max(0.0, float(wall_s))
        stranded = max(0.0, wall - busy)
        accounted = min(1.0, busy / wall) if wall > 0 else (1.0 if busy else 0.0)

        def _order(name: str) -> tuple:
            try:
                return (0, PLANE_ACCOUNTS.index(name))
            except ValueError:
                return (1, name)

        acc_block = {}
        for name in sorted(accounts, key=_order):
            s, d = accounts[name]
            acc_block[name] = {
                "seconds": round(s, 6),
                "dispatches": d,
                "share": round(s / busy, 4) if busy > 0 else 0.0,
            }

        causes: dict[str, float] = {}
        remaining = stranded
        live = self._live_stalls()
        for cause in STRANDED_CAUSES:
            got = min(remaining, max(0.0, live.get(cause, 0.0)))
            if got > 0:
                causes[cause] = round(got, 6)
                remaining -= got
        for cause, s in sorted(live.items()):
            if cause in STRANDED_CAUSES or remaining <= 0:
                continue
            got = min(remaining, max(0.0, s))
            if got > 0:
                causes[cause] = round(got, 6)
                remaining -= got
        if remaining > 1e-9:
            causes["unattributed"] = round(remaining, 6)

        out: dict[str, Any] = {
            "accounts": acc_block,
            "busy_seconds": round(busy, 6),
            "wall_seconds": round(wall, 6),
            "accounted_fraction": round(accounted, 4),
            "stranded_seconds": round(stranded, 6),
            "stranded_fraction": round(stranded / wall, 4) if wall > 0 else 0.0,
            "stranded_causes": causes,
        }
        mfu = self._mfu()
        if mfu is not None:
            out["encode_mfu"] = mfu
        tb = self._tenant_block(tenants)
        if tb:
            out["tenants"] = tb
        return out

    def reset(self) -> None:
        with self._lock:
            self._accounts.clear()
            self._tenants.clear()
            self._stalls.clear()
            self._touched = False
            self._window_t0 = None
            self._window_last = None


#: Process-wide singleton every dispatch site books into.
CHIP_LEDGER = ChipTimeLedger()
