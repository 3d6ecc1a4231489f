"""``pathway_request_stage_seconds`` — per-stage request latency with
trace-id exemplars.

Same registry discipline as every other plane (``SERVING_METRICS``,
``INDEX_METRICS``, ...): a process-wide singleton the monitoring HTTP
server renders only when :meth:`TracingMetrics.active` — a run that
never records a span scrapes byte-identical output. Buckets reuse the
serving plane's request-latency scale. Each bucket remembers the last
trace id that landed in it, rendered as an OpenMetrics exemplar
(``... # {trace_id="..."} value timestamp``) so a dashboard's p99
bucket links straight to ``pathway trace show <id>``.

Beside calls and seconds a stage's totals hold the sums of the work
units its sites pass as span attributes (``rows``, ``queries``,
``tokens``), so a per-layer ratio is taken where the work happens;
:meth:`TracingMetrics.totals` reads them, summed over workers.

A count that only the device knows — the tokens a sparse layer routed to
each expert held here — is *owed*: the dispatch site hands over the
device array un-fetched (:meth:`TracingMetrics.owe_expert_loads`) and
every read of the registry folds what is owed into the stage's totals
first, so the transfer is the reader's and never the write path's.
"""

from __future__ import annotations

import threading
import time as _time

import numpy as np

from ..serving.metrics import STAGE_BUCKETS

#: span attributes that count work: summed into the stage's totals
WORK_UNITS = ("rows", "queries", "tokens")
#: and those only some stages have: two that a stage fed from the device's
#: own counts brings, and the token rows a packed stream computes for its
#: real ``tokens``
OTHER_UNITS = ("max_load", "mean_load", "computed_tokens")
#: owed arrays kept before the hand-over itself folds them: a bound for
#: a process that traces and never reads
_OWED_MOST = 1024


class _ExemplarHistogram:
    """Fixed-bucket histogram where every bucket keeps its most recent
    (trace_id, value, unix_ts) exemplar."""

    __slots__ = ("counts", "total", "count", "exemplars", "units")

    def __init__(self) -> None:
        self.counts = [0] * (len(STAGE_BUCKETS) + 1)
        self.exemplars: list[tuple[str, float, float] | None] = [None] * (
            len(STAGE_BUCKETS) + 1
        )
        self.total = 0.0
        self.count = 0
        self.units = dict.fromkeys(WORK_UNITS, 0)

    def observe(self, seconds: float, trace_id: str, units: dict | None = None) -> None:
        seconds = max(0.0, float(seconds))
        idx = len(STAGE_BUCKETS)
        for i, le in enumerate(STAGE_BUCKETS):
            if seconds <= le:
                idx = i
                break
        self.counts[idx] += 1
        if trace_id:
            self.exemplars[idx] = (trace_id, seconds, _time.time())
        self.total += seconds
        self.count += 1
        if units:
            for name in WORK_UNITS + OTHER_UNITS:
                n = units.get(name)
                if n:
                    self.units[name] = self.units.get(name, 0) + (n if isinstance(n, float) else int(n))

    def cumulative(self) -> list[tuple[str, int, tuple[str, float, float] | None]]:
        """(le, cumulative count, bucket exemplar) ending at +Inf."""
        out = []
        running = 0
        for i, le in enumerate(STAGE_BUCKETS):
            running += self.counts[i]
            out.append((f"{le:g}", running, self.exemplars[i]))
        running += self.counts[-1]
        out.append(("+Inf", running, self.exemplars[-1]))
        return out


class TracingMetrics:
    """Thread-safe (stage, worker) → latency histogram registry."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._hists: dict[tuple[str, int], _ExemplarHistogram] = {}
        self._owed: list[tuple[str, int, object]] = []  # (stage, worker, device array)

    def owe_expert_loads(self, stage: str, loads, *, worker: int = 0) -> None:
        """``loads``: a device array ``[layer calls, experts held]`` of
        the real tokens a dispatch assigned to each held expert, not
        fetched here. Folded at the next read: a call of ``stage`` per
        row, ``rows`` its sum, ``max_load`` its largest entry and
        ``mean_load`` its mean."""
        with self._lock:
            self._owed.append((stage, int(worker), loads))
        if len(self._owed) > _OWED_MOST:
            self._pay_owed()

    def _fold(self, owed) -> None:
        for stage, worker, loads in owed:
            for row in np.asarray(loads):
                units = {"rows": int(row.sum()), "max_load": int(row.max()), "mean_load": float(row.mean())}
                self.observe(stage, 0.0, "", worker=worker, units=units)

    def _pay_owed(self) -> None:
        """Before every read, outside the lock ``observe`` takes."""
        if not self._owed:
            return
        with self._lock:
            owed, self._owed = self._owed, []
        self._fold(owed)

    def observe(
        self,
        stage: str,
        seconds: float,
        trace_id: str,
        *,
        worker: int = 0,
        units: dict | None = None,
    ) -> None:
        """One finished span of ``stage``. ``units`` is the span's
        attributes: those named in :data:`WORK_UNITS` add to the totals."""
        key = (stage, int(worker))
        with self._lock:
            hist = self._hists.get(key)
            if hist is None:
                hist = self._hists[key] = _ExemplarHistogram()
            hist.observe(seconds, trace_id, units)

    def active(self) -> bool:
        """Anything to render? (keeps /metrics byte-identical for runs
        that never record a span)"""
        self._pay_owed()
        with self._lock:
            return bool(self._hists)

    def series(self) -> list[dict]:
        """Render-ready rows for the monitoring server, sorted for
        stable scrape output."""
        self._pay_owed()
        with self._lock:
            items = sorted(self._hists.items())
            out = []
            for (stage, worker), hist in items:
                out.append(
                    {
                        "stage": stage,
                        "worker": worker,
                        "sum": hist.total,
                        "count": hist.count,
                        "buckets": hist.cumulative(),
                    }
                )
        return out

    def snapshot(self) -> dict:
        self._pay_owed()
        with self._lock:
            return {
                f"{stage}[w{worker}]": {
                    "count": h.count,
                    "sum": round(h.total, 6),
                    **{name: n for name, n in h.units.items() if n},
                }
                for (stage, worker), h in sorted(self._hists.items())
                if h.count
            }

    def totals(self) -> dict[str, dict]:
        """``{stage: {"calls", "seconds", "rows", "queries", "tokens"}}``,
        summed over workers: what a per-layer metric divides."""
        out: dict[str, dict] = {}
        self._pay_owed()
        with self._lock:
            for (stage, _worker), h in self._hists.items():
                t = out.setdefault(
                    stage, {"calls": 0, "seconds": 0.0, **dict.fromkeys(WORK_UNITS, 0)}
                )
                t["calls"] += h.count
                t["seconds"] += h.total
                for name, n in h.units.items():
                    t[name] = t.get(name, 0) + n
        return out

    def reset(self) -> None:
        with self._lock:
            self._hists.clear()
            self._owed.clear()


#: Process-wide registry surfaced on ``/metrics`` and ``/status``.
TRACING_METRICS = TracingMetrics()
