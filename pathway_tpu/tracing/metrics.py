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

The registry also holds the host's timeline (:class:`_Timeline`): each
thread that dispatches device work keeps, stage by stage, the host
seconds that passed with nothing of its own in flight on the device
(``starved``), with something in flight (``overlapped``) and blocked on
a result (``waiting``). :meth:`TracingMetrics.totals` gives them as
further fields of each stage, ``/metrics`` as
``pathway_stage_device_seconds{stage,state}``.
"""

from __future__ import annotations

import threading
import time as _time
from bisect import bisect_left as _bisect

import numpy as np

from ..serving.metrics import STAGE_BUCKETS

#: span attributes that count work: summed into the stage's totals
WORK_UNITS = ("rows", "queries", "tokens")
#: and those only some stages have: two that a stage fed from the device's
#: own counts brings, and the token rows a packed stream computes for its
#: real ``tokens``
OTHER_UNITS = ("max_load", "mean_load", "computed_tokens")
_UNITS = frozenset(WORK_UNITS + OTHER_UNITS)
_NUMBERS = (int, float)  # what a unit adds as it is; a numpy integer goes through int()
#: the headings a timeline charges an interval under, in the order of a row
STATES = ("starved", "overlapped", "waiting")
_STARVED, _OVERLAPPED, _WAITING = range(3)
#: the stage charged while a dispatching thread has none open (the epoch
#: loop around the device plane), and the one that sums every stage
CALLER, TIMELINE = "caller", "timeline"
#: owed arrays kept before the hand-over itself folds them: a bound for
#: a process that traces and never reads
_OWED_MOST = 1024


def _add_rows(into: dict[str, list[float]], rows: dict[str, list[float]]) -> None:
    for stage, row in list(rows.items()):
        have = into.setdefault(stage, [0.0, 0.0, 0.0])
        for state, seconds in enumerate(row):
            have[state] += seconds


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
        if not seconds > 0.0:
            seconds = 0.0
        idx = _bisect(STAGE_BUCKETS, seconds)
        self.counts[idx] += 1
        if trace_id:
            self.exemplars[idx] = (trace_id, seconds, _time.time())
        self.total += seconds
        self.count += 1
        if units:
            have = self.units
            for name, n in units.items():
                if n and name in _UNITS:
                    have[name] = have.get(name, 0) + (n if n.__class__ in _NUMBERS else int(n))

    def add(self, other: "_ExemplarHistogram") -> None:
        """Fold ``other`` in (a thread's own histogram into the registry's)."""
        self.counts = [a + b for a, b in zip(self.counts, other.counts)]
        self.exemplars = [a or b for a, b in zip(self.exemplars, other.exemplars)]
        self.total += other.total
        self.count += other.count
        for name, n in other.units.items():
            self.units[name] = self.units.get(name, 0) + n

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


def _ready(handle) -> bool:
    """Has the device finished what ``handle`` stands for? An array that
    was donated or deleted since counts as ready — and is asked first:
    ``is_ready()`` of a deleted ``jax.Array`` ends the process."""
    return handle.is_deleted() or handle.is_ready()


class _Timeline:
    """What one thread keeps of its calls into the device plane while
    tracing is on: the stack of its open stages, the time of its last
    boundary, and at most one in-flight handle — the smallest array the
    last dispatch returns, never a copy, dropped when seen ready or when
    the next dispatch replaces it.

    A boundary is a span's enter or exit, :meth:`dispatched` or
    :meth:`waited`. At each the interval since the last one is charged to
    the innermost open stage (``caller`` where none is open) under one
    heading: ``waiting`` where :meth:`waited` ends it, ``starved`` where
    no handle was held, ``overlapped`` where one was. Then a held handle
    is probed and, if ready, dropped: the device was seen empty, and what
    follows is starved until the next dispatch. Completion is seen one
    boundary late at worst, so ``starved`` is a **lower bound** of the
    time the device had nothing of this thread's to run; it says nothing
    of what another thread dispatched. Once the device is known empty no
    probe is made until the next dispatch.

    It also takes the spans of its thread that hang on no journey (a bare
    ``remove``, a thousand a write batch) into histograms of its own, so
    that they pay no lock; every read of the registry sums them in.

    Only its own thread writes to it; readers sum its rows unlocked."""

    __slots__ = ("stack", "last", "handle", "acc", "hists", "dispatching", "thread")

    def __init__(self) -> None:
        self.stack: list[str] = []
        self.acc: dict[str, list[float]] = {}  # stage -> seconds by STATES
        self.hists: dict[tuple[str, int], _ExemplarHistogram] = {}
        self.last: float | None = None  # None: the next boundary charges nothing
        self.handle = None
        self.dispatching = False  # a thread that never dispatches starves nothing
        self.thread = threading.current_thread()

    def _charge(self, now: float, state: int | None = None) -> None:
        last, self.last = self.last, now
        if last is not None:
            stage = self.stack[-1] if self.stack else CALLER
            row = self.acc.get(stage)
            if row is None:
                row = self.acc[stage] = [0.0, 0.0, 0.0]
            if state is None:
                state = _STARVED if self.handle is None else _OVERLAPPED
            row[state] += now - last

    def _probe(self) -> None:
        if _ready(self.handle):
            self.handle = None

    def enter(self, stage: str, now: float) -> None:
        self._charge(now)
        if self.handle is not None:
            self._probe()
        self.stack.append(stage)

    def exit(self, now: float) -> None:
        self._charge(now)
        if self.handle is not None:
            self._probe()
        if self.stack:
            self.stack.pop()

    def exit_bare(self, now: float, stage: str, seconds: float, worker: int, units: dict) -> None:
        """:meth:`exit` for a span with no trace id, which also leaves its
        seconds and units here: ``_ExemplarHistogram.observe`` without
        the exemplar, in line."""
        self.exit(now)
        hist = self.hists.get((stage, worker))
        if hist is None:
            hist = self.hists[stage, worker] = _ExemplarHistogram()
        hist.counts[_bisect(STAGE_BUCKETS, seconds)] += 1
        hist.total += seconds
        hist.count += 1
        have = hist.units
        for name, n in units.items():
            if n and name in _UNITS:
                have[name] = have.get(name, 0) + (n if n.__class__ in _NUMBERS else int(n))

    def dispatched(self, handle, now: float) -> None:
        self._charge(now)
        self.handle = handle  # the one before it is not asked: the device has work again
        self.dispatching = True

    def waited(self, now: float) -> None:
        self._charge(now, _WAITING)
        if self.handle is not None:
            self._probe()

    def restart(self) -> None:
        """Tracing went off or the totals were reset: the gap until the
        next boundary is nobody's."""
        self.last = self.handle = None


class TracingMetrics:
    """Thread-safe (stage, worker) → latency histogram registry."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._hists: dict[tuple[str, int], _ExemplarHistogram] = {}
        self._owed: list[tuple[str, int, object]] = []  # (stage, worker, device array)
        self._local = threading.local()
        self._timelines: list[_Timeline] = []
        self._ended: dict[str, list[float]] = {}  # the rows of dispatching threads that ended

    # -- the host's timeline --

    def timeline(self) -> _Timeline:
        """The calling thread's."""
        try:
            return self._local.timeline
        except AttributeError:
            tl = self._local.timeline = _Timeline()
            with self._lock:
                live = []
                for other in self._timelines:
                    if other.thread.is_alive():
                        live.append(other)
                        continue
                    for key, hist in other.hists.items():
                        self._hists.setdefault(key, _ExemplarHistogram()).add(hist)
                    if other.dispatching:
                        _add_rows(self._ended, other.acc)
                self._timelines = live + [tl]
        return tl

    def _all_hists(self) -> dict[tuple[str, int], _ExemplarHistogram]:
        """The registry's histograms with every thread's own summed in
        (the caller holds the lock; what it gets it does not change)."""
        out = dict(self._hists)
        for tl in self._timelines:
            for key, hist in list(tl.hists.items()):
                both = _ExemplarHistogram()
                if key in out:
                    both.add(out[key])
                both.add(hist)
                out[key] = both
        return out

    def restart_timelines(self) -> None:
        with self._lock:
            for tl in self._timelines:
                tl.restart()

    def device_seconds(self) -> dict[str, list[float]]:
        """``{stage: [starved, overlapped, waiting]}`` seconds of self time,
        summed over the threads that dispatched device work; ``caller``
        is what passed on them with no stage open."""
        with self._lock:
            rows = {stage: list(row) for stage, row in self._ended.items()}
            for tl in self._timelines:
                if tl.dispatching:
                    _add_rows(rows, tl.acc)
        return rows

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
            return bool(self._hists) or any(tl.hists for tl in self._timelines)

    def series(self) -> list[dict]:
        """Render-ready rows for the monitoring server, sorted for
        stable scrape output."""
        self._pay_owed()
        with self._lock:
            items = sorted(self._all_hists().items())
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
                for (stage, worker), h in sorted(self._all_hists().items())
                if h.count
            }

    def totals(self) -> dict[str, dict]:
        """``{stage: {"calls", "seconds", "rows", "queries", "tokens"}}``,
        summed over workers: what a per-layer metric divides. ``seconds``
        is inclusive of the stages nested in it. A stage that ran on a
        thread which dispatched device work also has ``self_seconds``
        (``seconds`` less what its children cover) and its three parts
        ``starved_seconds``, ``overlapped_seconds``, ``waiting_seconds``
        (:class:`_Timeline`); ``caller`` holds what passed on such a
        thread with no stage open, and ``timeline`` the sums over every
        stage, its ``seconds`` the wall of those threads while tracing
        was on."""
        out: dict[str, dict] = {}
        self._pay_owed()
        with self._lock:
            for (stage, _worker), h in self._all_hists().items():
                t = out.setdefault(stage, _no_totals())
                t["calls"] += h.count
                t["seconds"] += h.total
                for name, n in h.units.items():
                    t[name] = t.get(name, 0) + n
        rows = self.device_seconds()
        if rows:
            whole = [0.0, 0.0, 0.0]
            for stage, row in rows.items():
                t = out.setdefault(stage, _no_totals())
                t["self_seconds"] = sum(row)
                for state, seconds in zip(STATES, row):
                    t[state + "_seconds"] = seconds
                whole = [a + b for a, b in zip(whole, row)]
            if CALLER in rows:  # no span of its own: its wall is what it was charged
                out[CALLER]["seconds"] = out[CALLER]["self_seconds"]
            out[TIMELINE] = {**_no_totals(), "seconds": sum(whole)}
            for state, seconds in zip(STATES, whole):
                out[TIMELINE][state + "_seconds"] = seconds
        return out

    def reset(self) -> None:
        with self._lock:
            self._hists.clear()
            self._owed.clear()
            self._ended.clear()
            for tl in self._timelines:
                tl.acc, tl.hists = {}, {}
                tl.dispatching = False
                tl.restart()


def _no_totals() -> dict:
    return {"calls": 0, "seconds": 0.0, **dict.fromkeys(WORK_UNITS, 0)}


#: Process-wide registry surfaced on ``/metrics`` and ``/status``.
TRACING_METRICS = TracingMetrics()
