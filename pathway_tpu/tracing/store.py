"""Span recording and the bounded trace store.

Recording is gated on one process-wide switch: the flag
(``pw.run(tracing=...)`` / ``PATHWAY_TRACING``) or a running
``jax.profiler`` session. With tracing off a :class:`span` block costs
the check and records nothing, so the serving hot path stays within its
<5% overhead budget and ``/metrics`` output is byte-identical to a build
without the plane. While a profiler session runs every :class:`span`
that belongs to a journey is also a
``jax.profiler.TraceAnnotation("pw.<stage>")``: the program's spans land
in the same xplane as the device's ``XLA Ops`` line, on its clock. A
span's enter and exit, :func:`dispatched` and :func:`waited` are the
boundaries of the thread's timeline (``metrics._Timeline``), which
charges each stage its self time and whether the device had work of the
thread's to run.

The :class:`TraceStore` keeps completed spans in a bounded ring (like
the flight recorder's event ring) plus **p99 exemplar retention**: when
a request's *root* span completes, the trace's wall time competes for
one of ``PATHWAY_TRACE_EXEMPLARS`` slots in the current retention
window — the slowest-N complete traces of each window survive ring
eviction, so "where did the p99 go" is answerable long after the p50
traffic that evicted the ring. Worker processes buffer finished spans
in an outbox the cluster protocol piggybacks to the coordinator
(deduplicated by span id, so chaos-duplicated frames do not double
spans — same discipline as PR 7's seq-numbered frames).

At the end of a traced run the store is dumped to
``PATHWAY_TRACE_DIR`` (default ``<tmp>/pathway-traces``) for the
``pathway trace`` CLI, and any spans still open ride along in
flight-recorder crash dumps — a SIGKILLed worker's in-flight request
is visible in the blackbox.
"""

from __future__ import annotations

import heapq
import itertools
import json
import os
import sys
import threading
import time as _time
from collections import deque
from typing import Any, Optional

from ..internals.flight_recorder import _env_flag, _env_int
from .context import TraceContext, bind_trace, current_trace, gen_span_id, gen_trace_id
from .metrics import TRACING_METRICS

TRACE_DUMP_FORMAT_VERSION = 1

_ENABLED = _env_flag("PATHWAY_TRACING", False)

#: ``jax.profiler.TraceAnnotation``, once JAX is there to ask
_ANNOTATION = None


def _profiling() -> bool:
    """Is a ``jax.profiler`` session running? Asked only of a JAX some
    other module has imported, so ``import pathway_tpu`` stays jax-free:
    no session can run before JAX is loaded."""
    global _ANNOTATION
    if _ANNOTATION is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return False
        _ANNOTATION = profiler.TraceAnnotation
    return _ANNOTATION.is_enabled()


def tracing_enabled() -> bool:
    return _ENABLED or _profiling()


def set_tracing_enabled(on: bool) -> bool:
    """Flip the process-wide recording flag; returns the previous value
    (``pw.run`` restores it when the run ends)."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(on)
    if _ENABLED != prev:
        TRACING_METRICS.restart_timelines()  # the time it was off is no stage's
    return prev


def default_trace_dir() -> str:
    d = os.environ.get("PATHWAY_TRACE_DIR")
    if d:
        return d
    import tempfile

    return os.path.join(tempfile.gettempdir(), "pathway-traces")


class Span:
    """One recorded stage of a request journey."""

    __slots__ = (
        "trace_id",
        "span_id",
        "parent_id",
        "stage",
        "worker",
        "start_unix",
        "start_mono",
        "duration_s",
        "attrs",
        "links",
        "boundary",
    )

    def __init__(
        self,
        trace_id: str,
        span_id: str,
        parent_id: str,
        stage: str,
        *,
        worker: int = 0,
        start_unix: float | None = None,
        start_mono: float | None = None,
        duration_s: float | None = None,
        attrs: dict | None = None,
        links: tuple = (),
    ):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.stage = stage
        self.worker = worker
        self.start_unix = _time.time() if start_unix is None else start_unix
        self.start_mono = _time.monotonic() if start_mono is None else start_mono
        self.duration_s = duration_s
        self.attrs = attrs or {}
        self.links = tuple(links)
        #: journey boundary: finishing this span completes the trace
        #: locally even when the parent span is *remote* (an inbound
        #: ``traceparent`` makes the server's request span a child of
        #: the client's span, so it is never a local root)
        self.boundary = False

    @property
    def is_root(self) -> bool:
        return self.parent_id == ""

    def to_dict(self) -> dict:
        d = {
            "trace": self.trace_id,
            "span": self.span_id,
            "parent": self.parent_id,
            "stage": self.stage,
            "worker": self.worker,
            "start": round(self.start_unix, 6),
            "dur_ms": round((self.duration_s or 0.0) * 1000.0, 4),
        }
        if self.attrs:
            d["attrs"] = self.attrs
        if self.links:
            d["links"] = list(self.links)
        if self.boundary:
            d["boundary"] = True
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Span":
        sp = cls(
            d.get("trace", ""),
            d.get("span", ""),
            d.get("parent", ""),
            d.get("stage", "?"),
            worker=int(d.get("worker", 0)),
            start_unix=float(d.get("start", 0.0)),
            start_mono=0.0,
            duration_s=float(d.get("dur_ms", 0.0)) / 1000.0,
            attrs=d.get("attrs") or {},
            links=tuple(d.get("links") or ()),
        )
        sp.boundary = bool(d.get("boundary", False))
        return sp


class TraceStore:
    """Process-wide span ring + exemplar retention + remote ingest."""

    def __init__(
        self,
        ring_size: int | None = None,
        exemplar_slots: int | None = None,
        window_s: float | None = None,
    ):
        if ring_size is None:
            ring_size = max(64, _env_int("PATHWAY_TRACE_RING", 4096))
        if exemplar_slots is None:
            exemplar_slots = max(1, _env_int("PATHWAY_TRACE_EXEMPLARS", 10))
        if window_s is None:
            window_s = float(max(1, _env_int("PATHWAY_TRACE_WINDOW_S", 60)))
        self.exemplar_slots = exemplar_slots
        self.window_s = window_s
        self.worker = 0
        self._lock = threading.Lock()
        self._seq = itertools.count()
        self._ring: deque[Span] = deque(maxlen=ring_size)
        self._open: dict[str, Span] = {}
        # traces under assembly: trace_id -> spans finished so far
        self._by_trace: dict[str, list[Span]] = {}
        self._by_trace_cap = max(64, _env_int("PATHWAY_TRACE_INFLIGHT", 1024))
        # current retention window: min-heap of (wall_s, seq, trace_id, spans)
        self._window_start: float | None = None
        self._window_heap: list[tuple[float, int, str, list[Span]]] = []
        self._retained: deque[list[tuple[float, str, list[Span]]]] = deque(
            maxlen=max(1, _env_int("PATHWAY_TRACE_WINDOWS", 5))
        )
        # remote ingest dedup: span ids seen from worker piggybacks
        self._seen_remote: set[str] = set()
        self._seen_remote_order: deque[str] = deque(maxlen=8192)
        self._outbox: list[dict] = []
        self._outbox_enabled = False
        self.spans_total = 0
        self.traces_total = 0
        self.remote_spans_total = 0
        self.remote_dupes_total = 0

    # -- worker-side configuration (cluster piggyback) --

    def configure_worker(self, worker_id: int) -> None:
        """Mark this process as cluster worker ``worker_id``: finished
        spans are additionally queued for the coordinator piggyback."""
        with self._lock:
            self.worker = int(worker_id)
            self._outbox_enabled = True

    def drain_outbox(self, limit: int = 256) -> list[dict]:
        with self._lock:
            if not self._outbox:
                return []
            out, self._outbox = self._outbox[:limit], self._outbox[limit:]
            return out

    # -- recording --

    def begin(self, sp: Span) -> None:
        with self._lock:
            self._open[sp.span_id] = sp

    def finish(self, sp: Span) -> None:
        if sp.duration_s is None:
            sp.duration_s = max(0.0, _time.monotonic() - sp.start_mono)
        completed: list[Span] | None = None
        with self._lock:
            self._open.pop(sp.span_id, None)
            self._ring.append(sp)
            self.spans_total += 1
            if self._outbox_enabled and len(self._outbox) < 4096:
                self._outbox.append(sp.to_dict())
            bucket = self._by_trace.get(sp.trace_id)
            if bucket is None:
                if len(self._by_trace) >= self._by_trace_cap:
                    # drop the oldest half-assembled trace (shed or
                    # abandoned mid-journey); its spans stay in the ring
                    self._by_trace.pop(next(iter(self._by_trace)), None)
                bucket = self._by_trace[sp.trace_id] = []
            bucket.append(sp)
            if sp.is_root or sp.boundary:
                completed = self._by_trace.pop(sp.trace_id, [sp])
                self._retain(sp.trace_id, completed, sp.duration_s)
        TRACING_METRICS.observe(
            sp.stage, sp.duration_s, sp.trace_id, worker=sp.worker, units=sp.attrs
        )

    def _retain(self, trace_id: str, spans: list[Span], wall_s: float) -> None:
        """Exemplar retention (caller holds the lock): the slowest-N
        complete traces of each window survive ring eviction."""
        self.traces_total += 1
        now = _time.monotonic()
        if self._window_start is None:
            self._window_start = now
        elif now - self._window_start >= self.window_s:
            self._freeze_window()
            self._window_start = now
        entry = (wall_s, next(self._seq), trace_id, list(spans))
        if len(self._window_heap) < self.exemplar_slots:
            heapq.heappush(self._window_heap, entry)
        elif wall_s > self._window_heap[0][0]:
            heapq.heapreplace(self._window_heap, entry)

    def _freeze_window(self) -> None:
        if self._window_heap:
            frozen = sorted(
                ((w, tid, sp) for w, _seq, tid, sp in self._window_heap),
                reverse=True,
                key=lambda e: e[0],
            )
            self._retained.append(frozen)
        self._window_heap = []

    # -- remote ingest (coordinator side) --

    def ingest_remote(self, span_dicts: list[dict]) -> int:
        """Merge spans piggybacked from a cluster worker. Deduplicated
        by span id: the chaos harness can duplicate protocol frames
        (``cluster.send`` dup rules), and a duplicated frame must not
        double-count its spans."""
        ingested = 0
        for d in span_dicts or []:
            try:
                sid = d.get("span", "")
            except AttributeError:
                continue
            with self._lock:
                if not sid or sid in self._seen_remote:
                    self.remote_dupes_total += 1
                    continue
                if len(self._seen_remote_order) == self._seen_remote_order.maxlen:
                    self._seen_remote.discard(self._seen_remote_order[0])
                self._seen_remote_order.append(sid)
                self._seen_remote.add(sid)
                self.remote_spans_total += 1
            sp = Span.from_dict(d)
            self.finish(sp)
            ingested += 1
        return ingested

    # -- queries --

    def exemplar_traces(self) -> list[dict]:
        """All retained exemplar traces (current window + frozen
        windows), slowest first: ``{trace_id, wall_ms, spans}``."""
        with self._lock:
            entries = [(w, tid, sp) for w, _seq, tid, sp in self._window_heap]
            for window in self._retained:
                entries.extend(window)
        entries.sort(key=lambda e: e[0], reverse=True)
        out = []
        seen = set()
        for wall, tid, spans in entries:
            if tid in seen:
                continue
            seen.add(tid)
            out.append(
                {
                    "trace_id": tid,
                    "wall_ms": round(wall * 1000.0, 4),
                    "spans": [s.to_dict() for s in spans],
                }
            )
        return out

    def get_trace(self, trace_id: str) -> list[dict]:
        """Every known span of one trace (ring + exemplars + open),
        deduplicated, in start order."""
        found: dict[str, Span] = {}
        with self._lock:
            for sp in self._ring:
                if sp.trace_id == trace_id:
                    found[sp.span_id] = sp
            for sp in self._by_trace.get(trace_id, ()):
                found[sp.span_id] = sp
            entries = [(tid, sps) for _w, _s, tid, sps in self._window_heap]
            for window in self._retained:
                entries.extend((tid, sps) for _w, tid, sps in window)
            for tid, sps in entries:
                if tid == trace_id:
                    for sp in sps:
                        found[sp.span_id] = sp
            open_spans = [
                sp for sp in self._open.values() if sp.trace_id == trace_id
            ]
        out = [sp.to_dict() for sp in found.values()]
        now_mono = _time.monotonic()
        for sp in open_spans:
            d = sp.to_dict()
            d["open"] = True
            d["dur_ms"] = round((now_mono - sp.start_mono) * 1000.0, 4)
            out.append(d)
        out.sort(key=lambda d: d["start"])
        return out

    def open_spans(self) -> list[dict]:
        """Spans currently in flight — folded into flight-recorder
        dumps so a SIGKILLed worker's open request journeys survive."""
        with self._lock:
            spans = list(self._open.values())
        now_mono = _time.monotonic()
        out = []
        for sp in spans:
            d = sp.to_dict()
            d["open"] = True
            d["dur_ms"] = round(max(0.0, now_mono - sp.start_mono) * 1000.0, 4)
            out.append(d)
        return out

    def recent_spans(self, limit: int = 256) -> list[dict]:
        with self._lock:
            ring = list(self._ring)[-limit:]
        return [sp.to_dict() for sp in ring]

    def active(self) -> bool:
        with self._lock:
            return bool(self.spans_total or self._open)

    def snapshot(self) -> dict:
        with self._lock:
            exemplars = len(self._window_heap) + sum(
                len(w) for w in self._retained
            )
            return {
                "spans_total": self.spans_total,
                "traces_total": self.traces_total,
                "open_spans": len(self._open),
                "exemplars_retained": exemplars,
                "remote_spans_total": self.remote_spans_total,
                "remote_dupes_total": self.remote_dupes_total,
                "worker": self.worker,
            }

    # -- persistence (pathway trace CLI) --

    def dump(self, directory: str | None = None) -> str | None:
        """Write retained exemplars + the recent ring to
        ``trace-<stamp>-p<pid>.json``; returns the path (None when
        there is nothing to write or the write fails)."""
        if not self.active():
            return None
        try:
            directory = directory or default_trace_dir()
            os.makedirs(directory, exist_ok=True)
            stamp = _time.strftime("%Y%m%dT%H%M%S", _time.gmtime())
            pid = os.getpid()
            path = os.path.join(directory, f"trace-{stamp}-p{pid}.json")
            n = 1
            while os.path.exists(path):
                path = os.path.join(directory, f"trace-{stamp}-p{pid}-{n}.json")
                n += 1
            payload = {
                "version": TRACE_DUMP_FORMAT_VERSION,
                "pid": pid,
                "worker": self.worker,
                "created_at": _time.time(),
                "exemplars": self.exemplar_traces(),
                "recent": self.recent_spans(),
                "open": self.open_spans(),
            }
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1, default=repr)
                f.write("\n")
            os.replace(tmp, path)
            return path
        except Exception:
            return None

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._open.clear()
            self._by_trace.clear()
            self._window_start = None
            self._window_heap = []
            self._retained.clear()
            self._seen_remote.clear()
            self._seen_remote_order.clear()
            self._outbox = []
            self._outbox_enabled = False
            self.worker = 0
            self.spans_total = 0
            self.traces_total = 0
            self.remote_spans_total = 0
            self.remote_dupes_total = 0


#: Process-wide store (one per engine process; workers piggyback to the
#: coordinator's over the authenticated cluster channel).
TRACE_STORE = TraceStore()


# -- recording helpers ----------------------------------------------------


class _Off:
    """What :class:`span` hands out while tracing is off."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


_OFF = _Off()


class span:
    """``with span("stage", attr=...) as sp:`` — record one stage of
    the current request journey.

    With tracing off this is one check: the shared no-op comes back and
    nothing is built. On, the block's wall (``time.perf_counter``) and
    its work units (the attributes ``rows``, ``queries``, ``tokens``)
    add to the stage's totals, and its enter and exit are boundaries of
    the thread's timeline (``metrics._Timeline``), on the same two clock
    reads: a stage's self time is its wall less its children's.

    A :class:`Span` with ids is built (and yielded) only where there is
    a journey to hang it on: a trace context is bound, or
    ``new_trace=True`` — the admission path, where a request that
    arrived without a ``traceparent`` starts its journey, and the batch
    boundaries of the device plane, where with no request the batch is
    the request. Such a block is also, under a ``jax.profiler`` session,
    a ``TraceAnnotation("pw.<stage>")`` in the profile. Elsewhere (a
    bare ``remove``, a publish paid from another plane's read) the block
    yields None and leaves its totals: nothing in the ring, no event in
    the profile. While the block runs, the child context is bound so
    nested spans parent correctly — the same scoping ``bind_deadline``
    gives the request deadline.

    ``boundary=True`` marks the process-entry span of a journey (the
    HTTP request span): finishing it completes the trace for exemplar
    retention even when an inbound ``traceparent`` made it a child of
    the *client's* span rather than a local root.
    """

    __slots__ = ("_stage", "_options", "_profiled", "_sp", "_token", "_annotation", "_timeline", "_t0")

    def __new__(cls, stage: str, **options):
        """``options``: ``ctx``, ``new_trace``, ``boundary``, ``links`` and
        the span's attributes."""
        profiled = _profiling()  # asked once a span
        if not (_ENABLED or profiled):
            return _OFF
        self = object.__new__(cls)
        self._stage = stage
        self._options = options
        self._profiled = profiled
        self._sp = None
        return self

    def __enter__(self) -> Span | None:
        attrs = self._options  # what is left of them once the options are taken out
        parent = attrs.pop("ctx", None) or current_trace()
        journey = attrs.pop("new_trace", False) or parent is not None
        annotation = None
        if journey and self._profiled:
            annotation = _ANNOTATION("pw." + self._stage)
            annotation.__enter__()
        timeline = self._timeline = TRACING_METRICS.timeline()
        self._t0 = now = _time.perf_counter()
        timeline.enter(self._stage, now)
        if not journey:
            return None
        self._annotation = annotation
        if parent is None:
            trace_id, parent_id = gen_trace_id(), ""
        else:
            trace_id, parent_id = parent.trace_id, parent.span_id
        boundary, links = attrs.pop("boundary", False), attrs.pop("links", ())
        sp = Span(
            trace_id,
            gen_span_id(),
            parent_id,
            self._stage,
            worker=TRACE_STORE.worker,
            attrs=attrs,
            links=links,
        )
        sp.boundary = boundary
        self._sp = sp
        TRACE_STORE.begin(sp)
        self._token = bind_trace(TraceContext(trace_id, sp.span_id))
        self._token.__enter__()
        return sp

    def __exit__(self, exc_type, exc, tb) -> None:
        now = _time.perf_counter()
        seconds = now - self._t0
        sp = self._sp
        if sp is None:
            self._timeline.exit_bare(now, self._stage, seconds, TRACE_STORE.worker, self._options)
            return
        self._sp = None
        self._timeline.exit(now)
        self._token.__exit__()
        self._token = None
        if exc is not None:
            sp.attrs["error"] = type(exc).__name__
        sp.duration_s = seconds
        TRACE_STORE.finish(sp)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None


def dispatched(handle) -> None:
    """A site has enqueued device work. ``handle``: the smallest array
    that program returns — the thread's timeline keeps it, and no copy,
    until it is seen ready or the next dispatch replaces it. One check
    with tracing off."""
    if tracing_enabled():
        TRACING_METRICS.timeline().dispatched(handle, _time.perf_counter())


def waited() -> None:
    """A site has blocked until its results were on the host: the
    interval since the thread's last boundary was ``waiting``. One check
    with tracing off."""
    if tracing_enabled():
        TRACING_METRICS.timeline().waited(_time.perf_counter())


def record_span(
    stage: str,
    *,
    start_mono: float,
    end_mono: float,
    ctx: TraceContext | None = None,
    new_trace: bool = False,
    root_of: TraceContext | None = None,
    links: tuple = (),
    **attrs,
) -> Span | None:
    """Record an already-measured span from monotonic timestamps (the
    batcher measures queue wait / dispatch wall itself and records
    per-member spans after the fact).

    ``root_of=ctx`` closes the *root* span of ``ctx``'s trace — the
    span id is ``ctx.span_id`` (so spans recorded under ``ctx`` parent
    to it) and the parent is empty, which completes the trace and makes
    it eligible for exemplar retention. Embedded callers (bench
    drivers) use this: they admit and submit with a trace context, then
    close the journey root once the async dispatch finishes."""
    if not tracing_enabled():
        return None
    if root_of is not None:
        trace_id, parent_id, span_id = root_of.trace_id, "", root_of.span_id
    else:
        parent = ctx if ctx is not None else current_trace()
        if parent is None:
            if not new_trace:
                return None
            trace_id, parent_id = gen_trace_id(), ""
        else:
            trace_id, parent_id = parent.trace_id, parent.span_id
        span_id = gen_span_id()
    now_mono = _time.monotonic()
    sp = Span(
        trace_id,
        span_id,
        parent_id,
        stage,
        worker=TRACE_STORE.worker,
        start_unix=_time.time() - (now_mono - start_mono),
        start_mono=start_mono,
        duration_s=max(0.0, end_mono - start_mono),
        attrs=dict(attrs) if attrs else {},
        links=links,
    )
    TRACE_STORE.finish(sp)
    return sp


# -- dump files: load / list (pathway trace CLI) --------------------------


def load_trace_dump(path: str) -> dict:
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or "exemplars" not in data:
        raise ValueError(f"{path}: not a trace dump")
    return data


def list_trace_dumps(directory: str | None = None) -> list[str]:
    directory = directory or default_trace_dir()
    if not os.path.isdir(directory):
        return []
    out = [
        os.path.join(directory, name)
        for name in os.listdir(directory)
        if name.startswith("trace-") and name.endswith(".json")
    ]
    return sorted(out)
