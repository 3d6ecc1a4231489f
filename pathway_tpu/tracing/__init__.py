"""Request-journey tracing plane.

Per-request traces across the whole serving path — admission queue
wait, adaptive-batch fan-in (batch spans *link* their member request
traces), mesh per-shard top-k + on-device merge, tiered hot/cold
probes, reranking, and per-tick decode steps — with p99 exemplar
retention, a tail-attribution aggregator, OTLP export, and the
``pathway trace`` CLI. The device plane's write and text-query paths
(``ops/knn.py``, ``models/sentence_encoder.py``, the embedder's
``encode_device``) go through the same
:class:`span`: with no request bound, a write batch, an embed batch or
a query batch is the journey. See README "Request tracing".

On with ``pw.run(tracing=True)`` or ``PATHWAY_TRACING=1``, and for as
long as a ``jax.profiler`` session runs: every span of a journey is
then also a ``TraceAnnotation("pw.<stage>")`` in the profile, on the
device trace's clock. With tracing off every instrumentation site is a
single check. :func:`stage_totals` reads each stage's calls, seconds and
work units (``rows``, ``queries``, ``tokens``) and, for a thread that
dispatches device work (:func:`dispatched`, :func:`waited`), each
stage's self time split by whether the device had work to run.
"""

from __future__ import annotations

from .attribution import attribute, render_slow_report, render_waterfall, slow_report
from .context import (
    TRACE_RESPONSE_HEADER,
    TRACEPARENT_HEADER,
    TraceContext,
    bind_trace,
    current_trace,
)
from .metrics import TRACING_METRICS, TracingMetrics
from .store import (
    Span,
    TRACE_STORE,
    TraceStore,
    default_trace_dir,
    dispatched,
    list_trace_dumps,
    load_trace_dump,
    record_span,
    set_tracing_enabled,
    span,
    tracing_enabled,
    waited,
)

__all__ = [
    "Span",
    "TRACE_RESPONSE_HEADER",
    "TRACE_STORE",
    "TRACEPARENT_HEADER",
    "TRACING_METRICS",
    "TraceContext",
    "TraceStore",
    "TracingMetrics",
    "attribute",
    "bind_trace",
    "current_trace",
    "default_trace_dir",
    "dispatched",
    "emit_telemetry",
    "ensure_trace",
    "list_trace_dumps",
    "load_trace_dump",
    "record_span",
    "render_slow_report",
    "render_waterfall",
    "set_tracing_enabled",
    "set_worker",
    "slow_report",
    "span",
    "stage_totals",
    "tracing_enabled",
    "waited",
]


def stage_totals() -> dict[str, dict]:
    """``{stage: {"calls", "seconds", "rows", "queries", "tokens"}}``
    over every span finished since tracing came on (or the last
    ``TRACING_METRICS.reset()``), summed over workers. Stages of a
    thread that dispatched device work also have ``self_seconds`` and
    its parts ``starved_seconds`` (nothing of the thread's in flight on
    the device: a lower bound of its idle time), ``overlapped_seconds``
    and ``waiting_seconds``; the pseudo-stages ``caller`` and
    ``timeline`` hold what no stage covers and the sums
    (:meth:`TracingMetrics.totals`)."""
    return TRACING_METRICS.totals()


def ensure_trace() -> TraceContext | None:
    """The current trace context, generating a fresh one when tracing
    is on and the request arrived without a ``traceparent`` — the
    admission controller calls this so even requests admitted outside
    the HTTP surface (bench drivers, embedded callers) get a journey."""
    if not tracing_enabled():
        return current_trace()
    ctx = current_trace()
    return ctx if ctx is not None else TraceContext.new()


def set_worker(worker_id: int) -> None:
    """Cluster-worker initialization: label this process's spans and
    start buffering them for the coordinator piggyback."""
    TRACE_STORE.configure_worker(worker_id)


def emit_telemetry(telemetry) -> int:
    """Export the retained exemplar traces through the run's OTLP
    exporter (PR 2's :class:`~pathway_tpu.internals.telemetry.Telemetry`)
    with their *real* per-request trace ids, so an OTel collector shows
    request journeys alongside the run/profiler spans."""
    count = 0
    for tr in TRACE_STORE.exemplar_traces():
        for s in tr["spans"]:
            start_ns = int(float(s.get("start", 0.0)) * 1e9)
            end_ns = start_ns + int(float(s.get("dur_ms", 0.0)) * 1e6)
            attrs = dict(s.get("attrs") or {})
            attrs["pathway.stage"] = s.get("stage", "?")
            attrs["pathway.worker"] = s.get("worker", 0)
            telemetry.add_span(
                f"request.{s.get('stage', '?')}",
                start_unix_ns=start_ns,
                end_unix_ns=end_ns,
                attrs=attrs,
                trace_id=s.get("trace", ""),
                span_id=s.get("span", ""),
                parent_span_id=s.get("parent", ""),
            )
            count += 1
    return count
