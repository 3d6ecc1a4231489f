"""pw.run / pw.run_all.

Rebuild of /root/reference/python/pathway/internals/run.py (:12,:56)."""

from __future__ import annotations

import logging
import os
import sys
from dataclasses import dataclass, field
from typing import Any

from .graph_runner import GraphRunner
from .parse_graph import G

logger = logging.getLogger(__name__)


@dataclass
class RunResult:
    """What ``pw.run`` hands back after the graph completes.

    ``monitoring_http_port`` is the port the /metrics server actually
    bound (the ephemeral-port fallback and ``monitoring_http_port=0``
    resolve here), so tests and operators can discover the scrape
    endpoint programmatically; None when no HTTP server was requested.
    ``flight_recorder_dumps`` lists black-box dump files written during
    this run (supervisor restarts that later succeeded, etc.).
    ``serving_http_ports`` lists the ports the run's serving endpoints
    (``rest_connector`` / ``PathwayWebserver``) actually bound —
    explicit ports, ``port=0``, and the ephemeral-port fallback all
    resolve here. ``trace_dumps`` lists the request-trace exemplar
    files this run wrote (``tracing=True`` / PATHWAY_TRACING).
    ``health`` is the final :class:`HealthWatchdog` verdict (the
    machine-readable green/yellow/red document ``pathway doctor``
    renders) when the run had ``watchdog=`` / PATHWAY_WATCHDOG on;
    None otherwise."""

    monitoring_http_port: int | None = None
    flight_recorder_dumps: list[str] = field(default_factory=list)
    serving_http_ports: list[int] = field(default_factory=list)
    trace_dumps: list[str] = field(default_factory=list)
    health: dict | None = None


def _run_analysis(mode: str | None) -> None:
    """The opt-in pre-run verifier gate: "strict" raises AnalysisError
    on error-severity findings before any sink is built or connector
    started; "warn" prints them to stderr and continues; "off" (the
    default) skips. PATHWAY_ANALYSIS supplies the mode when the arg is
    None."""
    if mode is None:
        mode = os.environ.get("PATHWAY_ANALYSIS", "off")
    if mode in ("off", None):
        return
    if mode not in ("strict", "warn", "deep"):
        raise ValueError(
            f"analysis={mode!r}: expected 'strict', 'warn', 'deep', or 'off'"
        )
    from ..analysis import AnalysisError, analyze, has_errors, render_human

    # "deep" = strict + the jaxpr-level pass (PWL017..PWL020): the
    # pre-flight gate run before a composed graph touches a real chip
    diags = analyze(G, deep=(mode == "deep"))
    if not diags:
        return
    if mode in ("strict", "deep") and has_errors(diags):
        raise AnalysisError(diags)
    print(render_human(diags), file=sys.stderr)


def run(
    *,
    debug: bool = False,
    monitoring_level: Any = None,
    with_http_server: bool = False,
    monitoring_http_port: int | None = None,
    persistence_config: Any = None,
    license_key: str | None = None,
    runtime_typechecking: bool = True,
    terminate_on_error: bool = True,
    analysis: str | None = None,
    profile: Any = None,
    tracing: Any = None,
    watchdog: Any = None,
    chip_ledger: Any = None,
    recovery: Any = None,
    pipeline_depth: int | None = None,
    ingest_workers: int | None = None,
    mesh: Any = None,
    index_tiers: Any = None,
    decode: Any = None,
    tenancy: Any = None,
    elastic: Any = None,
    freshness: Any = None,
    cluster_accept_timeout: float | None = None,
    cluster_hello_timeout: float | None = None,
    cluster_lease_ms: float | None = None,
    cluster_partial_restarts: int | None = None,
    **kwargs: Any,
) -> RunResult | None:
    """Execute all registered outputs/subscriptions to completion
    (static sources) or until all streaming connectors close.

    ``profile``: a path (``profile="trace.json"``) writes a
    Chrome-trace-event JSON of per-operator epoch timings (open in
    Perfetto / chrome://tracing); ``profile=True`` uses
    ``pathway_profile.json``. The PATHWAY_PROFILE env var (set by the
    ``pathway profile`` CLI) supplies the path when the arg is None.

    ``tracing``: ``True`` turns on the per-request tracing plane for
    this run (spans for admission, batching, index search, decode…;
    slowest-trace exemplars dumped to PATHWAY_TRACE_DIR at run end and
    browsable with ``pathway trace``). Defaults to the PATHWAY_TRACING
    env var; ``tracing=False`` overrides an env-enabled plane.

    ``watchdog``: ``True`` starts the live :class:`HealthWatchdog`
    for this run — a background thread evaluating declarative rules
    (HBM time-to-OOM forecast, serving p99 burn rate, shed rate, tier
    hot-hit ratio) against the ledger/metrics streams, emitting
    ``health.breach`` flight events and a one-shot flight-recorder
    dump at critical. A string spec tunes it
    (``"interval=0.5,breach_for=3,oom_warn_s=900"``). Defaults to the
    PATHWAY_WATCHDOG env var; ``watchdog=False`` overrides. The final
    verdict lands in :attr:`RunResult.health` (and, when
    PATHWAY_HEALTH_OUT names a path, as JSON on disk for ``pathway
    doctor``).
    ``chip_ledger``: ``True`` turns on chip-time accounting for this
    run — every device dispatch books its device-seconds into the
    process-wide :data:`~pathway_tpu.internals.chip_ledger.CHIP_LEDGER`
    under plane accounts (encode, index.*, rerank, decode,
    ingest.stage, compile), surfaced on ``/metrics``/``/status``,
    ``pathway top`` and the flight recorder. Booking sites sync the
    dispatch to read the clock, so leave it off for latency-critical
    runs. Defaults to the PATHWAY_CHIP_LEDGER env var;
    ``chip_ledger=False`` overrides an env-enabled plane. Set
    PATHWAY_JOURNAL_DIR to also sample the ledger (plus the HBM ledger
    and serving/index gauges) into the on-disk metrics journal.

    ``freshness``: turns on the end-to-end freshness plane for this
    run — per-source event-time watermarks carried from connector
    arrival through staging, epoch execution and index publish, so
    every index shard exposes a visible watermark and every served
    answer carries a staleness bound (REST replies get an
    ``X-Pathway-Freshness-Ms`` header). ``True``/``"on"`` for
    defaults; ``"slo=250ms"`` (or ``{"slo_ms": 250}``) additionally
    sets the freshness SLO budget the watchdog's breach forecast and
    ``pathway top``'s coloring judge against. Defaults to the
    PATHWAY_FRESHNESS env var; ``freshness=False`` overrides an
    env-enabled plane. Surfaced on ``/metrics``/``/status``, the
    metrics journal, and the ``pathway freshness`` CLI.

    ``tenancy``: enables the multi-tenant serving plane for this run —
    ``True``/``"on"`` for defaults, a spec string
    (``"demote_every=64,qps=50,inflight=8"`` — quota knobs become the
    default per-tenant quota), a dict
    (``{"quotas": {"acme": {"qps": 100, "hbm": "64M", "weight": 2.0}},
    "default": {...}}``), or a
    :class:`~pathway_tpu.tenancy.TenancyConfig`. Admission, batching,
    and tenant-packed indexes built during the run read it via
    ``active_tenancy()``. Defaults to the PATHWAY_TENANCY env var.
    ``monitoring_http_port``: explicit /metrics port for
    ``with_http_server`` (0 = ephemeral); default 20000 + process_id.

    ``recovery``: ``True`` / restart budget int / a
    :class:`pathway_tpu.resilience.Recovery` — supervise the run: a
    worker-process death, connector exception or engine-epoch failure
    rebuilds the runner and restarts from the last persisted snapshot
    (requires ``persistence_config`` for exactly-once resumption; a
    restart without it re-reads sources from scratch). The budget
    exhausted, the run fails cleanly with
    :class:`pathway_tpu.resilience.RecoveryEscalated`.

    ``cluster_accept_timeout`` / ``cluster_hello_timeout``: bound
    multi-process cluster formation on the coordinator (defaults 60 s /
    10 s; also settable via PATHWAY_CLUSTER_ACCEPT_TIMEOUT /
    PATHWAY_CLUSTER_HELLO_TIMEOUT).

    ``cluster_lease_ms`` (default 30000, also PATHWAY_CLUSTER_LEASE_MS;
    0 disables): the cluster fault-domain lease. Coordinator and
    workers heartbeat at lease/3 over the authenticated protocol
    channel; a peer silent for a whole lease is declared lost. With
    persistence configured, a lost worker triggers a *partial restart*:
    the survivors quiesce at the last coordinated snapshot barrier,
    only the dead process is respawned (fenced against zombies by a
    durable generation token), and the run continues —
    ``cluster_partial_restarts`` (default 3, also
    PATHWAY_CLUSTER_PARTIAL_RESTARTS) bounds how many before the
    failure escalates to the full-restart supervisor. See README
    "Cluster fault domains".

    ``pipeline_depth``: overlapped host/device epoch pipeline (also
    PATHWAY_PIPELINE_DEPTH). 1 (default) keeps today's strict serial
    epoch loop; ``>= 2`` stages epoch N+1 on the host — connector
    drain, upsert resolution, the durable KIND_FEED record and
    non-blocking device staging — while epoch N still executes, so the
    scheduler only blocks on results a sink actually consumes. Output
    is identical at any depth (epochs still execute strictly in order);
    the recovered time shows up as ``overlap_ratio`` on the dashboard
    and ``pathway_host_prep_seconds`` / ``pathway_device_wait_seconds``
    on /metrics. See README "Performance".

    ``ingest_workers`` (also PATHWAY_INGEST_WORKERS; 0/None = off):
    size of the collaborative host-ingest stage — a bounded worker pool
    that parallelizes CPU-side prep (native tokenizer shards, image
    packing, per-source upsert resolution) while a single committer
    preserves order, so output is byte-identical at any worker count.
    PATHWAY_INGEST_AUTOSCALE=1 lets the pool grow/shrink from queue
    backlog and the host_prep/device_wait attribution. See README
    "Collaborative ingest"."""
    # recorded BEFORE the analyze-only return so `pathway analyze` sees
    # the run configuration too (rules PWL007/PWL008 read it off the
    # graph). The env fallback mirrors pwcfg.pipeline_depth, which is
    # not importable this early on the analyze-only path.
    try:
        _depth_ctx = (
            int(pipeline_depth)
            if pipeline_depth is not None
            else int(os.environ.get("PATHWAY_PIPELINE_DEPTH") or 1)
        )
    except ValueError:
        _depth_ctx = 1
    try:
        _ingest_ctx = (
            int(ingest_workers)
            if ingest_workers is not None
            else int(os.environ.get("PATHWAY_INGEST_WORKERS") or 0)
        )
    except ValueError:
        _ingest_ctx = 0
    try:
        _procs_ctx = int(os.environ.get("PATHWAY_PROCESSES") or 1)
    except ValueError:
        _procs_ctx = 1
    try:
        _threads_ctx = int(os.environ.get("PATHWAY_THREADS") or 1)
    except ValueError:
        _threads_ctx = 1
    try:
        _lease_ctx = (
            float(cluster_lease_ms)
            if cluster_lease_ms is not None
            else float(os.environ.get("PATHWAY_CLUSTER_LEASE_MS") or 30000.0)
        )
    except ValueError:
        _lease_ctx = 30000.0
    # mesh spec parsed jax-free so analyze-only runs (PWL010) see the
    # mesh shape without touching devices; malformed specs fail later,
    # loudly, on the real resolve_mesh path
    from ..parallel.mesh import parse_mesh_spec

    _mesh_spec = mesh if mesh is not None else (os.environ.get("PATHWAY_MESH") or None)
    try:
        _mesh_axes = parse_mesh_spec(_mesh_spec)
    except ValueError:
        _mesh_axes = None
    # tier spec parsed jax-free for the same reason: PWL010/PWL012 see
    # whether a cold tier is configured without touching devices
    from ..ops.tiered_knn import parse_tier_spec

    _tier_spec = (
        index_tiers
        if index_tiers is not None
        else (os.environ.get("PATHWAY_INDEX_TIERS") or None)
    )
    try:
        _tier_cfg = parse_tier_spec(_tier_spec)
    except ValueError:
        _tier_cfg = None
    # decode spec parsed jax-free too: PWL013 (HTTP LLM stage while a
    # device decode plane is configured) reads this off the graph
    from ..decode.config import parse_decode_spec

    _decode_spec = (
        decode if decode is not None else (os.environ.get("PATHWAY_DECODE") or None)
    )
    try:
        _decode_cfg = parse_decode_spec(_decode_spec)
    except ValueError:
        _decode_cfg = None
    # tenancy spec parsed jax-free too: PWL016 (tenancy without quotas)
    # reads this off the graph
    from ..tenancy.config import parse_tenancy_spec

    _tenancy_spec = (
        tenancy if tenancy is not None else (os.environ.get("PATHWAY_TENANCY") or None)
    )
    try:
        _tenancy_cfg = parse_tenancy_spec(_tenancy_spec)
    except ValueError:
        _tenancy_cfg = None
    # elastic spec parsed jax-free too: PWL022 (elastic watermarks with
    # no durable generation token) reads this off the graph
    from ..elastic.config import parse_elastic_spec

    _elastic_spec = (
        elastic if elastic is not None else (os.environ.get("PATHWAY_ELASTIC") or None)
    )
    try:
        _elastic_cfg = parse_elastic_spec(_elastic_spec)
    except ValueError:
        _elastic_cfg = None
    # explicit tracing= wins over PATHWAY_TRACING (tracing=False turns
    # an env-enabled plane off for this run)
    _tracing_on = (
        bool(tracing)
        if tracing is not None
        else str(os.environ.get("PATHWAY_TRACING", "")).strip().lower()
        in ("1", "true", "yes", "on")
    )
    # explicit watchdog= wins over PATHWAY_WATCHDOG (watchdog=False
    # turns an env-enabled watchdog off for this run); a malformed
    # spec raises here, before any sink is built
    from .ledger import parse_watchdog_spec

    _wd_raw = (
        watchdog
        if watchdog is not None
        else (os.environ.get("PATHWAY_WATCHDOG") or None)
    )
    _watchdog_cfg = parse_watchdog_spec(_wd_raw)
    # freshness spec parsed jax-free too (freshness/plane.py is
    # stdlib-only); a malformed spec raises here like watchdog's
    from ..freshness.plane import parse_freshness_spec

    _freshness_spec = (
        freshness
        if freshness is not None
        else (os.environ.get("PATHWAY_FRESHNESS") or None)
    )
    _freshness_cfg = parse_freshness_spec(_freshness_spec)
    # explicit chip_ledger= wins over PATHWAY_CHIP_LEDGER, same shape
    # as tracing; resolved jax-free (chip_ledger.py is stdlib-only)
    from .chip_ledger import CHIP_LEDGER, chip_ledger_enabled

    _chip_on = (
        bool(chip_ledger) if chip_ledger is not None else chip_ledger_enabled()
    )
    G.run_context = {
        "recovery": bool(recovery),
        "monitoring_level": monitoring_level,
        "with_http_server": bool(with_http_server),
        "persistence": persistence_config is not None,
        "pipeline_depth": max(1, _depth_ctx),
        # collaborative host-ingest stage size (0 = none configured);
        # PWL011 (host-bound ingest) reads this off the graph
        "ingest_workers": max(0, _ingest_ctx),
        # cluster shape for PWL009 (fault-domain coverage): analyze-only
        # runs read these off the graph without importing config
        "processes": max(1, _procs_ctx),
        "threads": max(1, _threads_ctx),
        "cluster_lease_ms": max(0.0, _lease_ctx),
        # {"data": n, "model": m} or None; PWL010 (index over HBM
        # budget) checks device-backed index footprints against this
        "mesh_axes": _mesh_axes,
        # TierConfig knob dict or None; PWL012 (beyond-HBM index with
        # no cold tier) treats a configured tier as the fix in place
        "index_tiers": _tier_cfg.as_dict() if _tier_cfg is not None else None,
        # DecodeConfig knob dict or None; PWL013 (HTTP LLM stage with a
        # device decode plane available) treats a configured decode as
        # the on-chip alternative being ready
        "decode": _decode_cfg.as_dict() if _decode_cfg is not None else None,
        # TenancyConfig knob dict or None; PWL016 (tenancy without
        # per-tenant quotas / oversubscribed quota HBM) reads this
        "tenancy": _tenancy_cfg.as_dict() if _tenancy_cfg is not None else None,
        # ElasticConfig knob dict or None; PWL022 (elastic reshard
        # configured without durable persistence) reads this
        "elastic": _elastic_cfg.as_dict() if _elastic_cfg is not None else None,
        # request-journey tracing + profiler intent, resolved jax-free;
        # PWL014 (SLO budget with no observability) reads both
        "tracing": _tracing_on,
        "profile": bool(profile) or bool(os.environ.get("PATHWAY_PROFILE")),
        # live health watchdog intent, resolved jax-free like tracing
        "watchdog": _watchdog_cfg is not None,
        # chip-time accounting intent, resolved jax-free; PWL021
        # (SLO/watchdog run with no chip-time attribution) reads this
        "chip_ledger": _chip_on,
        # FreshnessConfig knob dict or None; PWL024 (unmeasurable
        # freshness SLO) reads this plus whether the watchdog spec
        # tuned freshness thresholds with the plane itself off
        "freshness": _freshness_cfg.as_dict() if _freshness_cfg is not None else None,
        "watchdog_freshness": "freshness_" in str(_wd_raw or ""),
    }
    if os.environ.get("PATHWAY_ANALYZE_ONLY"):
        # `pathway analyze <program>`: the graph is fully described at
        # this point — return before sinks are built or readers started
        return None
    _run_analysis(analysis)
    # (re)configure the collaborative host-ingest stage for this run;
    # env-only configuration (PATHWAY_INGEST_WORKERS) is honored lazily
    # by ingest.get_stage(), so only explicit args need action here
    if ingest_workers is not None:
        from ..ingest import stage as _ingest_stage

        if _ingest_ctx > 0:
            _ingest_stage.configure_stage(_ingest_ctx)
        else:
            _ingest_stage.shutdown_stage()
    from .config import get_pathway_config, pathway_config
    from .licensing import License, check_worker_count
    from .telemetry import Telemetry

    pwcfg = get_pathway_config()
    # precedence: explicit arg > pw.set_license_key() (mutates the
    # module-level pathway_config) > env
    lic = License.new(license_key or pathway_config.license_key or pwcfg.license_key)
    # scale gate (reference config.rs MAX_WORKERS free tier)
    check_worker_count(lic, pwcfg.n_workers)
    telemetry = Telemetry()  # PATHWAY_TELEMETRY_SERVER (local file) or no-op

    # per-operator profiler: explicit profile=/PATHWAY_PROFILE always
    # activates it; it also rides along whenever another surface that
    # can show its numbers is up (telemetry, /metrics)
    if profile is True:
        profile_path: str | None = "pathway_profile.json"
    elif profile:
        profile_path = os.fspath(profile)
    else:
        profile_path = pwcfg.profile_path
    profiler = None
    if profile_path is not None or telemetry.enabled or with_http_server:
        from .profiler import RunProfiler, set_current_profiler

        profiler = RunProfiler()
    # request-journey tracing plane: installed for the whole run (the
    # admission/batching/index/decode span sites read the module flag),
    # restored on exit so nested test runs do not leak the setting
    from .. import tracing as _req_tracing

    _prev_tracing = _req_tracing.set_tracing_enabled(_tracing_on)
    # live health watchdog: a background thread evaluating declarative
    # rules against the ledger/serving/index metric streams for the
    # duration of the run; the final verdict lands in RunResult.health
    _watchdog = None
    if _watchdog_cfg is not None:
        from .ledger import HealthWatchdog

        _watchdog = HealthWatchdog(
            rules=_watchdog_cfg["rules"],
            interval_s=_watchdog_cfg["interval_s"],
        )
        _watchdog.start()
    # chip-time accounting override for this run (restored on exit so
    # nested test runs do not leak the setting)
    _prev_chip = CHIP_LEDGER._override
    CHIP_LEDGER.set_enabled(bool(chip_ledger) if chip_ledger is not None else None)
    # freshness plane override for this run, same shape (restored on
    # exit); the SLO budget rides on the plane for watchdog/top/status
    from ..freshness.plane import FRESHNESS

    _prev_fresh = FRESHNESS._override
    FRESHNESS.set_enabled(
        (_freshness_cfg is not None) if freshness is not None else None
    )
    FRESHNESS.configure(_freshness_cfg)
    # metrics journal sampler: periodic chip/HBM/serving/index samples
    # under PATHWAY_JOURNAL_DIR for the duration of the run
    _journal_sampler = None
    from ..perf.journal import JournalSampler, get_journal

    _journal = get_journal()
    if _journal is not None:
        _journal_sampler = JournalSampler(_journal)
        _journal_sampler.start()

    n_workers = max(1, pwcfg.threads)
    processes = max(1, pwcfg.processes)
    depth = max(
        1, int(pipeline_depth) if pipeline_depth is not None else pwcfg.pipeline_depth
    )
    if persistence_config is None:
        # CLI record/replay wiring (reference cli.py:166-193): spawn's
        # --record/--replay-mode flags arrive via PATHWAY_REPLAY_* env
        if pwcfg.replay_storage:
            from .. import persistence as _persistence

            persistence_config = _persistence.Config.simple_config(
                _persistence.Backend.filesystem(pwcfg.replay_storage),
                persistence_mode=pwcfg.replay_mode or "batch",
            )
            # CLI-driven runs record/replay every source, not just those
            # with an explicit persistent_id
            persistence_config.auto_persistent_ids = True
    accept_timeout = (
        cluster_accept_timeout
        if cluster_accept_timeout is not None
        else pwcfg.cluster_accept_timeout
    )
    hello_timeout = (
        cluster_hello_timeout
        if cluster_hello_timeout is not None
        else pwcfg.cluster_hello_timeout
    )
    lease_ms = (
        float(cluster_lease_ms)
        if cluster_lease_ms is not None
        else pwcfg.cluster_lease_ms
    )
    partial_budget = (
        max(0, int(cluster_partial_restarts))
        if cluster_partial_restarts is not None
        else pwcfg.cluster_partial_restarts
    )

    def _build_runner(is_restart: bool) -> GraphRunner:
        """Fresh runner + sinks + subscriptions per (re)start attempt:
        a crashed attempt's engine state is unrecoverable in place —
        the persistence layer replays input snapshots into a clean
        graph instead."""
        runner = GraphRunner(n_workers=n_workers, pipeline_depth=depth)
        # consumed by sinks (e.g. fs.write appends instead of
        # truncating when the supervisor restarts a run)
        runner.recovery_restart = is_restart
        if processes > 1 and pwcfg.process_id > 0:
            # worker process of a `pathway spawn --processes P` cluster:
            # same graph, no sink callbacks, no reader threads
            runner.suppress_callbacks = True
        runner.engine.terminate_on_error = terminate_on_error
        for r in runner._replicas:
            r.engine.terminate_on_error = terminate_on_error
        if profiler is not None:
            runner.attach_profiler(profiler)
        if persistence_config is not None:
            runner.engine.persistence_config = persistence_config
        for table, sink in list(G.outputs):
            sink_builder = sink.get("build")
            if sink_builder is not None:
                sink_builder(runner, table)
        for spec in list(G.subscriptions):
            runner.subscribe(
                spec["table"],
                on_change=spec.get("on_change"),
                on_time_end=spec.get("on_time_end"),
                on_end=spec.get("on_end"),
            )
        return runner

    if profiler is not None:
        set_current_profiler(profiler)  # jit hooks in models/ + udfs/
    import contextlib

    from .monitoring import MonitoringLevel, monitor_stats

    level = MonitoringLevel.coerce(monitoring_level).resolve()
    need_monitor = with_http_server or level is not MonitoringLevel.NONE
    # monitor_stats renders the reference's rich PROGRESS DASHBOARD
    # (monitoring.py:56) at IN_OUT/ALL on process 0; NONE yields a plain
    # collector (still wanted for the Prometheus endpoint)
    mon_ctx = (
        monitor_stats(
            level, process_id=pwcfg.process_id, screen=sys.stderr.isatty()
        )
        if need_monitor
        else contextlib.nullcontext(None)
    )
    from . import flight_recorder

    result = RunResult()
    dumps_before = len(flight_recorder.RECORDER._dumped_paths)
    # activate the run-scoped mesh: device-backed indexes built during
    # lowering (nearest_neighbors._make_device_index) pick it up via
    # parallel.mesh.active_mesh() — zero query-API change. Only installed
    # when the run has one, so an outer use_mesh() scope survives runs
    # that don't override it.
    from ..parallel.mesh import resolve_mesh, set_active_mesh

    # JAX's persistent compile cache, placed before this run compiles
    # anything. Only in a process that has loaded JAX (an embedder or
    # an index built at graph time has) or is about to for the mesh:
    # host-only ETL runs never import it and should not for a cache.
    if mesh is not None or "jax" in sys.modules:
        from .compile_cache import configure_compile_cache

        configure_compile_cache()
    _run_mesh = resolve_mesh(mesh) if mesh is not None else None
    if _run_mesh is not None:
        set_active_mesh(_run_mesh)
    # activate the run-scoped tier config the same way: tiered indexes
    # built during lowering pick it up via tiered_knn.active_tiers()
    from ..ops.tiered_knn import set_active_tiers

    if index_tiers is not None and _tier_cfg is not None:
        set_active_tiers(_tier_cfg)
    # and the run-scoped decode config: DecodeEngine / DecodeService
    # construction during this run picks it up via active_decode()
    from ..decode.config import set_active_decode

    if decode is not None and _decode_cfg is not None:
        set_active_decode(_decode_cfg)
    # and the run-scoped tenancy config: admission / batching / packed
    # indexes during this run pick it up via active_tenancy()
    from ..tenancy.config import set_active_tenancy

    if tenancy is not None and _tenancy_cfg is not None:
        set_active_tenancy(_tenancy_cfg)
    # and the run-scoped elastic config: register_handle-wrapped indexes
    # and the reshard controller pick it up via active_elastic(); the
    # watermark loop only starts when there is something to watch
    from ..elastic.config import set_active_elastic

    _elastic_ctl = None
    if elastic is not None and _elastic_cfg is not None:
        set_active_elastic(_elastic_cfg)
    elif _mesh_axes is not None and _mesh_axes.get("auto") and _elastic_cfg is None:
        # mesh="auto" with no explicit elastic= arms the default
        # auto-watermark envelope
        from ..elastic.config import ElasticConfig

        _elastic_cfg = ElasticConfig(auto=True)
        set_active_elastic(_elastic_cfg)
    if _elastic_cfg is not None and (
        _elastic_cfg.watermarks_armed() or _elastic_cfg.shards is not None
    ):
        from ..elastic.controller import ElasticController

        _elastic_ctl = ElasticController(_elastic_cfg)
        _elastic_ctl.start()
    with mon_ctx as monitor:
        http_server = None
        if with_http_server:
            # Prometheus endpoint on 20000 + process_id (reference
            # src/engine/http_server.rs:21), or an explicit port
            from .http_monitoring import MonitoringHttpServer

            http_server = MonitoringHttpServer(monitor, port=monitoring_http_port)
            http_server.start()
            # the actually-bound port (explicit, default, or the
            # ephemeral fallback) — discoverable programmatically
            result.monitoring_http_port = http_server.port
            if monitor is not None:
                monitor.http_port = http_server.port
        run_span = None

        # cluster fault domain: partial restarts replace ONLY the dead
        # worker process. The regroup loops live OUTSIDE the supervisor,
        # so a partial restart never charges the full-restart budget
        # (pathway_supervisor_restarts_total stays 0 for them).
        children: list[Any] = []
        fence_gens: dict[int, int] = {}

        def _respawn_worker(wpid: int, generation: int) -> None:
            """Same interpreter + argv (every process runs the same
            program), with the dead worker's slot and the bumped
            generation in the environment — the generation is what lets
            the coordinator tell the replacement from a zombie. Like
            every worker it is held to the CPU: this process keeps the
            chips."""
            import subprocess

            from .config import worker_process_env

            env = worker_process_env(os.environ, wpid)
            env["PATHWAY_CLUSTER_GENERATION"] = str(generation)
            children.append(subprocess.Popen([sys.executable] + sys.argv, env=env))

        def _coordinator_attempt(runner: GraphRunner) -> None:
            from ..resilience import ClusterRegroup

            budget = partial_budget
            while True:
                try:
                    runner.run_coordinator(
                        processes,
                        pwcfg.first_port,
                        monitoring_callback=monitor.update if monitor else None,
                        accept_timeout=accept_timeout,
                        hello_timeout=hello_timeout,
                        lease_ms=lease_ms,
                        fence=fence_gens,
                    )
                    return
                except ClusterRegroup as regroup:
                    path = flight_recorder.dump("cluster.partial_restart", regroup)
                    if path:
                        logger.warning(
                            "cluster partial restart (generation %d, dead=%s): "
                            "flight recorder dump written to %s",
                            regroup.generation,
                            regroup.dead_pids,
                            path,
                        )
                    if budget <= 0:
                        from ..engine.dataflow import EngineError

                        raise EngineError(
                            "cluster partial-restart budget exhausted "
                            f"({partial_budget}): {regroup}"
                        ) from regroup
                    budget -= 1
                    if pwcfg.cluster_respawn:
                        for wpid in regroup.dead_pids:
                            fence_gens[wpid] = regroup.generation
                            _respawn_worker(wpid, regroup.generation)
                    # survivors' volatile state is stale: rebuild the
                    # runner like a supervisor restart and re-form the
                    # cluster; persistence rehydrates from the barrier
                    runner = _build_runner(True)

        def _worker_attempt(runner: GraphRunner) -> None:
            from ..resilience import ClusterRegroup

            # a survivor regroups once per coordinator partial restart
            # (plus its own lease expiries under partitions); the real
            # budget is enforced on the coordinator
            budget = partial_budget + 2
            while True:
                try:
                    runner.run_worker(
                        processes,
                        pwcfg.first_port,
                        pwcfg.process_id,
                        lease_ms=lease_ms,
                    )
                    return
                except ClusterRegroup:
                    if budget <= 0:
                        raise
                    budget -= 1
                    runner = _build_runner(True)

        def _attempt(is_restart: bool) -> None:
            runner = _build_runner(is_restart)
            if processes > 1:
                # reference CommunicationConfig::Cluster (config.rs:62-86):
                # P processes × T threads; coordinator = process 0
                if pwcfg.process_id == 0:
                    _coordinator_attempt(runner)
                else:
                    _worker_attempt(runner)
            else:
                runner.run(monitoring_callback=monitor.update if monitor else None)

        from ..resilience import Recovery, RecoveryEscalated, Supervisor

        try:
            with telemetry.span(
                "graph_runner.run", workers=pwcfg.n_workers
            ) as run_span:
                rec = Recovery.coerce(recovery)
                if rec is None:
                    _attempt(False)
                else:
                    if persistence_config is None:
                        import warnings

                        warnings.warn(
                            "pw.run(recovery=...) without persistence_config: "
                            "restarts re-read every source from scratch and "
                            "may re-deliver output already flushed before the "
                            "crash; configure persistence for exactly-once "
                            "resumption",
                            stacklevel=2,
                        )
                    Supervisor(rec).run(_attempt)
        except RecoveryEscalated:
            raise  # the supervisor already dumped + attached the path
        except Exception as exc:
            # unsupervised crash: preserve the last seconds of engine
            # events before the traceback unwinds the run
            path = flight_recorder.dump("crash", exc)
            if path:
                logger.error("flight recorder dump written to %s", path)
            raise
        finally:
            # reap respawned worker processes: on a clean run they saw
            # END and exit immediately; after a failure they must not
            # outlive the coordinator
            for child in children:
                try:
                    child.wait(timeout=15.0)
                except Exception:
                    try:
                        child.kill()
                    except Exception:
                        pass
            if profiler is not None:
                set_current_profiler(None)
            if monitor is not None:
                telemetry.gauge("rows_in", monitor.snapshot.rows_in)
                telemetry.gauge("rows_out", monitor.snapshot.rows_out)
            if profiler is not None and telemetry.enabled:
                # per-operator child spans nest under the run span and
                # must land before the flush posts /v1/traces
                profiler.emit_telemetry(telemetry, parent=run_span)
            if _tracing_on and telemetry.enabled:
                # retained request-journey exemplars ride the same OTLP
                # flush, with their real trace/span ids preserved
                _req_tracing.emit_telemetry(telemetry)
            telemetry.flush()
            if profiler is not None and profile_path is not None:
                profiler.write_chrome_trace(profile_path)
            if http_server is not None:
                http_server.stop()
            if _run_mesh is not None:
                set_active_mesh(None)
            if index_tiers is not None and _tier_cfg is not None:
                set_active_tiers(None)
            if decode is not None and _decode_cfg is not None:
                set_active_decode(None)
            if tenancy is not None and _tenancy_cfg is not None:
                set_active_tenancy(None)
            if _elastic_ctl is not None:
                _elastic_ctl.stop()
            if _elastic_cfg is not None:
                set_active_elastic(None)
            if _watchdog is not None:
                _watchdog.stop()
                # one final evaluation so even runs shorter than the
                # watchdog interval leave a verdict (and a critical
                # breach observed only at the end still dumps)
                _watchdog.evaluate_once()
                result.health = _watchdog.verdict()
                health_out = os.environ.get("PATHWAY_HEALTH_OUT")
                if health_out:
                    import json

                    try:
                        with open(health_out, "w", encoding="utf-8") as fh:
                            json.dump(result.health, fh, indent=2, sort_keys=True)
                    except OSError:
                        logger.warning(
                            "could not write health verdict to %s", health_out
                        )
            result.flight_recorder_dumps = list(
                flight_recorder.RECORDER._dumped_paths[dumps_before:]
            )
            if _tracing_on:
                tp = _req_tracing.TRACE_STORE.dump()
                if tp:
                    result.trace_dumps.append(tp)
                    logger.info("request trace dump written to %s", tp)
            _req_tracing.set_tracing_enabled(_prev_tracing)
            if _journal_sampler is not None:
                # writes one final sample (the run's parting state)
                _journal_sampler.stop()
            CHIP_LEDGER.set_enabled(_prev_chip)
            FRESHNESS.set_enabled(_prev_fresh)
    try:
        from ..io.http._server import bound_serving_ports

        result.serving_http_ports = bound_serving_ports()
    except ImportError:  # aiohttp not installed — no serving surface
        pass
    return result


def run_all(**kwargs: Any) -> RunResult | None:
    return run(**kwargs)
