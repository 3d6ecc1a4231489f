"""``pathway`` command-line interface.

Rebuild of /root/reference/python/pathway/cli.py: ``spawn`` launches a
program as N OS processes with the PATHWAY_* worker-topology env vars
(reference cli.py:53-110; engine config src/engine/dataflow/config.rs:
88-120), ``spawn-from-env`` re-reads the spawn arguments from
PATHWAY_SPAWN_ARGS, and ``--record``/``--replay`` wire stream
record/replay through env (reference cli.py:166-193). In the TPU build
the spawned processes scale the host dataflow only: process 0 owns
every chip of the host (one process per chip, driven through
``pw.run(mesh=...)``) and the others are started with
``JAX_PLATFORMS=cpu`` (internals/config.py worker_process_env).
"""

from __future__ import annotations

import os
import shlex
import subprocess
import sys

import click


@click.group()
def cli() -> None:
    """Pathway-TPU command line."""


def _spawn_program(
    threads: int,
    processes: int,
    first_port: int,
    record: bool,
    record_path: str | None,
    replay_mode: str | None,
    program: tuple[str, ...],
) -> int:
    argv = list(program)
    if not argv:
        raise click.UsageError("no program given")
    if argv[0].endswith(".py"):
        argv = [sys.executable] + argv
    import secrets

    env_base = os.environ.copy()
    env_base["PATHWAY_THREADS"] = str(threads)
    env_base["PATHWAY_PROCESSES"] = str(processes)
    env_base["PATHWAY_FIRST_PORT"] = str(first_port)
    # per-cluster shared secret authenticating the worker protocol
    # (parallel/multiprocess.py handshake)
    env_base.setdefault("PATHWAY_CLUSTER_TOKEN", secrets.token_hex(16))
    env_base["PATHWAY_SPAWN_ARGS"] = shlex.join(
        [f"--threads={threads}", f"--processes={processes}", f"--first-port={first_port}"]
        + (["--record"] if record else [])
        + ([f"--record-path={record_path}"] if record_path else [])
        + ([f"--replay-mode={replay_mode}"] if replay_mode else [])
        + list(program)
    )
    if record or replay_mode:
        env_base["PATHWAY_REPLAY_STORAGE"] = record_path or "./record"
        env_base["PATHWAY_REPLAY_MODE"] = replay_mode or "record"

    procs: list[subprocess.Popen] = []
    try:
        from .internals.config import worker_process_env

        for pid in range(processes):
            procs.append(
                subprocess.Popen(argv, env=worker_process_env(env_base, pid))
            )
    except OSError:
        for p in procs:
            p.terminate()
        raise
    rc = 0
    try:
        for p in procs:
            code = p.wait()
            if code < 0:
                # killed by signal: report the conventional 128+N status
                # instead of letting sys.exit() truncate the negative
                code = 128 - code
            if code and not rc:
                rc = code
    except KeyboardInterrupt:
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait()
        rc = 130
    return rc


@cli.command(
    context_settings={"allow_extra_args": True, "ignore_unknown_options": True}
)
@click.option("--threads", "-t", default=1, show_default=True, help="worker threads per process")
@click.option("--processes", "-n", default=1, show_default=True, help="OS processes (hosts of the mesh)")
@click.option("--first-port", default=10000, show_default=True, help="base port for inter-process coordination")
@click.option("--record", is_flag=True, help="record input streams to --record-path for later replay")
@click.option("--record-path", default=None, help="stream record/replay storage directory")
@click.option(
    "--replay-mode",
    default=None,
    type=click.Choice(["batch", "speedrun"]),
    help="replay previously recorded streams instead of reading sources",
)
@click.argument("program", nargs=-1, required=True)
def spawn(threads, processes, first_port, record, record_path, replay_mode, program):
    """Run PROGRAM with a pathway worker topology, e.g.:

    pathway spawn --threads 2 --processes 4 my_pipeline.py
    """
    sys.exit(
        _spawn_program(threads, processes, first_port, record, record_path, replay_mode, program)
    )


@cli.command(name="spawn-from-env")
def spawn_from_env():
    """Re-run ``spawn`` with arguments taken from PATHWAY_SPAWN_ARGS
    (reference cli.py spawn-from-env; used by container deployments)."""
    raw = os.environ.get("PATHWAY_SPAWN_ARGS", "")
    if not raw:
        raise click.UsageError("PATHWAY_SPAWN_ARGS is not set")
    # standalone_mode=False returns instead of exiting, so the child's
    # status reaches OUR caller rather than being decided inside the
    # nested click invocation
    try:
        rv = spawn.main(args=shlex.split(raw), standalone_mode=False)
    except SystemExit as e:  # spawn's callback sys.exit()s its rc
        sys.exit(e.code or 0)
    sys.exit(int(rv) if rv else 0)


@cli.command()
@click.option("--json", "as_json", is_flag=True, help="emit diagnostics as JSON")
@click.option(
    "--strict-warnings",
    is_flag=True,
    help="deprecated alias for --fail-on=warn",
)
@click.option(
    "--fail-on",
    type=click.Choice(["warn", "error"]),
    default="error",
    show_default=True,
    help="lowest severity that makes the exit code nonzero",
)
@click.option(
    "--deep",
    is_flag=True,
    help="also run the jaxpr-level deep pass (rules PWL017..PWL020)",
)
@click.argument("program", required=True)
@click.argument("arguments", nargs=-1)
def analyze(as_json, strict_warnings, fail_on, deep, program, arguments):
    """Statically verify PROGRAM's dataflow graph without running it.

    The program executes with PATHWAY_ANALYZE_ONLY=1, so pw.run()
    returns before building sinks or starting connectors; the verifier
    (pathway_tpu.analysis, rules PWL001..PWL016 — plus PWL017..PWL020
    with --deep) then walks the graph it described. Exits 1 when
    findings at or above --fail-on severity exist, 3 when the program
    itself fails to build its graph.
    """
    from .analysis.program import analyze_program

    sys.exit(
        analyze_program(
            program,
            list(arguments),
            as_json=as_json,
            strict_warnings=strict_warnings,
            fail_on=fail_on,
            deep=deep,
        )
    )


@cli.command(
    context_settings={"allow_extra_args": True, "ignore_unknown_options": True}
)
@click.option(
    "--output",
    "-o",
    default="pathway_profile.json",
    show_default=True,
    help="Chrome-trace-event JSON output path",
)
@click.argument("program", nargs=-1, required=True)
def profile(output, program):
    """Run PROGRAM with the per-operator profiler enabled and write a
    Chrome-trace-event JSON, e.g.:

    pathway profile -o trace.json my_pipeline.py

    Open the result in Perfetto (https://ui.perfetto.dev) or
    chrome://tracing: one track per worker, one slice per node-epoch,
    plus a jit track with compile/execute splits.
    """
    argv = list(program)
    if argv[0].endswith(".py"):
        argv = [sys.executable] + argv
    env = os.environ.copy()
    env["PATHWAY_PROFILE"] = output
    # make pathway_tpu importable from dev checkouts: the child's
    # sys.path roots at the program's directory, not ours
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (
        pkg_root + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH")
        else pkg_root
    )
    rc = subprocess.call(argv, env=env)
    if rc == 0:
        click.echo(
            f"profile written to {output} — load it at https://ui.perfetto.dev",
            err=True,
        )
    sys.exit(rc)


_DOCTOR_EXIT = {"green": 0, "yellow": 1, "red": 2}


@cli.command(
    context_settings={"allow_extra_args": True, "ignore_unknown_options": True}
)
@click.option(
    "--json", "as_json", is_flag=True, help="emit the machine-readable verdict JSON"
)
@click.option(
    "--watchdog",
    "spec",
    default=None,
    help="watchdog spec override, e.g. 'interval=0.2,breach_for=1'",
)
@click.argument("program", nargs=-1, required=True)
def doctor(as_json, spec, program):
    """Run PROGRAM with the health watchdog on and render its verdict.

    The child runs with PATHWAY_WATCHDOG set (kept if already set,
    unless --watchdog overrides) and writes the machine-readable
    verdict to a temp file via PATHWAY_HEALTH_OUT; doctor renders it
    green/yellow/red per plane with evidence lines. Exit codes:
    0 green, 1 yellow, 2 red, 3 the program failed or left no verdict.
    """
    import json as _json
    import tempfile

    argv = list(program)
    if argv[0].endswith(".py"):
        argv = [sys.executable] + argv
    env = os.environ.copy()
    if spec is not None:
        env["PATHWAY_WATCHDOG"] = spec
    elif not env.get("PATHWAY_WATCHDOG"):
        env["PATHWAY_WATCHDOG"] = "on"
    fd, out_path = tempfile.mkstemp(prefix="pathway-doctor-", suffix=".json")
    os.close(fd)
    env["PATHWAY_HEALTH_OUT"] = out_path
    # make pathway_tpu importable from dev checkouts (same reason as
    # `pathway profile`): the child's sys.path roots at the program's
    # directory, not ours
    pkg_root = os.path.dirname(os.path.abspath(__file__))
    pkg_parent = os.path.dirname(pkg_root)
    env["PYTHONPATH"] = (
        pkg_parent + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH")
        else pkg_parent
    )
    try:
        rc = subprocess.call(argv, env=env)
        verdict = None
        try:
            with open(out_path, encoding="utf-8") as fh:
                raw = fh.read()
            if raw.strip():
                verdict = _json.loads(raw)
        except (OSError, ValueError):
            verdict = None
    finally:
        try:
            os.unlink(out_path)
        except OSError:
            pass
    if rc != 0 or verdict is None:
        msg = (
            f"program exited with status {rc}"
            if rc != 0
            else "program left no health verdict (did it call pw.run()?)"
        )
        if as_json:
            click.echo(_json.dumps({"status": "unknown", "error": msg}))
        else:
            click.echo(f"doctor: {msg}", err=True)
        sys.exit(3)
    if as_json:
        click.echo(_json.dumps(verdict, indent=2, sort_keys=True))
    else:
        from .internals.ledger import render_verdict

        click.echo(render_verdict(verdict))
    sys.exit(_DOCTOR_EXIT.get(verdict.get("status"), 3))


@cli.group()
def blackbox():
    """Inspect black-box flight-recorder dumps.

    Every run keeps a bounded in-memory ring of engine events (epoch
    transitions, connector commits, retry attempts, chaos hits); on a
    crash, a worker death, or recovery escalation the ring is written
    to a timestamped JSON file. These commands list, render, and
    compare those dumps.
    """


@blackbox.command(name="list")
@click.option(
    "--dir",
    "directory",
    default=None,
    help="dump directory [default: PATHWAY_FLIGHT_RECORDER_DIR or "
    "<tmp>/pathway-blackbox]",
)
def blackbox_list(directory):
    """List flight-recorder dumps, oldest first."""
    from .internals import flight_recorder as fr

    directory = directory or fr.default_dump_dir()
    paths = fr.list_dumps(directory)
    if not paths:
        click.echo(f"no dumps in {directory}")
        return
    for path in paths:
        try:
            data = fr.load_dump(path)
        except Exception as exc:
            click.echo(f"{path}  <unreadable: {exc}>")
            continue
        last = fr.last_epoch(data)
        click.echo(
            f"{path}  reason={data.get('reason', '?')}"
            f" pid={data.get('pid', '?')}"
            f" events={len(data.get('events', []))}"
            + (f" last_epoch={last}" if last is not None else "")
        )


@blackbox.command(name="show")
@click.option(
    "--tail-epochs",
    default=3,
    show_default=True,
    help="how many trailing epoch transitions to highlight",
)
@click.argument("path", required=True)
def blackbox_show(tail_epochs, path):
    """Render one dump: header, the last epoch transitions before the
    crash, then the full event log."""
    from .internals import flight_recorder as fr

    try:
        data = fr.load_dump(path)
    except Exception as exc:
        raise click.ClickException(f"cannot read {path}: {exc}")
    click.echo(fr.render(data, tail_epochs=tail_epochs))


@blackbox.command(name="diff")
@click.argument("path_a", required=True)
@click.argument("path_b", required=True)
def blackbox_diff(path_a, path_b):
    """Compare two dumps by event-kind counts (e.g. the dumps of two
    workers of the same crashed cluster)."""
    from .internals import flight_recorder as fr

    try:
        a = fr.load_dump(path_a)
        b = fr.load_dump(path_b)
    except Exception as exc:
        raise click.ClickException(str(exc))
    click.echo(fr.diff(a, b))


@cli.group()
def trace():
    """Inspect per-request trace dumps.

    A run with tracing on (``pw.run(tracing=True)`` / PATHWAY_TRACING)
    records a span per pipeline stage each request touches and retains
    the slowest complete traces per window; at run end they are written
    to a timestamped JSON file. These commands list the dumps, render
    one request's journey as a waterfall (cross-linked with black-box
    flight-recorder events), and answer "where did the tail go".
    """


def _trace_dumps(directory):
    from .tracing import store as ts

    directory = directory or ts.default_trace_dir()
    paths = ts.list_trace_dumps(directory)
    return directory, paths


_TRACE_DIR_HELP = "trace dump directory [default: PATHWAY_TRACE_DIR or <tmp>/pathway-traces]"


@trace.command(name="list")
@click.option("--dir", "directory", default=None, help=_TRACE_DIR_HELP)
def trace_list(directory):
    """List trace dumps and the slowest retained trace of each."""
    from .tracing import store as ts

    directory, paths = _trace_dumps(directory)
    if not paths:
        click.echo(f"no trace dumps in {directory}")
        return
    for path in paths:
        try:
            data = ts.load_trace_dump(path)
        except Exception as exc:
            click.echo(f"{path}  <unreadable: {exc}>")
            continue
        exemplars = data.get("exemplars", [])
        head = ""
        if exemplars:
            worst = exemplars[0]
            head = (
                f" slowest={worst.get('trace_id', '?')[:16]}"
                f" ({worst.get('wall_ms', 0.0):.1f} ms)"
            )
        click.echo(
            f"{path}  pid={data.get('pid', '?')}"
            f" worker={data.get('worker', '?')}"
            f" exemplars={len(exemplars)}"
            f" open={len(data.get('open', []))}" + head
        )


def _collect_trace(paths, trace_id):
    """Spans for ``trace_id`` (unique-prefix match allowed) across all
    dumps — a journey fans out over coordinator + worker processes, so
    one dump rarely holds the whole picture."""
    from .tracing import store as ts

    matches: dict[str, list[dict]] = {}
    for path in paths:
        try:
            data = ts.load_trace_dump(path)
        except Exception:
            continue
        buckets = [
            tr.get("spans", []) for tr in data.get("exemplars", [])
        ] + [data.get("recent", []), data.get("open", [])]
        for spans in buckets:
            for sp in spans:
                tid = str(sp.get("trace", ""))
                if tid.startswith(trace_id):
                    matches.setdefault(tid, []).append(sp)
    if len(matches) > 1:
        raise click.ClickException(
            f"trace id prefix {trace_id!r} is ambiguous: "
            + ", ".join(sorted(matches))
        )
    if not matches:
        return trace_id, []
    tid, spans = next(iter(matches.items()))
    seen: set[str] = set()
    unique = []
    for sp in sorted(spans, key=lambda s: float(s.get("start", 0.0))):
        sid = str(sp.get("span", ""))
        if sid in seen:
            continue
        seen.add(sid)
        unique.append(sp)
    return tid, unique


@trace.command(name="show")
@click.option("--dir", "directory", default=None, help=_TRACE_DIR_HELP)
@click.option(
    "--no-blackbox",
    is_flag=True,
    help="skip scanning flight-recorder dumps for matching events",
)
@click.argument("trace_id", required=True)
def trace_show(directory, no_blackbox, trace_id):
    """Render one request's journey as a stage waterfall.

    Flight-recorder events carrying the same trace id (sheds, degrades,
    chaos hits) are interleaved at their timestamps.
    """
    from .tracing.attribution import render_waterfall

    directory, paths = _trace_dumps(directory)
    if not paths:
        raise click.ClickException(f"no trace dumps in {directory}")
    tid, spans = _collect_trace(paths, trace_id)
    if not spans:
        raise click.ClickException(f"trace {trace_id!r} not found in {directory}")
    events = []
    if not no_blackbox:
        from .internals import flight_recorder as fr

        try:
            events = fr.events_for_trace(tid)
        except Exception:
            events = []
    click.echo(render_waterfall(tid, spans, blackbox_events=events))


@trace.command(name="slow")
@click.option("--dir", "directory", default=None, help=_TRACE_DIR_HELP)
@click.option(
    "--top", "top_n", default=10, show_default=True, help="how many traces"
)
def trace_slow(directory, top_n):
    """Tail-latency report: the slowest retained traces with per-stage
    attribution, plus the aggregate "where the tail went" line."""
    from .tracing import store as ts
    from .tracing.attribution import render_slow_report, slow_report

    directory, paths = _trace_dumps(directory)
    if not paths:
        raise click.ClickException(f"no trace dumps in {directory}")
    exemplars = []
    for path in paths:
        try:
            exemplars.extend(ts.load_trace_dump(path).get("exemplars", []))
        except Exception:
            continue
    if not exemplars:
        raise click.ClickException(f"no retained exemplars in {directory}")
    click.echo(render_slow_report(slow_report(exemplars, top_n=top_n)))


@cli.group()
def perf():
    """Chip-time performance snapshots and regression diffs.

    A run with the chip ledger on (``pw.run(chip_ledger=True)`` /
    PATHWAY_CHIP_LEDGER=1) and a journal directory (PATHWAY_JOURNAL_DIR)
    persists periodic samples plus every bench FINAL SUMMARY. These
    commands fold that journal into a BENCH_r*-style snapshot JSON and
    compare two snapshots with per-metric regression gates.
    """


_JOURNAL_DIR_HELP = "metrics journal directory [default: PATHWAY_JOURNAL_DIR]"


@perf.command(name="snapshot")
@click.option("--journal", "directory", default=None, help=_JOURNAL_DIR_HELP)
@click.option(
    "--output",
    "-o",
    default=None,
    help="write the snapshot JSON here instead of stdout",
)
def perf_snapshot(directory, output):
    """Build a BENCH_r*-style snapshot from the journal's bench records
    (automates the BENCH_r06 runbook's 'save the FINAL SUMMARY' step)."""
    import json as _json

    from .perf.snapshot import build_snapshot

    try:
        snap = build_snapshot(directory)
    except ValueError as exc:
        raise click.ClickException(str(exc))
    text = _json.dumps(snap, indent=2, sort_keys=True)
    if output:
        with open(output, "w", encoding="utf-8") as fh:
            fh.write(text + "\n")
        click.echo(f"snapshot written to {output}", err=True)
    else:
        click.echo(text)


@perf.command(name="diff")
@click.option(
    "--gate",
    default=None,
    type=float,
    help="relative regression gate [default: 0.10]",
)
@click.argument("path_a", required=True)
@click.argument("path_b", required=True)
def perf_diff(gate, path_a, path_b):
    """Compare two snapshots (baseline A vs candidate B) per metric.

    Exits 1 when any metric regresses past its gate — the per-record
    absolute ``gate`` field when present, else --gate relative.
    """
    from .perf.snapshot import DEFAULT_GATE, diff_snapshots, load_snapshot, render_diff

    try:
        a = load_snapshot(path_a)
        b = load_snapshot(path_b)
    except Exception as exc:
        raise click.ClickException(str(exc))
    result = diff_snapshots(a, b, gate=DEFAULT_GATE if gate is None else gate)
    click.echo(render_diff(result))
    sys.exit(result["rc"])


@cli.command()
@click.option("--url", default=None, help="monitoring server base URL (reads /status)")
@click.option("--journal", "directory", default=None, help=_JOURNAL_DIR_HELP)
@click.option("--once", is_flag=True, help="render one frame and exit")
@click.option(
    "--interval",
    default=2.0,
    show_default=True,
    type=float,
    help="refresh interval in seconds",
)
def top(url, directory, once, interval):
    """Live chip-time view: per-plane share, MFU, stranded causes,
    per-tenant share vs DRR weight, HBM per account.

    Reads --url's /status when given, else the newest journal sample
    (--journal / PATHWAY_JOURNAL_DIR). With --once, exits 0 when a
    chip-time sample was rendered, 1 when there is none yet.
    """
    import time as _time

    from .perf.top import load_from_journal, load_status_from_url, render_top

    def _frame():
        if url:
            data = load_status_from_url(url)
        else:
            data = load_from_journal(directory)
        return render_top(data)

    if once:
        try:
            text, state = _frame()
        except Exception as exc:
            raise click.ClickException(str(exc))
        click.echo(text)
        sys.exit(0 if state != "empty" else 1)
    try:
        while True:
            try:
                text, _state = _frame()
            except Exception as exc:
                text = f"pathway top — error: {exc}"
            # clear screen + home, like watch(1)
            click.echo("\033[2J\033[H" + text)
            _time.sleep(max(0.1, interval))
    except KeyboardInterrupt:
        pass


@cli.command()
@click.option("--url", default=None, help="monitoring server base URL (reads /status)")
@click.option("--journal", "directory", default=None, help=_JOURNAL_DIR_HELP)
@click.option("--json", "as_json", is_flag=True, help="emit the raw freshness block")
def freshness(url, directory, as_json):
    """Where the visibility lag accrues: per-plane split (ingest queue /
    staging / epoch / publish / promotion / migration), end-to-end
    p50/p99, per-index visible watermarks with current staleness, and
    the verdict against the configured freshness SLO.

    Reads --url's /status when given, else the newest journal sample
    (--journal / PATHWAY_JOURNAL_DIR). Exits 0 when a freshness sample
    was rendered, 1 when there is none yet.
    """
    import json as _json

    from .freshness.report import render_freshness
    from .perf.top import load_from_journal, load_status_from_url

    try:
        data = load_status_from_url(url) if url else load_from_journal(directory)
    except Exception as exc:
        raise click.ClickException(str(exc))
    if as_json:
        fresh = data.get("freshness")
        click.echo(_json.dumps(fresh or {}, indent=2, sort_keys=True))
        sys.exit(0 if fresh else 1)
    text, state = render_freshness(data)
    click.echo(text)
    sys.exit(0 if state != "empty" else 1)


def main() -> None:
    cli()


if __name__ == "__main__":
    main()
