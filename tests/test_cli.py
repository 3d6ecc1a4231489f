"""CLI tests: pathway spawn / spawn-from-env / record+replay.

Mirrors the reference's CLI coverage
(/root/reference/python/pathway/tests/cli/): worker-topology env wiring
and stream record/replay via env vars.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pathway_tpu as pw

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(args, cwd, extra_env=None):
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-m", "pathway_tpu"] + args,
        cwd=cwd,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_spawn_runs_n_processes_with_topology_env(tmp_path):
    prog = tmp_path / "prog.py"
    prog.write_text(
        "import os, json\n"
        "pid = os.environ['PATHWAY_PROCESS_ID']\n"
        "info = {k: os.environ.get(k) for k in\n"
        "        ('PATHWAY_THREADS', 'PATHWAY_PROCESSES', 'PATHWAY_FIRST_PORT')}\n"
        "info['JAX_PLATFORMS'] = os.environ.get('JAX_PLATFORMS')\n"
        "open(f'out_{pid}.json', 'w').write(json.dumps(info))\n"
    )
    res = _run_cli(
        ["spawn", "--threads", "2", "--processes", "2", "--first-port", "11500", str(prog)],
        cwd=tmp_path,
        extra_env={"JAX_PLATFORMS": "tpu,cpu"},
    )
    assert res.returncode == 0, res.stderr
    # one process per chip: process 0 keeps the launcher's platforms,
    # every other worker is held to the CPU
    for pid, platforms in ((0, "tpu,cpu"), (1, "cpu")):
        info = json.loads((tmp_path / f"out_{pid}.json").read_text())
        assert info == {
            "PATHWAY_THREADS": "2",
            "PATHWAY_PROCESSES": "2",
            "PATHWAY_FIRST_PORT": "11500",
            "JAX_PLATFORMS": platforms,
        }


def test_spawn_propagates_failure(tmp_path):
    prog = tmp_path / "prog.py"
    prog.write_text("import sys; sys.exit(3)\n")
    res = _run_cli(["spawn", str(prog)], cwd=tmp_path)
    assert res.returncode == 3


def test_spawn_from_env(tmp_path):
    prog = tmp_path / "prog.py"
    prog.write_text("open('ran.txt', 'w').write('yes')\n")
    res = _run_cli(
        ["spawn-from-env"],
        cwd=tmp_path,
        extra_env={"PATHWAY_SPAWN_ARGS": f"--processes=1 {prog}"},
    )
    assert res.returncode == 0, res.stderr
    assert (tmp_path / "ran.txt").read_text() == "yes"


class _WordSubject(pw.io.python.ConnectorSubject):
    def __init__(self, words):
        super().__init__()
        self.words = words

    def run(self):
        start = int(self.offsets.get("next", 0))
        for i in range(start, len(self.words)):
            self.next_with_offset("next", i + 1, word=self.words[i])
        self.commit()


class _WordSchema(pw.Schema):
    word: str


def _wordcount_events(words, storage, mode):
    """Run the wordcount pipeline with PATHWAY_REPLAY_* env set."""
    os.environ["PATHWAY_REPLAY_STORAGE"] = storage
    os.environ["PATHWAY_REPLAY_MODE"] = mode
    try:
        t = pw.io.python.read(
            _WordSubject(words), schema=_WordSchema, autocommit_duration_ms=None
        )
        counts = t.groupby(pw.this.word).reduce(
            word=pw.this.word, count=pw.reducers.count()
        )
        events: list = []
        pw.io.subscribe(
            counts,
            on_change=lambda key, row, time, is_addition: events.append(
                (row["word"], row["count"], is_addition)
            ),
        )
        pw.run()
        pw.clear_graph()
        return events
    finally:
        del os.environ["PATHWAY_REPLAY_STORAGE"]
        del os.environ["PATHWAY_REPLAY_MODE"]


def test_record_then_speedrun_replay(tmp_path):
    """--record captures the stream (auto persistent ids); speedrun
    replay recomputes identical sink output without running readers."""
    storage = str(tmp_path / "rec")
    recorded = _wordcount_events(["a", "b", "a"], storage, "record")
    assert ("a", 2, True) in recorded and ("b", 1, True) in recorded

    # speedrun: the subject would emit NOTHING new (offsets persisted),
    # and readers never even start; output comes purely from the log
    replayed = _wordcount_events(["a", "b", "a"], storage, "speedrun")
    assert sorted(replayed) == sorted(recorded)


def test_speedrun_replay_multi_worker(tmp_path):
    """A recorded run replays deterministically across N workers: the
    sharded engine's replay equals both the recording and a
    single-worker replay (reference PersistenceMode::SpeedrunReplay
    works under any worker config, src/connectors/mod.rs:108)."""
    storage = str(tmp_path / "rec")
    words = ["a", "b", "a", "c", "b", "a", "d", "c"]
    recorded = _wordcount_events(words, storage, "record")
    assert ("a", 3, True) in recorded

    replay_1w = _wordcount_events(words, storage, "speedrun")
    os.environ["PATHWAY_THREADS"] = "4"
    try:
        replay_4w = _wordcount_events(words, storage, "speedrun")
        # replay again: a sharded replay is itself reproducible
        replay_4w_again = _wordcount_events(words, storage, "speedrun")
    finally:
        del os.environ["PATHWAY_THREADS"]
    assert sorted(replay_4w) == sorted(recorded)
    assert sorted(replay_4w) == sorted(replay_1w)
    assert sorted(replay_4w_again) == sorted(replay_4w)


def test_speedrun_replay_multi_worker_sees_every_epoch(tmp_path):
    """Sharded replay must re-deliver intermediate epochs (retract/insert
    pairs), not just the final state — it is the debugging tool for
    multi-worker nondeterminism claims."""
    storage = str(tmp_path / "rec")

    class _EpochSubject(pw.io.python.ConnectorSubject):
        def run(self):
            import time as _time

            start = int(self.offsets.get("next", 0))
            for i in range(start, 4):
                self.next_with_offset("next", i + 1, word="w")
                self.commit()  # one epoch per row -> count 1,2,3,4
                _time.sleep(0.15)  # outlive the engine poll so commits
                # land in distinct epochs instead of coalescing

    def run_events(mode, threads=None):
        os.environ["PATHWAY_REPLAY_STORAGE"] = storage
        os.environ["PATHWAY_REPLAY_MODE"] = mode
        if threads:
            os.environ["PATHWAY_THREADS"] = str(threads)
        try:
            t = pw.io.python.read(
                _EpochSubject(), schema=_WordSchema, autocommit_duration_ms=None
            )
            counts = t.groupby(pw.this.word).reduce(
                word=pw.this.word, count=pw.reducers.count()
            )
            events: list = []
            pw.io.subscribe(
                counts,
                on_change=lambda key, row, time, is_addition: events.append(
                    (row["count"], is_addition)
                ),
            )
            pw.run()
            pw.clear_graph()
            return events
        finally:
            del os.environ["PATHWAY_REPLAY_STORAGE"]
            del os.environ["PATHWAY_REPLAY_MODE"]
            if threads:
                del os.environ["PATHWAY_THREADS"]

    recorded = run_events("record")
    replayed = run_events("speedrun", threads=4)
    assert replayed == recorded
    # the full incremental history: 1, then retract 1 / insert 2, ...
    assert (1, True) in replayed and (1, False) in replayed
    assert replayed[-1] == (4, True)
