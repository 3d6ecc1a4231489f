"""What a process does before it touches the device, and what it refuses
to hide afterwards: the compile-cache placement, the run scripts' exit
codes, and the fallbacks PR 21 took off the device path."""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from pathway_tpu.internals import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config(monkeypatch):
    """A process that is not held to the CPU and has not placed its
    cache yet; whatever the helper sets in jax.config is restored."""
    import jax

    before = {
        name: getattr(jax.config, name)
        for name in ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    }
    monkeypatch.setattr(compile_cache, "_held_to_cpu", lambda: False)
    monkeypatch.setattr(compile_cache, "_directory", None)
    yield jax.config
    jax.monitoring.unregister_event_listener(compile_cache._count)
    for name, value in before.items():
        jax.config.update(name, value)


def test_compile_cache_placed_from_outside_sets_no_directory(cache_config, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    before = cache_config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == "/placed/from/outside"
    assert cache_config.jax_compilation_cache_dir == before  # JAX reads the variable itself
    assert compile_cache.compile_cache_stats()["dir"] == "/placed/from/outside"


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    directory = compile_cache.configure_compile_cache()
    assert directory == os.path.join(REPO, ".jax_cache") == cache_config.jax_compilation_cache_dir
    assert not directory.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in directory
    assert cache_config.jax_persistent_cache_min_compile_time_secs == 0
    assert compile_cache.configure_compile_cache() == directory  # idempotent
    # and git would not commit it
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_is_off_where_jax_is_held_to_the_cpu(monkeypatch):
    """The tests themselves are such a process (tests/conftest.py)."""
    import jax

    monkeypatch.setattr(compile_cache, "_directory", None)
    assert compile_cache.configure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode != 0
    assert "JAX found platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout  # no result line


def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_startup_target", os.path.join(REPO, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_bench_run_suite_reports_the_suites_that_raised(monkeypatch, capsys):
    bench = _bench()

    def suite_fine():
        bench._emit("fine", 1.0, "count")

    def suite_broken():
        raise RuntimeError("device said no")

    monkeypatch.setattr(bench, "SUITES", (suite_broken, suite_fine))
    assert bench.run_suite() == ["suite_broken"]  # __main__ exits non-zero on a non-empty list
    assert [r["metric"] for r in bench._RECORDS] == ["suite_broken", "fine"]
    assert "device said no" in capsys.readouterr().out


def test_bench_requires_the_native_library(monkeypatch):
    from pathway_tpu import native

    bench = _bench()
    monkeypatch.setattr(native, "NATIVE", None)
    with pytest.raises(SystemExit, match="native library"):
        bench._startup()


def test_device_ring_lets_a_refused_put_raise(monkeypatch):
    import jax

    from pathway_tpu.engine.device_ring import DeviceRing

    def refuse(*_a, **_k):
        raise RuntimeError("no such device")

    monkeypatch.setattr(jax, "device_put", refuse)
    with pytest.raises(RuntimeError, match="no such device"):
        DeviceRing(name="test.refused").stage([np.zeros((4,), np.float32)])


def test_a_checkpoint_that_is_there_and_does_not_load_raises(tmp_path, monkeypatch):
    from pathway_tpu.models import sentence_encoder

    seeded = {"params": {}}
    # a missing directory still means seeded weights
    assert sentence_encoder._checkpoint_or_seeded(seeded, str(tmp_path / "absent")) is seeded
    assert sentence_encoder._checkpoint_or_seeded(seeded, None) is seeded
    # a directory with no weights in it: present, but nothing to load
    with pytest.raises(FileNotFoundError, match="no checkpoint"):
        sentence_encoder._checkpoint_or_seeded(seeded, str(tmp_path))
    # ... and neither constructor swallows that (seeded init stubbed: slow)
    monkeypatch.setattr(sentence_encoder, "init_params", lambda *a, **k: seeded)
    for model in (sentence_encoder.SentenceEncoder, sentence_encoder.CrossEncoderScorer):
        with pytest.raises(FileNotFoundError, match="no checkpoint"):
            model(checkpoint_dir=str(tmp_path))
