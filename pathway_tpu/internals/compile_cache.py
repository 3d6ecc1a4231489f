"""Where JAX keeps compiled programs between processes.

One helper, called by ``pw.run``, ``chip_smoke.py`` and ``bench.py``
before they compile anything. The directory is placed from outside when
``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads that variable itself,
and no other directory is set in code); otherwise it is one fixed,
git-ignored path inside the checkout. Never a ``tempfile``, pid or
time-derived path: a directory that moves between processes never hits.

A process held to the CPU (``JAX_PLATFORMS=cpu``: the tests, the
workers of a cluster, the children ``bench.py`` starts) caches nothing.
Nobody waits on those compiles, and an XLA:CPU entry is tied to the
machine features of the host that wrote it.
"""

from __future__ import annotations

import collections
import os

__all__ = ["DEFAULT_DIR", "configure_compile_cache", "compile_cache_stats"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    # JAX records a "miss" when it writes the entry it did not find
    "/jax/compilation_cache/cache_misses": "misses",
}
_counts: collections.Counter = collections.Counter()
_directory: str | None = None


def _count(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def _held_to_cpu() -> bool:
    import jax

    return jax.config.jax_platforms == "cpu"


def configure_compile_cache() -> str | None:
    """Turn the persistent compilation cache on for this process and
    return its directory (``None`` in a process held to the CPU).
    Idempotent; programs compiled before the first call are simply not
    cached."""
    global _directory
    if _directory is not None:
        return _directory
    if _held_to_cpu():
        return None
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", directory)
    # the seq/batch buckets compile many small programs, each under
    # JAX's default one-second threshold for caching
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.monitoring.register_event_listener(_count)
    _directory = directory
    return directory


def compile_cache_stats() -> dict:
    """Directory and hit/miss counts since :func:`configure_compile_cache`."""
    return {
        "dir": _directory,
        "requests": _counts["requests"],
        "hits": _counts["hits"],
        "misses": _counts["misses"],
    }
