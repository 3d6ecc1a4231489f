"""Test configuration: force JAX onto a virtual 8-device CPU mesh so
sharding tests run without TPU hardware (SURVEY.md §4 implication).

The CPU is forced in code, not left to JAX_PLATFORMS: the tests must run
the same wherever they are started, a machine with a chip included.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


@pytest.fixture(autouse=True)
def _clear_parse_graph():
    """Each test builds its own pipeline graph."""
    yield
    import pathway_tpu as pw

    pw.clear_graph()
