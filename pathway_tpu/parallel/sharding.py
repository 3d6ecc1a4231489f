"""Mesh + sharding helpers (the framework's scaling substrate).

Replaces the reference's worker topology (threads × processes over TCP,
/root/reference/src/engine/dataflow/config.rs:36-120) with a
``jax.sharding.Mesh``: the "data" axis plays the role of key-sharded
workers (R7 shard.rs — hash(key) → shard), the "model" axis shards
embedder/reranker weights tensor-parallel. Collectives ride ICI. One
process drives every chip of its host through this mesh; the
multi-process cluster (``pathway spawn --processes``) is host dataflow
only.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

# flax logical axis -> mesh axis (see models/encoder.py annotations)
LOGICAL_RULES = (
    ("embed", None),
    ("heads", MODEL_AXIS),
    ("mlp", MODEL_AXIS),
    ("vocab", None),
    ("batch", DATA_AXIS),
)


def make_mesh(
    n_devices: int | None = None,
    model_parallel: int | None = None,
    devices: Sequence | None = None,
    heads: int | None = None,
) -> Mesh:
    """Build a (data, model) mesh. ``model_parallel`` must divide the
    device count; the default picks the largest of {4, 2, 1} dividing
    the device count — and ``heads`` too when given, so attention
    weights shard on head boundaries."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    n = len(devices)
    if model_parallel is None:
        model_parallel = next(
            tp for tp in (4, 2, 1) if n % tp == 0 and (heads is None or heads % tp == 0)
        )
    assert n % model_parallel == 0
    arr = np.asarray(devices).reshape(n // model_parallel, model_parallel)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def param_sharding(mesh: Mesh, logical_axes):
    """Map a flax logical-axis pytree (from ``nn.get_partition_spec``)
    to NamedShardings on ``mesh``."""
    from flax import linen as nn

    return nn.logical_to_mesh_sharding(logical_axes, mesh, rules=list(LOGICAL_RULES))


def data_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Batch-dim sharding for activations/inputs."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
