"""Rounding to the precision below the configuration's, for the control:
``fp8`` (e4m3) or ``int8``, symmetric, one scale along an axis. The one
file of ``lib/`` a family imports: its reference encoder rounds its
matmuls with ``roundtrip``, and ``reference.py`` rounds the index rows and
the queries with ``to_low``. The careful form of each, so that the control
reads as low as that precision can.
"""

from __future__ import annotations

import jax.numpy as jnp

QUANT = {"int8": (127.0, jnp.int8), "fp8": (448.0, jnp.float8_e4m3fn)}


def to_low(x, axis, quant: str):
    """-> (x in the low type, scale): symmetric, one scale along ``axis``."""
    top, dtype = QUANT[quant]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top, 1e-30)
    y = x / scale
    if quant == "int8":
        y = jnp.clip(jnp.round(y), -top, top)
    return y.astype(dtype), scale


def roundtrip(x, axis, quant: str):
    """To the low type and back: what a matmul in it would see."""
    y, scale = to_low(x, axis, quant)
    return y.astype(jnp.float32) * scale
