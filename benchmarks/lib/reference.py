"""The plain reference of the index: what the configuration says the
system computes once texts are rows, in straightforward ``jax.numpy`` and
float32 at ``highest`` precision. The model's half of the reference — its
tokenizer and its encoder — is its family's (``families/<name>.py``).

It imports nothing of the program and takes nothing the program made.
Its inputs are the benchmark's own: the pool's embeddings as the family's
reference encoder gives them, and the seed's noise. The index is a matrix
of unit rows by key, cosine top-k by brute force.

``quant`` is the control, the step below the configuration's bfloat16:
the index rows and the queries rounded to ``fp8`` (e4m3) or ``int8`` with a
scale per row (``lowprec.py``), beside the family's encoder rounded the
same way.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .lowprec import QUANT, to_low

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -3.0e38


# ---- standing rows -----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk",))
def standing_chunk(key, index, pool_emb, sigma, *, chunk: int):
    """Rows ``index*chunk ...`` of the standing corpus, not normalised:
    key ``r`` sits at pool document ``r % pool`` plus seeded noise of
    length about ``sigma``, so standing rows lie where embedded text
    lies and compete in every top-k."""
    pool, dim = pool_emb.shape
    rows = index * chunk + jnp.arange(chunk)
    noise = jax.random.normal(jax.random.fold_in(key, index), (chunk, dim), jnp.float32)
    return pool_emb[rows % pool] + (sigma / math.sqrt(dim)) * noise


# ---- index -------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,))
def _put_rows(matrix, at, rows):
    return jax.lax.dynamic_update_slice(matrix, rows, (at, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def _replace(matrix, keys, rows):
    return matrix.at[keys].set(rows)


@jax.jit
def _judge(matrix, dead, q_emb, keys):
    """For each query: the reference's score of every returned key, and
    the best score among all keys that were not returned."""
    scores = jnp.matmul(q_emb, matrix.T, precision=HIGHEST)  # [q, rows]
    scores = jnp.where(dead[None, :], NEG, scores)
    returned = jnp.take_along_axis(scores, keys, axis=1)
    rows = jnp.arange(q_emb.shape[0])[:, None]
    best_out = scores.at[rows, keys].set(NEG).max(axis=1)
    return returned, best_out


@functools.partial(jax.jit, static_argnames=("k", "quant"))
def _low_topk(matrix_q, row_scale, dead, q_emb, k: int, quant: str):
    """Top-k by the low type's own arithmetic: exact products of the
    rounded values (int32 accumulation for int8; fp8 values are exact in
    bfloat16, their products in float32), then the two scales."""
    q_q, q_scale = to_low(q_emb, 1, quant)
    if quant == "int8":
        acc = jax.lax.dot_general(q_q, matrix_q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
    else:
        acc = jax.lax.dot_general(
            q_q.astype(jnp.bfloat16),
            matrix_q.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    scores = acc.astype(jnp.float32) * q_scale * row_scale[None, :]
    return jax.lax.top_k(jnp.where(dead[None, :], NEG, scores), k)


class ReferenceIndex:
    """The standing corpus by key, under the window's writes. Row ``r``
    is key ``r``; a write replaces rows with pool embeddings."""

    def __init__(self, pool_emb, rows: int, sigma: float, key, chunk: int, quant: str | None = None):
        self.pool_emb = pool_emb
        self.quant = quant
        dim = pool_emb.shape[1]
        n_chunks = -(-rows // chunk)
        dtype = QUANT[quant][1] if quant else jnp.float32
        self.matrix = jnp.zeros((n_chunks * chunk, dim), dtype)
        self.scale = jnp.ones((n_chunks * chunk,), jnp.float32) if quant else None
        self.rows = rows
        for c in range(n_chunks):
            block = standing_chunk(key, c, pool_emb, sigma, chunk=chunk)
            self._write(c * chunk, None, block)
        # rows past the last key are never scored
        self.dead = jnp.arange(n_chunks * chunk) >= rows

    def _write(self, at, keys, block):
        block = block / jnp.maximum(jnp.linalg.norm(block, axis=1, keepdims=True), 1e-12)
        if self.quant:
            block, scale = to_low(block, 1, self.quant)
            scale = scale[:, 0]
            if keys is None:
                self.scale = jax.lax.dynamic_update_slice(self.scale, scale, (at,))
            else:
                self.scale = self.scale.at[keys].set(scale)
        if keys is None:
            self.matrix = _put_rows(self.matrix, at, block)
        else:
            self.matrix = _replace(self.matrix, keys, block)

    def replace(self, keys, docs) -> None:
        self._write(None, jnp.asarray(keys, jnp.int32), self.pool_emb[jnp.asarray(docs)])

    def judge(self, q_emb, keys):
        returned, best_out = _judge(self.matrix, self.dead, q_emb, jnp.asarray(keys, jnp.int32))
        return np.asarray(returned), np.asarray(best_out)

    def answer(self, q_emb, k: int):
        """The control's own answers: keys and scores, by its own arithmetic."""
        vals, idx = _low_topk(self.matrix, self.scale, self.dead, q_emb, k, self.quant)
        return np.asarray(idx), np.asarray(vals)
