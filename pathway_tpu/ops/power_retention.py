"""Power retention over a packed token stream (Manifest AI, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239; the attention
of Brumby-14B-Base): causal attention whose weight is a power of the
query-key product under a learned decay, normalised by its own sum.

A stream is ``T`` tokens of consecutive documents; token ``t`` belongs to
document ``seg[t]`` (``-1``: padding) at position ``pos[t]`` in it. For
query head ``a`` of key/value head ``b = a // group`` and tokens ``j <=
i`` of one document::

    L_t  = sum of log_g[s, b] over the document's tokens s <= t
    A_ij = exp(L_i - L_j) * (q_i . k_j) ** degree
    o_i  = sum_j A_ij v_j / (sum_j A_ij + eps)

(the caller folds ``1 / sqrt(head_dim)`` into ``q``). The same function
as a recurrence — what makes it a retention — with ``phi(x)`` the
symmetric square of ``x`` (``phi(x) . phi(y) = (x . y) ** 2``)::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    o_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps)        S, z = 0 at pos 0

:func:`power_retention` is the Pallas kernel: blocks of queries against
blocks of keys, pair by pair — scores, power, decay and the causal and
document mask on chip, numerator and denominator in float32 in VMEM,
nothing of size ``T x T`` in HBM, the ``group`` query heads of a
key/value head reading one key/value block. Only the (query block, key
block) pairs that hold a causal pair of one document are visited: the
list is made on the device from ``pos`` and is the kernel's grid, so a
stream costs its documents' pairs, not ``T ** 2``, and a document is as
long as the stream allows. A block far from the diagonal is scored pair
by pair too, not through a carried ``S``: per query head a pair costs
``4 * head_dim`` operations, a token through the state ``~2.5 M``, so
the state wins past ~9,900 tokens of context (PERF.md has the chip's
reading at 4,096). :func:`power_retention_reference` is the recurrence
under ``lax.scan``, the tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: queries and keys of one grid step; a stream shorter than a block is one
BLOCK_Q, BLOCK_K = 256, 512
_FIRST, _LAST = 1, 2


def segment_cumsum(x, first):
    """Running sum of ``x`` ``[T, ...]`` along axis 0 that starts anew
    wherever ``first`` ``[T]`` is true."""
    flags = first.reshape((-1,) + (1,) * (x.ndim - 1))

    def combine(a, b):
        (fa, va), (fb, vb) = a, b
        return fa | fb, jnp.where(fb, vb, va + vb)

    return jax.lax.associative_scan(combine, (jnp.broadcast_to(flags, x.shape), x))[1]


def block_pairs(pos, live, block_q: int, block_k: int):
    """The kernel's visits for a stream whose token ``t`` sits at
    ``pos[t]`` of its document (padding: 0): query block ``i`` (of the
    ``ceil(live / block_q)`` live ones) meets every key block from the
    one holding the first token of its first document to its own last
    token's. -> (query block, key block, first | last flags), each
    ``[most]`` int32, and the number of visits."""
    t = pos.shape[0]
    nq = t // block_q
    start = (jnp.arange(t, dtype=jnp.int32) - pos).reshape(nq, block_q).min(axis=1)
    lo = start // block_k
    hi = ((jnp.arange(nq, dtype=jnp.int32) + 1) * block_q - 1) // block_k
    visits = jnp.where(jnp.arange(nq) * block_q < live, hi - lo + 1, 0)
    end = jnp.cumsum(visits, dtype=jnp.int32)
    # one document spanning the stream visits the most
    most = sum(((i + 1) * block_q - 1) // block_k + 1 for i in range(nq))
    at = jnp.arange(most, dtype=jnp.int32)
    qi = jnp.minimum(jnp.searchsorted(end, at, side="right"), nq - 1).astype(jnp.int32)
    nth = at - (end - visits)[qi]
    kj = jnp.clip(lo[qi] + nth, 0, t // block_k - 1)
    flags = jnp.where(nth == 0, _FIRST, 0) | jnp.where(nth == visits[qi] - 1, _LAST, 0)
    return qi, kj, flags.astype(jnp.int32), end[-1]


def _kernel(qi_ref, kj_ref, flag_ref, q_ref, k_ref, v_ref, lq_ref, lk_ref, sq_ref, sk_ref, o_ref, acc_ref, den_ref,
            *, group: int, dim: int, degree: int, eps: float):  # fmt: skip
    step = pl.program_id(1)
    flag = flag_ref[step]
    bq, bk = q_ref.shape[0], k_ref.shape[0]

    @pl.when(flag & _FIRST != 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    row = qi_ref[step] * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = kj_ref[step] * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = (sq_ref[...] == sk_ref[...]) & (col <= row)
    # one decay for the group's heads; a masked pair's is exp(-1e30) = 0
    decay = jnp.exp(jnp.where(keep, lq_ref[...] - lk_ref[...], -1e30))
    k, v = k_ref[...], v_ref[...]
    for h in range(group):
        at = slice(h * dim, (h + 1) * dim)
        s = jax.lax.dot_general(q_ref[:, at], k, (((1,), (1,)), ((), ())), preferred_element_type=F32)
        a = jax.lax.integer_pow(s, degree) * decay
        acc_ref[:, at] += jnp.dot(a.astype(v.dtype), v, preferred_element_type=F32)
        den_ref[h] += a.sum(axis=1, keepdims=True)

    @pl.when(flag & _LAST != 0)
    def _():
        for h in range(group):
            at = slice(h * dim, (h + 1) * dim)
            o_ref[:, at] = (acc_ref[:, at] / (den_ref[h] + eps)).astype(o_ref.dtype)


def power_retention(q, k, v, log_g, seg, pos, *, live=None, degree: int = 2, eps: float = 1e-6,
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K, interpret: bool = False):  # fmt: skip
    """``q`` ``[T, heads * dim]`` (scaled), ``k`` and ``v`` ``[T, kv_heads *
    dim]``, ``log_g`` ``[T, kv_heads]`` float32, ``seg`` and ``pos``
    ``[T]`` int32 -> ``[T, heads * dim]`` in ``q``'s type. ``T`` is a
    multiple of both blocks (each at most ``T``). Only the query blocks
    under ``live`` tokens (all of them by default) are written; what a
    padding token gets is finite and means nothing."""
    t, kv_heads = log_g.shape
    dim = k.shape[1] // kv_heads
    group = q.shape[1] // (kv_heads * dim)
    block_q, block_k = min(block_q, t), min(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(f"a stream of {t} tokens is no whole number of blocks of {block_q} and {block_k}")
    total = segment_cumsum(log_g.astype(F32), pos == 0).T  # [kv_heads, T]
    qi, kj, flags, visits = block_pairs(pos, t if live is None else live, block_q, block_k)
    seg = seg.astype(jnp.int32)

    def q_at(b, s, qi, kj, fl):
        return qi[s], b

    def k_at(b, s, qi, kj, fl):
        return kj[s], b

    return pl.pallas_call(
        functools.partial(_kernel, group=group, dim=dim, degree=degree, eps=eps),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(kv_heads, visits),
            in_specs=[
                pl.BlockSpec((block_q, group * dim), q_at),
                pl.BlockSpec((block_k, dim), k_at),
                pl.BlockSpec((block_k, dim), k_at),
                pl.BlockSpec((None, block_q, 1), lambda b, s, qi, kj, fl: (b, qi[s], 0)),
                pl.BlockSpec((None, 1, block_k), lambda b, s, qi, kj, fl: (b, 0, kj[s])),
                pl.BlockSpec((block_q, 1), lambda b, s, qi, kj, fl: (qi[s], 0)),
                pl.BlockSpec((1, block_k), lambda b, s, qi, kj, fl: (0, kj[s])),
            ],
            out_specs=pl.BlockSpec((block_q, group * dim), q_at),
            scratch_shapes=[pltpu.VMEM((block_q, group * dim), F32), pltpu.VMEM((group, block_q, 1), F32)],
        ),
        name="power_retention",
        interpret=interpret,
    )(qi, kj, flags, q, k, v, total[:, :, None], total[:, None, :], seg[:, None], seg[None, :])


def symmetric_square(x):
    """``phi(x)`` ``[..., dim (dim + 1) / 2]``: the products ``x_a x_b``, ``a
    <= b``, the off-diagonal ones times sqrt 2."""
    dim = x.shape[-1]
    a, b = jnp.triu_indices(dim)
    return x[..., a] * x[..., b] * jnp.where(a == b, 1.0, jnp.sqrt(2.0)).astype(x.dtype)


def power_retention_reference(q, k, v, log_g, seg, pos, *, eps: float = 1e-6):
    """The recurrence, token by token in float32, degree 2: the state
    ``S`` ``[kv_heads, dim (dim + 1) / 2, dim]`` and ``z`` carried under
    ``lax.scan`` and cleared where a document starts. Arguments as
    :func:`power_retention`; a padding token restarts the state."""
    t, kv_heads = log_g.shape
    dim = k.shape[1] // kv_heads
    group = q.shape[1] // (kv_heads * dim)
    q = q.astype(F32).reshape(t, kv_heads, group, dim)
    k = k.astype(F32).reshape(t, kv_heads, dim)
    v = v.astype(F32).reshape(t, kv_heads, dim)
    features = dim * (dim + 1) // 2

    def step(carry, x):
        state, z = carry
        q_t, k_t, v_t, g_t, new = x
        keep = jnp.where(new, 0.0, jnp.exp(g_t))[:, None]
        phi_k = symmetric_square(k_t)
        state = keep[:, :, None] * state + phi_k[:, :, None] * v_t[:, None, :]
        z = keep * z + phi_k
        phi_q = symmetric_square(q_t)  # [kv_heads, group, features]
        num = jnp.einsum("bgf,bfd->bgd", phi_q, state, precision="highest")
        den = jnp.einsum("bgf,bf->bg", phi_q, z, precision="highest")
        return (state, z), num / (den[..., None] + eps)

    init = (jnp.zeros((kv_heads, features, dim), F32), jnp.zeros((kv_heads, features), F32))
    new = (pos == 0) | (seg < 0)
    _, out = jax.lax.scan(step, init, (q, k, v, log_g.astype(F32), new))
    return out.reshape(t, kv_heads * group * dim)
