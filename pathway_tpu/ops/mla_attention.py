"""Causal multi-head latent attention (MLA, the DeepSeek-V2 lineage's,
un-absorbed) over a packed token stream, as one Pallas TPU kernel: the
attention of ``models/latent_moe.py``.

A stream is ``T`` tokens of consecutive documents; token ``t`` belongs to
document ``seg[t]`` (``-1``: padding) at position ``pos[t]`` in it. Per
head ``h`` and tokens ``j <= i`` of one document::

    r_ih = rope(q_rope[i, h], pos[i])                     # float32, then the inputs' type
    s_ij = (q_nope[i, h] . k_nope[j, h] + r_ih . k_rope[j]) / sqrt(nope + rope)
    p_i  = softmax_j(s_ij over j <= i, seg[j] = seg[i])   # float32
    o_ih = sum_j bf16(p_ij) v[j, h]                       # float32 accumulation

``rope`` turns the halves of a head's rope lanes by the token's position
in its document (the ``cos``/``sin`` rows of its position); ``k_rope``,
already turned, is one vector a token that every head shares. A padded
``[b, s]`` batch is the stream whose document ``i`` starts at ``i * s``.
A call computes the queries of a run of whole tiles of the stream (a
chunk of it, or all of it) against the whole stream's keys.

The grid is (head group, visit). A visit is a (query tile, key tile) pair
of ``BLOCK`` rows each that holds a causal pair of one document; the list
is made on the device from ``pos`` and the live length
(:func:`visits`), so a stream costs its documents' pairs, a query tile
wholly past the live length is not visited, and neither is a key tile
wholly above the diagonal. A query tile's visits are consecutive: each
scores its key tile for the group's heads into VMEM and keeps the tile's
values beside them; the last turns the whole row — at most ``span`` key
tiles, the longest document's — into exact two-pass softmax weights with
no rescaling and writes the context, in the ``[Q, heads * v]`` layout the
output projection reads, in the inputs' type.

A padding query row attends the padding keys before it in its visits
(its own among them): finite, and meaningless. Rows of a query tile that
is not visited are not written.

:func:`route` is the shape rule: the kernel where a stream is whole
tiles, XLA elsewhere (the query program's 16-token bucket).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: rows of a query or key tile; a stream is whole tiles
BLOCK = 128
#: lanes of the widest per-head input a grid step holds (8 heads of 128)
STEP_LANES = 1024
_MASKED = -1e30


def route(seq: int, impl: str) -> str:
    """``"kernel"`` where a stream of ``seq`` tokens is whole tiles and the
    kernel can run — on a TPU, or in the interpreter where a test asks
    for it — else ``"xla"``. A function of the compiled shape."""
    if seq % BLOCK:
        return "xla"
    return "kernel" if impl == "interpret" or jax.default_backend() == "tpu" else "xla"


def span_of(longest: int) -> int:
    """The most key tiles a query tile meets where no document is longer
    than ``longest`` tokens: its first document may start ``longest - 1``
    rows before the tile."""
    return (max(longest, 1) - 1) // BLOCK + 2


def heads_per_step(heads: int, width: int) -> int:
    """The most heads (a divisor of ``heads``) whose ``width``-lane
    inputs stay within ``STEP_LANES`` and tile the lanes: a multiple of
    128 lanes, or all of them."""
    fits = [g for g in range(1, heads + 1) if heads % g == 0 and g * width <= STEP_LANES and (g * width) % 128 == 0]
    return max(fits) if fits else heads


def visits(pos, live, span: int, first=0, tiles: int | None = None):
    """The kernel's visits over a stream whose token ``t`` sits at
    ``pos[t]`` of its document (padding: 0), for the ``tiles`` query tiles
    from row ``first`` (all of them by default): query tile ``i`` (of
    those under the ``live`` tokens) meets every key tile from the one
    holding its first document's first token to its own — ``span`` at
    most. -> (query tile, key tile, the visit's place among its query
    tile's, its query tile's number of visits), each ``[tiles * span]``
    int32, and the number of visits."""
    t = pos.shape[0]
    nk = t // BLOCK
    nq = nk if tiles is None else tiles
    tile = first // BLOCK + jnp.arange(nq, dtype=jnp.int32)
    start = (jnp.arange(t, dtype=jnp.int32) - pos).reshape(nk, BLOCK).min(axis=1)[tile]
    lo = jnp.maximum(start // BLOCK, tile - span + 1)
    count = jnp.where(tile * BLOCK < jnp.maximum(live, 1), tile - lo + 1, 0)
    end = jnp.cumsum(count, dtype=jnp.int32)
    at = jnp.arange(nq * span, dtype=jnp.int32)
    i = jnp.minimum(jnp.searchsorted(end, at, side="right"), nq - 1).astype(jnp.int32)
    nth = at - (end - count)[i]
    kj = jnp.clip(lo[i] + nth, 0, nk - 1)
    return tile[i], kj, nth, count[i], end[-1]


def _kernel(qi_ref, kj_ref, nth_ref, count_ref, base_ref, qn_ref, qr_ref, cos_ref, sin_ref, kn_ref, kr_ref, v_ref,
            sq_ref, sk_ref, o_ref, turned_ref, score_ref, value_ref, *, group, nope, rot, vd, span):  # fmt: skip
    step = pl.program_id(1)
    nth, count = nth_ref[step], count_ref[step]

    @pl.when(nth == 0)
    def _():
        # the group's rope queries turned once a query tile: a head's
        # halves swap inside its own lanes
        x = qr_ref[...]
        lanes = x.shape[1]
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        turned = jnp.where(lane % rot < rot // 2, -pltpu.roll(x, lanes - rot // 2, 1), pltpu.roll(x, rot // 2, 1))
        turned_ref[...] = (x * cos_ref[...] + turned * sin_ref[...]).astype(turned_ref.dtype)

    row = qi_ref[step] * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
    col = kj_ref[step] * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)
    keep = (sq_ref[...] == sk_ref[...]) & (col <= row)
    root = math.sqrt(nope + rot)
    # a head's rope key sits in slot h % slots, in the lanes its query has
    # in the slice of ``width`` lanes that holds it: products of whole lanes
    slots, width = kr_ref.shape[0], kr_ref.shape[2]

    def nt(a, b):
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=F32)

    for h in range(group):
        heads_lanes = slice((h // slots) * width, (h // slots + 1) * width)
        s = nt(qn_ref[:, h * nope : (h + 1) * nope], kn_ref[:, h * nope : (h + 1) * nope])
        s = s + nt(turned_ref[:, heads_lanes], kr_ref[h % slots])
        score_ref[nth, h] = jnp.where(keep, s / root, _MASKED)
    value_ref[nth] = v_ref[...]

    for n in range(1, span + 1):  # the last visit: the row is the query tile's n key tiles

        @pl.when((nth == n - 1) & (count == n))
        def _():
            for h in range(group):
                part = [score_ref[m, h] for m in range(n)]
                top = functools.reduce(jnp.maximum, [jnp.max(p, axis=1, keepdims=True) for p in part])
                e = [jnp.exp(p - top) for p in part]
                total = functools.reduce(jnp.add, [jnp.sum(p, axis=1, keepdims=True) for p in e])
                ctx = None
                for m in range(n):
                    p = (e[m] / total).astype(value_ref.dtype)
                    c = jnp.dot(p, value_ref[m, :, h * vd : (h + 1) * vd], preferred_element_type=F32)
                    ctx = c if ctx is None else ctx + c
                o_ref[:, h * vd : (h + 1) * vd] = ctx.astype(o_ref.dtype)


def mla_attention(q_nope, q_rope, k_nope, k_rope, v, seg, pos, cos, sin, *, span: int, first=0, live=None,
                  out_dtype=None, interpret: bool = False):  # fmt: skip
    """The queries of the ``Q`` rows from ``first`` (a multiple of
    ``BLOCK``) of a stream of ``T`` tokens against its keys: ``q_nope``
    ``[Q, heads * nope]``, ``k_nope`` ``[T, heads * nope]`` and ``v`` ``[T,
    heads * v]`` in one type (bfloat16 on the normal path); ``q_rope`` ``[Q,
    heads * rope]`` float32, not yet turned; ``k_rope`` ``[T, rope]``
    turned, in that type; ``seg``, ``pos`` ``[T]`` int32
    (:func:`token_stream.token_layout`); ``cos``, ``sin`` ``[positions,
    rope]`` float32 by position; ``span`` the most key tiles a query tile
    meets (:func:`span_of` the longest document); ``live`` the tokens up to
    the last real one (all ``T`` by default) -> ``[Q, heads * v]`` in that
    type (or ``out_dtype``). ``Q`` and ``T`` are whole tiles of ``BLOCK``."""
    tq, t = q_nope.shape[0], k_nope.shape[0]
    rot = k_rope.shape[-1]
    heads = q_rope.shape[-1] // rot
    nope, vd = q_nope.shape[-1] // heads, v.shape[-1] // heads
    if tq % BLOCK or t % BLOCK:
        raise ValueError(f"a stream of {t} tokens, {tq} of them queries, is no whole number of tiles of {BLOCK}")
    group = heads_per_step(heads, max(nope, vd))
    width = min(128, group * rot)
    if (group * rot) % width or width % rot:
        raise ValueError(f"{group} heads of {rot} rope lanes do not tile the lanes")
    slots = width // rot
    # slot p: the rope key in lanes [p * rope, (p + 1) * rope) of ``width``
    k_slots = jnp.stack([jnp.pad(k_rope, ((0, 0), (p * rot, width - (p + 1) * rot))) for p in range(slots)])
    pos, seg = pos.astype(jnp.int32), seg.astype(jnp.int32)
    first = jnp.asarray(first, jnp.int32)
    qi, kj, nth, count, steps = visits(pos, t if live is None else live, span, first, tq // BLOCK)
    base = (first // BLOCK).reshape(1)
    at = jax.lax.dynamic_slice_in_dim(pos, first, tq)  # the queries' positions

    def q_at(g, s, qi, kj, nth, count, base):
        return qi[s] - base[0], g

    def k_at(g, s, qi, kj, nth, count, base):
        return kj[s], g

    rows = pl.BlockSpec((BLOCK, group * rot), lambda g, s, qi, kj, nth, count, base: (qi[s] - base[0], 0))
    return pl.pallas_call(
        functools.partial(_kernel, group=group, nope=nope, rot=rot, vd=vd, span=span),
        out_shape=jax.ShapeDtypeStruct((tq, v.shape[1]), out_dtype or v.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(heads // group, steps),
            in_specs=[
                pl.BlockSpec((BLOCK, group * nope), q_at),
                pl.BlockSpec((BLOCK, group * rot), q_at),
                rows,
                rows,
                pl.BlockSpec((BLOCK, group * nope), k_at),
                pl.BlockSpec((slots, BLOCK, width), lambda g, s, qi, kj, nth, count, base: (0, kj[s], 0)),
                pl.BlockSpec((BLOCK, group * vd), k_at),
                pl.BlockSpec((BLOCK, 1), lambda g, s, qi, kj, nth, count, base: (qi[s], 0)),
                pl.BlockSpec((1, BLOCK), lambda g, s, qi, kj, nth, count, base: (0, kj[s])),
            ],
            out_specs=pl.BlockSpec((BLOCK, group * vd), q_at),
            scratch_shapes=[
                pltpu.VMEM((BLOCK, group * rot), q_nope.dtype),
                pltpu.VMEM((span, group, BLOCK, BLOCK), F32),
                pltpu.VMEM((span, BLOCK, group * vd), v.dtype),
            ],
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        name="mla_attention",
        interpret=interpret,
    )(qi, kj, nth, count, base, q_nope, q_rope, jnp.tile(cos[at], group), jnp.tile(sin[at], group), k_nope, k_slots, v,
      seg[:, None], seg[None, :])  # fmt: skip
