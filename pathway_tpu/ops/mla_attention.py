"""Causal multi-head latent attention (MLA, the DeepSeek-V2 lineage's,
un-absorbed) over right-padded texts, as one Pallas TPU kernel: the
attention of ``models/latent_moe.py``.

Per text of ``len`` real tokens, per head ``h``, query ``i`` and key
``j``::

    r_ih = rope(q_rope[i, h])                             # float32, then the inputs' type
    s_ij = (q_nope[i, h] . k_nope[j, h] + r_ih . k_rope[j]) / sqrt(nope + rope)
    p_i  = softmax_j(s_ij over j <= i, j < len)           # float32
    o_ih = sum_j bf16(p_ij) v[j, h]                       # float32 accumulation

``rope`` turns the halves of a head's rope lanes by the token's position
(``cos``/``sin`` tables); ``k_rope``, already turned, is one vector a
token that every head shares. A grid step is one text and a group of
heads: its rows, keys and values come in once, and a head's float32
scores live in VMEM, one key tile at a time against the queries from
that tile's diagonal down — the whole row fits, so the softmax is exact
in two passes with no rescaling, and no tile wholly above the diagonal
is touched. Key validity comes from the texts' lengths (scalar
prefetch), not from a mask array. The context goes out in the ``[b, s,
heads * v]`` layout the output projection reads, in the inputs' type —
what that product casts the float32 context to in the XLA chain this
replaces.

A padded query row (``i >= len``) gets the attention the XLA chain gives
it: over the real keys up to ``i``; a text of no real token gets a
finite row that means nothing.

:func:`route` is the shape rule: the kernel where a text is whole tiles,
XLA elsewhere (the query program's 16-token bucket).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: rows of a query or key tile; a text is whole tiles
BLOCK = 128
#: lanes of the widest per-head input a grid step holds (8 heads of 128)
STEP_LANES = 1024
_MASKED = -1e30


def route(seq: int, impl: str) -> str:
    """``"kernel"`` where a text of ``seq`` tokens is whole tiles and the
    kernel can run — on a TPU, or in the interpreter where a test asks
    for it — else ``"xla"``. A function of the compiled shape."""
    if seq % BLOCK:
        return "xla"
    return "kernel" if impl == "interpret" or jax.default_backend() == "tpu" else "xla"


def heads_per_step(heads: int, width: int) -> int:
    """The most heads (a divisor of ``heads``) whose ``width``-lane
    inputs stay within ``STEP_LANES`` and tile the lanes: a multiple of
    128 lanes, or all of them."""
    fits = [g for g in range(1, heads + 1) if heads % g == 0 and g * width <= STEP_LANES and (g * width) % 128 == 0]
    return max(fits) if fits else heads


def _kernel(lens_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, cos_ref, sin_ref, o_ref, *, group, nope, rot, vd):  # fmt: skip
    seq = qn_ref.shape[0]
    tiles = seq // BLOCK
    live = lens_ref[pl.program_id(0)]
    root = math.sqrt(nope + rot)
    # the group's rope queries turned at once: a head's halves swap inside
    # its own lanes
    x = qr_ref[...]
    lanes = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    turned = jnp.where(lane % rot < rot // 2, -pltpu.roll(x, lanes - rot // 2, 1), pltpu.roll(x, rot // 2, 1))
    q_rope = (x * cos_ref[...] + turned * sin_ref[...]).astype(qn_ref.dtype)
    # a head's rope key sits in slot h % slots, in the lanes its query has
    # in the slice of ``width`` lanes that holds it: products of whole lanes
    slots, width = kr_ref.shape[0], kr_ref.shape[2]

    keeps = []
    for j in range(tiles):  # key tile j meets the queries from its diagonal down
        row = j * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (seq - j * BLOCK, BLOCK), 0)
        col = j * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (seq - j * BLOCK, BLOCK), 1)
        keeps.append((col <= row) & (col < live))

    def nt(a, b):
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=F32)

    for h in range(group):
        heads_lanes = slice((h // slots) * width, (h // slots + 1) * width)
        scores = []
        for j in range(tiles):
            rows, keys = slice(j * BLOCK, seq), slice(j * BLOCK, (j + 1) * BLOCK)
            s = nt(qn_ref[rows, h * nope : (h + 1) * nope], kn_ref[keys, h * nope : (h + 1) * nope])
            s = s + nt(q_rope[rows, heads_lanes], kr_ref[h % slots, keys, :])
            scores.append(jnp.where(keeps[j], s / root, _MASKED))
        # query tile i holds row block (i - j) of key tile j's scores, j <= i
        probs = [[] for _ in range(tiles)]
        for i in range(tiles):
            part = [scores[j][(i - j) * BLOCK : (i - j + 1) * BLOCK] for j in range(i + 1)]
            top = functools.reduce(jnp.maximum, [jnp.max(p, axis=1, keepdims=True) for p in part])
            e = [jnp.exp(p - top) for p in part]
            total = functools.reduce(jnp.add, [jnp.sum(p, axis=1, keepdims=True) for p in e])
            for j in range(i + 1):
                probs[j].append((e[j] / total).astype(v_ref.dtype))
        ctx = [None] * tiles
        for j in range(tiles):
            p = probs[j][0] if len(probs[j]) == 1 else jnp.concatenate(probs[j], axis=0)
            c = jnp.dot(p, v_ref[j * BLOCK : (j + 1) * BLOCK, h * vd : (h + 1) * vd], preferred_element_type=F32)
            for i in range(j, tiles):
                part = c[(i - j) * BLOCK : (i - j + 1) * BLOCK]
                ctx[i] = part if ctx[i] is None else ctx[i] + part
        for i in range(tiles):
            o_ref[i * BLOCK : (i + 1) * BLOCK, h * vd : (h + 1) * vd] = ctx[i].astype(o_ref.dtype)


def mla_attention(q_nope, q_rope, k_nope, k_rope, v, lens, cos, sin, *, out_dtype=None, interpret: bool = False):
    """``q_nope``, ``k_nope`` ``[b, s, heads * nope]`` and ``v`` ``[b, s,
    heads * v]`` in one type (bfloat16 on the normal path); ``q_rope``
    ``[b, s, heads * rope]`` float32, not yet turned; ``k_rope`` ``[b, s,
    rope]`` turned, in that type; ``lens`` ``[b]`` real tokens a text;
    ``cos``, ``sin`` ``[s, rope]`` float32 -> ``[b, s, heads * v]`` in that
    type (or ``out_dtype``). ``s`` is whole tiles of ``BLOCK``."""
    b, s, _ = q_nope.shape
    rot = k_rope.shape[-1]
    heads = q_rope.shape[-1] // rot
    nope, vd = q_nope.shape[-1] // heads, v.shape[-1] // heads
    if s % BLOCK:
        raise ValueError(f"a text of {s} tokens is no whole number of tiles of {BLOCK}")
    group = heads_per_step(heads, max(nope, vd))
    width = min(128, group * rot)
    if (group * rot) % width or width % rot:
        raise ValueError(f"{group} heads of {rot} rope lanes do not tile the lanes")
    slots = width // rot
    # slot p: the rope key in lanes [p * rope, (p + 1) * rope) of ``width``
    k_slots = jnp.stack([jnp.pad(k_rope, ((0, 0), (0, 0), (p * rot, width - (p + 1) * rot))) for p in range(slots)], axis=1)

    def heads_of(lanes):
        return pl.BlockSpec((None, s, group * lanes), lambda t, g, lens: (t, 0, g))

    table = pl.BlockSpec((s, group * rot), lambda t, g, lens: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, group=group, nope=nope, rot=rot, vd=vd),
        out_shape=jax.ShapeDtypeStruct(v.shape, out_dtype or v.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, heads // group),
            in_specs=[
                heads_of(nope),
                heads_of(rot),
                heads_of(nope),
                pl.BlockSpec((None, slots, s, width), lambda t, g, lens: (t, 0, 0, 0)),
                heads_of(vd),
                table,
                table,
            ],
            out_specs=heads_of(vd),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name="mla_attention",
        interpret=interpret,
    )(lens.astype(jnp.int32), q_nope, q_rope, k_nope, k_slots, v, jnp.tile(cos, group), jnp.tile(sin, group))
