"""The routed experts of a sparse feed-forward, as one expert-parallel
rank computes them: the layer is told which experts it holds
(``first`` and the leading axis of the expert weights), routes every
token over the router's whole width, and returns the weighted sum of its
own experts' outputs for the tokens routed to them. What the experts it
does not hold would have added is left out; nothing stands in for the
other ranks or their exchange.

Dropless, with static shapes: the (token, expert) pairs of held experts
are sorted by expert and taken ``capacity`` at a time — the number a
full, evenly routed batch brings here — so an even batch is one round
and a router skewed onto this rank's experts costs more rounds, never a
dropped pair. A round gathers its pairs' token rows, runs three grouped
products over the ragged groups (gate, up, down: SwiGLU) and adds the
weighted rows back to their tokens, so the work follows the assignments
and not tokens x experts held. Pad tokens are not routed.

The way back is a gather, not a scatter (XLA's scatter-add of 4,096 rows
of 7,680 float32 took 22.5 ms on the v5e, five times the three products):
the round's rows are sorted by token, a token's rows — ``top_k`` at most,
neighbours now — are summed onto the first of them, and every token
looks its first row up.

The grouped product is a Pallas kernel: a grid step multiplies one row
tile of one group by that group's ``[K, tn]`` block of the weights (all
of ``K``, so a group's block is read once however many tiles it spans),
and the grid's second axis is as long as the round's tiles, counted on
the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
ROW_TILE = 128
#: the largest ``[K, tn]`` block of expert weights a grid step holds (two
#: of them are in flight): 4 MiB leaves the row tile and the output room
#: under the compiler's default scoped-VMEM limit
_WEIGHT_BLOCK_BYTES = 4 << 20


def route(scores, top_k: int, *, scale: float, norm_topk: bool = True):
    """-> (expert ids ``[tokens, top_k]``, weights ``[tokens, top_k]``):
    the ``top_k`` highest of each token's scores, each weight the score
    over the sum of the chosen ones (all of them, held here or not)
    times ``scale``."""
    vals, idx = jax.lax.top_k(scores, top_k)
    if norm_topk:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return idx, scale * vals


def capacity_of(tokens: int, top_k: int, held: int, experts: int, tile: int = ROW_TILE) -> int:
    """Pairs of one round: what a full, evenly routed batch brings to
    ``held`` of ``experts`` experts, rounded up to a row tile."""
    even = -(-tokens * top_k * held // experts)
    return -(-even // tile) * tile


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """The widest multiple of 128 that divides ``n`` and keeps a ``[k,
    tn]`` block under ``_WEIGHT_BLOCK_BYTES``; all of ``n`` where none does."""
    best = 0
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and k * tn * itemsize <= _WEIGHT_BLOCK_BYTES:
            best = tn
    return best or n


def _gmm_kernel(visit_group, visit_tile, group_lo, group_hi, x_ref, w_ref, o_ref, *, tm: int):
    i = pl.program_id(1)
    g = visit_group[i]
    acc = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=F32)
    rows = visit_tile[i] * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    mine = (rows >= group_lo[g]) & (rows < group_hi[g])
    # a tile that two groups share is visited twice in a row and stays
    # in VMEM between the visits: keep what the earlier one wrote
    o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(F32)).astype(o_ref.dtype)


def grouped_matmul(x, w, sizes, *, out_dtype=F32, tile: int = ROW_TILE, interpret: bool = False):
    """``x[lo_g:hi_g] @ w[g]`` for the consecutive row groups of
    ``sizes`` (``[groups]`` int32, summing to at most ``x.shape[0]``, a
    multiple of ``tile``). Rows past the last group are not written:
    the caller masks them."""
    m, k = x.shape
    groups, _, n = w.shape
    tm = tile
    tn = _column_tile(k, n, w.dtype.itemsize)
    hi = jnp.cumsum(sizes, dtype=jnp.int32)
    lo = hi - sizes
    # visits: every (group, row tile it touches), groups in order
    visits = jnp.where(sizes > 0, (hi - 1) // tm - lo // tm + 1, 0)
    visit_end = jnp.cumsum(visits, dtype=jnp.int32)
    at = jnp.arange(m // tm + groups - 1, dtype=jnp.int32)
    visit_group = jnp.minimum(jnp.searchsorted(visit_end, at, side="right"), groups - 1).astype(jnp.int32)
    visit_tile = lo[visit_group] // tm + at - (visit_end - visits)[visit_group]
    visit_tile = jnp.clip(visit_tile, 0, m // tm - 1)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, visit_end[-1]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, vg, vt, lo_, hi_: (vt[i], 0)),
                pl.BlockSpec((None, k, tn), lambda j, i, vg, vt, lo_, hi_: (vg[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, vg, vt, lo_, hi_: (vt[i], j)),
        ),
        name="expert_grouped_matmul",
        interpret=interpret,
    )(visit_group, visit_tile, lo, hi, x, w)


def _add_to_tokens(out, token, y, live, most: int):
    """``out[token[i]] += y[i]`` over the live rows ``i``, a token in at
    most ``most`` of them. Dead rows of ``y`` may hold anything."""
    tokens, cap = out.shape[0], token.shape[0]
    key = jnp.where(live, token, tokens)
    order = jnp.argsort(key)
    tok = jnp.pad(key[order], (0, most - 1), constant_values=-1)
    rows = jnp.pad(y[order], ((0, most - 1), (0, 0)))
    total = rows[:cap]
    for j in range(1, most):  # a token's rows are neighbours: onto the first of them
        total = total + jnp.where((tok[j : j + cap] == tok[:cap])[:, None], rows[j : j + cap], 0.0)
    at = jnp.arange(tokens)
    first = jnp.minimum(jnp.searchsorted(tok[:cap], at), cap - 1)
    return out + jnp.where((tok[first] == at)[:, None], total[first], 0.0)


def held_expert_sum(
    x,
    expert_ids,
    weights,
    real,
    w_gate,
    w_up,
    w_down,
    *,
    first: int,
    experts: int,
    tile: int = ROW_TILE,
    interpret: bool = False,
):
    """-> (``[tokens, d]`` float32: for every token the sum over its
    chosen experts that are held here of ``weight * swiglu_e(x)``;
    ``[held]`` int32: real tokens assigned to each held expert).

    ``x`` ``[tokens, d]`` in the weights' type; ``expert_ids`` and
    ``weights`` ``[tokens, top_k]`` from :func:`route`; ``real``
    ``[tokens]`` bool; ``w_gate``/``w_up`` ``[held, d, inner]`` and
    ``w_down`` ``[held, inner, d]``: experts ``first ... first + held -
    1`` of the router's ``experts``."""
    tokens, d = x.shape
    top_k = expert_ids.shape[1]
    held = w_gate.shape[0]
    local = expert_ids - first
    ours = (local >= 0) & (local < held) & real[:, None]
    key = jnp.where(ours, local, held).reshape(-1)
    loads = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32)
    hi = jnp.cumsum(loads, dtype=jnp.int32)
    lo, n_pairs = hi - loads, hi[-1]
    cap = min(capacity_of(tokens, top_k, held, experts, tile), -(-tokens * top_k // tile) * tile)
    # held pairs first, by expert; padded so that every round is whole
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, (-order.shape[0]) % cap))
    flat_weights = weights.reshape(-1)
    product = functools.partial(grouped_matmul, tile=tile, interpret=interpret)

    def one_round(r, out):
        start = r * cap
        pairs = jax.lax.dynamic_slice(order, (start,), (cap,))
        token = pairs // top_k
        rows = x[token]
        sizes = jnp.clip(hi, start, start + cap) - jnp.clip(lo, start, start + cap)
        with jax.named_scope("pw.encode.moe_experts"):
            gate = product(rows, w_gate, sizes)
            up = product(rows, w_up, sizes)
            act = (jax.nn.silu(gate) * up).astype(x.dtype)
            y = product(act, w_down, sizes)
        live = start + jnp.arange(cap) < n_pairs
        return _add_to_tokens(out, token, y * flat_weights[pairs][:, None], live, min(top_k, held))

    rounds = (n_pairs + cap - 1) // cap
    return jax.lax.fori_loop(0, rounds, one_round, jnp.zeros((tokens, d), F32)), loads
