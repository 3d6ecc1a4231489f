"""Selective scan (the Mamba-1 recurrence) over a packed token stream, as
one Pallas dispatch.

A stream is ``T`` tokens of consecutive documents. For every channel
``d``, with a state of ``N`` values::

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * u_t) * B_t        s = 0 before a document's first token
    y_t = (s_t . C_t + D_skip * u_t) * silu(z_t)

Grid over (channel blocks, time blocks): channels in parallel, time in
order, so the ``[N, block]`` float32 state stays in VMEM scratch from one
time block to the next and nothing of shape ``[tokens, channels, N]``
ever exists in HBM. A document starts on a multiple of ``TIME_CHUNK``
only (the caller aligns them), so a boundary is one flag a chunk — read
from SMEM before the chunk's unrolled steps, which clears the state with
a store — and no select sits on the per-step vector path. The state is
cleared, never scaled: whatever a padding token behind a document's last
real one left in it (it may be anything) does not reach the next
document. The grid's time axis is as long as the stream's live length:
time blocks past it are not visited and their ``y`` is not written. The
recurrence is causal and a document's padding is on its right, so a pad
token cannot reach a real token's state.

Layout: channels on lanes, the state's ``N`` on sublanes. ``B_t`` and
``C_t`` are needed as ``[N, 1]`` columns, so the wrapper hands them over
as ``[T / TIME_CHUNK, N, TIME_CHUNK]`` — a chunk of time steps is one
small tile whose columns the unrolled inner loop takes by static lane
index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "selective_scan"
TIME_CHUNK = 16  # time steps unrolled per loop turn; a bf16 tile's rows; what a document's start is aligned to
TIME_BLOCK = 512  # time steps a grid step holds in VMEM
CHANNEL_BLOCKS = (512, 256, 128)  # lanes of state a grid step holds


def _kernel(reset_ref, u_ref, dt_ref, z_ref, bt_ref, ct_ref, a_ref, dskip_ref, y_ref, state, ybuf):
    T = TIME_CHUNK
    n_chunks = u_ref.shape[0] // T
    first = pl.program_id(1) * n_chunks  # this time block's first chunk, in the stream
    a = a_ref[...]  # [N, blk] float32

    def chunk(c, carry):
        @pl.when(reset_ref[first + c] != 0)
        def _():
            state[...] = jnp.zeros_like(state)

        t0 = pl.multiple_of(c * T, T)
        u = u_ref[pl.ds(t0, T), :].astype(jnp.float32)  # [T, blk]
        dt = dt_ref[pl.ds(t0, T), :]
        bt = bt_ref[c]  # [N, T]
        ct = ct_ref[c]
        dtu = dt * u
        s = state[...]
        for j in range(T):
            decay = jnp.exp(dt[j : j + 1, :] * a)  # [N, blk]
            s = decay * s + bt[:, j : j + 1] * dtu[j : j + 1, :]
            ybuf[j : j + 1, :] = jnp.sum(s * ct[:, j : j + 1], axis=0, keepdims=True)
        state[...] = s
        z = z_ref[pl.ds(t0, T), :].astype(jnp.float32)
        y = (ybuf[...] + dskip_ref[...] * u) * (z * jax.nn.sigmoid(z))
        y_ref[pl.ds(t0, T), :] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(u, dt, z, b, c, a, d_skip, starts, *, live=None, interpret: bool = False):
    """``u``, ``z`` ``[T, D]`` (any float type; ``y`` comes back in
    ``u``'s), ``dt`` ``[T, D]`` float32 (after softplus), ``b``, ``c``
    ``[T, N]`` float32, ``a`` ``[D, N]`` float32 (negative), ``d_skip``
    ``[D]`` float32, ``starts`` ``[docs]`` int32 — the tokens at which a
    document starts, each a multiple of ``TIME_CHUNK`` (one at or past
    ``T`` is no document; token 0 always starts one) -> ``y`` ``[T, D]``.
    Only the time blocks (of ``TIME_BLOCK`` tokens) under ``live`` tokens
    (all of them by default) are computed; what ``y`` holds past them
    means nothing. ``interpret`` runs the kernel in the Pallas
    interpreter (CPU tests)."""
    length, d = u.shape
    n = a.shape[1]
    T = TIME_CHUNK
    tb = min(TIME_BLOCK, -(-length // T) * T)
    lp = -(-length // tb) * tb
    dp = -(-d // 128) * 128
    blk = next(x for x in CHANNEL_BLOCKS if dp % x == 0)

    def pad(x):  # zeros on the right of time and of channels: causal, so harmless
        return jnp.pad(x, ((0, lp - length), (0, dp - d)))

    def columns(x):  # [T, N] -> [T / TIME_CHUNK, N, TIME_CHUNK]
        x = jnp.pad(x.astype(jnp.float32), ((0, lp - length), (0, 0)))
        return x.reshape(lp // T, T, n).transpose(0, 2, 1)

    reset = jnp.zeros((lp // T,), jnp.int32).at[starts // T].set(1, mode="drop").at[0].set(1)
    blocks = lp // tb if live is None else jnp.clip((live + tb - 1) // tb, 1, lp // tb).astype(jnp.int32)
    at = jnp.pad(a.astype(jnp.float32).T, ((0, 0), (0, dp - d)))  # [N, D]
    dsk = jnp.pad(d_skip.astype(jnp.float32), (0, dp - d))[None, :]
    seq = pl.BlockSpec((tb, blk), lambda i, j, reset: (j, i))
    col = pl.BlockSpec((tb // T, n, T), lambda i, j, reset: (j, 0, 0))
    y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(dp // blk, blocks),
            in_specs=[
                seq,
                seq,
                seq,
                col,
                col,
                pl.BlockSpec((n, blk), lambda i, j, reset: (0, i)),
                pl.BlockSpec((1, blk), lambda i, j, reset: (0, i)),
            ],
            out_specs=seq,
            scratch_shapes=[pltpu.VMEM((n, blk), jnp.float32), pltpu.VMEM((T, blk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((lp, dp), u.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(reset, pad(u), pad(dt.astype(jnp.float32)), pad(z), columns(b), columns(c), at, dsk)
    return y[:length, :d]


def selective_scan_reference(u, dt, z, b, c, a, d_skip, starts):
    """The same recurrence as a ``lax.scan`` over the stream, float32, with
    the ``[D, N]`` state as its carry, cleared at every token of
    ``starts``: what the kernel is held to."""
    u32, z32 = u.astype(jnp.float32), z.astype(jnp.float32)
    new = jnp.zeros((u.shape[0],), bool).at[starts].set(True, mode="drop")

    def step(s, x):
        u_t, dt_t, b_t, c_t, new_t = x  # [D], [D], [N], [N], []
        s = jnp.where(new_t, 0.0, s)
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, jnp.einsum("dn,n->d", s, c_t, precision=jax.lax.Precision.HIGHEST)

    s0 = jnp.zeros((u.shape[1], a.shape[1]), jnp.float32)
    _, ys = jax.lax.scan(step, s0, (u32, dt.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32), new))
    return ((ys + d_skip[None, :] * u32) * jax.nn.silu(z32)).astype(u.dtype)
