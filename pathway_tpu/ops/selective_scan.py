"""Selective scan (the Mamba-1 recurrence) as one Pallas dispatch.

For every document ``b`` and channel ``d``, with a state of ``N`` values::

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * u_t) * B_t        s_{-1} = 0
    y_t = (s_t . C_t + D_skip * u_t) * silu(z_t)

Grid over (documents, channel blocks); the whole time axis of a block is
resident and the loop over time runs inside the kernel, so the
``[N, block]`` float32 state lives in VMEM from a document's first token
to its last and nothing of shape ``[tokens, channels, N]`` ever exists in
HBM. Documents are rows of the batch: a grid step starts from a zero
state, so no state crosses a document. The recurrence is causal and
padding is on the right, so a pad token cannot reach a real token's
state.

Layout: channels on lanes, the state's ``N`` on sublanes. ``B_t`` and
``C_t`` are needed as ``[N, 1]`` columns, so the wrapper hands them over
as ``[batch, L/T, N, T]`` — a chunk of ``T`` time steps is one small tile
whose columns the unrolled inner loop takes by static lane index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "selective_scan"
TIME_CHUNK = 16  # time steps unrolled per loop turn; a bf16 tile's rows
CHANNEL_BLOCKS = (512, 256, 128)  # lanes of state a grid step holds


def _kernel(u_ref, dt_ref, z_ref, bt_ref, ct_ref, a_ref, dskip_ref, y_ref, state, ybuf):
    T = TIME_CHUNK
    n_chunks = u_ref.shape[1] // T
    a = a_ref[...]  # [N, blk] float32
    state[...] = jnp.zeros_like(state)

    def chunk(c, carry):
        t0 = pl.multiple_of(c * T, T)
        u = u_ref[0, pl.ds(t0, T), :].astype(jnp.float32)  # [T, blk]
        dt = dt_ref[0, pl.ds(t0, T), :]
        bt = bt_ref[0, c]  # [N, T]
        ct = ct_ref[0, c]
        dtu = dt * u
        s = state[...]
        for j in range(T):
            decay = jnp.exp(dt[j : j + 1, :] * a)  # [N, blk]
            s = decay * s + bt[:, j : j + 1] * dtu[j : j + 1, :]
            ybuf[j : j + 1, :] = jnp.sum(s * ct[:, j : j + 1], axis=0, keepdims=True)
        state[...] = s
        z = z_ref[0, pl.ds(t0, T), :].astype(jnp.float32)
        y = (ybuf[...] + dskip_ref[...] * u) * (z * jax.nn.sigmoid(z))
        y_ref[0, pl.ds(t0, T), :] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(u, dt, z, b, c, a, d_skip, *, interpret: bool = False):
    """``u``, ``z`` [batch, L, D] (any float type; ``y`` comes back in
    ``u``'s), ``dt`` [batch, L, D] float32 (after softplus), ``b``, ``c``
    [batch, L, N] float32, ``a`` [D, N] float32 (negative), ``d_skip`` [D]
    float32 -> ``y`` [batch, L, D]. ``interpret`` runs the kernel in the
    Pallas interpreter (CPU tests), the way ``fused_encoder_interpret``
    chooses it for the whole-layer kernel."""
    batch, length, d = u.shape
    n = a.shape[1]
    T = TIME_CHUNK
    lp = -(-length // T) * T
    dp = -(-d // 128) * 128
    blk = next(x for x in CHANNEL_BLOCKS if dp % x == 0)

    def pad(x):  # zeros on the right of time and of channels: causal, so harmless
        return jnp.pad(x, ((0, 0), (0, lp - length), (0, dp - d)))

    def columns(x):  # [batch, L, N] -> [batch, L/T, N, T]
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, lp - length), (0, 0)))
        return x.reshape(batch, lp // T, T, n).transpose(0, 1, 3, 2)

    at = jnp.pad(a.astype(jnp.float32).T, ((0, 0), (0, dp - d)))  # [N, D]
    dsk = jnp.pad(d_skip.astype(jnp.float32), (0, dp - d))[None, :]
    seq = pl.BlockSpec((1, lp, blk), lambda i, j: (i, 0, j))
    col = pl.BlockSpec((1, lp // T, n, T), lambda i, j: (i, 0, 0, 0))
    y = pl.pallas_call(
        _kernel,
        grid=(batch, dp // blk),
        in_specs=[
            seq,
            seq,
            seq,
            col,
            col,
            pl.BlockSpec((n, blk), lambda i, j: (0, j)),
            pl.BlockSpec((1, blk), lambda i, j: (0, j)),
        ],
        out_specs=seq,
        out_shape=jax.ShapeDtypeStruct((batch, lp, dp), u.dtype),
        scratch_shapes=[pltpu.VMEM((n, blk), jnp.float32), pltpu.VMEM((T, blk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(pad(u), pad(dt.astype(jnp.float32)), pad(z), columns(b), columns(c), at, dsk)
    return y[:, :length, :d]


def selective_scan_reference(u, dt, z, b, c, a, d_skip):
    """The same recurrence as a ``lax.scan`` over time, float32, with the
    ``[batch, D, N]`` state as its carry: what the kernel is held to."""
    u32, z32 = u.astype(jnp.float32), z.astype(jnp.float32)

    def step(s, x):
        u_t, dt_t, b_t, c_t = x  # [batch, D], [batch, D], [batch, N], [batch, N]
        s = jnp.exp(dt_t[:, :, None] * a[None]) * s + (dt_t * u_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("bdn,bn->bd", s, c_t, precision=jax.lax.Precision.HIGHEST)

    s0 = jnp.zeros((u.shape[0], u.shape[2], a.shape[1]), jnp.float32)
    xs = tuple(jnp.swapaxes(x.astype(jnp.float32), 0, 1) for x in (u32, dt, b, c))
    _, ys = jax.lax.scan(step, s0, xs)
    y = jnp.swapaxes(ys, 0, 1) + d_skip[None, None, :] * u32
    return (y * jax.nn.silu(z32)).astype(u.dtype)
