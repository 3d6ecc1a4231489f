"""Pallas paged-KV attention: decode-step attention over a page pool.

The decode plane (``pathway_tpu/decode``) keeps every in-flight query's
KV cache in *fixed-size pages* carved out of one preallocated HBM pool,
so thousands of concurrent sequences of wildly different lengths share
the chip without per-sequence reallocation or fragmentation (the
Ragged Paged Attention recipe, PAPERS.md). A sequence owns a *page
table* — the list of pool slots holding its context in order — and a
decode step attends one query token against that scattered context.

Kernel layout (one ``pallas_call``, grid ``(batch, pages_per_seq)``):

- the per-sequence page tables and context lengths ride in SMEM via
  scalar prefetch, so the *index map* of the K/V operands can chase the
  page table — grid step ``(b, p)`` streams pool page ``table[b, p]``
  into VMEM, nothing else moves;
- each live page is copied into a persistent VMEM gather buffer at its
  logical offset; pages wholly past the sequence length are dead and
  skipped (``pl.when``), reusing the PR 8 dead-skip idea at page
  granularity;
- at the last page step the buffer holds the sequence's whole context
  and one fused softmax·V finishes the query token (single softmax —
  no online rescaling — so the paged output is *bitwise* equal to the
  dense reference, which the CPU parity suite asserts via
  ``interpret=True`` exactly like ``fused_encoder_interpret``).

Padding positions inside the buffer may hold stale data from earlier
grid steps; they are masked with the same additive ``KEY_OFF`` bias as
the fused encoder, which underflows their softmax weight to exactly
``0.0`` — stale finite values then contribute exact zeros to the
weighted sum, which is what makes bitwise parity possible at all.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_attention import KEY_OFF

__all__ = [
    "PagedKvPool",
    "dense_decode_attention",
    "paged_decode_attention",
    "paged_attention_reference",
    "require_kernel_page_size",
    "pages_for",
    "kv_pool_bytes",
]


def pages_for(length: int, page_size: int) -> int:
    """Number of fixed-size pages covering ``length`` context tokens."""
    return max(0, (int(length) + page_size - 1) // page_size)


def deep_trace_spec(decode_cfg: dict) -> dict | None:
    """Representative decode-step callable for the deep verifier's
    jaxpr pass (analysis.deep): the gather-then-dense reference path,
    which carries the same op structure as the production step minus
    the pallas kernel body. Shapes follow the configured pool geometry
    at a tiny hidden dim — tracing only, nothing compiles."""
    try:
        import jax
    except Exception:  # pragma: no cover - jax is baked into the image
        return None
    import numpy as _np

    lanes = max(1, int(decode_cfg.get("lanes") or 1))
    page_size = max(1, int(decode_cfg.get("page_size") or 16))
    max_seq = max(page_size, int(decode_cfg.get("max_seq") or 512))
    pps = pages_for(max_seq, page_size)
    n_pages = max(int(decode_cfg.get("pages") or 0), pps, 1)
    d, n_heads = 64, 4
    args = (
        jax.ShapeDtypeStruct((lanes, d), _np.float32),
        jax.ShapeDtypeStruct((n_pages, page_size, d), _np.float32),
        jax.ShapeDtypeStruct((n_pages, page_size, d), _np.float32),
        jax.ShapeDtypeStruct((lanes, pps), _np.int32),
        jax.ShapeDtypeStruct((lanes,), _np.int32),
    )
    return {
        "name": f"decode.step[lanes={lanes},page={page_size}]",
        "fn": lambda q, kp, vp, pt, ln: paged_attention_reference(
            q, kp, vp, pt, ln, n_heads=n_heads
        ),
        "args": args,
    }


def deep_compile_profile(decode_cfg: dict) -> dict:
    """Predicted distinct-compile count for the decode plane
    (analysis.deep, PWL018): the step always runs at the padded
    (lanes, pages_per_seq) width — one program regardless of live
    sequences — plus one prefill program per seq bucket up to
    ``max_seq``."""
    from ..models.batching import DEFAULT_SEQ_BUCKETS, bucket

    max_seq = int(decode_cfg.get("max_seq") or 512)
    cap = bucket(max_seq, DEFAULT_SEQ_BUCKETS)
    prefill = [s for s in DEFAULT_SEQ_BUCKETS if s <= cap] or [cap]
    detail: dict = {"prefill_seq_buckets": prefill, "step_programs": 1}
    compiles = 1 + len(prefill)
    if decode_cfg.get("spec_tokens"):
        # speculative serving swaps the step for a draft scan plus a
        # verify scan — two programs regardless of spec_tokens
        detail["spec_programs"] = 2
        compiles += 2
    if decode_cfg.get("prefix_cache") or decode_cfg.get("prefill_chunk"):
        # chunked prefill compiles per chunk bucket, capped by the
        # configured chunk size (or max_seq when only the cache is on)
        chunk_cap = bucket(
            int(decode_cfg.get("prefill_chunk") or max_seq), DEFAULT_SEQ_BUCKETS
        )
        chunks = [s for s in DEFAULT_SEQ_BUCKETS if s <= chunk_cap] or [chunk_cap]
        detail["chunk_buckets"] = chunks
        compiles += len(chunks)
    return {
        "compiles": compiles,
        "detail": detail,
        "unbucketed": [],
    }


def kv_pool_bytes(
    n_pages: int, page_size: int, layers: int, dim: int, dtype_bytes: int = 4
) -> int:
    """HBM footprint of a K+V page pool (the PWL010/012 budget unit).
    Delegates to the shared footprint model in ``internals/ledger``."""
    from ..internals.ledger import kv_pool_bytes as _kv_pool_bytes

    return _kv_pool_bytes(n_pages, page_size, layers, dim, dtype_bytes)


def require_kernel_page_size(page_size: int) -> None:
    """The compiled kernel stores each page into the gather buffer at
    the dynamic row offset ``p * page_size``, and Mosaic takes a dynamic
    sublane offset only where it can prove it a multiple of the f32
    tile's 8 rows. Interpret mode and the XLA reference take any size."""
    if page_size % 8:
        raise ValueError(
            f"paged attention: the TPU kernel needs page_size to be a "
            f"multiple of 8, got {page_size} (or run impl='xla')"
        )


def _attend(q, k, v, length, n_heads: int, scale: float):
    """One query row against one gathered context — the *shared* op
    sequence. The kernel calls it on VMEM refs' values; the dense
    reference vmaps it over the batch. Using literally the same ops in
    the same order is what the bitwise-parity acceptance gate rides on.

    ``q``: (1, d) · ``k``/``v``: (ctx, d) · ``length``: scalar int32.
    Positions ``>= length`` get the additive ``KEY_OFF`` bias; their
    softmax weight underflows to exactly 0.0, so arbitrary (finite)
    values there cannot perturb the output.
    """
    d = q.shape[-1]
    hd = d // n_heads
    ctx = k.shape[0]
    kiota = jax.lax.broadcasted_iota(jnp.int32, (1, ctx), 1)
    bias = jnp.where(kiota < length, 0.0, KEY_OFF)
    outs = []
    for h in range(n_heads):
        qh = q[:, h * hd : (h + 1) * hd]
        kh = k[:, h * hd : (h + 1) * hd]
        vh = v[:, h * hd : (h + 1) * hd]
        s = (
            jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
            + bias
        )
        m = jnp.max(s, axis=1, keepdims=True)
        e = jnp.exp(s - m)
        p = e / jnp.sum(e, axis=1, keepdims=True)
        outs.append(
            jax.lax.dot_general(
                p, vh, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
        )
    return jnp.concatenate(outs, axis=1)


def dense_decode_attention(q, k_ctx, v_ctx, lens, *, n_heads: int, scale=None):
    """Dense reference: one query token per sequence over a contiguous
    context. ``q``: [B, d] · ``k_ctx``/``v_ctx``: [B, ctx, d] ·
    ``lens``: [B] int32. Returns [B, d] float32; rows with
    ``lens == 0`` are exactly zero (matching the kernel's dead path)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1] // n_heads)
    q = q.astype(jnp.float32)
    k_ctx = k_ctx.astype(jnp.float32)
    v_ctx = v_ctx.astype(jnp.float32)
    # unrolled per-row, NOT vmap: a vmapped batch fuses the per-head
    # dots into batched GEMMs whose accumulation order differs from the
    # kernel's per-sequence (1, d) dots by ~1 ulp — bitwise parity
    # requires the reference to walk rows exactly like the grid does
    rows = []
    for b in range(q.shape[0]):
        out = _attend(q[b : b + 1], k_ctx[b], v_ctx[b], lens[b], n_heads, scale)
        rows.append(jnp.where(lens[b] > 0, out, jnp.zeros_like(out)))
    return jnp.concatenate(rows, axis=0)


def _paged_kernel(
    pt_ref,  # SMEM [B, P] page tables (scalar prefetch)
    lens_ref,  # SMEM [B] context lengths (scalar prefetch)
    q_ref,  # VMEM (1, 1, d) query token for sequence b
    k_ref,  # VMEM (1, page_size, d) pool page table[b, p]
    v_ref,  # VMEM (1, page_size, d)
    o_ref,  # VMEM (1, 1, d)
    k_buf,  # VMEM scratch (P * page_size, d) — persists across grid steps
    v_buf,
    *,
    page_size: int,
    pages_per_seq: int,
    n_heads: int,
    scale: float,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    length = lens_ref[b]

    # gather phase: copy this page into the buffer at its logical slot;
    # pages wholly past the sequence length never move (dead-skip) —
    # their buffer slot is zero-filled instead, because VMEM scratch is
    # UNDEFINED (NaN in interpret mode, arbitrary bits on hardware) and
    # the KEY_OFF mask only yields exact zeros against finite values
    @pl.when(p * page_size < length)
    def _copy():
        k_buf[pl.ds(p * page_size, page_size), :] = k_ref[0]
        v_buf[pl.ds(p * page_size, page_size), :] = v_ref[0]

    @pl.when(p * page_size >= length)
    def _zero():
        k_buf[pl.ds(p * page_size, page_size), :] = jnp.zeros(
            (page_size, k_buf.shape[1]), k_buf.dtype
        )
        v_buf[pl.ds(p * page_size, page_size), :] = jnp.zeros(
            (page_size, v_buf.shape[1]), v_buf.dtype
        )

    # compute phase: the buffer is complete once the last page step of
    # this sequence ran — one softmax over the whole gathered context
    @pl.when(p == pages_per_seq - 1)
    def _compute():
        @pl.when(length == 0)
        def _dead():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(length > 0)
        def _live():
            o_ref[0] = _attend(
                q_ref[0], k_buf[...], v_buf[...], length, n_heads, scale
            ).astype(o_ref.dtype)


def paged_decode_attention(
    q,
    k_pages,
    v_pages,
    page_tables,
    lens,
    *,
    n_heads: int,
    scale=None,
    interpret: bool = False,
):
    """Paged-KV decode attention. ``q``: [B, d] · ``k_pages``/
    ``v_pages``: [n_pages, page_size, d] pool · ``page_tables``:
    [B, P] int32 (entries past ``pages_for(lens[b])`` are ignored and
    may be any in-range value) · ``lens``: [B] int32. Returns [B, d]
    float32, bitwise-equal to :func:`paged_attention_reference` *under
    jit* (both paths compiled — eager dispatch skips the FMA
    contraction the compiled pipeline applies to ``dot·scale + bias``
    and lands ~1 ulp away; the parity suite and the decode engine both
    run the reference jitted)."""
    b, d = q.shape
    n_pages, page_size, _ = k_pages.shape
    if not interpret:
        require_kernel_page_size(page_size)
    pages_per_seq = page_tables.shape[1]
    ctx = pages_per_seq * page_size
    if scale is None:
        scale = 1.0 / math.sqrt(d // n_heads)
    # dead entries may carry an out-of-range sentinel; the index map
    # must still name a real pool slot (the copy is skipped anyway)
    page_tables = jnp.minimum(page_tables.astype(jnp.int32), n_pages - 1)
    kernel = functools.partial(
        _paged_kernel,
        page_size=page_size,
        pages_per_seq=pages_per_seq,
        n_heads=n_heads,
        scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_seq),
        # q and the output ride as [B, 1, d]: a (1, d) block over [B, d]
        # does not lower (Mosaic wants the last two block dims
        # (8, 128)-aligned or whole), a (1, 1, d) block's are whole
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, p, pt, ln: (i, 0, 0)),
            pl.BlockSpec((1, page_size, d), lambda i, p, pt, ln: (pt[i, p], 0, 0)),
            pl.BlockSpec((1, page_size, d), lambda i, p, pt, ln: (pt[i, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, p, pt, ln: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((ctx, d), jnp.float32),
            pltpu.VMEM((ctx, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, d), jnp.float32),
        # the gather buffer carries state across page steps of one
        # sequence, so the grid must run sequentially
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(
        page_tables,
        lens.astype(jnp.int32),
        q.astype(jnp.float32).reshape(b, 1, d),
        k_pages.astype(jnp.float32),
        v_pages.astype(jnp.float32),
    )
    return out.reshape(b, d)


def paged_attention_reference(
    q, k_pages, v_pages, page_tables, lens, *, n_heads: int, scale=None
):
    """Gather-then-dense reference (also the XLA fallback path the
    decode engine uses off-TPU): reassemble each sequence's context
    from its pages with a plain take, then run the dense kernel."""
    n_pages, page_size, d = k_pages.shape
    b, pages_per_seq = page_tables.shape
    pt = jnp.minimum(page_tables.astype(jnp.int32), n_pages - 1)
    k_ctx = k_pages[pt].reshape(b, pages_per_seq * page_size, d)
    v_ctx = v_pages[pt].reshape(b, pages_per_seq * page_size, d)
    return dense_decode_attention(q, k_ctx, v_ctx, lens, n_heads=n_heads, scale=scale)


class PagedKvPool:
    """A preallocated K+V page pool plus its host-side free list.

    Device state is two arrays ``[layers, n_pages, page_size, dim]``
    updated functionally by the decode step jits; the allocator is pure
    host bookkeeping (LIFO free list, so recently-evicted pages — hot
    in cache — are reused first). ``alloc`` returning ``None`` is the
    backpressure signal the scheduler turns into queueing.

    Pages are refcounted so the prefix cache can map one physical page
    into many sequences' page tables: ``alloc`` grants at refcount 1,
    ``share`` adds a holder, ``free`` drops one — the page returns to
    the free list only when the last holder releases it. A shared page
    is read-only by convention (every holder's writes land at positions
    past the shared prefix), which is what makes the sharing safe with
    the kernel's page-table indirection: two rows of ``page_tables``
    naming the same physical page read the same bytes, bitwise."""

    #: scatter/gather sentinel for unused page-table slots — one past
    #: the pool, so ``mode="drop"`` scatters skip and gathers clamp
    @property
    def sentinel(self) -> int:
        return self.n_pages

    def __init__(
        self,
        *,
        layers: int,
        dim: int,
        n_pages: int,
        page_size: int,
        dtype=jnp.float32,
    ):
        if n_pages <= 0 or page_size <= 0:
            raise ValueError("paged kv pool: n_pages and page_size must be positive")
        self.layers = layers
        self.dim = dim
        self.n_pages = n_pages
        self.page_size = page_size
        self.k = jnp.zeros((layers, n_pages, page_size, dim), dtype)
        self.v = jnp.zeros((layers, n_pages, page_size, dim), dtype)
        self._free = list(range(n_pages - 1, -1, -1))
        self._refs: dict[int, int] = {}

    @property
    def pages_in_use(self) -> int:
        """Physical pages allocated — what the ``decode.kv`` ledger
        books. Shared pages count once here no matter how many holders
        reference them; that is the book-once invariant."""
        return self.n_pages - len(self._free)

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pool_bytes(self) -> int:
        return int(self.k.nbytes) + int(self.v.nbytes)

    def refcount(self, page) -> int:
        return self._refs.get(int(page), 0)

    def alloc(self, n: int) -> list[int] | None:
        """Take ``n`` pages at refcount 1, or ``None`` (and take
        nothing) if the pool cannot cover the request — never a partial
        grant."""
        if n < 0:
            raise ValueError("paged kv pool: cannot allocate a negative page count")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def share(self, pages) -> None:
        """Add one holder to each (already-allocated) page."""
        for p in pages:
            p = int(p)
            if p not in self._refs:
                raise ValueError(f"paged kv pool: cannot share unallocated page {p}")
        for p in pages:
            self._refs[int(p)] += 1

    def free(self, pages) -> None:
        """Drop one holder from each page; physically free at zero."""
        for p in pages:
            p = int(p)
            if not 0 <= p < self.n_pages:
                raise ValueError(f"paged kv pool: page {p} is not in the pool")
            if p not in self._refs:
                raise ValueError(f"paged kv pool: double free of page {p}")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)
