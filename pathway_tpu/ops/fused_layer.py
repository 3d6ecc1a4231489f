"""Pallas TPU kernel: one FULL transformer encoder layer per dispatch.

The per-op XLA lowering of a MiniLM-geometry layer (hidden 384) streams
every intermediate — qkv, attention context, FFN activations — through
HBM between ops; at the embed hot path's shapes the layer is memory-
bound, not FLOP-bound (reference hot path: sentence-transformers torch
encode, /root/reference/python/pathway/xpacks/llm/embedders.py:270-329).
This kernel keeps a block of packed sequences resident in VMEM for the
whole layer:

    x -> qkv proj -> ragged block attention -> out proj
      -> +residual, LayerNorm -> FFN (gelu, chunked f32 accumulation)
      -> +residual, LayerNorm

MFU round (ROADMAP item 1) tiling:

* **Ragged lengths instead of a key-bias stream.**  Per-sequence real
  lengths ride SMEM as one scalar-prefetched [bp * p] int32 vector
  (a blocked (1, p) SMEM operand does not lower: Mosaic wants the last
  two block dims (8, 128)-aligned or whole) instead of the old
  [bp, 8, rows] f32 key-bias tensor; the key-padding bias is rebuilt
  on the VPU from a (1, seq) iota.  That deletes the largest non-token
  HBM stream the kernel had and is what lets the grid *skip* padded
  work instead of computing it.
* **Dead-block skip.**  A block whose sequences are all padding (the
  tail of a batch bucket) writes zeros and does no matmul — padded
  tiles are skipped, not computed.
* **Live row tiles only, for seq > 128.**  Where a packed sequence has
  a score tile of its own and more than one row tile of ROW_TILE rows
  (``tile_rule``), a sequence of real length ``len`` computes its first
  ceil(len / ROW_TILE) * ROW_TILE rows (never more than seq): those
  rows go through the whole layer attending to each other, under the
  KEY_OFF bias from ``len``; rows past the last live tile are written
  as zeros and cost nothing.  A 70-token document in a 256 bucket pays
  for 128 rows, not 256.  No live tile is the dead sequence: the
  dead-block skip is the zero-tiles case of the same rule, taken a
  sequence at a time.  The tile count comes from ``live_tiles``, which
  the dispatch counter (``computed_tokens``) shares.  A block whose
  sequences all have their last tile live (a group of a length-sorted
  batch at its own bucket) has nothing to leave out and goes through
  in one pass, as every live block does where the rule does not apply.
* **Diagonal-only attention for seq >= 128.**  The old kernel computed
  a full rows x rows score matrix per head and masked off-diagonal
  sequence pairs with BLOCK_OFF — at seq=160 / p=3 that is 3x the
  useful score FLOPs and 3x the softmax VPU work.  Now each packed
  sequence gets its own (seq, seq) score tile; off-diagonal tiles are
  never computed.  Below 128 the packed full-block matmul stays: p
  tiny (seq, seq) matmuls would starve the MXU's 128-deep pipeline,
  and attention is a small FLOP fraction there anyway.
* **Chunked FFN epilogue.**  The 4*d intermediate is processed in
  lane-aligned chunks with a f32 accumulator that already carries the
  residual + output bias, bounding peak VMEM so Mosaic keeps the x/out
  block streams double-buffered across the grid.

Weights ride constant-index BlockSpecs, so Mosaic fetches them into
VMEM once and re-uses them across the token-block grid; HBM traffic per
layer is x in + x out + weights once + p ints of lengths per block.
Numerics: matmuls accumulate f32 on the MXU, layernorm and softmax run
in f32 on the VPU, activations carry bf16 between stages — matching the
flax module (encoder.py EncoderLayer) to bf16 tolerance.  Backward
recomputes through the flax/XLA path via custom_vjp (attention-style:
recompute beats storing probs).

Masks on this path are prefix-contiguous (every caller derives them
from per-row lengths); the ragged kernel takes the lengths themselves.

``encoder_forward`` runs the whole TextEncoder (embeddings + N fused
layers + pooling) straight off the flax params tree, so checkpoints and
the module stay the single source of truth.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_attention import BLOCK_OFF, KEY_OFF

# Sequences at/above this length get a private (seq, seq) score tile per
# packed sub-block (no cross-sequence score FLOPs); shorter sequences
# keep the single rows x rows matmul whose MXU shapes are far better.
DIAG_ATTENTION_MIN_SEQ = 128

# FFN intermediate is processed in lane-aligned chunks of this many
# columns, accumulating in f32 — bounds peak VMEM at large row blocks.
FFN_CHUNK = 512

# Row tile of the ragged path: in a program of seq >=
# DIAG_ATTENTION_MIN_SEQ with more than one such tile to a sequence, a
# sequence of real length ``len`` computes its first
# ceil(len / ROW_TILE) * ROW_TILE rows (never more than seq) and writes
# the rest as zeros. Chosen from chip runs (PERF.md, section 6, PR 30):
# every tile count is a copy of the layer body in every program; 128 is
# the one measured in full, 64 gave ~7% more documents a second in the
# probes and is the next to measure.
ROW_TILE = 128


def _ln(x32, scale, bias, eps):
    """LayerNorm over the last axis; ``scale`` / ``bias`` are (1, n) rows."""
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    return (x32 - mu) * inv * scale + bias


def _gelu_tanh(x32):
    # tanh-approximate gelu, matching jax.nn.gelu(approximate=True)
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x32 * (1.0 + jnp.tanh(c * (x32 + 0.044715 * x32**3)))


def _head_attention(qkv, bias, d: int, hd: int, n_heads: int, scale: float):
    """Per-head scores -> stable softmax -> probs @ V over one token
    block; ``bias`` broadcasts over the score rows."""
    parts = []
    for i in range(n_heads):
        qh = qkv[:, i * hd : (i + 1) * hd]
        kh = qkv[:, d + i * hd : d + (i + 1) * hd]
        vh = qkv[:, 2 * d + i * hd : 2 * d + (i + 1) * hd]
        s = (
            jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
            + bias
        )
        m = jnp.max(s, axis=1, keepdims=True)
        e = jnp.exp(s - m)
        p = (e / jnp.sum(e, axis=1, keepdims=True)).astype(qkv.dtype)
        parts.append(jnp.dot(p, vh, preferred_element_type=jnp.float32))
    return jnp.concatenate(parts, axis=1)


def live_tiles(length):
    """Row tiles of ``ROW_TILE`` that a sequence of real ``length``
    computes: ``ceil(length / ROW_TILE)``, none for padding.  A python
    int, a numpy array or a scalar read from SMEM — the kernel's branch
    and the dispatch counter (``computed_tokens``) share it."""
    return (length + ROW_TILE - 1) // ROW_TILE


def tile_rule(seq: int) -> bool:
    """Whether a program of ``seq`` computes a live sequence by its live
    row tiles: each packed sequence has a score tile of its own and
    more than one row tile to choose from."""
    return seq >= DIAG_ATTENTION_MIN_SEQ and live_tiles(seq) > 1


def computed_tokens(lens, seq: int) -> int:
    """Token rows one layer call computes for a batch of real lengths
    ``lens`` (numpy, zeros for padding rows) at the program's ``seq``.
    Under the tile rule a sequence costs its live tiles,
    ``ceil(len / ROW_TILE) * ROW_TILE`` rows and never more than
    ``seq``; elsewhere a block of ``p`` packed sequences costs all its
    ``p * seq`` rows unless every one of them is padding."""
    lens = np.asarray(lens, np.int64)
    if tile_rule(seq):
        return int(np.minimum(live_tiles(lens) * ROW_TILE, seq).sum())
    p = _pack_rows(seq)
    blocks = np.pad(lens, (0, (-len(lens)) % p)).reshape(-1, p)
    return int(np.count_nonzero(blocks.max(axis=1))) * p * seq


def _layer_rows(
    x,
    biases,
    wqkv_ref,
    bqkv_ref,
    wout_ref,
    bout_ref,
    ln1s_ref,
    ln1b_ref,
    w1_ref,
    b1_ref,
    w2_ref,
    b2_ref,
    ln2s_ref,
    ln2b_ref,
    *,
    n_heads: int,
    scale: float,
    eps: float,
):
    """The layer over token rows ``x``: as many equal groups as
    ``biases``, each attending to itself under its bias and to no other
    group (everything but attention is row-wise)."""
    d = x.shape[1]
    n = x.shape[0] // len(biases)
    qkv = (
        jnp.dot(x, wqkv_ref[...], preferred_element_type=jnp.float32) + bqkv_ref[...]
    ).astype(x.dtype)
    ctx = jnp.concatenate(
        [
            _head_attention(qkv[j * n : (j + 1) * n], bias, d, d // n_heads, n_heads, scale)
            for j, bias in enumerate(biases)
        ],
        axis=0,
    ).astype(x.dtype)
    att = jnp.dot(ctx, wout_ref[...], preferred_element_type=jnp.float32) + bout_ref[...]
    h1 = _ln(x.astype(jnp.float32) + att, ln1s_ref[...], ln1b_ref[...], eps)
    h1b = h1.astype(x.dtype)
    interm = w1_ref.shape[1]
    chunk = FFN_CHUNK if interm % FFN_CHUNK == 0 else interm
    # residual + mlp_out bias seed the f32 accumulator; each chunk
    # adds gelu(x @ W1[:, c]) @ W2[c, :]
    acc = h1 + b2_ref[...]
    for c0 in range(0, interm, chunk):
        mid = (
            jnp.dot(h1b, w1_ref[:, c0 : c0 + chunk], preferred_element_type=jnp.float32)
            + b1_ref[:, c0 : c0 + chunk]
        )
        acc = acc + jnp.dot(
            _gelu_tanh(mid).astype(x.dtype),
            w2_ref[c0 : c0 + chunk, :],
            preferred_element_type=jnp.float32,
        )
    return _ln(acc, ln2s_ref[...], ln2b_ref[...], eps).astype(x.dtype)


def _layer_kernel(
    lens_ref,
    x_ref,
    *refs,
    n_heads: int,
    seq: int,
    scale: float,
    eps: float,
):
    *weights, out_ref = refs
    rows, d = out_ref.shape
    p = rows // seq
    layer = dict(n_heads=n_heads, scale=scale, eps=eps)
    # this block's p real lengths out of the prefetched [bp * p] vector
    # (scalar SMEM reads)
    base = pl.program_id(0) * p
    blk_lens = [lens_ref[base + j] for j in range(p)]

    def whole_block():
        kiota = jax.lax.broadcasted_iota(jnp.int32, (1, seq), 1)
        key_bias = [jnp.where(kiota < ln, 0.0, KEY_OFF) for ln in blk_lens]
        if seq >= DIAG_ATTENTION_MIN_SEQ:
            # ragged diagonal tiling: one (seq, seq) score tile per
            # packed sequence; cross-sequence tiles never computed
            biases = tuple(key_bias)
        else:
            # packed short sequences: one rows x rows matmul (good MXU
            # shapes); block-diagonal bias isolates the sequences and
            # the per-sequence key bias masks padding
            qi = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0) // seq
            ki = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1) // seq
            biases = (
                jnp.where(qi == ki, 0.0, BLOCK_OFF) + jnp.concatenate(key_bias, axis=1),
            )
        out_ref[...] = _layer_rows(x_ref[...], biases, *weights, **layer)

    if not tile_rule(seq):
        live = functools.reduce(jnp.maximum, blk_lens)

        @pl.when(live == 0)
        def _dead_block():
            # whole block is batch-bucket padding: skipped, not computed.
            # Pad rows are masked off at pooling/scatter downstream.
            out_ref[...] = jnp.zeros_like(out_ref)

        pl.when(live > 0)(whole_block)
        return

    # the tile rule: of a sequence only its live tiles — the first
    # ceil(len / ROW_TILE) * ROW_TILE rows go through the layer,
    # attending to each other; rows past the last live tile are written
    # as zeros (the next layer reads them as keys under KEY_OFF and
    # pooling multiplies them by the mask, so they must be finite).
    # No live tile = the dead sequence.
    def sequence(j, carry):
        row0 = j * seq if isinstance(j, int) else pl.multiple_of(j * seq, 32)
        length = jnp.minimum(lens_ref[base + j], seq)  # a length past the bucket has no variant
        tiles = live_tiles(length)

        @pl.when(tiles == 0)
        def _dead_sequence():
            out_ref[pl.ds(row0, seq), :] = jnp.zeros((seq, d), out_ref.dtype)

        for k in range(1, live_tiles(seq) + 1):
            r = min(k * ROW_TILE, seq)

            @pl.when(tiles == k)
            def _live_tiles(r=r):
                kiota = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
                kb = jnp.where(kiota < length, 0.0, KEY_OFF)
                out_ref[pl.ds(row0, r), :] = _layer_rows(
                    x_ref[pl.ds(row0, r), :], (kb,), *weights, **layer
                )
                if r < seq:
                    out_ref[pl.ds(row0 + r, seq - r), :] = jnp.zeros(
                        (seq - r, d), out_ref.dtype
                    )

        return carry

    if p == 1:
        sequence(0, None)
        return
    # the last tile of every sequence live (a group of a length-sorted
    # batch at its own bucket): nothing to leave out, so the block goes
    # through in one pass, its row-wise matmuls p * seq rows deep
    dense = live_tiles(functools.reduce(jnp.minimum, blk_lens)) == live_tiles(seq)
    pl.when(dense)(whole_block)

    @pl.when(jnp.logical_not(dense))
    def _by_sequence():
        # traced once for the block's p sequences, not p times
        jax.lax.fori_loop(0, p, sequence, None)


def _pack_rows(s: int) -> int:
    """Sequences packed per token block — same policy the attention
    kernel measured best on v5e (fused_attention._fused_call)."""
    if s <= 128:
        return max(1, 256 // s)
    if s < 256:
        return max(1, 512 // s)
    return 1


def _row2(v):
    """1D param vector -> (1, n) so it tiles onto VMEM lanes."""
    return v.reshape(1, -1)


def block_lens(lens, s: int):
    """Per-row real lengths [B] -> per-block [bp, p] int32 (rows padded
    with zero-length sequences so dead blocks are skippable)."""
    p = _pack_rows(s)
    lens = jnp.asarray(lens, jnp.int32)
    pad = (-lens.shape[0]) % p
    if pad:
        lens = jnp.pad(lens, (0, pad))
    return lens.reshape(-1, p)


# Jitted, inline: the layers of a program share one trace and one
# lowering of the kernel (the call's jaxpr is cached by shape, so the
# lowering of layer 1 is found again for the others) where each layer
# call used to trace and lower its own copy of the body — seconds of
# every start-up that no compile cache saves (PERF.md, section 7), more
# with every row-tile variant. Inlined, the call leaves nothing in the
# name stack: the device op keeps the name of the jit or scope above it.
@functools.partial(
    jax.jit, static_argnames=("n_heads", "seq", "eps", "interpret"), inline=True
)
def fused_layer_tokens(
    tokens,
    lens,
    layer_params: dict,
    *,
    n_heads: int,
    seq: int,
    eps: float,
    interpret: bool = False,
):
    """One encoder layer over pre-packed tokens [bp*rows, d] with the
    per-block sequence lengths [bp, p] (see ``pack_tokens``)."""
    d = tokens.shape[1]
    p = _pack_rows(seq)
    rows = p * seq
    bp = tokens.shape[0] // rows
    att, ln1 = layer_params["attention"], layer_params["ln_att"]
    w = lambda t: t.astype(tokens.dtype)
    const = lambda shape: pl.BlockSpec(shape, lambda i, lens: (0,) * len(shape))
    args = [
        w(att["qkv"]["kernel"]),
        _row2(att["qkv"]["bias"].astype(jnp.float32)),
        w(att["out"]["kernel"]),
        _row2(att["out"]["bias"].astype(jnp.float32)),
        _row2(ln1["scale"].astype(jnp.float32)),
        _row2(ln1["bias"].astype(jnp.float32)),
        w(layer_params["mlp_in"]["kernel"]),
        _row2(layer_params["mlp_in"]["bias"].astype(jnp.float32)),
        w(layer_params["mlp_out"]["kernel"]),
        _row2(layer_params["mlp_out"]["bias"].astype(jnp.float32)),
        _row2(layer_params["ln_mlp"]["scale"].astype(jnp.float32)),
        _row2(layer_params["ln_mlp"]["bias"].astype(jnp.float32)),
    ]
    return pl.pallas_call(
        functools.partial(
            _layer_kernel,
            n_heads=n_heads,
            seq=seq,
            scale=1.0 / math.sqrt(d // n_heads),
            eps=eps,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bp,),
            in_specs=[
                pl.BlockSpec((rows, d), lambda i, lens: (i, 0)),
                *[const(a.shape) for a in args],
            ],
            out_specs=pl.BlockSpec((rows, d), lambda i, lens: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(tokens.shape, tokens.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(lens.reshape(-1), tokens, *args)


def pack_tokens(x, key_mask, lens=None):
    """[B, S, d] -> packed [bp*rows, d] tokens + [bp, p] per-sequence
    lengths (+ the original B for unpacking).  ``key_mask`` must be
    prefix-contiguous; pass precomputed ``lens`` [B] to skip the
    mask reduction."""
    b, s, d = x.shape
    p = _pack_rows(s)
    pad = (-b) % p
    if lens is None:
        lens = key_mask.astype(jnp.int32).sum(axis=1)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
    tokens = x.reshape(-1, d)
    return tokens, block_lens(lens, s), b


def unpack_tokens(tokens, b: int, s: int):
    d = tokens.shape[1]
    return tokens.reshape(-1, s, d)[:b]


def _forward_impl(params, cfg, ids, mask, lens, interpret: bool):
    from flax.core import meta as _meta

    p = params["params"] if "params" in params else params
    p = _meta.unbox(p)
    dtype = cfg.dtype
    x = p["tok_embed"]["embedding"].astype(dtype)[ids]
    x = x + p["pos_embed"]["embedding"].astype(dtype)[None, : ids.shape[1]]
    if cfg.type_vocab_size:
        x = x + p["type_embed"]["embedding"].astype(dtype)[0][None, None, :]
    emb_ln = p["ln_embed"]
    x = _ln(
        x.astype(jnp.float32),
        _row2(emb_ln["scale"].astype(jnp.float32)),
        _row2(emb_ln["bias"].astype(jnp.float32)),
        cfg.layer_norm_eps,
    ).astype(dtype)
    b, s, d = x.shape
    tokens, lens_blk, b0 = pack_tokens(x, mask, lens)
    for i in range(cfg.num_layers):
        tokens = fused_layer_tokens(
            tokens,
            lens_blk,
            p[f"layer_{i}"],
            n_heads=cfg.num_heads,
            seq=s,
            eps=cfg.layer_norm_eps,
            interpret=interpret,
        )
    x = unpack_tokens(tokens, b0, s)
    if cfg.pooling == "cls":
        pooled = x[:, 0].astype(jnp.float32)
    else:
        m = mask[:, :, None].astype(x.dtype)
        pooled = ((x * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)).astype(
            jnp.float32
        )
    if cfg.normalize:
        pooled = pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12
        )
    return pooled


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 5))
def _encoder_forward(params, cfg, ids, mask, lens, interpret):
    return _forward_impl(params, cfg, ids, mask, lens, interpret)


def _efwd(params, cfg, ids, mask, lens, interpret):
    return _forward_impl(params, cfg, ids, mask, lens, interpret), (params, ids, mask)


def _ebwd(cfg, interpret, res, g):
    params, ids, mask = res
    from ..models.encoder import TextEncoder

    module = TextEncoder(cfg)
    _, vjp = jax.vjp(lambda pr: module.apply(pr, ids, mask), params)
    return (vjp(g)[0], None, None, None)


_encoder_forward.defvjp(_efwd, _ebwd)


def encoder_flops_per_token(cfg, seq: int) -> float:
    """Dense model forward FLOPs per token at padded length ``seq``
    (multiply-add = 2): the numerator of every achieved-TFLOPs number
    this repo reports (bench.py FINAL SUMMARY and the
    ``pathway_encoder_achieved_tflops`` gauge share it)."""
    d, interm, layers = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    per_layer = (
        2 * d * 3 * d  # qkv projection
        + 2 * 2 * seq * d  # scores + probs@V
        + 2 * d * d  # output projection
        + 2 * 2 * d * interm  # FFN in + out
    )
    return float(layers * per_layer)


def supports_fused_encoder(cfg, seq_len: int) -> bool:
    """Geometry gate: the fused-layer path covers the inference encoder
    exactly when the attention kernel's packing fits: whole heads, a
    sequence of at most 512, and a pooling the kernel path has."""
    return (
        cfg.hidden_size % cfg.num_heads == 0
        and seq_len <= 512
        and cfg.pooling in ("mean", "cls")
    )


def use_fused_encoder(cfg, seq_len: int) -> bool:
    """Policy gate — THE single dispatch decision for every encode path
    (SentenceEncoder jits, the fused text-query jit, benches): honors
    ``cfg.layer_impl`` ("xla" disables, "fused" forces, "interpret"
    forces the kernel in interpret mode — CPU parity tests) and
    otherwise picks the kernel on TPU when the geometry fits."""
    impl = getattr(cfg, "layer_impl", "auto")
    if impl == "xla":
        return False
    if impl in ("fused", "interpret"):
        return True
    return jax.default_backend() == "tpu" and supports_fused_encoder(cfg, seq_len)


def deep_route_info(cfg, seq_len: int) -> dict:
    """Static dispatch-routing metadata for the deep verifier
    (analysis.deep): which layer path the encode jits would take at
    this geometry and the kernel's internal bucket knobs, resolved
    without touching a device (``use_fused_encoder`` additionally gates
    on the live backend, which analyze-only runs must not query)."""
    return {
        "fused_supported": supports_fused_encoder(cfg, seq_len),
        "layer_impl": getattr(cfg, "layer_impl", "auto"),
        "diag_attention_min_seq": DIAG_ATTENTION_MIN_SEQ,
        "ffn_chunk": FFN_CHUNK,
    }


def fused_encoder_interpret(cfg) -> bool:
    """True when ``cfg.layer_impl`` asks for the kernel in interpret
    mode (exercises the exact pallas path on the CPU backend)."""
    return getattr(cfg, "layer_impl", "auto") == "interpret"


def encoder_forward(params, cfg, ids, mask, *, lens=None, interpret: bool = False):
    """TextEncoder forward (embeddings -> fused layers -> pooling)
    running each layer as ONE pallas dispatch.  ``lens`` [B] int32 (the
    per-row real lengths) skips the mask reduction and feeds the ragged
    kernel grid directly; ``mask`` must be prefix-contiguous either
    way.  Differentiable: the backward pass recomputes through the flax
    module."""
    if lens is None:
        lens = mask.astype(jnp.int32).sum(axis=1)
    return _encoder_forward(params, cfg, ids, mask, lens, interpret)
