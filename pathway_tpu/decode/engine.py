"""Continuous-batching generative decoder over the paged-KV pool.

The last hop of the RAG loop — answer generation — runs here instead
of an HTTP LLM xpack. One :class:`DecodeEngine` owns a
:class:`~pathway_tpu.ops.paged_attention.PagedKvPool` and a fixed set
of *lanes* (continuous-batching slots). Scheduling follows the
Gemma-on-TPU serving methodology (PAPERS.md): prefills admit into free
lanes as queries arrive, then every engine tick runs ONE fused decode
step for all live lanes — sequences join and leave the batch
mid-flight, no query waits for a "generation batch" to fill.

Batching is semantically invisible (an acceptance gate): the decode
step always runs at the full padded lane width with per-row math that
never crosses rows, and a lane's padding/garbage context is masked
with the exact-zero ``KEY_OFF`` trick (see ``ops/paged_attention``),
so a query's token stream is bitwise the same whether it decodes alone
or interleaved with seven strangers.

Crash discipline: a decode step is compute-then-commit. The fused jit
is functional (it returns the updated pool rather than mutating it);
the ``decode.step`` chaos site fires between compute and commit, so a
step killed there leaves the engine exactly at the pre-step state —
re-running it recomputes identical tokens (greedy argmax, f32) and
rewrites identical KV rows. No partial or duplicated token stream.

Deadlines: queries carry the serving plane's :class:`Deadline`;
mid-stream expiry preempts the lane — its KV pages return to the pool
(``decode.kv_evict``) and everyone else's stream is untouched. The
:class:`DecodeService` front door feeds the engine through the
existing ``AdaptiveBatcher`` so admission, ``query_share`` yielding
and shed/degrade apply to decode exactly as to retrieval queries
(degrade = skip rerank + clamp ``max_new_tokens``).
"""

from __future__ import annotations

import math
import threading
import time as _time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from ..internals.chip_ledger import CHIP_LEDGER

from ..ops.paged_attention import (
    PagedKvPool,
    dense_decode_attention,
    paged_attention_reference,
    paged_decode_attention,
    pages_for,
    require_kernel_page_size,
)
from .config import DecodeConfig, active_decode
from .metrics import DECODE_METRICS

__all__ = [
    "DecoderConfig",
    "init_decoder_params",
    "decode_greedy",
    "DecodeTicket",
    "DecodeEngine",
    "DecodeService",
]

#: prefill length buckets (compile-cache keys, like the encoder's)
_PREFILL_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024)


@dataclass(frozen=True)
class DecoderConfig:
    """Geometry of the small generative decoder (GPT-2-style blocks,
    learned positions, tied embedding/LM head, f32 everywhere — greedy
    decode must be bit-reproducible)."""

    vocab_size: int = 32000
    hidden_size: int = 256
    num_layers: int = 4
    num_heads: int = 4
    intermediate_size: int = 1024
    max_position: int = 512

    def __post_init__(self):
        if self.hidden_size % self.num_heads != 0:
            raise ValueError("decoder: num_heads must divide hidden_size")


def init_decoder_params(cfg: DecoderConfig, seed: int = 0) -> dict:
    """Deterministic random init (a checkpoint loader can replace this
    wholesale — the engine only reads the dict)."""
    import jax

    key = jax.random.PRNGKey(seed)
    d, f = cfg.hidden_size, cfg.intermediate_size

    def normal(key, shape, scale=0.02):
        return scale * jax.random.normal(key, shape, dtype="float32")

    keys = jax.random.split(key, 2 + 4 * cfg.num_layers)
    params: dict[str, Any] = {
        "tok": normal(keys[0], (cfg.vocab_size, d)),
        "pos": normal(keys[1], (cfg.max_position, d)),
        "lnf_s": np.ones(d, np.float32),
        "lnf_b": np.zeros(d, np.float32),
        "layers": [],
    }
    for l in range(cfg.num_layers):
        k0, k1, k2, k3 = keys[2 + 4 * l : 6 + 4 * l]
        params["layers"].append(
            {
                "ln1_s": np.ones(d, np.float32),
                "ln1_b": np.zeros(d, np.float32),
                "wqkv": normal(k0, (d, 3 * d)),
                "bqkv": np.zeros(3 * d, np.float32),
                "wo": normal(k1, (d, d)),
                "bo": np.zeros(d, np.float32),
                "ln2_s": np.ones(d, np.float32),
                "ln2_b": np.zeros(d, np.float32),
                "w1": normal(k2, (d, f)),
                "b1": np.zeros(f, np.float32),
                "w2": normal(k3, (f, d)),
                "b2": np.zeros(d, np.float32),
            }
        )
    return params


# -- pure model math (shared by the engine jits and the in-jit RAG
#    answer stage in ops/fused_rag.py) ---------------------------------------


def _ln(x, s, b, eps=1e-5):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * (1.0 / jnp.sqrt(var + eps)) * s + b


def _prefill_logits_math(params, cfg: DecoderConfig, ids, length):
    """Causal forward over one padded prompt. ``ids``: [S] int32,
    ``length``: scalar int32. Returns per-layer K/V rows
    (``[layers, S, d]``) and the next-token logits at position
    ``length - 1`` (``[vocab]``)."""
    import jax
    import jax.numpy as jnp

    from ..ops.fused_attention import KEY_OFF

    seq = ids.shape[0]
    d = cfg.hidden_size
    hd = d // cfg.num_heads
    scale = 1.0 / math.sqrt(hd)
    x = params["tok"][ids] + params["pos"][:seq]
    qi = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 1)
    bias = jnp.where((ki <= qi) & (ki < length), 0.0, KEY_OFF)
    ks, vs = [], []
    for lp in params["layers"]:
        h = _ln(x, lp["ln1_s"], lp["ln1_b"])
        qkv = h @ lp["wqkv"] + lp["bqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        ks.append(k)
        vs.append(v)
        outs = []
        for hh in range(cfg.num_heads):
            sl = slice(hh * hd, (hh + 1) * hd)
            s = (
                jax.lax.dot_general(
                    q[:, sl],
                    k[:, sl],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
                + bias
            )
            m = jnp.max(s, axis=1, keepdims=True)
            e = jnp.exp(s - m)
            p = e / jnp.sum(e, axis=1, keepdims=True)
            outs.append(
                jax.lax.dot_general(
                    p, v[:, sl], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        x = x + jnp.concatenate(outs, axis=1) @ lp["wo"] + lp["bo"]
        h2 = _ln(x, lp["ln2_s"], lp["ln2_b"])
        x = x + jax.nn.gelu(h2 @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]
    xf = _ln(x, params["lnf_s"], params["lnf_b"])
    last = jax.lax.dynamic_slice_in_dim(xf, length - 1, 1, 0)  # [1, d]
    logits = last @ params["tok"].T
    return jnp.stack(ks), jnp.stack(vs), logits[0]


def _prefill_math(params, cfg: DecoderConfig, ids, length):
    """:func:`_prefill_logits_math` plus the greedy argmax — the shape
    every greedy caller (engine prefill, ``decode_greedy``, the fused
    RAG answer stage) consumes."""
    import jax.numpy as jnp

    ks, vs, logits = _prefill_logits_math(params, cfg, ids, length)
    return ks, vs, jnp.argmax(logits).astype(jnp.int32)


def _step_logits_math(params, cfg: DecoderConfig, toks, positions, attend):
    """One decode step for a padded batch of tokens. ``toks``/
    ``positions``: [B] int32. ``attend(layer, q, k_new, v_new)`` must
    commit the new KV row into that layer's cache and return the
    attention output [B, d] — the engine plugs the paged pool in, the
    in-jit RAG path a dense cache. Per-row math only: nothing here may
    mix rows, that is the continuous-batching invisibility invariant.
    Returns the next-token logits [B, vocab] f32."""
    import jax
    import jax.numpy as jnp

    x = params["tok"][toks] + params["pos"][positions]
    for l, lp in enumerate(params["layers"]):
        h = _ln(x, lp["ln1_s"], lp["ln1_b"])
        qkv = h @ lp["wqkv"] + lp["bqkv"]
        q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
        x = x + attend(l, q, k_new, v_new) @ lp["wo"] + lp["bo"]
        h2 = _ln(x, lp["ln2_s"], lp["ln2_b"])
        x = x + jax.nn.gelu(h2 @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]
    xf = _ln(x, params["lnf_s"], params["lnf_b"])
    return xf @ params["tok"].T


def _step_math(params, cfg: DecoderConfig, toks, positions, attend):
    """Greedy step: argmax over :func:`_step_logits_math`. Returns the
    next tokens [B] int32."""
    import jax.numpy as jnp

    logits = _step_logits_math(params, cfg, toks, positions, attend)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _prompt_lookup(hist: list, n: int, k: int) -> list:
    """Prompt-lookup draft: propose the ``k`` tokens that followed the
    most recent *earlier* occurrence of the stream's trailing n-gram in
    the lane's own prompt + output. Tries match lengths ``n`` down to 1;
    with no match anywhere, proposes the last token repeated (the
    attractor-loop guess). Pure host work — a proposal chain costs zero
    device time, the target's batched verify is the only chip spend."""
    L = len(hist)
    for m in range(min(n, L - 1), 0, -1):
        pat = hist[L - m:]
        for j in range(L - m - 1, -1, -1):
            if hist[j:j + m] == pat:
                out = list(hist[j + m:j + m + k])
                if out:
                    while len(out) < k:
                        out.append(out[-1])
                    return out
    return [hist[-1]] * k if L else [0] * k


def _draft_view(params, draft_layers: int) -> dict:
    """The layer-skip self-draft: the first ``draft_layers`` target
    blocks plus the shared final LN and tied head. Because the draft's
    layer ``l`` *is* the target's layer ``l``, its KV rows are the
    target's — the draft attends the same paged pool, no second cache
    and no extra ``weights`` booking (the external-draft case declares
    its footprint via ``DecodeConfig.draft_weights`` instead)."""
    return {
        "tok": params["tok"],
        "pos": params["pos"],
        "lnf_s": params["lnf_s"],
        "lnf_b": params["lnf_b"],
        "layers": params["layers"][:draft_layers],
    }


def _chunk_prefill_math(
    params, cfg: DecoderConfig, pool_k, pool_v, page_ids, ids, start, count,
    *, page_size: int
):
    """Prefill one chunk of a prompt against pages already resident in
    the pool. ``ids``: [m] int32 chunk tokens (padded), ``start``: how
    many prompt tokens are already committed (a page-aligned prefix-
    cache hit plus earlier chunks), ``count``: valid tokens in this
    chunk. The chunk attends the gathered pool context at positions
    ``< start`` plus its own rows causally — exactly what a whole-prompt
    prefill would attend — then scatters its K/V rows into the pool.
    Returns the updated pool and the next-token logits at chunk row
    ``count - 1`` (only the final chunk's caller reads them)."""
    import jax
    import jax.numpy as jnp

    from ..ops.fused_attention import KEY_OFF

    m = ids.shape[0]
    d = cfg.hidden_size
    hd = d // cfg.num_heads
    scale = 1.0 / math.sqrt(hd)
    n_pages = pool_k.shape[1]
    pps = page_ids.shape[0]
    ctx = pps * page_size
    pos_idx = jnp.minimum(start + jnp.arange(m), cfg.max_position - 1)
    x = params["tok"][ids] + params["pos"][pos_idx]
    pt = jnp.minimum(page_ids.astype(jnp.int32), n_pages - 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, (m, ctx + m), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (m, ctx + m), 1)
    # causal over absolute positions; keys past start come only from
    # this chunk's own overlay rows (see below), so stale pool bytes at
    # not-yet-filled positions are never attendable
    bias = jnp.where(ki <= start + qi, 0.0, KEY_OFF)
    ks, vs = [], []
    pad = jnp.zeros((m, d), jnp.float32)
    for l, lp in enumerate(params["layers"]):
        h = _ln(x, lp["ln1_s"], lp["ln1_b"])
        qkv = h @ lp["wqkv"] + lp["bqkv"]
        q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
        ks.append(k_new)
        vs.append(v_new)
        # gather this lane's full context, then overlay the chunk's own
        # rows at their absolute offset (the tail padding guarantees the
        # overlay never wraps onto earlier rows)
        k_ctx = jnp.concatenate([pool_k[l][pt].reshape(ctx, d), pad])
        v_ctx = jnp.concatenate([pool_v[l][pt].reshape(ctx, d), pad])
        k_ctx = jax.lax.dynamic_update_slice(k_ctx, k_new, (start, 0))
        v_ctx = jax.lax.dynamic_update_slice(v_ctx, v_new, (start, 0))
        outs = []
        for hh in range(cfg.num_heads):
            sl = slice(hh * hd, (hh + 1) * hd)
            s = (
                jax.lax.dot_general(
                    q[:, sl],
                    k_ctx[:, sl],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
                + bias
            )
            mx = jnp.max(s, axis=1, keepdims=True)
            e = jnp.exp(s - mx)
            p = e / jnp.sum(e, axis=1, keepdims=True)
            outs.append(
                jax.lax.dot_general(
                    p, v_ctx[:, sl], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        x = x + jnp.concatenate(outs, axis=1) @ lp["wo"] + lp["bo"]
        h2 = _ln(x, lp["ln2_s"], lp["ln2_b"])
        x = x + jax.nn.gelu(h2 @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]
    xf = _ln(x, params["lnf_s"], params["lnf_b"])
    last = jax.lax.dynamic_slice_in_dim(xf, count - 1, 1, 0)  # [1, d]
    logits = (last @ params["tok"].T)[0]
    # commit the chunk's KV rows (padding rows scatter to the sentinel
    # and drop, the whole-prefill scatter's trick)
    pos = start + jnp.arange(m)
    pages = jnp.where(
        jnp.arange(m) < count,
        page_ids[jnp.minimum(pos // page_size, pps - 1)].astype(jnp.int32),
        n_pages,
    )
    offs = pos % page_size
    pool_k = pool_k.at[:, pages, offs].set(
        jnp.stack(ks), mode="drop", unique_indices=True
    )
    pool_v = pool_v.at[:, pages, offs].set(
        jnp.stack(vs), mode="drop", unique_indices=True
    )
    return pool_k, pool_v, logits


def _verify_math(
    params, cfg: DecoderConfig, pool_k, pool_v, page_tables, lens, inputs,
    *, page_size: int
):
    """Speculative verify: ONE batched causal forward over every lane's
    k-token proposal window — the whole point of speculation is that
    the target checks k tokens for the price of one dispatch, not k
    sequential steps. ``inputs``: [lanes, k] int32 (current token, then
    the first k-1 draft proposals); row ``j`` of the result is the
    token the target would have emitted at position ``lens + j``.
    Per-lane math only (batch rows never mix — the invisibility
    invariant): each lane's window attends its own gathered pool
    context plus its own overlay rows causally, exactly what k
    sequential greedy steps would attend. Returns targets [k, lanes]
    and the pool with every window row committed (positions past the
    lane's page span scatter to the sentinel and drop)."""
    import jax
    import jax.numpy as jnp

    from ..ops.fused_attention import KEY_OFF

    lanes, kk = inputs.shape
    d = cfg.hidden_size
    hd = d // cfg.num_heads
    scale = 1.0 / math.sqrt(hd)
    n_pages = pool_k.shape[1]
    pps = page_tables.shape[1]
    ctx = pps * page_size
    pos = lens[:, None] + jnp.arange(kk)[None, :]  # [lanes, k]
    x = params["tok"][inputs] + params["pos"][
        jnp.minimum(pos, cfg.max_position - 1)
    ]
    pt = jnp.minimum(page_tables.astype(jnp.int32), n_pages - 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, (kk, ctx + kk), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (kk, ctx + kk), 1)
    # causal over absolute positions, per lane; keys past a lane's len
    # come only from its own overlay rows (stale pool bytes at
    # not-yet-filled positions are never attendable)
    bias = jnp.where(
        ki[None] <= lens[:, None, None] + qi[None], 0.0, KEY_OFF
    )  # [lanes, k, ctx+k]
    overlay = jax.vmap(
        lambda c, rows, s: jax.lax.dynamic_update_slice(c, rows, (s, 0))
    )
    ks, vs = [], []
    pad = jnp.zeros((lanes, kk, d), jnp.float32)
    for l, lp in enumerate(params["layers"]):
        h = _ln(x, lp["ln1_s"], lp["ln1_b"])
        qkv = h @ lp["wqkv"] + lp["bqkv"]
        q, k_new, v_new = jnp.split(qkv, 3, axis=-1)  # [lanes, k, d]
        ks.append(k_new)
        vs.append(v_new)
        k_ctx = jnp.concatenate(
            [pool_k[l][pt].reshape(lanes, ctx, d), pad], axis=1
        )
        v_ctx = jnp.concatenate(
            [pool_v[l][pt].reshape(lanes, ctx, d), pad], axis=1
        )
        k_ctx = overlay(k_ctx, k_new, lens)
        v_ctx = overlay(v_ctx, v_new, lens)
        outs = []
        for hh in range(cfg.num_heads):
            sl = slice(hh * hd, (hh + 1) * hd)
            s = (
                jax.lax.dot_general(
                    q[..., sl],
                    k_ctx[..., sl],
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )
                * scale
                + bias
            )
            mx = jnp.max(s, axis=2, keepdims=True)
            e = jnp.exp(s - mx)
            p = e / jnp.sum(e, axis=2, keepdims=True)
            outs.append(
                jax.lax.dot_general(
                    p, v_ctx[..., sl], (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )
            )
        x = x + jnp.concatenate(outs, axis=2) @ lp["wo"] + lp["bo"]
        h2 = _ln(x, lp["ln2_s"], lp["ln2_b"])
        x = x + jax.nn.gelu(h2 @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]
    xf = _ln(x, params["lnf_s"], params["lnf_b"])
    logits = xf @ params["tok"].T  # [lanes, k, vocab]
    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32).T  # [k, lanes]
    pidx = pos // page_size
    pages = jnp.where(
        pidx < pps,
        jnp.take_along_axis(
            page_tables.astype(jnp.int32), jnp.minimum(pidx, pps - 1), axis=1
        ),
        n_pages,
    )
    offs = pos % page_size
    pool_k = pool_k.at[:, pages, offs].set(
        jnp.stack(ks), mode="drop", unique_indices=True
    )
    pool_v = pool_v.at[:, pages, offs].set(
        jnp.stack(vs), mode="drop", unique_indices=True
    )
    return targets, pool_k, pool_v


def decode_greedy(params, cfg: DecoderConfig, ids, length, max_new: int):
    """Greedy generation fully inside one trace (prefill + scan over
    dense KV) — the generate stage ``ops/fused_rag.py`` splices into
    its fused jit so embed→retrieve→rerank→generate is one device
    dispatch. ``ids``: [S] int32 padded prompt, ``length``: scalar,
    ``max_new``: static. Returns [max_new] int32 tokens."""
    import jax
    import jax.numpy as jnp

    seq = ids.shape[0]
    d = cfg.hidden_size
    layers = cfg.num_layers
    ctx = seq + max_new
    k_rows, v_rows, tok0 = _prefill_math(params, cfg, ids, length)
    cache_k = jnp.zeros((layers, ctx, d), jnp.float32).at[:, :seq].set(k_rows)
    cache_v = jnp.zeros((layers, ctx, d), jnp.float32).at[:, :seq].set(v_rows)

    def body(carry, _):
        ck, cv, tok, cur = carry

        def attend(l, q, k_new, v_new):
            nonlocal ck, cv
            ck = jax.lax.dynamic_update_slice(ck, k_new[None], (l, cur, 0))
            cv = jax.lax.dynamic_update_slice(cv, v_new[None], (l, cur, 0))
            return dense_decode_attention(
                q, ck[l][None], cv[l][None], (cur + 1)[None], n_heads=cfg.num_heads
            )

        nxt = _step_math(params, cfg, tok[None], cur[None], attend)[0]
        return (ck, cv, nxt, cur + 1), tok

    (_, _, last, _), toks = jax.lax.scan(
        body, (cache_k, cache_v, tok0, length), None, length=max_new - 1
    )
    return jnp.concatenate([toks, last[None]]) if max_new > 1 else tok0[None]


# -- seeded sampling (host side) ---------------------------------------------


def _sample_key(seed: int, prompt) -> int:
    """Counter-based sampling key: a hash of the engine seed and the
    prompt tokens. Content-addressed on purpose — the draw for stream
    position ``n`` depends only on (key, n), so recovery replay redraws
    identically and co-batched strangers cannot perturb a stream (the
    invisibility invariant extends to sampled decode)."""
    import hashlib

    h = hashlib.blake2b(str(int(seed)).encode(), digest_size=8)
    h.update(b"".join(int(t).to_bytes(8, "little", signed=True) for t in prompt))
    return int.from_bytes(h.digest(), "little")


def _sample_token(logits, cfg, key: int, position: int) -> int:
    """Draw one token from ``logits`` ([vocab] f32) with temperature /
    top-k / top-p, deterministically keyed on (ticket key, stream
    position). Ties break by stable descending sort, so the draw is
    reproducible across platforms."""
    z = np.asarray(logits, np.float64)
    order = np.argsort(-z, kind="stable")
    if cfg.top_k:
        order = order[: cfg.top_k]
    zs = z[order] / float(cfg.temperature)
    zs -= zs.max()
    p = np.exp(zs)
    p /= p.sum()
    if cfg.top_p < 1.0:
        # nucleus: keep the smallest prefix reaching top_p mass (always
        # at least the head token)
        keep = np.cumsum(p) - p < cfg.top_p
        keep[0] = True
        order, p = order[keep], p[keep]
        p /= p.sum()
    rng = np.random.default_rng(
        np.random.SeedSequence([key, int(position), int(cfg.seed)])
    )
    draw = rng.random()
    idx = int(np.searchsorted(np.cumsum(p), draw, side="right"))
    return int(order[min(idx, len(p) - 1)])


# -- engine ------------------------------------------------------------------


class DecodeTicket:
    """One query's handle through the decode plane."""

    __slots__ = (
        "prompt",
        "max_new",
        "deadline",
        "degraded",
        "skip_rerank",
        "tokens",
        "preempted",
        "done",
        "trace",
        "sample_key",
    )

    def __init__(self, prompt, max_new, deadline, degraded, trace=None):
        self.prompt = list(prompt)
        self.max_new = max_new
        self.deadline = deadline
        self.degraded = degraded
        self.skip_rerank = degraded  # degrade semantics: rerank is skipped
        self.tokens: list[int] = []
        self.preempted = False
        self.done = threading.Event()
        # request-journey trace of the submitting request (per-tick
        # decode_step spans link the live lanes' traces)
        self.trace = trace
        # counter-based sampling key (None = greedy)
        self.sample_key: int | None = None

    def result(self, timeout: float | None = None) -> list[int]:
        """Block for the final token stream (may be short if the query
        was preempted — check ``preempted``)."""
        self.done.wait(timeout)
        return list(self.tokens)


class _Lane:
    __slots__ = ("ticket", "pages", "t_admit", "shared", "filled", "prefill_wall")

    def __init__(self, ticket, pages, *, shared: int = 0, filled: int | None = None):
        self.ticket = ticket
        self.pages = pages
        self.t_admit = _time.monotonic()
        # prefix-cache / chunked-prefill state: the first ``shared``
        # pages are cache-mapped (read-only holders), ``filled`` counts
        # prompt tokens whose KV is committed — filled < len(prompt)
        # means the lane is still prefilling and sits out decode steps
        self.shared = shared
        self.filled = len(ticket.prompt) if filled is None else filled
        self.prefill_wall = 0.0

    @property
    def prefilling(self) -> bool:
        return self.filled < len(self.ticket.prompt)


#: process-wide jit cache shared by every engine (keyed by the static
#: geometry in ``_jit_base`` plus each factory's own key). The jitted
#: closures capture geometry only — params and pool arrays are call
#: arguments — so a respawned or duplicate engine reuses the compiled
#: artifacts instead of paying XLA compilation per instance.
_JIT_CACHE: dict = {}


class DecodeEngine:
    """Paged-KV continuous-batching decoder (see module docstring)."""

    def __init__(
        self,
        model_cfg: DecoderConfig | None = None,
        config: DecodeConfig | None = None,
        *,
        params=None,
        seed: int = 0,
    ):
        import jax

        self.model_cfg = model_cfg or DecoderConfig()
        self.config = config or active_decode() or DecodeConfig()
        self.config.check_budget(self.model_cfg.num_layers, self.model_cfg.hidden_size)
        impl = self.config.impl
        if impl == "auto":
            impl = "paged" if jax.default_backend() == "tpu" else "xla"
        if impl == "paged":
            require_kernel_page_size(self.config.page_size)
        self.impl = impl
        self.params = (
            params
            if params is not None
            else init_decoder_params(self.model_cfg, seed)
        )
        self.pool = PagedKvPool(
            layers=self.model_cfg.num_layers,
            dim=self.model_cfg.hidden_size,
            n_pages=self.config.pages,
            page_size=self.config.page_size,
        )
        self._pages_per_seq = self.config.pages_per_seq()
        # serving extensions (all off by default — off means the legacy
        # single-token whole-prefill scheduler runs byte-identically)
        self.cache = None
        if self.config.prefix_cache:
            from .prefix_cache import PrefixCache

            self.cache = PrefixCache(
                self.pool,
                page_size=self.config.page_size,
                model_version=f"{self.model_cfg}/seed={seed}",
            )
        self._incremental = bool(
            self.config.prefix_cache or self.config.prefill_chunk
        )
        self._draft_layers = 0
        if self.config.spec_tokens:
            self._draft_layers = self.config.draft_layers or max(
                1, self.model_cfg.num_layers // 2
            )
            if self._draft_layers >= self.model_cfg.num_layers:
                raise ValueError(
                    "decode: draft_layers must be smaller than the target's "
                    f"num_layers ({self.model_cfg.num_layers}) — a draft as "
                    "deep as the target verifies nothing"
                )
        lanes = self.config.lanes
        self._lanes: list[Optional[_Lane]] = [None] * lanes
        self._page_tables = np.full(
            (lanes, self._pages_per_seq), self.pool.sentinel, np.int32
        )
        self._lens = np.zeros(lanes, np.int32)
        self._pending: deque[DecodeTicket] = deque()
        # process-wide compile cache: every jit here closes over static
        # geometry only (params and pool arrays are arguments), so two
        # engines with the same (model, pool, impl) geometry share one
        # compiled artifact instead of recompiling per instance
        self._jit_base = (
            self.model_cfg,
            self.impl,
            self.config.page_size,
            self.config.lanes,
            self._pages_per_seq,
            self.pool.sentinel,
        )
        self._jits = _JIT_CACHE
        self.steps = 0
        DECODE_METRICS.set_pool(self.pool.pages_in_use, self.pool.n_pages)
        self._ledger_update()
        from ..internals.ledger import LEDGER, pytree_nbytes

        LEDGER.update("weights", "decoder", pytree_nbytes(self.params))

    def _ledger_update(self) -> None:
        """Report the KV page pool to the HBM ledger — exact bytes from
        the live pool arrays; ``used`` is the allocated-page fraction,
        so the ledger's fragmentation gauge reads idle pool capacity."""
        from ..internals.ledger import LEDGER

        nbytes = int(self.pool.pool_bytes)
        used = (
            int(nbytes * self.pool.pages_in_use / self.pool.n_pages)
            if self.pool.n_pages
            else 0
        )
        LEDGER.update("decode.kv", "pool", nbytes, used_bytes=used)

    # -- ticket lifecycle --

    def max_prompt_len(self) -> int:
        return min(self.config.max_seq, self.model_cfg.max_position)

    def make_ticket(
        self,
        prompt_ids,
        *,
        max_new_tokens: int | None = None,
        deadline=None,
        degraded: bool = False,
    ) -> DecodeTicket:
        max_new = max_new_tokens or self.config.max_new_tokens
        if degraded:
            max_new = min(max_new, self.config.degrade_max_new_tokens)
        prompt = [int(t) % self.model_cfg.vocab_size for t in prompt_ids]
        if not prompt:
            raise ValueError("decode: empty prompt")
        if len(prompt) + max_new > self.max_prompt_len():
            raise ValueError(
                f"decode: prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds the context limit {self.max_prompt_len()}"
            )
        DECODE_METRICS.record_query(degraded=degraded)
        from ..tracing import current_trace, tracing_enabled

        trace = current_trace() if tracing_enabled() else None
        ticket = DecodeTicket(prompt, max_new, deadline, degraded, trace=trace)
        if self.config.temperature > 0:
            # content-addressed, not order-addressed: the stream a
            # prompt samples is independent of its co-runners and
            # replays identically after recovery
            ticket.sample_key = _sample_key(self.config.seed, prompt)
        return ticket

    def enqueue(self, ticket: DecodeTicket) -> None:
        self._pending.append(ticket)

    def submit(self, prompt_ids, **kw) -> DecodeTicket:
        ticket = self.make_ticket(prompt_ids, **kw)
        self.enqueue(ticket)
        return ticket

    # -- jit factories --

    def _prefill_fn(self, seq: int):
        import functools

        import jax

        key = (*self._jit_base, "prefill", seq)
        if key not in self._jits:
            fn = functools.partial(_prefill_math, cfg=self.model_cfg)
            self._jits[key] = jax.jit(lambda p, ids, n: fn(p, ids=ids, length=n))
        return self._jits[key]

    def _prefill_logits_fn(self, seq: int):
        """Whole-prompt prefill that returns the first-token logits
        instead of their argmax — the sampled-decode variant."""
        import functools

        import jax

        key = (*self._jit_base, "prefill_logits", seq)
        if key not in self._jits:
            fn = functools.partial(_prefill_logits_math, cfg=self.model_cfg)
            self._jits[key] = jax.jit(lambda p, ids, n: fn(p, ids=ids, length=n))
        return self._jits[key]

    def _scatter_fn(self, seq: int):
        import jax
        import jax.numpy as jnp

        key = (*self._jit_base, "scatter", seq)
        if key not in self._jits:
            page_size = self.config.page_size
            sentinel = self.pool.sentinel

            def scatter(pool_k, pool_v, k_rows, v_rows, page_ids, length):
                pos = jnp.arange(seq)
                pages = jnp.where(
                    pos < length, page_ids[pos // page_size], sentinel
                )
                offs = pos % page_size
                pool_k = pool_k.at[:, pages, offs].set(
                    k_rows, mode="drop", unique_indices=True
                )
                pool_v = pool_v.at[:, pages, offs].set(
                    v_rows, mode="drop", unique_indices=True
                )
                return pool_k, pool_v

            # no donation: the commit-after-chaos contract needs the
            # pre-step buffers to stay valid until the host commits
            self._jits[key] = jax.jit(scatter)
        return self._jits[key]

    def _step_fn(self):
        import jax
        import jax.numpy as jnp

        key = (*self._jit_base, "step")
        if key not in self._jits:
            cfg = self.model_cfg
            page_size = self.config.page_size
            lanes = self.config.lanes
            impl = self.impl

            def step(params, pool_k, pool_v, page_tables, lens, toks):
                pages = page_tables[jnp.arange(lanes), lens // page_size]
                offs = lens % page_size

                def attend(l, q, k_new, v_new):
                    nonlocal pool_k, pool_v
                    pool_k = pool_k.at[l, pages, offs].set(
                        k_new, mode="drop", unique_indices=True
                    )
                    pool_v = pool_v.at[l, pages, offs].set(
                        v_new, mode="drop", unique_indices=True
                    )
                    if impl == "xla":
                        return paged_attention_reference(
                            q, pool_k[l], pool_v[l], page_tables, lens + 1,
                            n_heads=cfg.num_heads,
                        )
                    return paged_decode_attention(
                        q, pool_k[l], pool_v[l], page_tables, lens + 1,
                        n_heads=cfg.num_heads,
                        interpret=(impl == "interpret"),
                    )

                nxt = _step_math(params, cfg, toks, lens, attend)
                return nxt, pool_k, pool_v

            # no donation (see _scatter_fn): a step killed at the
            # decode.step chaos site must leave the old pool intact
            self._jits[key] = jax.jit(step)
        return self._jits[key]

    def _paged_attend(self):
        """The configured decode-attention path as a plain callable —
        shared by the sampled/draft/verify jits so every path attends
        with literally the same ops as the greedy step."""
        cfg = self.model_cfg
        impl = self.impl

        def att(q, pk, pv, page_tables, lens):
            if impl == "xla":
                return paged_attention_reference(
                    q, pk, pv, page_tables, lens, n_heads=cfg.num_heads
                )
            return paged_decode_attention(
                q, pk, pv, page_tables, lens,
                n_heads=cfg.num_heads,
                interpret=(impl == "interpret"),
            )

        return att

    def _step_logits_fn(self):
        """The sampled-decode step: identical to :meth:`_step_fn` up to
        the head, but returns the logits so the host can draw."""
        import jax
        import jax.numpy as jnp

        key = (*self._jit_base, "step_logits")
        if key not in self._jits:
            cfg = self.model_cfg
            page_size = self.config.page_size
            lanes = self.config.lanes
            att = self._paged_attend()

            def step(params, pool_k, pool_v, page_tables, lens, toks):
                pages = page_tables[jnp.arange(lanes), lens // page_size]
                offs = lens % page_size

                def attend(l, q, k_new, v_new):
                    nonlocal pool_k, pool_v
                    pool_k = pool_k.at[l, pages, offs].set(
                        k_new, mode="drop", unique_indices=True
                    )
                    pool_v = pool_v.at[l, pages, offs].set(
                        v_new, mode="drop", unique_indices=True
                    )
                    return att(q, pool_k[l], pool_v[l], page_tables, lens + 1)

                logits = _step_logits_math(params, cfg, toks, lens, attend)
                return logits, pool_k, pool_v

            self._jits[key] = jax.jit(step)
        return self._jits[key]

    def _chunk_fn(self, m: int):
        """Chunked-prefill jit at chunk bucket ``m`` (compile-cache key,
        like the prefill seq buckets)."""
        import functools

        import jax

        key = (*self._jit_base, "chunk", m)
        if key not in self._jits:
            fn = functools.partial(
                _chunk_prefill_math,
                cfg=self.model_cfg,
                page_size=self.config.page_size,
            )
            self._jits[key] = jax.jit(
                lambda p, pk, pv, pids, ids, start, count: fn(
                    p, pool_k=pk, pool_v=pv, page_ids=pids, ids=ids,
                    start=start, count=count,
                )
            )
        return self._jits[key]

    def _draft_fn(self):
        """Speculative draft: ``spec_tokens`` layer-skip steps in one
        scan, proposing a token chain per lane. Each lane's shallow-
        layer context is gathered out of the pool ONCE into a dense
        per-lane window buffer; the scan then carries only that small
        buffer (lanes × (ctx + k) rows), not a pool-sized copy — the
        draft's KV rows live in the window and are discarded, the
        verify pass writes the pool's rows for every layer."""
        import jax
        import jax.numpy as jnp

        key = (*self._jit_base, "draft", self.config.spec_tokens, self._draft_layers)
        if key not in self._jits:
            cfg = self.model_cfg
            page_size = self.config.page_size
            lanes = self.config.lanes
            pps = self._pages_per_seq
            k_spec = self.config.spec_tokens
            n_draft = self._draft_layers
            d = cfg.hidden_size
            ctx = pps * page_size

            def draft(params, pool_k, pool_v, page_tables, lens, toks):
                from ..ops.fused_attention import KEY_OFF

                dparams = _draft_view(params, n_draft)
                n_pages = pool_k.shape[1]
                pt = jnp.minimum(page_tables.astype(jnp.int32), n_pages - 1)
                pad = jnp.zeros((lanes, k_spec, d), jnp.float32)
                # read-only gather of each lane's committed rows; window
                # slots ctx..ctx+k-1 are unused (draft rows overlay at
                # their absolute offsets, clamped in-bounds: cur <= ctx)
                dk = jnp.stack(
                    [
                        jnp.concatenate(
                            [pool_k[l][pt].reshape(lanes, ctx, d), pad], axis=1
                        )
                        for l in range(n_draft)
                    ]
                )
                dv = jnp.stack(
                    [
                        jnp.concatenate(
                            [pool_v[l][pt].reshape(lanes, ctx, d), pad], axis=1
                        )
                        for l in range(n_draft)
                    ]
                )
                overlay = jax.vmap(
                    lambda c, row, s: jax.lax.dynamic_update_slice(
                        c, row[None], (s, 0)
                    )
                )
                ki = jax.lax.broadcasted_iota(
                    jnp.int32, (lanes, ctx + k_spec), 1
                )

                # unrolled (k_spec is static): XLA fuses across the k
                # proposal steps instead of paying scan carry copies
                tok, cur = toks, lens
                drafts = []
                for _ in range(k_spec):
                    # keys at absolute positions <= cur: committed pool
                    # rows below each lane's len plus the draft's own
                    # overlay rows — stale pool bytes are never attended
                    bias = jnp.where(ki <= cur[:, None], 0.0, KEY_OFF)

                    def attend(l, q, k_new, v_new, cur=cur, bias=bias):
                        nonlocal dk, dv
                        dk = dk.at[l].set(overlay(dk[l], k_new, cur))
                        dv = dv.at[l].set(overlay(dv[l], v_new, cur))
                        H = cfg.num_heads
                        hd = d // H
                        scale = 1.0 / math.sqrt(hd)
                        # all heads in one batched dot: [lanes, H, hd] x
                        # [lanes, ctx+k, H, hd] -> [lanes, H, ctx+k]
                        qh = q.reshape(lanes, H, hd)
                        kh = dk[l].reshape(lanes, ctx + k_spec, H, hd)
                        vh = dv[l].reshape(lanes, ctx + k_spec, H, hd)
                        s = (
                            jax.lax.dot_general(
                                qh,
                                kh,
                                (((2,), (3,)), ((0, 1), (0, 2))),
                                preferred_element_type=jnp.float32,
                            )
                            * scale
                            + bias[:, None, :]
                        )
                        mx = jnp.max(s, axis=2, keepdims=True)
                        e = jnp.exp(s - mx)
                        p = e / jnp.sum(e, axis=2, keepdims=True)
                        out = jax.lax.dot_general(
                            p,
                            vh,
                            (((2,), (1,)), ((0, 1), (0, 2))),
                            preferred_element_type=jnp.float32,
                        )
                        return out.reshape(lanes, d)

                    tok = _step_math(dparams, cfg, tok, cur, attend)
                    drafts.append(tok)
                    cur = cur + 1
                return jnp.stack(drafts)  # [spec_tokens, lanes]

            self._jits[key] = jax.jit(draft)
        return self._jits[key]

    def _verify_fn(self):
        """Speculative verify: ONE batched causal forward of the full
        target over every lane's proposal window (:func:`_verify_math`)
        — k tokens checked per dispatch, the speculative-decode payoff.
        The window attends the same gathered-pool keys causally as k
        sequential greedy steps would, so the verified tokens are
        bitwise the tokens sequential greedy would have produced (the
        spec-on == spec-off stream gate). ``inputs``/``targets`` keep
        the scan-shaped [k, lanes] layout the scheduler consumes."""
        import functools

        import jax
        import jax.numpy as jnp

        key = (*self._jit_base, "verify", self.config.spec_tokens)
        if key not in self._jits:
            fn = functools.partial(
                _verify_math,
                cfg=self.model_cfg,
                page_size=self.config.page_size,
            )

            # no donation (commit-after-chaos, as everywhere)
            def verify(p, pk, pv, pt, lens, tk, drafts):
                # inputs: the pending token, then the first k-1
                # proposals — built in-jit so the tick dispatches the
                # draft output straight into verify without a round trip
                inputs = jnp.concatenate([tk[None], drafts[:-1]], axis=0)
                return fn(
                    p, pool_k=pk, pool_v=pv, page_tables=pt, lens=lens,
                    inputs=inputs.T,
                )

            self._jits[key] = jax.jit(verify)
        return self._jits[key]

    # -- scheduler --

    def _free_lane_pages(self, lane_idx: int, reason: str) -> None:
        from ..internals import flight_recorder

        lane = self._lanes[lane_idx]
        assert lane is not None
        self.pool.free(lane.pages)
        flight_recorder.record(
            "decode.kv_evict",
            lane=lane_idx,
            pages=len(lane.pages),
            reason=reason,
        )
        self._lanes[lane_idx] = None
        self._page_tables[lane_idx, :] = self.pool.sentinel
        self._lens[lane_idx] = 0
        DECODE_METRICS.set_pool(self.pool.pages_in_use, self.pool.n_pages)
        self._ledger_update()

    def _preempt_expired(self) -> None:
        from ..internals import flight_recorder

        now = _time.monotonic()
        for i, lane in enumerate(self._lanes):
            if lane is None:
                continue
            dl = lane.ticket.deadline
            if dl is not None and dl.expires_at <= now:
                flight_recorder.record(
                    "decode.preempt",
                    lane=i,
                    emitted=len(lane.ticket.tokens),
                    prompt_tokens=len(lane.ticket.prompt),
                )
                DECODE_METRICS.record_preempt()
                ticket = lane.ticket
                self._free_lane_pages(i, "preempt")
                ticket.preempted = True
                ticket.done.set()

    def _finish(self, lane_idx: int) -> None:
        ticket = self._lanes[lane_idx].ticket
        self._free_lane_pages(lane_idx, "finish")
        ticket.done.set()

    def _prefill_whole(self, i: int, ticket: DecodeTicket, pages) -> None:
        """Whole-prompt prefill into lane ``i`` — the original one-shot
        path (also the cold path when the prefix cache misses and no
        chunking is configured). Installs the lane and emits the first
        token; the caller runs the max_new finish check."""
        from ..models.batching import bucket
        from ..internals import flight_recorder

        import jax.numpy as jnp

        plen = len(ticket.prompt)
        sampled = self.config.temperature > 0
        w0 = _time.monotonic()
        chip = CHIP_LEDGER.on()
        with CHIP_LEDGER.timed("decode") if chip else nullcontext():
            seq = bucket(plen, _PREFILL_BUCKETS)
            seq = min(seq, self.max_prompt_len())
            ids = np.zeros(seq, np.int32)
            ids[:plen] = ticket.prompt
            prefill = (
                self._prefill_logits_fn(seq) if sampled else self._prefill_fn(seq)
            )
            k_rows, v_rows, out0 = prefill(
                self.params, jnp.asarray(ids), jnp.int32(plen)
            )
            page_ids = np.full(self._pages_per_seq, self.pool.sentinel, np.int32)
            page_ids[: len(pages)] = pages
            self.pool.k, self.pool.v = self._scatter_fn(seq)(
                self.pool.k,
                self.pool.v,
                k_rows,
                v_rows,
                jnp.asarray(page_ids[: max(1, (seq + self.config.page_size - 1) // self.config.page_size)]),
                jnp.int32(plen),
            )
            if chip:
                # sync to read the clock (accounting opt-in trade)
                import jax

                jax.block_until_ready((self.pool.k, self.pool.v, out0))
        wall = _time.monotonic() - w0
        # commit: install the lane and emit the prefill token
        lane = _Lane(ticket, pages)
        lane.prefill_wall = wall
        self._lanes[i] = lane
        self._page_tables[i, :] = self.pool.sentinel
        self._page_tables[i, : len(pages)] = pages
        self._lens[i] = plen
        if sampled:
            tok0 = _sample_token(
                np.asarray(out0), self.config, ticket.sample_key, 0
            )
        else:
            tok0 = int(out0)
        ticket.tokens.append(int(tok0))
        DECODE_METRICS.record_prefill(plen, wall)
        DECODE_METRICS.set_pool(self.pool.pages_in_use, self.pool.n_pages)
        self._ledger_update()
        flight_recorder.record(
            "decode.prefill",
            lane=i,
            prompt_tokens=plen,
            pages=len(pages),
            wall_ms=round(wall * 1000.0, 3),
        )

    def _admit(self) -> None:
        if self._incremental:
            return self._admit_incremental()
        for i in range(len(self._lanes)):
            if not self._pending:
                return
            if self._lanes[i] is not None:
                continue
            ticket = self._pending[0]
            plen = len(ticket.prompt)
            need = pages_for(plen + ticket.max_new, self.config.page_size)
            pages = self.pool.alloc(need)
            if pages is None:
                return  # pool pressure: stay queued, retry next tick
            self._pending.popleft()
            self._prefill_whole(i, ticket, pages)
            if len(ticket.tokens) >= ticket.max_new:
                self._finish(i)

    @staticmethod
    def _deadline_key(ticket: DecodeTicket):
        """The AdaptiveBatcher's deadline comparator: earliest
        ``expires_at`` first, deadline-less work last, FIFO on ties."""
        dl = ticket.deadline
        return (1, 0.0) if dl is None else (0, dl.expires_at)

    def _admit_incremental(self) -> None:
        """Admission with the prefix cache and/or chunked prefill on.

        Differences from the legacy path: pending work admits in the
        AdaptiveBatcher's deadline order (chunk admission inherits it);
        the prompt's cached full-page prefix is mapped instead of
        allocated + prefilled; pool pressure reclaims idle cached
        prefixes before giving up; and a prompt with work left to
        prefill installs as a *prefilling* lane that
        :meth:`_advance_prefills` completes chunk by chunk."""
        from ..internals import flight_recorder

        while self._pending:
            i = next((j for j, l in enumerate(self._lanes) if l is None), -1)
            if i < 0:
                return
            idx = min(
                range(len(self._pending)),
                key=lambda j: self._deadline_key(self._pending[j]),
            )
            ticket = self._pending[idx]
            plen = len(ticket.prompt)
            need = pages_for(plen + ticket.max_new, self.config.page_size)
            shared = self.cache.lookup(ticket.prompt) if self.cache else []
            priv_need = need - len(shared)
            priv = self.pool.alloc(priv_need)
            if priv is None and self.cache is not None:
                # pool pressure: evict idle cached prefixes, retry once
                self.cache.reclaim(priv_need - self.pool.pages_free)
                DECODE_METRICS.set_cached_pages(self.cache.cached_pages)
                priv = self.pool.alloc(priv_need)
            if priv is None:
                if shared:
                    self.pool.free(shared)  # drop the lookup's refs
                return  # stay queued, retry next tick
            del self._pending[idx]
            pages = list(shared) + priv
            hit_tokens = len(shared) * self.config.page_size
            if self.cache is not None:
                DECODE_METRICS.record_prefix(
                    len(shared),
                    pages_for(plen, self.config.page_size) - len(shared),
                )
            if not shared and not self.config.prefill_chunk:
                # cold miss, chunking off: the one-shot prefill, then
                # publish the fresh pages for the next request to share
                self._prefill_whole(i, ticket, pages)
                if self.cache is not None:
                    self.cache.publish(ticket.prompt, pages, plen)
                    DECODE_METRICS.set_cached_pages(self.cache.cached_pages)
                if len(ticket.tokens) >= ticket.max_new:
                    self._finish(i)
                continue
            # install as a prefilling lane; chunks advance per tick
            self._lanes[i] = _Lane(
                ticket, pages, shared=len(shared), filled=hit_tokens
            )
            self._page_tables[i, :] = self.pool.sentinel
            self._page_tables[i, : len(pages)] = pages
            self._lens[i] = hit_tokens
            DECODE_METRICS.set_pool(self.pool.pages_in_use, self.pool.n_pages)
            self._ledger_update()
            flight_recorder.record(
                "decode.admit",
                lane=i,
                prompt_tokens=plen,
                pages=len(pages),
                prefix_hit_tokens=hit_tokens,
            )

    def _advance_prefills(self) -> None:
        """Advance the most urgent prefilling lane by one chunk. One
        chunk per tick: a long prefill interleaves with decode steps
        instead of stalling them (flat p99 under mixed lengths)."""
        if not self._incremental:
            return
        idxs = [
            i for i, l in enumerate(self._lanes) if l is not None and l.prefilling
        ]
        if not idxs:
            return
        from ..models.batching import bucket
        from ..internals import flight_recorder

        import jax.numpy as jnp

        i = min(idxs, key=lambda j: self._deadline_key(self._lanes[j].ticket))
        lane = self._lanes[i]
        ticket = lane.ticket
        plen = len(ticket.prompt)
        count = plen - lane.filled
        if self.config.prefill_chunk:
            count = min(count, self.config.prefill_chunk)
        m = min(bucket(count, _PREFILL_BUCKETS), self.max_prompt_len())
        ids = np.zeros(m, np.int32)
        ids[:count] = ticket.prompt[lane.filled : lane.filled + count]
        w0 = _time.monotonic()
        chip = CHIP_LEDGER.on()
        with CHIP_LEDGER.timed("decode") if chip else nullcontext():
            new_k, new_v, logits = self._chunk_fn(m)(
                self.params,
                self.pool.k,
                self.pool.v,
                jnp.asarray(self._page_tables[i]),
                jnp.asarray(ids),
                jnp.int32(lane.filled),
                jnp.int32(count),
            )
            if chip:
                import jax

                jax.block_until_ready((new_k, new_v, logits))
        wall = _time.monotonic() - w0
        # commit the chunk
        self.pool.k, self.pool.v = new_k, new_v
        lane.filled += count
        lane.prefill_wall += wall
        self._lens[i] = lane.filled
        if lane.filled < plen:
            return
        # prefill complete: emit the first token, publish the prefix
        if self.config.temperature > 0:
            tok0 = _sample_token(
                np.asarray(logits), self.config, ticket.sample_key, 0
            )
        else:
            tok0 = int(np.argmax(np.asarray(logits)))
        ticket.tokens.append(int(tok0))
        if self.cache is not None:
            self.cache.publish(ticket.prompt, lane.pages, plen)
            DECODE_METRICS.set_cached_pages(self.cache.cached_pages)
        hit_tokens = lane.shared * self.config.page_size
        DECODE_METRICS.record_prefill(plen, lane.prefill_wall)
        DECODE_METRICS.set_pool(self.pool.pages_in_use, self.pool.n_pages)
        self._ledger_update()
        flight_recorder.record(
            "decode.prefill",
            lane=i,
            prompt_tokens=plen,
            pages=len(lane.pages),
            wall_ms=round(lane.prefill_wall * 1000.0, 3),
            prefix_hit_tokens=hit_tokens,
        )
        from ..tracing import record_span, tracing_enabled

        if tracing_enabled() and ticket.trace is not None:
            record_span(
                "decode_prefill",
                start_mono=w0,
                end_mono=w0 + wall,
                new_trace=True,
                links=(ticket.trace.trace_id,),
                prefix_hit=hit_tokens,
                prompt_tokens=plen,
            )
        if len(ticket.tokens) >= ticket.max_new:
            self._finish(i)

    def step(self) -> int:
        """One engine tick: preempt expired lanes, admit pending
        prefills, then run one fused decode step across every live
        lane. Returns the number of tokens emitted. Compute happens
        before the ``decode.step`` chaos site, commit after — a step
        killed at the site leaves no trace."""
        from ..internals import flight_recorder
        from ..resilience import chaos

        import jax.numpy as jnp

        self._preempt_expired()
        self._admit()
        self._advance_prefills()
        live = [
            i
            for i, ln in enumerate(self._lanes)
            if ln is not None and not ln.prefilling
        ]
        DECODE_METRICS.set_active_lanes(len(live))
        if not live:
            return 0
        if self.config.spec_tokens:
            return self._spec_tick(live)
        toks = np.zeros(self.config.lanes, np.int32)
        for i in live:
            toks[i] = self._lanes[i].ticket.tokens[-1]
        # captured before the commit loop finishes lanes (a finished
        # lane's journey still belongs to this tick's step span)
        lane_tickets = [self._lanes[i].ticket for i in live]
        sampled = self.config.temperature > 0
        w0 = _time.monotonic()
        with CHIP_LEDGER.timed("decode") if CHIP_LEDGER.on() else nullcontext():
            if sampled:
                logits, new_k, new_v = self._step_logits_fn()(
                    self.params,
                    self.pool.k,
                    self.pool.v,
                    jnp.asarray(self._page_tables),
                    jnp.asarray(self._lens),
                    jnp.asarray(toks),
                )
                # counter-based draws (ticket key × stream position):
                # deterministic, so the compute-then-commit replay
                # contract holds for sampled decode too
                logits = np.asarray(logits)
                nxt = np.zeros(self.config.lanes, np.int32)
                for i in live:
                    t = self._lanes[i].ticket
                    nxt[i] = _sample_token(
                        logits[i], self.config, t.sample_key, len(t.tokens)
                    )
            else:
                nxt, new_k, new_v = self._step_fn()(
                    self.params,
                    self.pool.k,
                    self.pool.v,
                    jnp.asarray(self._page_tables),
                    jnp.asarray(self._lens),
                    jnp.asarray(toks),
                )
                nxt = np.asarray(nxt)
        wall = _time.monotonic() - w0
        # ---- point of no state: everything above is functional ----
        # (time = the step counter, so plans can target "the Nth step")
        chaos.inject("decode.step", time=self.steps)
        # ---- commit ----
        self.pool.k, self.pool.v = new_k, new_v
        emitted = 0
        for i in live:
            lane = self._lanes[i]
            self._lens[i] += 1
            lane.ticket.tokens.append(int(nxt[i]))
            emitted += 1
            if len(lane.ticket.tokens) >= lane.ticket.max_new:
                self._finish(i)
        self.steps += 1
        DECODE_METRICS.record_step(emitted, wall)
        flight_recorder.record(
            "decode.step",
            batch=len(live),
            tokens=emitted,
            wall_ms=round(wall * 1000.0, 3),
        )
        from ..tracing import record_span, tracing_enabled

        if tracing_enabled():
            lane_traces = tuple(
                {t.trace.trace_id for t in lane_tickets if t.trace is not None}
            )
            if lane_traces:
                # one fused tick serves N lanes: the step span gets its
                # own trace and links every member request journey
                record_span(
                    "decode_step",
                    start_mono=w0,
                    end_mono=w0 + wall,
                    new_trace=True,
                    links=lane_traces,
                    step=self.steps - 1,
                    batch=len(live),
                    tokens=emitted,
                )
        return emitted

    def _spec_tick(self, live) -> int:
        """One speculative tick: the layer-skip draft proposes
        ``spec_tokens`` tokens per lane in one dispatch, the full target
        verifies the chain in a second, and the longest argmax-matching
        prefix (plus the target's bonus token) commits. Greedy-exact:
        every committed token is the target's own argmax given the same
        context, so the emitted stream is bitwise the single-token
        stream — speculation only changes how many tokens one tick
        yields. Chip time books draft and verify separately
        (``decode.draft`` / ``decode.verify``)."""
        from ..internals import flight_recorder
        from ..resilience import chaos

        import jax.numpy as jnp

        k_spec = self.config.spec_tokens
        toks = np.zeros(self.config.lanes, np.int32)
        for i in live:
            toks[i] = self._lanes[i].ticket.tokens[-1]
        lane_tickets = [self._lanes[i].ticket for i in live]
        chip = CHIP_LEDGER.on()
        pt = jnp.asarray(self._page_tables)
        lens = jnp.asarray(self._lens)
        tk = jnp.asarray(toks)
        w0 = _time.monotonic()
        with CHIP_LEDGER.timed("decode.draft") if chip else nullcontext():
            if self.config.draft_ngram:
                # prompt-lookup draft: proposals copied from the lane's
                # own history — zero device-seconds in decode.draft,
                # the batched verify is the tick's only chip time
                dr = np.zeros((k_spec, self.config.lanes), np.int32)
                for i in live:
                    t = self._lanes[i].ticket
                    dr[:, i] = _prompt_lookup(
                        t.prompt + t.tokens, self.config.draft_ngram, k_spec
                    )
                drafts = jnp.asarray(dr)
            else:
                drafts = self._draft_fn()(
                    self.params, self.pool.k, self.pool.v, pt, lens, tk
                )
                if chip:
                    import jax

                    jax.block_until_ready(drafts)
        with CHIP_LEDGER.timed("decode.verify") if chip else nullcontext():
            import jax

            # verify output j is the target's argmax at position
            # lens + j, trustworthy iff every earlier proposal matched
            targets, new_k, new_v = self._verify_fn()(
                self.params, self.pool.k, self.pool.v, pt, lens, tk, drafts
            )
            drafts, targets = jax.device_get((drafts, targets))
            if chip:
                jax.block_until_ready((new_k, new_v))
        wall = _time.monotonic() - w0
        # ---- point of no state (same contract as the greedy step) ----
        chaos.inject("decode.step", time=self.steps)
        # ---- commit ----
        self.pool.k, self.pool.v = new_k, new_v
        emitted = proposed = accepted = 0
        for i in live:
            lane = self._lanes[i]
            a = 0
            while a < k_spec and drafts[a][i] == targets[a][i]:
                a += 1
            proposed += k_spec
            accepted += a
            # a matched proposals commit, plus the target's bonus token
            # (the output after the last accepted input); KV rows past
            # the commit point stay masked until a later write
            c = a + 1 if a < k_spec else k_spec
            c = min(c, lane.ticket.max_new - len(lane.ticket.tokens))
            self._lens[i] += c
            lane.ticket.tokens.extend(int(targets[j][i]) for j in range(c))
            emitted += c
            if len(lane.ticket.tokens) >= lane.ticket.max_new:
                self._finish(i)
        self.steps += 1
        DECODE_METRICS.record_step(emitted, wall)
        DECODE_METRICS.record_spec(proposed, accepted)
        flight_recorder.record(
            "decode.step",
            batch=len(live),
            tokens=emitted,
            wall_ms=round(wall * 1000.0, 3),
            proposed=proposed,
            accepted=accepted,
        )
        from ..tracing import record_span, tracing_enabled

        if tracing_enabled():
            lane_traces = tuple(
                {t.trace.trace_id for t in lane_tickets if t.trace is not None}
            )
            if lane_traces:
                record_span(
                    "decode_step",
                    start_mono=w0,
                    end_mono=w0 + wall,
                    new_trace=True,
                    links=lane_traces,
                    step=self.steps - 1,
                    batch=len(live),
                    tokens=emitted,
                    proposed=proposed,
                    accepted=accepted,
                )
        return emitted

    def busy(self) -> bool:
        return bool(self._pending) or any(l is not None for l in self._lanes)

    def drain(self, max_steps: int = 1_000_000) -> None:
        """Run the scheduler until every queued query finished (or was
        preempted)."""
        for _ in range(max_steps):
            if not self.busy():
                return
            self.step()
        raise RuntimeError("decode: drain did not converge")

    def generate(self, prompts, **kw) -> list[list[int]]:
        """Convenience batch API: submit every prompt, run to drain,
        return the token streams (continuous batching interleaves them
        on the way — the streams are identical to one-at-a-time runs)."""
        tickets = [self.submit(p, **kw) for p in prompts]
        self.drain()
        return [t.result() for t in tickets]


class DecodeService:
    """Deadline-aware front door: the serving plane's
    ``AdaptiveBatcher`` coalesces decode queries, drops the ones whose
    deadline expired while queued, and yields the ingest stream's
    ``query_share`` between fused dispatches — decode obeys the same
    admission economics as retrieval."""

    def __init__(self, engine: DecodeEngine, *, config=None):
        from ..serving.batching import AdaptiveBatcher

        self.engine = engine
        self._batcher = AdaptiveBatcher(
            self._dispatch,
            config=config,
            name="decode",
            on_expired=self._expired,
        )

    def submit(
        self,
        prompt_ids,
        *,
        deadline=None,
        max_new_tokens: int | None = None,
        degraded: bool = False,
    ) -> DecodeTicket:
        ticket = self.engine.make_ticket(
            prompt_ids,
            max_new_tokens=max_new_tokens,
            deadline=deadline,
            degraded=degraded,
        )
        self._batcher.submit(ticket, deadline)
        return ticket

    def _dispatch(self, items) -> None:
        for ticket in items:
            self.engine.enqueue(ticket)
        self.engine.drain()

    @staticmethod
    def _expired(ticket) -> None:
        DECODE_METRICS.record_preempt()
        ticket.preempted = True
        ticket.done.set()

    def stop(self) -> None:
        self._batcher.stop()

    @property
    def error(self):
        return self._batcher.error
