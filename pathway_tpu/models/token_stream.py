"""A write batch as one packed token stream: what the modules that pack
(``power_retention.py``, ``hybrid_ssm.py``) share — the stream's length,
its layout, and the loop over its live chunks. ``SentenceEncoder.
_stream_groups`` lays the documents one after another, each padded to the
module's ``doc_align`` only, and hands ``apply_stream`` the ids with where
each document starts and how long it is."""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


def stream_length(token_chunk: int, tokens: int) -> int:
    """The stream a forward of ``tokens`` runs as: whole chunks, or the
    least halving of a chunk that holds them."""
    n = token_chunk
    if tokens >= n:
        return -(-tokens // n) * n
    while n // 2 >= max(tokens, 16):
        n //= 2
    return n


def token_layout(starts, lens, t: int):
    """For a stream of ``t`` tokens whose document ``i`` is ``starts[i] ...
    starts[i] + lens[i] - 1`` (``starts`` ascending; a document that is
    not there has length 0 and starts at ``t``) -> ``seg`` ``[t]`` (the
    document of each token, -1 for padding), ``pos`` ``[t]`` (its place
    in its document, 0 for padding) and the live length: the tokens up
    to the last real one."""
    at = jnp.arange(t, dtype=jnp.int32)
    doc = jnp.clip(jnp.searchsorted(starts, at, side="right").astype(jnp.int32) - 1, 0, starts.shape[0] - 1)
    pos = at - starts[doc]
    real = (pos >= 0) & (pos < lens[doc])
    seg, pos = jnp.where(real, doc, -1), jnp.where(real, pos, 0)
    return seg, pos, jnp.max(jnp.where(lens > 0, starts + lens, 0))


@dataclasses.dataclass(frozen=True)
class TokenStream:
    """A packed stream as a forward's loops see it: ``t`` tokens in chunks
    of ``chunk``; ``seg``, ``pos`` and ``live`` as :func:`token_layout`
    gives them, ``starts`` the documents' first tokens, ``live_chunks``
    the loops' trip count: the chunks that hold a real token."""

    t: int
    chunk: int
    seg: Any
    pos: Any
    starts: Any
    live: Any
    live_chunks: Any

    @classmethod
    def of(cls, token_chunk: int, t: int, starts, lens) -> "TokenStream":
        chunk = min(token_chunk, t)
        seg, pos, live = token_layout(starts, lens, t)
        return cls(t, chunk, seg, pos, starts, live, (live + chunk - 1) // chunk)

    def over(self, body, carry):
        """``body(lo, carry)`` for the first row ``lo`` of every live chunk."""
        if self.chunk == self.t:
            return body(0, carry)
        return jax.lax.fori_loop(0, self.live_chunks, lambda i, cr: body(i * self.chunk, cr), carry)

    def rows(self, x, lo):
        """The chunk of ``x`` that starts at row ``lo``."""
        return jax.lax.dynamic_slice_in_dim(x, lo, self.chunk, axis=0)
