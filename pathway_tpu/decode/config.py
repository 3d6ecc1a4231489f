"""Decode-plane configuration: ``pw.run(decode=)`` / ``PATHWAY_DECODE``.

Mirrors the tiered-index knob (``ops/tiered_knn.parse_tier_spec``): a
frozen validated config, a forgiving spec parser shared by the run
kwarg and the environment variable, and a run-scoped active config the
lowering/serving layers consult. Module top stays jax-free so the
analysis plane (``PATHWAY_ANALYZE_ONLY`` runs, the self-lint CLI) can
reason about decode configs without touching a device.

Spec forms accepted everywhere a decode config is taken::

    pw.run(decode=True)                        # defaults
    pw.run(decode="pages=256,page=16,max_new=64")
    pw.run(decode={"pages": 256, "lanes": 8})
    PATHWAY_DECODE=auto | off | pages=512,page=32

The page-pool budget check shares ``PATHWAY_HBM_BYTES`` with the
PWL010/PWL012 index-footprint math: K+V pool bytes are
``2 × pages × page_size × layers × hidden × dtype_bytes`` and a config
that cannot fit the device is rejected at parse time, not at OOM time.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass, replace
from typing import Any

from ..internals.ledger import default_hbm_bytes, parse_bytes

__all__ = [
    "DecodeConfig",
    "parse_decode_spec",
    "active_decode",
    "set_active_decode",
    "use_decode",
]

_IMPLS = ("auto", "xla", "paged", "interpret")


@dataclass(frozen=True)
class DecodeConfig:
    """Validated decode-plane settings.

    ``pages``/``page_size`` size the paged-KV pool; ``lanes`` is the
    continuous-batching width (concurrent sequences per decode step —
    the step always runs at this padded width so a sequence's token
    stream is bitwise-independent of its co-runners); ``max_new_tokens``
    is the per-query generation cap and ``degrade_max_new_tokens`` the
    clamp applied when admission degrades a query (degrade also skips
    the rerank stage); ``max_seq`` bounds prompt+generation context;
    ``impl`` picks the attention path (``auto`` = paged kernel on TPU,
    which needs ``page_size`` to be a multiple of 8, XLA gather
    elsewhere; ``interpret`` = Pallas interpret mode, the CPU parity
    path); ``hbm_bytes`` overrides the pool budget check.

    Serving extensions (all default off — the defaults reproduce the
    original single-token greedy engine byte-for-byte):

    ``prefix_cache``
        Refcounted hash-addressed sharing of full prompt pages across
        requests: a request whose prompt starts with an already-cached
        prefix maps the shared physical pages instead of re-prefilling
        them. Shared pages are read-only by construction (decode writes
        land past the prompt) and booked once in the ``decode.kv``
        ledger account regardless of reference count.
    ``spec_tokens`` / ``draft_layers`` / ``draft_ngram`` / ``draft_weights``
        Speculative multi-token steps: a draft proposes ``spec_tokens``
        tokens per tick and the target verifies them in one batched
        forward. The default draft is layer-skip self-drafting — the
        first ``draft_layers`` target layers (0 = half) plus the tied
        head, so it shares weights *and* KV pages with the target.
        ``draft_ngram > 0`` selects prompt-lookup drafting instead: the
        proposal is copied from the last place the stream's trailing
        n-gram occurred in the lane's own prompt + output, costing zero
        device time (RAG answers quote their retrieved context, so
        lookup hits are the common case — the chip ledger's
        ``decode.draft`` account shows ~0 device-seconds, all the chip
        time is verify). ``draft_weights`` declares the HBM bytes of an
        external draft checkpoint for budget math (0 = self-draft, no
        extra weights). Requires greedy decode (``temperature == 0``):
        verification is exact argmax equality, so the emitted stream is
        bitwise the single-token stream.
    ``prefill_chunk``
        Prefill at most this many prompt tokens per engine tick
        (0 = whole prompt in one dispatch), interleaved with decode
        steps so a long prefill never stalls in-flight decodes. Chunk
        admission follows deadline order (the AdaptiveBatcher's).
    ``temperature`` / ``top_k`` / ``top_p`` / ``seed``
        Sampled decode. Draws are counter-based — keyed on the ticket
        seed and the absolute token position, never on global RNG
        state — so recovery replay and co-batching cannot perturb a
        stream. ``temperature == 0`` is exact greedy (the default).
    """

    pages: int = 256
    page_size: int = 16
    lanes: int = 8
    max_new_tokens: int = 64
    degrade_max_new_tokens: int = 16
    max_seq: int = 512
    rerank: bool = True
    impl: str = "auto"
    hbm_bytes: int | None = None
    prefix_cache: bool = False
    spec_tokens: int = 0
    draft_layers: int = 0
    draft_ngram: int = 0
    draft_weights: int = 0
    prefill_chunk: int = 0
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.pages <= 0:
            raise ValueError("decode: pages must be positive")
        if self.page_size <= 0:
            raise ValueError("decode: page_size must be positive")
        if self.lanes <= 0:
            raise ValueError("decode: lanes must be positive")
        if self.max_new_tokens <= 0:
            raise ValueError("decode: max_new_tokens must be positive")
        if not 0 < self.degrade_max_new_tokens <= self.max_new_tokens:
            raise ValueError(
                "decode: degrade_max_new_tokens must be in (0, max_new_tokens]"
            )
        if self.max_seq < self.page_size:
            raise ValueError("decode: max_seq must cover at least one page")
        if self.impl not in _IMPLS:
            raise ValueError(f"decode: impl must be one of {_IMPLS}")
        if self.hbm_bytes is not None and self.hbm_bytes <= 0:
            raise ValueError("decode: hbm_bytes must be positive")
        if self.spec_tokens < 0:
            raise ValueError("decode: spec_tokens must be >= 0")
        if self.draft_layers < 0:
            raise ValueError("decode: draft_layers must be >= 0")
        if self.draft_ngram < 0:
            raise ValueError("decode: draft_ngram must be >= 0")
        if self.draft_weights < 0:
            raise ValueError("decode: draft_weights must be >= 0")
        if self.prefill_chunk < 0:
            raise ValueError("decode: prefill_chunk must be >= 0")
        if self.temperature < 0:
            raise ValueError("decode: temperature must be >= 0")
        if self.top_k < 0:
            raise ValueError("decode: top_k must be >= 0")
        if not 0 < self.top_p <= 1:
            raise ValueError("decode: top_p must be in (0, 1]")
        if self.spec_tokens > 0 and self.temperature > 0:
            raise ValueError(
                "decode: speculative steps require greedy decode "
                "(temperature=0) — verification is exact argmax equality"
            )

    def as_dict(self) -> dict[str, Any]:
        return {
            "pages": self.pages,
            "page_size": self.page_size,
            "lanes": self.lanes,
            "max_new_tokens": self.max_new_tokens,
            "degrade_max_new_tokens": self.degrade_max_new_tokens,
            "max_seq": self.max_seq,
            "rerank": self.rerank,
            "impl": self.impl,
            "hbm_bytes": self.hbm_bytes,
            "prefix_cache": self.prefix_cache,
            "spec_tokens": self.spec_tokens,
            "draft_layers": self.draft_layers,
            "draft_ngram": self.draft_ngram,
            "draft_weights": self.draft_weights,
            "prefill_chunk": self.prefill_chunk,
            "temperature": self.temperature,
            "top_k": self.top_k,
            "top_p": self.top_p,
            "seed": self.seed,
        }

    def pages_per_seq(self) -> int:
        """Static page-table width: pages covering ``max_seq``."""
        return (self.max_seq + self.page_size - 1) // self.page_size

    def pool_bytes(self, layers: int, hidden: int, dtype_bytes: int = 4) -> int:
        """K+V pool footprint for a given decoder geometry — the number
        the README sizing math and PWL010/012 budget share (one formula,
        in ``internals/ledger``)."""
        from ..internals.ledger import kv_pool_bytes

        return kv_pool_bytes(
            self.pages, self.page_size, layers, hidden, dtype_bytes
        )

    def check_budget(self, layers: int, hidden: int, dtype_bytes: int = 4) -> None:
        budget = self.hbm_bytes if self.hbm_bytes is not None else default_hbm_bytes()
        need = self.pool_bytes(layers, hidden, dtype_bytes)
        if need > budget:
            raise ValueError(
                f"decode: KV page pool needs {need} bytes "
                f"({self.pages} pages x {self.page_size} tokens x "
                f"{layers} layers x {hidden} hidden x 2 (K+V) x "
                f"{dtype_bytes} B) but the HBM budget is {budget} "
                f"(PATHWAY_HBM_BYTES / hbm_bytes=)"
            )


#: spec-key aliases accepted by :func:`parse_decode_spec`
_SPEC_KEYS = {
    "pages": "pages",
    "page": "page_size",
    "page_size": "page_size",
    "lanes": "lanes",
    "batch": "lanes",
    "max_new": "max_new_tokens",
    "max_new_tokens": "max_new_tokens",
    "degrade": "degrade_max_new_tokens",
    "degrade_max_new": "degrade_max_new_tokens",
    "degrade_max_new_tokens": "degrade_max_new_tokens",
    "max_seq": "max_seq",
    "rerank": "rerank",
    "impl": "impl",
    "hbm": "hbm_bytes",
    "hbm_bytes": "hbm_bytes",
    "cache": "prefix_cache",
    "prefix_cache": "prefix_cache",
    "spec": "spec_tokens",
    "spec_tokens": "spec_tokens",
    "draft": "draft_layers",
    "draft_layers": "draft_layers",
    "ngram": "draft_ngram",
    "draft_ngram": "draft_ngram",
    "draft_weights": "draft_weights",
    "chunk": "prefill_chunk",
    "prefill_chunk": "prefill_chunk",
    "temp": "temperature",
    "temperature": "temperature",
    "top_k": "top_k",
    "top_p": "top_p",
    "seed": "seed",
}

_BOOL_FIELDS = ("rerank", "prefix_cache")
_FLOAT_FIELDS = ("temperature", "top_p")
_BYTES_FIELDS = ("hbm_bytes", "draft_weights")

_OFF = ("off", "none", "0", "false", "no")
_ON = ("on", "true", "auto", "yes", "1", "")


def _coerce(kw: dict[str, Any]) -> dict[str, Any]:
    out: dict[str, Any] = {}
    for key, value in kw.items():
        if key not in _SPEC_KEYS:
            raise ValueError(
                f"decode: unknown spec key {key!r} (known: "
                f"{sorted(set(_SPEC_KEYS))})"
            )
        field = _SPEC_KEYS[key]
        if field in _BOOL_FIELDS:
            if isinstance(value, str):
                value = value.strip().lower() not in _OFF
            out[field] = bool(value)
        elif field == "impl":
            out[field] = str(value).strip().lower()
        elif field in _BYTES_FIELDS:
            out[field] = parse_bytes(value)
        elif field in _FLOAT_FIELDS:
            out[field] = float(value)
        else:
            out[field] = int(value)
    return out


def parse_decode_spec(spec: Any) -> DecodeConfig | None:
    """Coerce any accepted decode spec into a config (or ``None`` =
    decode off). Raises ``ValueError`` on malformed specs."""
    if spec is None or spec is False:
        return None
    if isinstance(spec, DecodeConfig):
        return spec
    if spec is True:
        return DecodeConfig()
    if isinstance(spec, int):
        return None if spec == 0 else DecodeConfig(pages=spec)
    if isinstance(spec, dict):
        return DecodeConfig(**_coerce(spec))
    if isinstance(spec, str):
        text = spec.strip().lower()
        if text in _OFF:
            return None
        if text in _ON:
            return DecodeConfig()
        kw: dict[str, Any] = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(
                    f"decode: spec entries must be key=value, got {part!r}"
                )
            key, _, value = part.partition("=")
            kw[key.strip().lower()] = value.strip()
        return DecodeConfig(**_coerce(kw))
    raise ValueError(f"decode: cannot parse spec of type {type(spec).__name__}")


# -- run-scoped active config (mirrors ops/tiered_knn.active_tiers) ---------

_decode_lock = threading.Lock()
_active_decode: DecodeConfig | None = None
_active_set = False
_env_cache: tuple[str, DecodeConfig | None] | None = None


def active_decode() -> DecodeConfig | None:
    """The decode config in effect: the run-installed one if a run is
    active, else ``PATHWAY_DECODE`` from the environment (parsed once
    per distinct value; a malformed env value counts as off)."""
    global _env_cache
    with _decode_lock:
        if _active_set:
            return _active_decode
    raw = os.environ.get("PATHWAY_DECODE", "")
    if not raw.strip():
        return None
    with _decode_lock:
        if _env_cache is not None and _env_cache[0] == raw:
            return _env_cache[1]
    try:
        cfg = parse_decode_spec(raw)
    except ValueError:
        cfg = None
    with _decode_lock:
        _env_cache = (raw, cfg)
    return cfg


def set_active_decode(cfg: DecodeConfig | None) -> None:
    """Install (or clear, with ``None``) the run-scoped decode config.
    ``pw.run(decode=...)`` installs around the engine run; the paired
    clear in its ``finally`` keeps env fallback working between runs."""
    global _active_decode, _active_set
    with _decode_lock:
        _active_decode = cfg
        _active_set = cfg is not None


@contextmanager
def use_decode(spec: Any):
    """Context-scoped decode config (tests and embedded callers)."""
    global _active_decode, _active_set
    cfg = parse_decode_spec(spec)
    prev_cfg, prev_set = _active_decode, _active_set
    set_active_decode(cfg)
    try:
        yield cfg
    finally:
        with _decode_lock:
            _active_decode, _active_set = prev_cfg, prev_set


def degraded(cfg: DecodeConfig) -> DecodeConfig:
    """The config admission applies to a degraded query: rerank off,
    generation clamped — the documented shed/degrade semantics."""
    return replace(
        cfg, rerank=False, max_new_tokens=cfg.degrade_max_new_tokens
    )
