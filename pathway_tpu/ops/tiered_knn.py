"""Two-tier online KNN index: an HBM-resident hot tier over a
host-memory cold tier, for corpora beyond one slice's HBM budget.

EdgeRAG-style layout (PAPERS.md): every vector lives in a host-side
cold store (int8 scale-per-vector by default, f32 optional); the hot
tier is a ``DeviceKnnIndex`` acting as an HBM cache over the hottest
IVF clusters, riding the existing per-shard slab layout and
incremental scatter updates unchanged. Cluster assignment happens
online at ingest (mini-batch k-means over the first ``n_clusters``
seeds); background promotion/demotion is driven by per-cluster hit
counts decayed each rebalance sweep.

Query path: the hot top-k is DISPATCHED first (async device call, the
hot path never waits on host tiering work), then the centroid probe
runs host-side over the tiny [n_clusters, dim] table — the probe
result is needed on host anyway to gather cold slots, so probing
on-device would only add a blocking round trip before the gather.
Cold candidates of the probed clusters are dequantized, staged through
a DeviceRing slot (donated, non-blocking put), rescored with one
jitted matmul on the SAME score scale as the flat index, and merged
with the resolved hot candidates on host. Keys present in both tiers
(the crash window mid-promotion) dedup at merge with the hot copy
winning, so a killed worker can never surface a vector twice or lose
one: the cold store is authoritative until the hot insert lands.

When every document is hot-resident the search delegates wholesale to
``DeviceKnnIndex.search_batch`` — the single-tier path stays
bit-identical with tiering configured but not yet exercised.

Snapshots: ``tier_state()`` captures the centroid table, per-key
cluster assignment, hit counters, and the exact hot-resident key set;
``restore_tier_state`` + ``finish_tier_restore`` replay them around
the engine's re-add so recovery restores the exact tier assignment.

Module top imports numpy only — jax loads lazily on first device use,
matching ops/knn.py.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass, replace
from typing import Any, Callable

import numpy as np

from ..internals.ledger import (  # noqa: F401  (re-exported; the shared
    _DEFAULT_HBM_BYTES,  # footprint model lives in internals/ledger.py)
    cold_row_bytes,
    default_hbm_bytes,
    hot_row_bytes,
    parse_bytes,
)
from .knn import _NEG, _k_bucket, _shard_of_key

_COLD_DTYPES = ("int8", "f32")
_HOT_DTYPES = ("f32", "int8")


@dataclass(frozen=True)
class TierConfig:
    """Knobs for the two-tier index. ``hot_rows == 0`` derives the hot
    tier size from ``hbm_bytes`` (default: PATHWAY_HBM_BYTES or 16 GiB
    per device, shared with PWL010's budget math)."""

    hot_rows: int = 0
    hbm_bytes: int | None = None
    n_clusters: int = 64
    n_probe: int = 8
    cold_dtype: str = "int8"
    hot_dtype: str = "f32"
    promote_every: int = 64
    decay: float = 0.5

    def __post_init__(self):
        if self.cold_dtype not in _COLD_DTYPES:
            raise ValueError(
                f"index tiers: cold dtype {self.cold_dtype!r}: expected one of {_COLD_DTYPES}"
            )
        if self.hot_dtype not in _HOT_DTYPES:
            raise ValueError(
                f"index tiers: hot dtype {self.hot_dtype!r}: expected one of {_HOT_DTYPES}"
            )
        if self.n_clusters < 1 or self.n_probe < 1:
            raise ValueError("index tiers: n_clusters and n_probe must be >= 1")
        if self.hot_rows < 0 or self.promote_every < 1:
            raise ValueError(
                "index tiers: hot_rows must be >= 0 and promote_every >= 1"
            )
        if self.hbm_bytes is not None and self.hbm_bytes <= 0:
            raise ValueError("index tiers: hbm_bytes must be positive")
        if not (0.0 < self.decay <= 1.0):
            raise ValueError("index tiers: decay must be in (0, 1]")

    def resolve_hot_rows(self, dim: int, n_shards: int = 1) -> int:
        """Total hot-tier rows across the mesh: explicit ``hot_rows``,
        else the per-device HBM budget divided by the slab row cost."""
        if self.hot_rows > 0:
            return self.hot_rows
        budget = self.hbm_bytes if self.hbm_bytes is not None else default_hbm_bytes()
        per_dev = max(1, budget // hot_row_bytes(dim, self.hot_dtype))
        return max(64, int(per_dev) * max(1, n_shards))

    def as_dict(self) -> dict:
        return {
            "hot_rows": self.hot_rows,
            "hbm_bytes": self.hbm_bytes,
            "n_clusters": self.n_clusters,
            "n_probe": self.n_probe,
            "cold_dtype": self.cold_dtype,
            "hot_dtype": self.hot_dtype,
            "promote_every": self.promote_every,
            "decay": self.decay,
        }


def deep_tier_profile(cfg) -> dict | None:
    """Static tier-plane metadata for the deep verifier (analysis.deep,
    PWL018): the compile-relevant knobs of the two-tier index. The cold
    tier adds two kernel families on top of the hot-tier search — the
    cluster-probe gather and the cold rescore — each keyed on the
    (n_clusters, n_probe, cold_dtype) geometry, so the bucket space is
    one entry per configured geometry, not per corpus size."""
    if cfg is None:
        return None
    d = cfg if isinstance(cfg, dict) else cfg.as_dict()
    return {
        "n_clusters": int(d.get("n_clusters") or 64),
        "n_probe": int(d.get("n_probe") or 8),
        "hot_dtype": d.get("hot_dtype", "f32"),
        "cold_dtype": d.get("cold_dtype", "int8"),
        "extra_kernel_families": 2,
    }


_SPEC_KEYS = {
    "hot": "hot_rows",
    "hot_rows": "hot_rows",
    "hbm": "hbm_bytes",
    "hbm_bytes": "hbm_bytes",
    "clusters": "n_clusters",
    "n_clusters": "n_clusters",
    "probe": "n_probe",
    "n_probe": "n_probe",
    "cold": "cold_dtype",
    "cold_dtype": "cold_dtype",
    "hot_dtype": "hot_dtype",
    "promote": "promote_every",
    "promote_every": "promote_every",
    "decay": "decay",
}


def parse_tier_spec(spec: Any) -> TierConfig | None:
    """jax-free spec parsing (mirrors parse_mesh_spec): accepts None,
    a TierConfig, an int (hot rows), a dict of knob names, or a string
    like ``"hot=4096,clusters=64,probe=8,cold=int8,hbm=4G"``. Raises
    ValueError on malformed input; ``"off"``/``""`` -> None."""
    if spec is None:
        return None
    if isinstance(spec, TierConfig):
        return spec
    if isinstance(spec, bool):
        return TierConfig() if spec else None
    if isinstance(spec, int):
        return TierConfig(hot_rows=spec)
    if isinstance(spec, dict):
        kw: dict[str, Any] = {}
        for k, v in spec.items():
            field = _SPEC_KEYS.get(str(k))
            if field is None:
                raise ValueError(f"index tiers: unknown knob {k!r}")
            kw[field] = v
        return TierConfig(**_coerce(kw))
    if isinstance(spec, str):
        s = spec.strip()
        if not s or s.lower() in ("off", "none", "0", "false"):
            return None
        if s.lower() in ("on", "true", "auto"):
            return TierConfig()
        kw = {}
        for part in s.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(f"index tiers: bad spec part {part!r}")
            k, _, v = part.partition("=")
            field = _SPEC_KEYS.get(k.strip())
            if field is None:
                raise ValueError(f"index tiers: unknown knob {k.strip()!r}")
            kw[field] = v.strip()
        return TierConfig(**_coerce(kw))
    raise ValueError(f"index tiers: cannot parse spec of type {type(spec).__name__}")


def _coerce(kw: dict[str, Any]) -> dict[str, Any]:
    out: dict[str, Any] = {}
    for field, v in kw.items():
        if field in ("cold_dtype", "hot_dtype"):
            out[field] = str(v)
        elif field == "decay":
            out[field] = float(v)
        elif field == "hbm_bytes":
            out[field] = parse_bytes(v)
        else:
            try:
                out[field] = int(v)
            except (TypeError, ValueError):
                raise ValueError(f"index tiers: bad value {v!r} for {field}") from None
    return out


# ---------------------------------------------------------------------------
# run-scoped active config (mirrors parallel/mesh.py's active mesh)

_tier_lock = threading.Lock()
_active_tiers: TierConfig | None = None
_env_tier_cache: tuple[str, TierConfig | None] | None = None


def active_tiers() -> TierConfig | None:
    """The tier config indexes built inside pw.run(index_tiers=) should
    pick up: the run-scoped config first, then PATHWAY_INDEX_TIERS."""
    global _env_tier_cache
    with _tier_lock:
        if _active_tiers is not None:
            return _active_tiers
    raw = os.environ.get("PATHWAY_INDEX_TIERS", "")
    if not raw:
        return None
    with _tier_lock:
        if _env_tier_cache is not None and _env_tier_cache[0] == raw:
            return _env_tier_cache[1]
    try:
        cfg = parse_tier_spec(raw)
    except ValueError:
        cfg = None
    with _tier_lock:
        _env_tier_cache = (raw, cfg)
    return cfg


def set_active_tiers(cfg: TierConfig | None) -> None:
    global _active_tiers
    with _tier_lock:
        _active_tiers = cfg


@contextmanager
def use_tiers(spec: Any):
    prev = _active_tiers
    set_active_tiers(parse_tier_spec(spec))
    try:
        yield
    finally:
        set_active_tiers(prev)


# ---------------------------------------------------------------------------
# int8 scale-per-vector quantization

def quantize_int8(vecs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """f32 [n, dim] -> (int8 [n, dim], f32 [n] scale) with
    scale = max|v| per vector; v̂ = q * scale / 127."""
    vecs = np.asarray(vecs, np.float32)
    scale = np.max(np.abs(vecs), axis=1)
    safe = np.maximum(scale, 1e-12)
    q = np.clip(np.rint(vecs * (127.0 / safe[:, None])), -127, 127).astype(np.int8)
    return q, scale.astype(np.float32)


def dequantize_int8(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * (np.asarray(scale, np.float32)[:, None] / 127.0)


class ColdStore:
    """Host-memory slab of quantized vectors with LIFO slot reuse —
    the same free-list discipline as the device slabs, minus jax."""

    def __init__(self, dim: int, dtype: str = "int8", capacity: int = 1024):
        self.dim = dim
        self.dtype = dtype
        self.capacity = max(64, int(capacity))
        if dtype == "int8":
            self._q = np.zeros((self.capacity, dim), np.int8)
            self._scale = np.zeros((self.capacity,), np.float32)
        else:
            self._f = np.zeros((self.capacity, dim), np.float32)
        self._free = list(range(self.capacity - 1, -1, -1))
        self.rows = 0

    @property
    def bytes_per_row(self) -> int:
        return cold_row_bytes(self.dim, self.dtype)

    def _grow(self) -> None:
        old = self.capacity
        self.capacity = old * 2
        if self.dtype == "int8":
            q = np.zeros((self.capacity, self.dim), np.int8)
            q[:old] = self._q
            self._q = q
            s = np.zeros((self.capacity,), np.float32)
            s[:old] = self._scale
            self._scale = s
        else:
            f = np.zeros((self.capacity, self.dim), np.float32)
            f[:old] = self._f
            self._f = f
        self._free.extend(range(self.capacity - 1, old - 1, -1))

    def put(self, vecs: np.ndarray) -> np.ndarray:
        vecs = np.asarray(vecs, np.float32)
        n = len(vecs)
        while len(self._free) < n:
            self._grow()
        slots = np.array([self._free.pop() for _ in range(n)], np.int64)
        if self.dtype == "int8":
            q, scale = quantize_int8(vecs)
            self._q[slots] = q
            self._scale[slots] = scale
        else:
            self._f[slots] = vecs
        self.rows += n
        return slots

    def erase(self, slots) -> None:
        for s in slots:
            self._free.append(int(s))
        self.rows -= len(slots)

    def fetch(self, slots) -> np.ndarray:
        sl = np.asarray(slots, np.int64)
        if self.dtype == "int8":
            return dequantize_int8(self._q[sl], self._scale[sl])
        return self._f[sl].copy()

    def export_rows(self, slots) -> dict:
        """Raw row payload for an elastic migration chunk — the stored
        bytes, NOT a dequantized view. Re-quantizing a dequantized
        vector is not an identity in general; transplanting the q/scale
        (or f32) bytes keeps cold scores bit-identical across a
        reshard."""
        sl = np.asarray(slots, np.int64)
        if self.dtype == "int8":
            return {
                "dtype": "int8",
                "q": self._q[sl].copy(),
                "scale": self._scale[sl].copy(),
            }
        return {"dtype": self.dtype, "f": self._f[sl].copy()}

    def import_rows(self, payload: dict) -> np.ndarray:
        """Land an :meth:`export_rows` payload byte-exactly; returns the
        slots the rows were placed in."""
        if payload.get("dtype") != self.dtype:
            raise ValueError(
                f"cold store dtype mismatch: {payload.get('dtype')!r} vs {self.dtype!r}"
            )
        n = len(payload["q" if self.dtype == "int8" else "f"])
        while len(self._free) < n:
            self._grow()
        slots = np.array([self._free.pop() for _ in range(n)], np.int64)
        if self.dtype == "int8":
            self._q[slots] = payload["q"]
            self._scale[slots] = payload["scale"]
        else:
            self._f[slots] = payload["f"]
        self.rows += n
        return slots


# ---------------------------------------------------------------------------
# cold rescoring (one jitted matmul on the flat index's score scale)

_COLD_JIT: dict[str, Callable] = {}


def _cold_score_fn(metric: str) -> Callable:
    if metric not in _COLD_JIT:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def score_dot(q, docs):
            return q @ docs.T

        @jax.jit
        def score_l2(q, docs):
            # matches _topk_fn: -||q-x||^2 = 2 q.x - ||x||^2 - ||q||^2
            s = 2.0 * (q @ docs.T)
            s = s - jnp.sum(docs * docs, axis=1)[None, :]
            return s - jnp.sum(q * q, axis=1)[:, None]

        _COLD_JIT["cos"] = score_dot
        _COLD_JIT["ip"] = score_dot
        _COLD_JIT["l2"] = score_l2
    return _COLD_JIT[metric]


class TieredKnnIndex:
    """Hot ``DeviceKnnIndex`` cache over an authoritative host
    ``ColdStore``, presenting the same add/remove/search_batch protocol
    the engine duck-types. See the module docstring for the design."""

    is_tiered = True

    def __init__(
        self,
        dim: int,
        metric: str = "cos",
        reserved_space: int = 1024,
        tiers: Any = None,
        dtype: Any = np.float32,
        mesh: Any = None,
        name: str | None = None,
    ):
        from .knn import _NAME_SEQ, DeviceKnnIndex

        cfg = parse_tier_spec(tiers)
        if cfg is None:
            cfg = TierConfig()
        self.tiers = cfg
        self.dim = int(dim)
        self.metric = metric
        self.mesh = mesh
        self.name = name if name is not None else f"knn{next(_NAME_SEQ)}"
        n_shards = int(mesh.shape["data"]) if mesh is not None else 1
        if cfg.hot_rows > 0:
            hot_rows = cfg.hot_rows
        else:
            # budget-derived hot tier, capped by the caller's reserved
            # space: the hot slab is an HBM cache sized to the SMALLER
            # of what the budget allows and what the corpus expects
            hot_rows = min(
                max(64, int(reserved_space)),
                cfg.resolve_hot_rows(self.dim, n_shards),
            )
        # the hot tier carries the logical index name: its flight events
        # and search records ARE this index's, and tiered _publish_metrics
        # below replaces its per-tier accounting with both-tier totals
        self.hot = DeviceKnnIndex(
            dim,
            metric,
            reserved_space=hot_rows,
            dtype=dtype,
            mesh=mesh,
            name=self.name,
        )
        self.hot._publish_metrics = self._publish_metrics
        self.hot._tier_cold_docs = self.cold_docs
        self.n_shards = self.hot.n_shards

        C = cfg.n_clusters
        self._cold = ColdStore(self.dim, cfg.cold_dtype)
        self._centroids = np.zeros((C, self.dim), np.float32)
        self._centroid_n = np.zeros((C,), np.int64)
        self._n_centroids = 0
        self._hits = np.zeros((C,), np.float64)
        self._cluster_of: dict[Any, int] = {}
        self._members: list[set] = [set() for _ in range(C)]
        self._cold_keys: list[set] = [set() for _ in range(C)]  # not hot-resident
        self._cold_slot: dict[Any, int] = {}
        self._meta: dict[Any, Any] = {}
        self._cold_docs_shard = [0] * self.n_shards
        self._cold_total = 0
        self._searches_since_rebalance = 0
        self._promotions = 0
        self._demotions = 0
        self._cold_ring = None
        self._encoder = None
        # snapshot-restore staging: exact assignment + hot set replay
        self._restore_assign: dict[Any, int] | None = None
        self._restore_hot: list | None = None
        self.generation = 0  # elastic reshard fencing token

    # -- sizing ------------------------------------------------------------

    def __len__(self) -> int:
        return len(self._cluster_of)

    @property
    def capacity(self) -> int:
        return self.hot.capacity

    @property
    def shard_capacity(self) -> int:
        return self.hot.shard_capacity

    def hot_docs(self) -> int:
        return len(self.hot._slot_of)

    def cold_docs(self) -> int:
        return self._cold_total

    # -- metrics -----------------------------------------------------------

    def _publish_metrics(self) -> None:
        from .index_metrics import INDEX_METRICS

        hrb = hot_row_bytes(self.dim, self.tiers.hot_dtype)
        crb = self._cold.bytes_per_row
        # under the hot tier's publish lock, as its own publishes are: a
        # plane's reader may be paying what the hot tier's removes owe
        with self.hot._publish_lock:
            INDEX_METRICS.update_index(
                self.name,
                list(self.hot._docs_shard),
                self.hot.shard_capacity,
                cold_docs_shard=list(self._cold_docs_shard),
                hot_bytes_shard=[int(d) * hrb for d in self.hot._docs_shard],
                cold_bytes_shard=[int(d) * crb for d in self._cold_docs_shard],
            )
            # The hot tier is a DeviceKnnIndex whose publish hook this
            # method replaces — keep its HBM ledger account (bytes + used
            # fraction) current here instead.
            self.hot._ledger_update()

    # -- cluster assignment ------------------------------------------------

    def _assign_batch(self, vecs: np.ndarray) -> np.ndarray:
        """Online mini-batch k-means: the first n_clusters vectors seed
        centroids; later batches take the nearest centroid and shift it
        toward the batch mean weighted by assignment counts."""
        n = len(vecs)
        C = self.tiers.n_clusters
        out = np.empty(n, np.int64)
        i = 0
        while self._n_centroids < C and i < n:
            c = self._n_centroids
            self._centroids[c] = vecs[i]
            self._centroid_n[c] = 1
            self._n_centroids += 1
            out[i] = c
            i += 1
        if i < n:
            rest = vecs[i:]
            cents = self._centroids[: self._n_centroids]
            if self.metric == "l2":
                s = 2.0 * (rest @ cents.T) - np.sum(cents * cents, axis=1)[None, :]
            else:
                s = rest @ cents.T
            a = np.argmax(s, axis=1)
            out[i:] = a
            for c in np.unique(a):
                mask = a == c
                m = int(mask.sum())
                nc = int(self._centroid_n[c])
                self._centroids[c] += (rest[mask].mean(axis=0) - self._centroids[c]) * (
                    m / (nc + m)
                )
                self._centroid_n[c] = nc + m
        return out

    def _assign_keys(self, keys: list, vecs: np.ndarray) -> np.ndarray:
        if self._restore_assign is None:
            return self._assign_batch(vecs)
        # snapshot replay: exact assignment, no centroid drift
        out = np.empty(len(keys), np.int64)
        missing: list[int] = []
        for i, key in enumerate(keys):
            c = self._restore_assign.get(key)
            if c is None:
                missing.append(i)
            else:
                out[i] = c
        if missing:
            out[missing] = self._assign_batch(vecs[missing])
        return out

    # -- mutation ----------------------------------------------------------

    def add(self, key, vector, metadata=None) -> None:
        vec = np.asarray(vector, np.float32).reshape(1, -1)
        self.add_batch_arrays([key], vec, [metadata])

    def add_batch(self, items: list[tuple]) -> None:
        if not items:
            return
        keys = [k for k, _, _ in items]
        vecs = np.stack(
            [np.asarray(p, np.float32).reshape(-1) for _, p, _ in items]
        )
        self.add_batch_arrays(keys, vecs, [m for _, _, m in items])

    def add_batch_device(self, keys, dev_vectors, metadatas=None) -> None:
        """Device-resident ingest lands in the authoritative host cold
        store first, so the encoder output is pulled once; hot
        placement then follows the normal policy. Beyond-HBM capacity
        is bought with this one pull."""
        keys = list(keys)
        if not keys:
            return
        vecs = np.asarray(dev_vectors)[: len(keys)].astype(np.float32)
        self.add_batch_arrays(keys, vecs, metadatas)

    def add_batch_arrays(self, keys, vectors, metadatas=None) -> None:
        vecs = np.asarray(vectors, np.float32)
        if vecs.ndim == 1:
            vecs = vecs[None, :]
        if vecs.shape[1] != self.dim:
            raise ValueError(
                f"index {self.name}: expected dim {self.dim}, got {vecs.shape[1]}"
            )
        self.hot._check_fence()  # fenced generation: reject cold-landing writes too
        for key in keys:
            if key in self._cluster_of:
                self.remove(key)
        # the raw vectors go to the HOT tier untouched — it normalizes
        # exactly like the flat index, keeping the fits-hot path
        # bit-identical; the normalized copy feeds assignment + cold
        if self.metric == "cos":
            norms = np.linalg.norm(vecs, axis=1, keepdims=True)
            unit = vecs / np.maximum(norms, 1e-12)
        else:
            unit = vecs
        clusters = self._assign_keys(list(keys), unit)
        slots = self._cold.put(unit)
        restoring = self._restore_assign is not None
        free = [len(f) for f in self.hot._free_shard]
        cap_before = self.hot.shard_capacity
        hot_keys: list = []
        hot_idx: list[int] = []
        for i, key in enumerate(keys):
            c = int(clusters[i])
            self._cluster_of[key] = c
            self._members[c].add(key)
            self._cold_slot[key] = int(slots[i])
            if metadatas is not None and metadatas[i] is not None:
                self._meta[key] = metadatas[i]
            sh = _shard_of_key(key, self.n_shards)
            # fresh inserts go hot while the shard has room (ingest is
            # demand: a brand-new doc is as hot as it gets); during
            # snapshot replay everything lands cold and the recorded
            # hot set is promoted afterward
            if not restoring and free[sh] > 0:
                free[sh] -= 1
                hot_keys.append(key)
                hot_idx.append(i)
            else:
                self._cold_keys[c].add(key)
                self._cold_docs_shard[sh] += 1
                self._cold_total += 1
        if hot_keys:
            hv = vecs[hot_idx]
            if self.tiers.hot_dtype == "int8":
                hv = dequantize_int8(*quantize_int8(unit[hot_idx]))
            self.hot.add_batch_arrays(
                hot_keys, hv, [self._meta.get(k) for k in hot_keys]
            )
        else:
            self._publish_metrics()
        # inserts are gated on free slots, so the hot slab (sized to the
        # HBM budget) must never trigger the grow path
        assert cap_before == self.hot.shard_capacity

    def remove(self, key) -> None:
        self.hot._check_fence()
        c = self._cluster_of.pop(key, None)
        if c is None:
            return
        self._members[c].discard(key)
        slot = self._cold_slot.pop(key, None)
        if slot is not None:
            self._cold.erase([slot])
        self._meta.pop(key, None)
        if key in self.hot._slot_of:
            self.hot.remove(key)  # owes a publish, paid via the tiered override
        else:
            self._cold_keys[c].discard(key)
            self._cold_docs_shard[_shard_of_key(key, self.n_shards)] -= 1
            self._cold_total -= 1
            self._publish_metrics()

    # -- search ------------------------------------------------------------

    def attach_encoder(self, encoder) -> None:
        self._encoder = encoder
        self.hot.attach_encoder(encoder)

    def search_texts_batch(self, texts, k, filter_fns=None):
        """Text queries: when everything is hot the fused single-dispatch
        kernel runs untouched; with cold docs live, encode then run the
        tiered vector search (two dispatches — the fused program scans
        only the hot slab, so it cannot see demoted vectors)."""
        if self._cold_total == 0:
            return self.hot.search_texts_batch(texts, k, filter_fns)
        enc = self._encoder
        if enc is None:
            raise RuntimeError("search_texts_batch requires attach_encoder()")
        texts = ["" if t is None else str(t) for t in texts]
        return self.search_batch(np.asarray(enc.encode(texts)), k, filter_fns)

    def search_batch(self, queries, k: int, filter_fns=None):
        nq = len(queries)
        if nq == 0:
            return []
        if len(self._cluster_of) == 0:
            return [[] for _ in range(nq)]
        if self._cold_total == 0:
            # every doc hot-resident: delegate wholesale — bit-identical
            # to the flat index (records its own search metrics)
            out = self.hot.search_batch(queries, k, filter_fns)
            self._note_results(out, record=False)
            return out
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None, :]
        if self.metric == "cos":
            norms = np.linalg.norm(q, axis=1, keepdims=True)
            q = q / np.maximum(norms, 1e-12)
        fetch = 4 * k if filter_fns else k
        out, cold_fetch_s = self._tiered_search(q, k, fetch, filter_fns)
        self._record_tiered_search(nq, k, cold_fetch_s)
        self._note_results(out, record=True)
        return out

    def _tiered_search(self, q, k, fetch, filter_fns):
        """One tiered pass: async hot dispatch, host centroid probe,
        cold gather/rescore through the ring, host merge."""
        import time as _time

        from ..tracing import record_span

        nq = len(q)
        # 1. hot path dispatches FIRST and never waits on tiering work
        h0 = _time.monotonic()
        hot_disp = None
        if len(self.hot._slot_of):
            hot_disp = self.hot.search_dispatch(q, fetch)
        # 2. probe centroids host-side (tiny [q, C] matmul)
        p0 = _time.monotonic()
        probed = self._probe(q)
        # 3. gather cold candidates of every probed cluster
        need = sorted(
            {int(c) for row in probed for c in row if self._cold_keys[int(c)]}
        )
        cand_keys: list = []
        for c in need:
            cand_keys.extend(self._cold_keys[c])
        record_span(
            "tier_cold_probe",
            start_mono=p0,
            end_mono=_time.monotonic(),
            clusters=len(need),
        )
        cold_scores = None
        cold_fetch_s = 0.0
        if cand_keys:
            from contextlib import nullcontext

            from ..internals.chip_ledger import CHIP_LEDGER

            t0 = _time.perf_counter()
            with (
                CHIP_LEDGER.timed("index.tier")
                if CHIP_LEDGER.on()
                else nullcontext()
            ):
                g0 = _time.monotonic()
                cvecs = self._cold.fetch(
                    [self._cold_slot[key] for key in cand_keys]
                )
                g1 = _time.monotonic()
                record_span(
                    "tier_cold_gather",
                    start_mono=g0,
                    end_mono=g1,
                    candidates=len(cand_keys),
                )
                cold_scores = self._cold_score(q, cvecs)
                record_span(
                    "tier_cold_rescore",
                    start_mono=g1,
                    end_mono=_time.monotonic(),
                    candidates=len(cand_keys),
                )
            cold_fetch_s = _time.perf_counter() - t0
        # 4. resolve hot candidates (blocking half)
        hot_lists = [[] for _ in range(nq)]
        if hot_disp is not None:
            hs, hi = hot_disp
            hot_lists = self.hot.search_resolve(hs, hi, int(np.asarray(hs).shape[1]))
            # hot-tier span covers dispatch → resolve (the async half
            # overlaps the probe/gather work above by design)
            record_span(
                "tier_hot",
                start_mono=h0,
                end_mono=_time.monotonic(),
                hot_docs=len(self.hot._slot_of),
            )
        # 5. merge per query: hot wins dedup; filters apply to both tiers
        out = []
        for qi in range(nq):
            flt = filter_fns[qi] if filter_fns else None
            row: list[tuple[Any, float]] = []
            for key, score in hot_lists[qi]:
                if score <= _NEG / 2:
                    break
                if flt is not None and not flt(self._meta.get(key)):
                    continue
                row.append((key, float(score)))
            if cold_scores is not None:
                hot_res = self.hot._slot_of
                for j, key in enumerate(cand_keys):
                    if key in hot_res:
                        continue  # mid-promotion dup: the hot copy wins
                    if flt is not None and not flt(self._meta.get(key)):
                        continue
                    row.append((key, float(cold_scores[qi, j])))
            row.sort(key=lambda t: -t[1])
            out.append(row[:k])
        return out, cold_fetch_s

    def _probe(self, q: np.ndarray) -> np.ndarray:
        C = self._n_centroids
        if C == 0:
            return np.empty((len(q), 0), np.int64)
        cents = self._centroids[:C]
        if self.metric == "l2":
            s = 2.0 * (q @ cents.T) - np.sum(cents * cents, axis=1)[None, :]
        else:
            s = q @ cents.T
        p = min(self.tiers.n_probe, C)
        if p >= C:
            return np.tile(np.arange(C, dtype=np.int64), (len(q), 1))
        return np.argpartition(-s, p - 1, axis=1)[:, :p].astype(np.int64)

    def _cold_score(self, q: np.ndarray, cvecs: np.ndarray) -> np.ndarray:
        """Rescore fetched cold candidates: pad both axes to buckets so
        the jit compiles per size class, stage the candidate block
        through the ring (donated slot, non-blocking put)."""
        m = len(cvecs)
        mb = _k_bucket(m)
        qb = _k_bucket(len(q))
        docs = np.zeros((mb, self.dim), np.float32)
        docs[:m] = cvecs
        qpad = np.zeros((qb, self.dim), np.float32)
        qpad[: len(q)] = q
        handles = self._stage_cold(docs)
        scores = _cold_score_fn(self.metric)(qpad, handles[0])
        out = np.asarray(scores)[: len(q), :m]
        self._cold_ring.retire(handles)
        return out

    def _stage_cold(self, docs: np.ndarray):
        from ..engine.device_ring import DeviceRing

        if self._cold_ring is None:
            sharding = None
            if self.mesh is not None:
                from ..parallel.sharding import replicated

                sharding = replicated(self.mesh)
            self._cold_ring = DeviceRing(
                depth=2, name=f"{self.name}.cold", sharding=sharding
            )
        return self._cold_ring.stage(docs)

    def _record_tiered_search(self, nq: int, k: int, cold_fetch_s: float) -> None:
        from ..internals import flight_recorder
        from .index_metrics import INDEX_METRICS

        INDEX_METRICS.record_search(self.name, nq)
        if cold_fetch_s > 0.0:
            INDEX_METRICS.observe_cold_fetch(cold_fetch_s)
        flight_recorder.record(
            "index.search",
            index=self.name,
            queries=nq,
            k=k,
            shards=self.n_shards,
            merge_ms=0.0,
            cold_fetch_ms=round(cold_fetch_s * 1e3, 4),
        )

    def _note_results(self, results, record: bool) -> None:
        """Demand signal: bump per-cluster hit counters from result keys
        and (tiered path) the hot/cold result split for the hit ratio."""
        hot_n = 0
        cold_n = 0
        hot_res = self.hot._slot_of
        for row in results:
            for key, _ in row:
                c = self._cluster_of.get(key)
                if c is not None:
                    self._hits[c] += 1.0
                if key in hot_res:
                    hot_n += 1
                else:
                    cold_n += 1
        if record and (hot_n or cold_n):
            from .index_metrics import INDEX_METRICS

            INDEX_METRICS.record_tier_hits(self.name, hot_n, cold_n)
        self._searches_since_rebalance += 1
        if self._searches_since_rebalance >= self.tiers.promote_every:
            self.maybe_rebalance(force=True)

    # -- promotion / demotion ---------------------------------------------

    def maybe_rebalance(self, force: bool = False) -> bool:
        """Hit-driven tier rebalance on the epoch pipeline: promote the
        hottest cold clusters into HBM, demoting colder hot clusters
        when the slabs are full. Throttled to every ``promote_every``
        searches unless forced."""
        if not force and self._searches_since_rebalance < self.tiers.promote_every:
            return False
        self._searches_since_rebalance = 0
        C = self._n_centroids
        if C == 0:
            return False
        cold_cands = [c for c in range(C) if self._cold_keys[c] and self._hits[c] > 0]
        cold_cands.sort(key=lambda c: -self._hits[c])
        hot_cands = [
            c for c in range(C) if len(self._members[c]) > len(self._cold_keys[c])
        ]
        hot_cands.sort(key=lambda c: self._hits[c])  # coldest first
        free_total = sum(len(f) for f in self.hot._free_shard)
        changed = False
        for c in cold_cands:
            need = len(self._cold_keys[c])
            while free_total < need and hot_cands:
                d = hot_cands[0]
                if self._hits[d] >= self._hits[c] or d == c:
                    break
                hot_cands.pop(0)
                freed = self._demote_cluster(d)
                free_total += freed
                changed = changed or freed > 0
            if free_total <= 0:
                break
            moved = self._promote_cluster(c)
            free_total -= moved
            changed = changed or moved > 0
        self._hits *= self.tiers.decay
        if changed:
            self._record_rebalance()
        return changed

    def _promote_cluster(self, c: int) -> int:
        """Move cluster ``c``'s cold members into the hot slabs, in two
        chunks with a chaos site before each — a worker killed between
        chunks leaves keys hot-resident AND still listed cold; search
        dedups (hot wins) and the cold entry is cleared on retry, so
        nothing is lost or duplicated."""
        import time as _wall

        from ..freshness.plane import FRESHNESS
        from ..resilience import chaos

        free = [len(f) for f in self.hot._free_shard]
        keys: list = []
        for key in list(self._cold_keys[c]):
            sh = _shard_of_key(key, self.n_shards)
            if free[sh] > 0:
                free[sh] -= 1
                keys.append(key)
        if not keys:
            return 0
        moved = 0
        _t0 = _wall.perf_counter()
        touched: set[int] = set()
        half = max(1, len(keys) // 2)
        for chunk in (keys[:half], keys[half:]):
            if not chunk:
                continue
            chaos.inject("index.tier.promote")
            vecs = self._cold.fetch([self._cold_slot[key] for key in chunk])
            if self.tiers.hot_dtype == "int8":
                vecs = dequantize_int8(*quantize_int8(vecs))
            self.hot.add_batch_arrays(
                chunk, vecs, [self._meta.get(key) for key in chunk]
            )
            for key in chunk:
                sh = _shard_of_key(key, self.n_shards)
                touched.add(sh)
                self._cold_keys[c].discard(key)
                self._cold_docs_shard[sh] -= 1
                self._cold_total -= 1
            moved += len(chunk)
        self._promotions += 1
        # promotion-completion watermark: the promoted cluster is fully
        # hot-resident now; the wall spent is off-hot-path lag accrual
        FRESHNESS.accrue("promotion", _wall.perf_counter() - _t0)
        FRESHNESS.note_index_add(self, touched)
        self._tier_event("index.tier.promote", c, moved)
        return moved

    def _demote_cluster(self, c: int) -> int:
        """Evict cluster ``c``'s hot members; vectors already live in
        the cold store, so demotion moves no data. The cold listing is
        re-added BEFORE the hot remove: a crash between the two leaves
        a dedup-able duplicate, never a lost vector."""
        hot_keys = [key for key in self._members[c] if key in self.hot._slot_of]
        for key in hot_keys:
            self._cold_keys[c].add(key)
            self._cold_docs_shard[_shard_of_key(key, self.n_shards)] += 1
            self._cold_total += 1
            self.hot.remove(key)
        if hot_keys:
            self._demotions += 1
            self._tier_event("index.tier.demote", c, len(hot_keys))
        return len(hot_keys)

    def force_demote(self, clusters=None) -> int:
        """Test/bench hook: demote the given clusters (default: all)."""
        if clusters is None:
            clusters = range(self._n_centroids)
        moved = 0
        for c in clusters:
            moved += self._demote_cluster(int(c))
        if moved:
            self._record_rebalance()
        return moved

    def _tier_event(self, event: str, cluster: int, moved: int) -> None:
        from ..internals import flight_recorder
        from .index_metrics import INDEX_METRICS

        INDEX_METRICS.record_tier_events(
            self.name,
            promotions=1 if event.endswith("promote") else 0,
            demotions=1 if event.endswith("demote") else 0,
        )
        flight_recorder.record(
            event,
            index=self.name,
            cluster=int(cluster),
            moved=int(moved),
            hot_docs=self.hot_docs(),
            cold_docs=self.cold_docs(),
        )

    def _record_rebalance(self) -> None:
        """index.rebalance accounts BOTH tiers: a shard whose corpus is
        merely demoted reports its full doc count, not zero."""
        from ..internals import flight_recorder

        docs = [
            int(h) + int(cd)
            for h, cd in zip(self.hot._docs_shard, self._cold_docs_shard)
        ]
        flight_recorder.record(
            "index.rebalance",
            index=self.name,
            shards=self.n_shards,
            shard_capacity=self.hot.shard_capacity,
            docs=docs,
            docs_hot=[int(h) for h in self.hot._docs_shard],
            docs_cold=[int(cd) for cd in self._cold_docs_shard],
        )
        self._publish_metrics()

    # -- snapshots ---------------------------------------------------------

    def tier_state(self) -> dict:
        """Everything recovery needs to restore the EXACT tier layout:
        centroid table + counts, per-key cluster assignment, decayed hit
        counters, and the hot-resident key set."""
        n = self._n_centroids
        return {
            "version": 1,
            "config": self.tiers.as_dict(),
            "centroids": self._centroids[:n].copy(),
            "centroid_n": self._centroid_n[:n].copy(),
            "cluster_of": dict(self._cluster_of),
            "hot_keys": [k for k in self._cluster_of if k in self.hot._slot_of],
            "hits": self._hits.copy(),
        }

    def restore_tier_state(self, state: dict) -> None:
        """Install snapshot assignment BEFORE the engine re-adds rows:
        replayed adds land cold with their exact recorded cluster, then
        ``finish_tier_restore`` promotes the recorded hot set."""
        cents = np.asarray(state["centroids"], np.float32)
        n = min(len(cents), self.tiers.n_clusters)
        self._centroids[:n] = cents[:n]
        self._centroid_n[:n] = np.asarray(state["centroid_n"])[:n]
        self._n_centroids = n
        hits = np.asarray(state.get("hits", ()), np.float64)
        m = min(len(hits), len(self._hits))
        self._hits[:m] = hits[:m]
        self._restore_assign = dict(state["cluster_of"])
        self._restore_hot = list(state["hot_keys"])

    def finish_tier_restore(self) -> None:
        """Promote exactly the snapshotted hot set from the cold store
        and leave restore mode. Idempotent; safe without a snapshot."""
        hot_keys = self._restore_hot or []
        self._restore_assign = None
        self._restore_hot = None
        todo = [
            key
            for key in hot_keys
            if key in self._cluster_of and key not in self.hot._slot_of
        ]
        if todo:
            free = [len(f) for f in self.hot._free_shard]
            fit: list = []
            for key in todo:
                sh = _shard_of_key(key, self.n_shards)
                if free[sh] > 0:
                    free[sh] -= 1
                    fit.append(key)
            if fit:
                vecs = self._cold.fetch([self._cold_slot[key] for key in fit])
                if self.tiers.hot_dtype == "int8":
                    vecs = dequantize_int8(*quantize_int8(vecs))
                self.hot.add_batch_arrays(
                    fit, vecs, [self._meta.get(key) for key in fit]
                )
                for key in fit:
                    c = self._cluster_of[key]
                    if key in self._cold_keys[c]:
                        self._cold_keys[c].discard(key)
                        self._cold_docs_shard[
                            _shard_of_key(key, self.n_shards)
                        ] -= 1
                        self._cold_total -= 1
        self._publish_metrics()

    # -- elastic reshard protocol (elastic/controller.py drives) -----------

    def fence(self, generation: int | None = None) -> None:
        """Freeze this index as a dead generation (reads still serve the
        cutover dual-answer window; writes raise ``StaleGeneration``)."""
        self.hot.fence(generation)
        if generation is not None:
            self.generation = max(self.generation, int(generation))

    def spawn_like(self, mesh, reserved_space: int | None = None):
        """An EMPTY tiered index with this one's tier config on a target
        mesh. The hot slab re-derives from the same budget (explicit
        ``hot_rows`` carries over; budget-derived sizing re-splits over
        the new shard count)."""
        return TieredKnnIndex(
            self.dim,
            metric=self.metric,
            reserved_space=(
                int(reserved_space) if reserved_space else self.hot.capacity
            ),
            tiers=self.tiers,
            dtype=self.hot.dtype,
            mesh=mesh,
            name=self.name,
        )

    def reshard_export_chunks(self, chunk_rows: int):
        """Migration stream: one tier-state chunk (assignment, centroids,
        hits, hot set), then every doc's COLD payload as raw stored
        bytes in bounded chunks, then the hot-resident rows as the exact
        post-normalization (or dequantized-int8) values the hot slab
        holds. Raw transplant on both tiers is what keeps a resharded
        tiered index score-bit-identical to one that never moved."""
        yield {"kind": "tier_state", "state": self.tier_state()}
        step = max(1, int(chunk_rows))
        keys = list(self._cluster_of)
        for i in range(0, len(keys), step):
            batch = [k for k in keys[i : i + step] if k in self._cluster_of]
            if not batch:
                continue
            slots = [self._cold_slot[k] for k in batch]
            yield {
                "kind": "tier_rows",
                "keys": batch,
                "payload": self._cold.export_rows(slots),
                "metas": [self._meta.get(k) for k in batch],
            }
        self.hot._refresh_host()
        hot_keys = sorted(self.hot._slot_of.items(), key=lambda kv: kv[1])
        hot_keys = [k for k, _ in hot_keys]
        for i in range(0, len(hot_keys), step):
            batch = [
                k
                for k in hot_keys[i : i + step]
                if k in self._cluster_of and k in self.hot._slot_of
            ]
            if not batch:
                continue
            slots = np.asarray([self.hot._slot_of[k] for k in batch])
            yield {
                "kind": "tier_hot",
                "keys": batch,
                "vecs": self.hot._host[slots].copy(),
                "metas": [self._meta.get(k) for k in batch],
            }

    def reshard_import_chunk(self, chunk: dict) -> None:
        kind = chunk.get("kind")
        if kind == "tier_state":
            self.restore_tier_state(chunk["state"])
            return
        if kind == "tier_rows":
            assign = self._restore_assign or {}
            keys = chunk["keys"]
            for key in keys:
                if key in self._cluster_of:
                    self.remove(key)
            slots = self._cold.import_rows(chunk["payload"])
            metas = chunk["metas"]
            for i, key in enumerate(keys):
                c = int(assign.get(key, 0))
                self._cluster_of[key] = c
                self._members[c].add(key)
                self._cold_slot[key] = int(slots[i])
                self._cold_keys[c].add(key)
                # shard routing under the TARGET shard count
                self._cold_docs_shard[_shard_of_key(key, self.n_shards)] += 1
                self._cold_total += 1
                if metas[i] is not None:
                    self._meta[key] = metas[i]
            self._publish_metrics()
            return
        if kind == "tier_hot":
            # promote exactly the source's hot rows (byte-exact: the hot
            # slab normalizes on add, these are its POST-normalization
            # values, so the import bypasses normalization). The hot
            # slab grows per-shard on demand, so the full hot set always
            # transplants — hot/cold membership is preserved exactly.
            fit: list = []
            fit_idx: list[int] = []
            for i, key in enumerate(chunk["keys"]):
                if key not in self._cluster_of or key in self.hot._slot_of:
                    continue
                fit.append(key)
                fit_idx.append(i)
            if fit:
                self.hot.reshard_import_chunk(
                    {
                        "kind": "rows",
                        "keys": fit,
                        "vecs": np.asarray(chunk["vecs"])[fit_idx],
                        "metas": [self._meta.get(k) for k in fit],
                    }
                )
                for key in fit:
                    c = self._cluster_of[key]
                    if key in self._cold_keys[c]:
                        self._cold_keys[c].discard(key)
                        self._cold_docs_shard[
                            _shard_of_key(key, self.n_shards)
                        ] -= 1
                        self._cold_total -= 1
                self._publish_metrics()
            return
        raise ValueError(f"tiered index cannot import chunk kind {kind!r}")

    def reshard_finish(self) -> None:
        """Leave restore mode (hot promotion already happened via the
        ``tier_hot`` chunks, byte-exact) and commit the hot slab."""
        if self._restore_hot is not None:
            self._restore_hot = [
                k for k in self._restore_hot if k not in self.hot._slot_of
            ]
        self.finish_tier_restore()
        self.hot._sync()
