"""Tenant-packed device slabs: many small indexes, one compiled program.

A :class:`TenantPackedIndex` is a :class:`~..ops.knn.DeviceKnnIndex`
whose rows belong to many tenants at once. The device state is the
parent's ``[capacity, dim]`` matrix / validity / bias arrays plus one
int32 *tenant-routing column* aligned with the slab (4 bytes/row). All
of the parent's compiled programs — scatter, grow, flat and sharded
top-k, the fused pallas kernel — are reused untouched: 10k tiny
tenants cost one compile, not 10k.

Layout: each tenant owns contiguous *extents* of slab rows, granted
with per-tenant doubling (grant ``max(short, rows_so_far)`` rows, the
PR 9 per-shard-doubling trick applied per tenant) and carved from a
per-shard bump pointer so sibling rows stay adjacent. Keys are
namespaced ``(tenant, key)`` internally, so tenants can reuse each
other's key space. A tenant's HBM quota (``TenantQuotas.hbm_bytes``)
is enforced at extent-grant time, and every tenant's segment bytes are
booked under the ``index.tenant`` ledger account (owner
``"<index>/<tenant>"``; the ungranted remainder books under
``"<index>/__unassigned__"`` so the account reconciles *exactly*
against ``index.hot``).

Queries mask by tenant id inside the existing top-k dispatch: the
routing column turns into ``valid & (tenant_col == tid)`` (plus the
matching bias column), the masked pair is swapped into
``_dev_valid``/``_dev_bias`` for the duration of one parent
``search_batch``, and every dispatch path — pallas, sharded shard_map,
flat jit — reads the instance attributes, so one swap covers them all.
Masked-out rows score exactly like empty rows, which is what makes a
tenant's results bit-identical to a standalone per-tenant index over
the same corpus.

Cold tenants demote *wholesale* to a host-resident store on a
hit-decay schedule (EdgeRAG-style selective residency): every
``demote_every`` searches the per-tenant hit counters decay by
``decay``; a tenant falling below ``demote_below`` moves its rows to
host numpy, frees its extents for reuse, and serves subsequent queries
from an exact host scan. Two queries while cold promote the tenant
back into the slab.
"""

from __future__ import annotations

import threading
from typing import Any, Callable

import numpy as np

from ..ops.knn import DeviceKnnIndex
from .config import TenancyConfig, TenantQuotas, active_tenancy

#: smallest extent ever granted — keeps the 1-doc-per-tenant worst case
#: from fragmenting the slab into single-row segments
_MIN_EXTENT = 8

#: raw hits while cold that promote a tenant back into the slab
_PROMOTE_HITS = 2

_MASK_JIT: dict = {}


def _mask_fn() -> Callable:
    """Jitted tenant mask: one fused pass producing the masked validity
    and bias columns. Masked rows get the exact invalid-row bias
    (pallas NEG), preserving bit-identity with a standalone index."""
    if "fn" not in _MASK_JIT:
        import jax
        import jax.numpy as jnp

        from ..ops.pallas_knn import NEG as _PNEG

        @jax.jit
        def mask(valid, bias, tenant_col, tid):
            keep = valid & (tenant_col == tid)
            return keep, jnp.where(keep, bias, _PNEG)

        _MASK_JIT["fn"] = mask
    return _MASK_JIT["fn"]


class TenantOverBudget(RuntimeError):
    """A tenant's extent grant would exceed its ``hbm_bytes`` quota."""

    def __init__(self, tenant: str, need_bytes: int, budget_bytes: int):
        self.tenant = tenant
        self.need_bytes = int(need_bytes)
        self.budget_bytes = int(budget_bytes)
        super().__init__(
            f"tenant {tenant!r} needs {need_bytes} HBM bytes but its quota "
            f"is {budget_bytes}"
        )


class TenantPackedIndex(DeviceKnnIndex):
    """Many tenants' vectors packed into one device slab (see module
    docstring). Keys are namespaced ``(tenant, key)`` tuples; use the
    ``*_tenant`` methods or a :class:`TenantView`."""

    def __init__(
        self,
        dim: int,
        metric: str = "cos",
        reserved_space: int = 1024,
        mesh=None,
        name: str | None = None,
        config: TenancyConfig | None = None,
    ):
        super().__init__(
            dim,
            metric=metric,
            reserved_space=reserved_space,
            mesh=mesh,
            name=name,
        )
        self._config = config
        self._tenant_host = np.full((self.capacity,), -1, np.int32)
        self._dev_tenant = None
        self._tenant_dirty = True
        self._tid: dict[str, int] = {}
        self._tenant_free: dict[str, list[int]] = {}
        self._tenant_rows: dict[str, int] = {}
        self._segments: dict[str, list[list[int]]] = {}  # [start, size]
        self._free_extents: list[tuple[int, int]] = []  # demoted tenants' rows
        self._bump = [0] * self.n_shards  # next ungranted local row per shard
        self._hits: dict[str, float] = {}
        self._cold: dict[str, dict] = {}
        self._search_count = 0

    # -- config --

    def _cfg(self) -> TenancyConfig | None:
        return self._config if self._config is not None else active_tenancy()

    def _quota_for(self, tenant: str) -> TenantQuotas | None:
        cfg = self._cfg()
        return cfg.quota_for(tenant) if cfg is not None else None

    @staticmethod
    def _tenant_of_key(key) -> str:
        if not (isinstance(key, tuple) and len(key) == 2):
            raise TypeError(
                "TenantPackedIndex keys are namespaced (tenant, key) tuples; "
                "use add_tenant/add_tenant_batch or a TenantView"
            )
        return str(key[0])

    # -- segment allocation --

    def _alloc_slots(self, keys) -> list[int]:
        by_tenant: dict[str, int] = {}
        for k in keys:
            t = self._tenant_of_key(k)
            by_tenant[t] = by_tenant.get(t, 0) + 1
        for t, need in by_tenant.items():
            self._ensure_rows(t, need)
        out = []
        for k in keys:
            t = self._tenant_of_key(k)
            slot = self._tenant_free[t].pop()
            self._tenant_host[slot] = self._tid[t]
            self._docs_shard[slot // self.shard_capacity] += 1
            out.append(slot)
        self._tenant_dirty = True
        return out

    def _ensure_rows(self, tenant: str, need: int) -> None:
        """Grow ``tenant``'s free pool to at least ``need`` slots,
        granting a doubled extent (quota-clamped) when short."""
        if tenant not in self._tid:
            self._tid[tenant] = len(self._tid)
            self._tenant_free.setdefault(tenant, [])
            self._tenant_rows.setdefault(tenant, 0)
            self._segments.setdefault(tenant, [])
        short = need - len(self._tenant_free[tenant])
        if short <= 0:
            return
        rows = self._tenant_rows[tenant]
        grant = max(short, max(_MIN_EXTENT, rows))  # per-tenant doubling
        quota = self._quota_for(tenant)
        if quota is not None and quota.hbm_bytes is not None:
            from ..internals.ledger import hot_row_bytes

            max_rows = quota.hbm_bytes // hot_row_bytes(self.dim)
            if rows + short > max_rows:
                raise TenantOverBudget(
                    tenant,
                    (rows + short) * hot_row_bytes(self.dim),
                    quota.hbm_bytes,
                )
            grant = min(grant, max_rows - rows)
        granted = 0
        while granted < short or grant > 0:
            ext = self._carve(grant if grant > 0 else short - granted)
            if ext is None:
                self._grow()
                continue
            start, size = ext
            self._segments[tenant].append([start, size])
            self._tenant_rows[tenant] += size
            # LIFO with low slots first, matching the parent's order;
            # re-fetched through self because _remap_grow rebuilds the
            # per-tenant lists when _carve had to grow the slab
            self._tenant_free[tenant].extend(
                range(start + size - 1, start - 1, -1)
            )
            granted += size
            grant -= size
            from ..internals import flight_recorder

            flight_recorder.record(
                "tenant.grant",
                index=self.name,
                tenant=tenant,
                rows=size,
                start=start,
                total_rows=self._tenant_rows[tenant],
            )

    def _carve(self, want: int) -> tuple[int, int] | None:
        """Take up to ``want`` contiguous rows: freed extents (demoted
        tenants) first, then a shard bump tail; None = slab full."""
        for i, (start, size) in enumerate(self._free_extents):
            if size >= want:
                rest = (start + want, size - want)
                if rest[1]:
                    self._free_extents[i] = rest
                else:
                    del self._free_extents[i]
                return (start, want)
        if self._free_extents:
            i = max(
                range(len(self._free_extents)),
                key=lambda j: self._free_extents[j][1],
            )
            return self._free_extents.pop(i)
        s = max(range(self.n_shards), key=lambda j: -self._bump[j])
        room = self.shard_capacity - self._bump[s]
        if room <= 0:
            return None
        take = min(want, room)
        start = s * self.shard_capacity + self._bump[s]
        self._bump[s] += take
        return (start, take)

    # -- growth (parent doubling + tenant column / extent remap) --

    def _grow(self) -> None:
        super()._grow()
        if self.n_shards == 1 and len(self._tenant_host) < self.capacity:
            pad = self.capacity - len(self._tenant_host)
            self._tenant_host = np.concatenate(
                [self._tenant_host, np.full((pad,), -1, np.int32)]
            )
        self._tenant_dirty = True

    def _remap_grow(self, old_shard: int) -> None:
        super()._remap_grow(old_shard)
        S, new_shard = self.n_shards, self.shard_capacity
        col = self._tenant_host.reshape(S, old_shard)
        self._tenant_host = np.concatenate(
            [col, np.full((S, old_shard), -1, np.int32)], axis=1
        ).reshape(self.capacity)

        def remap(g: int) -> int:
            return (g // old_shard) * new_shard + (g % old_shard)

        # extents never span a shard boundary, so a remapped extent
        # stays contiguous (same local offset, doubled shard base)
        self._tenant_free = {
            t: [remap(g) for g in fr] for t, fr in self._tenant_free.items()
        }
        self._segments = {
            t: [[remap(s0), sz] for s0, sz in segs]
            for t, segs in self._segments.items()
        }
        self._free_extents = [
            (remap(s0), sz) for s0, sz in self._free_extents
        ]

    # -- updates --

    def add_tenant(self, tenant: str, key, vector, metadata=None) -> None:
        vec = np.asarray(vector, np.float32).reshape(-1)
        self.add_tenant_batch(tenant, [key], vec[None, :], [metadata])

    def add_tenant_batch(self, tenant: str, keys, vectors, metadatas=None) -> None:
        tenant = str(tenant)
        if tenant in self._cold:
            self._promote(tenant)  # re-pack before the new rows land
        ns = [(tenant, k) for k in keys]
        self.add_batch_arrays(ns, vectors, metadatas)

    def add_batch_device(self, keys, dev_vectors, metadatas=None) -> None:
        # the parent's device path hands slots back through the shard
        # free lists on its growth fallback, which a packed slab does
        # not use — route through the host path instead
        n = len(keys)
        if n == 0:
            return
        self.add_batch_arrays(keys, np.asarray(dev_vectors)[:n], metadatas)

    def remove_tenant(self, tenant: str, key) -> None:
        self.remove((str(tenant), key))

    def remove(self, key) -> None:
        self._check_fence()
        shard = self._drop(key)
        if shard is None:
            self._cold_remove(key)
        else:
            self._owe_publish(shard)

    def _free_slot(self, key, slot: int) -> None:
        # the slot stays reserved to its tenant's segment
        self._tenant_free[self._tenant_of_key(key)].append(slot)

    def _cold_remove(self, key) -> None:
        if not (isinstance(key, tuple) and len(key) == 2):
            return
        store = self._cold.get(str(key[0]))
        if store is None or key[1] not in store["index_of"]:
            return
        pos = store["index_of"].pop(key[1])
        store["keys"].pop(pos)
        store["vecs"] = np.delete(store["vecs"], pos, axis=0)
        store["meta"].pop(key[1], None)
        store["index_of"] = {k: i for i, k in enumerate(store["keys"])}
        self._publish_metrics()

    # -- search --

    def search_tenant_batch(
        self,
        tenant: str,
        queries: np.ndarray,
        k: int,
        filter_fns: list[Callable | None] | None = None,
    ) -> list[list[tuple[Any, float]]]:
        """Per-tenant top-k: the parent's search over the slab with the
        tenant mask swapped into the validity/bias columns."""
        from .metrics import TENANCY_METRICS

        tenant = str(tenant)
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None, :]
        TENANCY_METRICS.record_search(tenant, len(q))
        from ..freshness.plane import FRESHNESS

        # per-tenant staleness attribution (the base _record_search
        # already records the untagged answer bound)
        FRESHNESS.observe_answer(self, tenant=tenant)
        self._note_hit(tenant)
        self._maybe_sweep(exclude=tenant)
        if tenant in self._cold:
            return self._cold_search(tenant, q, k, filter_fns)
        if len(q) == 0 or self.tenant_docs(tenant) == 0:
            return [[] for _ in range(len(q))]
        self._sync()  # flush pending BEFORE masking: the parent's
        # search-time _sync must see nothing to scatter into the
        # masked columns
        self._sync_tenant_column()
        keep, masked_bias = _mask_fn()(
            self._dev_valid,
            self._dev_bias,
            self._dev_tenant,
            np.int32(self._tid[tenant]),
        )
        orig = (self._dev_valid, self._dev_bias)
        self._dev_valid, self._dev_bias = keep, masked_bias
        try:
            rows = super().search_batch(q, k, filter_fns)
        finally:
            self._dev_valid, self._dev_bias = orig
        return [[(key[1], score) for key, score in row] for row in rows]

    def _sync_tenant_column(self) -> None:
        if (
            self._dev_tenant is not None
            and not self._tenant_dirty
            and int(self._dev_tenant.shape[0]) == self.capacity
        ):
            return
        import jax

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._dev_tenant = jax.device_put(
                self._tenant_host, NamedSharding(self.mesh, P("data"))
            )
        else:
            self._dev_tenant = jax.device_put(self._tenant_host)
        self._tenant_dirty = False

    # -- hit decay / cold demotion --

    def _note_hit(self, tenant: str) -> None:
        self._hits[tenant] = self._hits.get(tenant, 0.0) + 1.0
        store = self._cold.get(tenant)
        if store is not None:
            store["hits"] += 1
            if store["hits"] >= _PROMOTE_HITS:
                self._promote(tenant)

    def _maybe_sweep(self, exclude: str | None = None) -> None:
        cfg = self._cfg()
        if cfg is None or cfg.demote_every <= 0:
            return
        self._search_count += 1
        if self._search_count % cfg.demote_every:
            return
        for t in list(self._tid):
            if t == exclude or t in self._cold:
                continue
            self._hits[t] = self._hits.get(t, 0.0) * cfg.decay
            if self._hits[t] < cfg.demote_below and self.tenant_docs(t) > 0:
                self._demote(t)

    def _demote(self, tenant: str) -> None:
        """Move every one of ``tenant``'s rows to a host store and free
        its extents for other tenants to reuse."""
        self._refresh_host()
        keys: list[Any] = []
        vecs: list[np.ndarray] = []
        meta: dict[Any, Any] = {}
        for start, size in self._segments.get(tenant, ()):
            for slot in range(start, start + size):
                nk = self._keys[slot]
                if nk is not None:
                    keys.append(nk[1])
                    vecs.append(self._host[slot].copy())
                    if nk in self._meta:
                        meta[nk[1]] = self._meta.pop(nk)
                    self._slot_of.pop(nk, None)
                    self._keys[slot] = None
                    self._valid_host[slot] = False
                    self._docs_shard[slot // self.shard_capacity] -= 1
                    if not self._full:
                        self._pending[slot] = None
                self._tenant_host[slot] = -1
        self._free_extents.extend(
            (start, size) for start, size in self._segments.get(tenant, ())
        )
        self._segments[tenant] = []
        self._tenant_rows[tenant] = 0
        self._tenant_free[tenant] = []
        self._cold[tenant] = {
            "keys": keys,
            "vecs": (
                np.asarray(vecs, np.float32)
                if vecs
                else np.zeros((0, self.dim), np.float32)
            ),
            "meta": meta,
            "index_of": {k: i for i, k in enumerate(keys)},
            "hits": 0,
        }
        self._tenant_dirty = True
        from ..internals import flight_recorder

        flight_recorder.record(
            "tenant.demote", index=self.name, tenant=tenant, docs=len(keys)
        )
        self._publish_metrics()

    def _promote(self, tenant: str) -> None:
        store = self._cold.pop(tenant)
        self._hits[tenant] = 1.0
        if store["keys"]:
            metas = [store["meta"].get(k) for k in store["keys"]]
            # cos vectors were stored normalized; re-normalizing on the
            # way back in is a no-op up to float rounding
            self.add_tenant_batch(tenant, store["keys"], store["vecs"], metas)
        from ..internals import flight_recorder

        flight_recorder.record(
            "tenant.promote",
            index=self.name,
            tenant=tenant,
            docs=len(store["keys"]),
        )
        self._publish_metrics()

    def _cold_search(self, tenant, q, k, filter_fns):
        """Exact host scan over a demoted tenant's store — same score
        formulas as the device paths."""
        store = self._cold[tenant]
        vecs, keys = store["vecs"], store["keys"]
        if not len(keys) or not len(q):
            return [[] for _ in range(len(q))]
        if self.metric == "cos":
            norms = np.linalg.norm(q, axis=1, keepdims=True)
            q = q / np.maximum(norms, 1e-12)
        if self.metric == "l2":
            sq = np.sum(vecs * vecs, axis=1)
            qq = np.sum(q * q, axis=1, keepdims=True)
            scores = 2.0 * (q @ vecs.T) - sq[None, :] - qq
        else:
            scores = q @ vecs.T
        out = []
        for i in range(len(q)):
            order = np.argsort(-scores[i], kind="stable")
            flt = filter_fns[i] if filter_fns is not None else None
            row = []
            for j in order:
                key = keys[int(j)]
                if flt is not None:
                    from ..ops.knn import _apply_filter

                    if not _apply_filter(flt, store["meta"].get(key)):
                        continue
                row.append((key, float(scores[i][int(j)])))
                if len(row) >= k:
                    break
            out.append(row)
        return out

    # -- elastic reshard protocol (elastic/controller.py drives) --

    def spawn_like(self, mesh, reserved_space: int | None = None):
        """An EMPTY packed slab with this one's tenancy config on a
        target mesh; extent grants replay as tenants re-land, growing
        shard-by-shard through the compiled per-slab-shape programs."""
        return TenantPackedIndex(
            self.dim,
            metric=self.metric,
            reserved_space=int(reserved_space) if reserved_space else 64,
            mesh=mesh,
            name=self.name,
            config=self._config,
        )

    def reshard_export_chunks(self, chunk_rows: int):
        """Migration stream, tenant by tenant in registration order:
        hot tenants' live rows from the slab (slot order, already
        normalized — the import bypasses re-normalization), cold
        tenants' host-store rows followed by a ``tenant_cold`` marker
        so the target demotes them back to exactly a host store."""
        step = max(1, int(chunk_rows))
        self._refresh_host()
        for tenant in list(self._tid):
            if tenant in self._cold:
                store = self._cold[tenant]
                keys = list(store["keys"])
                for i in range(0, len(keys), step):
                    batch = keys[i : i + step]
                    idx = [
                        store["index_of"][k]
                        for k in batch
                        if k in store["index_of"]
                    ]
                    batch = [k for k in batch if k in store["index_of"]]
                    if not batch:
                        continue
                    yield {
                        "kind": "tenant_rows",
                        "tenant": tenant,
                        "keys": batch,
                        "vecs": store["vecs"][idx].copy(),
                        "metas": [store["meta"].get(k) for k in batch],
                    }
                yield {"kind": "tenant_cold", "tenant": tenant, "keys": []}
                continue
            slots = sorted(
                slot
                for start, size in self._segments.get(tenant, ())
                for slot in range(start, start + size)
                if self._keys[slot] is not None
            )
            for i in range(0, len(slots), step):
                batch = [
                    s for s in slots[i : i + step] if self._keys[s] is not None
                ]
                if not batch:
                    continue
                ns_keys = [self._keys[s] for s in batch]
                yield {
                    "kind": "tenant_rows",
                    "tenant": tenant,
                    "keys": [nk[1] for nk in ns_keys],
                    "vecs": self._host[np.asarray(batch)].copy(),
                    "metas": [self._meta.get(nk) for nk in ns_keys],
                }

    def reshard_import_chunk(self, chunk: dict) -> None:
        kind = chunk.get("kind")
        tenant = str(chunk.get("tenant", ""))
        if kind == "tenant_rows":
            self._import_raw = True
            try:
                self.add_tenant_batch(
                    tenant, chunk["keys"], chunk["vecs"], chunk["metas"]
                )
            finally:
                self._import_raw = False
            return
        if kind == "tenant_cold":
            self._ensure_rows(tenant, 0)  # register the tenant id
            if tenant not in self._cold:
                self._demote(tenant)
            return
        raise ValueError(f"packed index cannot import chunk kind {kind!r}")

    # -- introspection / accounting --

    def view(self, tenant: str) -> "TenantView":
        """One tenant's duck-typed index API over this slab."""
        return TenantView(self, tenant)

    def tenants(self) -> list[str]:
        return list(self._tid)

    def tenant_docs(self, tenant: str) -> int:
        tenant = str(tenant)
        if tenant in self._cold:
            return len(self._cold[tenant]["keys"])
        return self._tenant_rows.get(tenant, 0) - len(
            self._tenant_free.get(tenant, ())
        )

    def tenant_is_cold(self, tenant: str) -> bool:
        return str(tenant) in self._cold

    def _publish_metrics(self) -> None:
        with self._publish_lock:  # a reader may be paying what removes owe
            super()._publish_metrics()
            self._publish_tenants()

    def _publish_tenants(self) -> None:
        """Book every tenant's segment bytes under the ``index.tenant``
        ledger account (plus the ungranted remainder under
        ``__unassigned__``, so the account sums exactly to
        ``index.hot``) and feed the per-tenant registry."""
        from ..internals.ledger import LEDGER, hot_row_bytes
        from .metrics import TENANCY_METRICS

        row_b = hot_row_bytes(self.dim)
        alloc = sum(
            int(getattr(a, "nbytes", 0) or 0)
            for a in (self._dev_matrix, self._dev_valid, self._dev_bias)
        )
        total_seg = 0
        for t in self._tid:
            rows = self._tenant_rows.get(t, 0)
            docs = rows - len(self._tenant_free.get(t, ()))
            owner = f"{self.name}/{t}"
            if rows and alloc:
                LEDGER.update(
                    "index.tenant", owner, rows * row_b, used_bytes=docs * row_b
                )
            else:
                LEDGER.drop("index.tenant", owner)
            total_seg += rows
            TENANCY_METRICS.set_index(
                t,
                docs=self.tenant_docs(t),
                hbm_bytes=rows * row_b if alloc else 0,
                cold=t in self._cold,
            )
        spare = f"{self.name}/__unassigned__"
        if alloc and self.capacity > total_seg:
            LEDGER.update(
                "index.tenant",
                spare,
                (self.capacity - total_seg) * row_b,
                used_bytes=0,
            )
        else:
            LEDGER.drop("index.tenant", spare)


class TenantView:
    """One tenant's duck-typed index API over a shared packed slab —
    what ``stdlib`` hands the engine when ``tenant=`` is set. Strips
    the ``(tenant, key)`` namespacing both ways."""

    def __init__(self, packed: TenantPackedIndex, tenant: str):
        self.packed = packed
        self.tenant = str(tenant)

    @property
    def dim(self) -> int:
        return self.packed.dim

    @property
    def metric(self) -> str:
        return self.packed.metric

    def __len__(self) -> int:
        return self.packed.tenant_docs(self.tenant)

    def add(self, key, vector, metadata=None) -> None:
        self.packed.add_tenant(self.tenant, key, vector, metadata)

    def add_batch(self, items: list[tuple]) -> None:
        if not items:
            return
        keys = [k for k, _, _ in items]
        vectors = np.asarray(
            [np.asarray(p, np.float32).reshape(-1) for _, p, _ in items]
        )
        metadatas = [m for _, _, m in items]
        self.packed.add_tenant_batch(self.tenant, keys, vectors, metadatas)

    def add_batch_arrays(self, keys, vectors, metadatas=None) -> None:
        self.packed.add_tenant_batch(self.tenant, keys, vectors, metadatas)

    def remove(self, key) -> None:
        self.packed.remove_tenant(self.tenant, key)

    def search_batch(self, queries, k, filter_fns=None):
        return self.packed.search_tenant_batch(
            self.tenant, queries, k, filter_fns
        )

    def search_one(self, query, k: int, filter_fn: Callable | None = None):
        return self.search_batch(
            np.asarray(query, np.float32)[None, :],
            k,
            [filter_fn] if filter_fn is not None else None,
        )[0]


# ---------------------------------------------------------------------------
# process-wide slab registry: every (dim, metric, mesh) combination
# shares ONE packed slab, so 10k tenants with the same geometry share
# one compile and one device allocation

_SLAB_LOCK = threading.Lock()
_SLABS: dict[tuple, TenantPackedIndex] = {}


def shared_slab(
    dim: int,
    metric: str = "cos",
    reserved_space: int = 1024,
    mesh=None,
    config: TenancyConfig | None = None,
) -> TenantPackedIndex:
    key = (int(dim), str(metric), id(mesh) if mesh is not None else None)
    with _SLAB_LOCK:
        slab = _SLABS.get(key)
        if slab is None:
            slab = TenantPackedIndex(
                dim,
                metric=metric,
                reserved_space=reserved_space,
                mesh=mesh,
                name=f"tenant-slab-{dim}-{metric}",
                config=config,
            )
            _SLABS[key] = slab
        return slab


def reset_slabs() -> None:
    """Drop the slab registry (tests)."""
    with _SLAB_LOCK:
        _SLABS.clear()
