"""Donated double-buffer ring of engine-owned device buffers.

The overlapped epoch pipeline (``engine/pipeline.py``) stages epoch
N+1's wire payloads (packed token ids, flat image rows) onto the device
with a non-blocking ``jax.device_put`` while epoch N's compute is still
in flight. Left unmanaged, that doubles the HBM footprint of every
staged tensor each epoch; the ring bounds it: each logical payload
stream owns ``depth`` slots, and staging into a slot *donates* the
buffer the slot held two generations ago — the engine deletes its
handle (``jax.Array.delete()``) once the consuming epoch has retired,
so at most ``depth`` generations of a stream live in HBM.

Donation rules (documented in README "Performance"):

- a staged handle is **engine-owned**: consumers read through it for
  exactly one epoch, then the slot may be recycled at any time;
- after recycling, the old ``jax.Array`` is invalid — holding a
  reference across epochs is a use-after-donate bug, which
  :meth:`DeviceRing.stage` enforces by deleting the buffer;
- operator snapshots must never pickle an aliased/in-flight buffer:
  :func:`quiesce_all` blocks until every registered ring's staged puts
  are committed, and runs before state pickling in
  ``EngineGraph._snapshot_operators`` / ``ShardCluster``.

A put the device refuses raises: a ring that handed back the host array
would let every consumer run on whatever device jit picked next.
"""

from __future__ import annotations

import itertools
import threading
import weakref
from typing import Any

__all__ = ["DeviceRing", "quiesce_all", "active_rings", "staging_placement"]


def staging_placement(mesh_axes: dict | None) -> dict:
    """Declarative placement intent of ring-staged wire payloads for
    the deep verifier (analysis.deep, PWL019), resolved without
    constructing a ring or touching a device: with a run mesh the ring
    stages onto that mesh's data axis (the ``sharding=`` each epoch
    pipeline passes); without one, payloads land on the default device
    and any mesh-sharded consumer must reshard through host."""
    axes = dict(mesh_axes) if mesh_axes else None
    return {
        "kind": "device_ring",
        "mesh_axes": axes,
        "sharded": bool(axes and int(axes.get("data", 1)) > 1),
    }

_ring_seq = itertools.count()

# every live ring, so the snapshot path can quiesce staged transfers it
# has no direct handle to (model-layer rings inside encoders)
_registry: "weakref.WeakSet[DeviceRing]" = weakref.WeakSet()
_registry_lock = threading.Lock()


def active_rings() -> list["DeviceRing"]:
    with _registry_lock:
        return list(_registry)


def quiesce_all() -> None:
    """Block until no registered ring has an uncommitted device_put in
    flight. Called before operator-state pickling: a snapshot taken
    while a donated buffer is mid-transfer must not capture the alias."""
    for ring in active_rings():
        ring.sync()


def _block(arr) -> None:
    bur = getattr(arr, "block_until_ready", None)
    if bur is not None:
        bur()


def _delete(arr) -> None:
    d = getattr(arr, "delete", None)
    if d is not None:
        try:
            d()
        except Exception:
            pass  # already deleted / committed donation


class DeviceRing:
    """A ``depth``-slot ring of staged device buffers for one payload
    stream.

    ``stage(arrays)`` does a non-blocking ``jax.device_put`` of each
    array into the next slot and returns the device handles. When the
    ring wraps, the slot's previous generation is donated back: its
    buffers are deleted once :meth:`retire` has been called for that
    generation (or immediately if the consumer never registered — the
    conservative default keeps them until wrap + retire).
    """

    def __init__(self, depth: int = 2, name: str = "ring", sharding=None):
        self.depth = max(2, int(depth))
        self.name = name
        # mesh-aware staging: a jax.sharding.Sharding (e.g. a
        # NamedSharding over a mesh) applied to every staged put, so
        # donated slots land on the correct device(s) — replicated
        # query blocks land on every chip of a sharded index's mesh,
        # per-shard payloads on their owning chip — instead of
        # defaulting to device 0 and paying a GSPMD reshard later.
        self.sharding = sharding
        self._slots: list[list[Any] | None] = [None] * self.depth
        self._retired: list[bool] = [True] * self.depth
        self._next = 0
        self._in_flight: list[list[Any]] = []
        self._lock = threading.Lock()
        # serializes whole stage() calls: with >= 2 producers (the
        # collaborative ingest stage makes that real), producer B could
        # otherwise lap the ring back to the slot index producer A
        # grabbed but has not yet filled, donating A's buffers mid-put
        self._stage_lock = threading.Lock()
        self.staged = 0       # total stage() calls
        self.donated = 0      # buffers invalidated by slot reuse
        self.stage_stall_s = 0.0  # time stage() blocked on unretired slots
        self.bytes_staged = 0     # host->device bytes pushed through the ring
        self.high_water = 0       # max generations simultaneously in flight
        self._slot_bytes: list[int] = [0] * self.depth
        # ledger rows are per-instance (names repeat across streams);
        # the finalizer clears the row when the ring is collected
        self._ledger_owner = f"{name}@{next(_ring_seq)}"
        from ..internals.ledger import LEDGER

        weakref.finalize(self, LEDGER.drop, "ring", self._ledger_owner)
        with _registry_lock:
            _registry.add(self)

    def stage(
        self,
        arrays: list[Any] | tuple[Any, ...] | Any,
        shardings: list[Any] | None = None,
    ) -> list[Any]:
        """Non-blocking device_put of ``arrays`` into the next slot;
        returns device handles valid for one consuming epoch.
        ``shardings`` overrides the ring's default placement per array
        (None entries fall back to ``self.sharding``)."""
        single = not isinstance(arrays, (list, tuple))
        items = [arrays] if single else list(arrays)
        if shardings is None:
            per_item = [self.sharding] * len(items)
        else:
            per_item = [
                s if s is not None else self.sharding for s in shardings
            ]
        with self._stage_lock:
            with self._lock:
                idx = self._next
                self._next = (idx + 1) % self.depth
                prev = self._slots[idx]
                prev_retired = self._retired[idx]
            if prev is not None:
                if not prev_retired:
                    # consumer still reading the old generation: the put
                    # below would donate it out from under them — wait for
                    # the device to drain it first (backpressure, not UB).
                    # Stall time here means the host is outrunning the ring:
                    # raise the depth (PATHWAY_WIRE_RING_DEPTH for encoder
                    # wire uploads) so staging keeps pace with the kernel.
                    import time as _time

                    t0 = _time.perf_counter()
                    for a in prev:
                        _block(a)
                    self.stage_stall_s += _time.perf_counter() - t0
                for a in prev:
                    _delete(a)
                self.donated += len(prev)
                from ..internals import flight_recorder

                flight_recorder.record(
                    "ring.donate", ring=self.name, buffers=len(prev), total=self.donated
                )
            import jax

            from ..internals.chip_ledger import CHIP_LEDGER

            if CHIP_LEDGER.on():
                import time as _time

                c0 = _time.perf_counter()
                handles = [jax.device_put(a, s) for a, s in zip(items, per_item)]
                # put-issue wall only: staging stays non-blocking even
                # under accounting (the stall above is already a
                # stranded-time cause, not chip work)
                CHIP_LEDGER.book("ingest.stage", _time.perf_counter() - c0)
            else:
                handles = [jax.device_put(a, s) for a, s in zip(items, per_item)]
            nbytes = sum(int(getattr(a, "nbytes", 0) or 0) for a in items)
            with self._lock:
                self._slots[idx] = handles
                self._retired[idx] = False
                self._in_flight.append(handles)
                self.staged += 1
                self.bytes_staged += nbytes
                self.high_water = max(self.high_water, len(self._in_flight))
                self._slot_bytes[idx] = nbytes
                live = sum(self._slot_bytes)
                in_use = sum(
                    b
                    for b, retired in zip(self._slot_bytes, self._retired)
                    if not retired
                )
            from ..internals.ledger import LEDGER

            LEDGER.update("ring", self._ledger_owner, live, used_bytes=in_use)
            return handles

    def stats(self) -> dict:
        """Staging-depth telemetry for the host-path attribution."""
        with self._lock:
            return {
                "ring": self.name,
                "depth": self.depth,
                "staged": self.staged,
                "donated": self.donated,
                "bytes_staged": self.bytes_staged,
                "high_water": self.high_water,
                "stage_stall_s": self.stage_stall_s,
            }

    def retire(self, handles: list[Any]) -> None:
        """The consuming epoch delivered: the slot holding ``handles``
        may be donated on the next wrap without blocking."""
        def same(slot) -> bool:
            return (
                slot is handles
                or (
                    slot is not None
                    and len(slot) == len(handles)
                    and all(a is b for a, b in zip(slot, handles))
                )
            )

        with self._lock:
            for i, slot in enumerate(self._slots):
                if same(slot):
                    self._retired[i] = True
            self._in_flight = [hs for hs in self._in_flight if not same(hs)]

    def in_flight(self) -> int:
        with self._lock:
            return len(self._in_flight)

    def sync(self) -> None:
        """Block until every staged-but-unretired transfer is committed
        on device. After sync, a snapshot observes no aliased buffer."""
        with self._lock:
            pending = [a for hs in self._in_flight for a in hs]
        for a in pending:
            _block(a)

    def snapshot_view(self, handles: list[Any]) -> list[Any]:
        """Host-safe copies of staged handles for state pickling: the
        returned arrays are detached numpy copies, never the donated
        device buffers themselves."""
        import numpy as np

        out = []
        for a in handles:
            _block(a)
            out.append(np.asarray(a).copy())
        return out
