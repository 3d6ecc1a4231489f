"""ctypes bindings for the C++ native runtime (native/pathway_native.cc).

The native library is the host-side state/persistence engine — the
TPU-native counterpart of the reference's Rust engine state layer
(/root/reference/src/engine/dataflow.rs arrangements,
/root/reference/src/persistence/). Built on demand with g++ into the
git-ignored pathway_tpu/_native/, under a name that carries a hash of
the source and the compiler flags — so the library that loads is always
the one built from the pathway_native.cc that is on disk, whatever
other artefacts (and mtimes) a copied tree brings along. Library users
degrade to pure-Python fallbacks if the toolchain is missing (`NATIVE`
is None then); ``chip_smoke.py`` and ``bench.py`` refuse to run so.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import pickle
import subprocess
import sys
import threading
from typing import Any, Iterator

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_HERE), "native", "pathway_native.cc")
_OUT_DIR = os.path.join(_HERE, "_native")
_CXX = ("g++", "-O2", "-std=c++17", "-shared", "-fPIC")

_build_lock = threading.Lock()


def _lib_path() -> str:
    """Where the library built from the current source and flags lives."""
    digest = hashlib.sha256(" ".join(_CXX).encode())
    with open(_SRC, "rb") as f:
        digest.update(f.read())
    return os.path.join(_OUT_DIR, f"libpathway_native-{digest.hexdigest()[:16]}.so")


def _build() -> str | None:
    if not os.path.exists(_SRC):
        return None
    with _build_lock:
        try:
            lib_path = _lib_path()
            if os.path.exists(lib_path):
                return lib_path
            os.makedirs(_OUT_DIR, exist_ok=True)
            # pid-unique temp + atomic replace: concurrent processes (e.g.
            # pytest-xdist on a fresh checkout) each build their own copy
            # and the last replace wins with a complete .so
            tmp = f"{lib_path}.{os.getpid()}.tmp"
            subprocess.run(
                [*_CXX, "-o", tmp, _SRC], check=True, capture_output=True, timeout=120
            )
            os.replace(tmp, lib_path)
            # artefacts of other sources or flags are never loaded again
            for stale in glob.glob(os.path.join(_OUT_DIR, "libpathway_native*.so")):
                if stale != lib_path:
                    with contextlib.suppress(OSError):  # a racing process won
                        os.unlink(stale)
            return lib_path
        except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
            # includes read-only installs (makedirs/replace PermissionError):
            # import must survive and fall back to the python paths
            print(f"pathway_tpu: native build failed ({e}); using python fallbacks", file=sys.stderr)
            return None


def _load() -> ctypes.CDLL | None:
    if os.environ.get("PATHWAY_DISABLE_NATIVE"):
        return None
    path = _build()  # no-op when this source's artefact is already there
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    sigs = {
        "pn_store_new": ([], ctypes.c_void_p),
        "pn_store_free": ([ctypes.c_void_p], None),
        "pn_store_len": ([ctypes.c_void_p], ctypes.c_uint64),
        "pn_store_upsert": ([ctypes.c_void_p, ctypes.c_uint64, u8p, ctypes.c_uint64], ctypes.c_int32),
        "pn_store_remove": ([ctypes.c_void_p, ctypes.c_uint64], ctypes.c_int32),
        "pn_store_get": ([ctypes.c_void_p, ctypes.c_uint64, ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64)], ctypes.c_int32),
        "pn_store_contains": ([ctypes.c_void_p, ctypes.c_uint64], ctypes.c_int32),
        "pn_store_clear": ([ctypes.c_void_p], None),
        "pn_store_scratch": ([ctypes.c_void_p, ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64)], None),
        "pn_store_iter_new": ([ctypes.c_void_p], ctypes.c_void_p),
        "pn_store_iter_next": ([ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64)], ctypes.c_int32),
        "pn_store_iter_free": ([ctypes.c_void_p], None),
        "pn_consolidate": ([u8p, ctypes.c_uint64], ctypes.c_void_p),
        "pn_buf_read": ([ctypes.c_void_p, ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64)], None),
        "pn_buf_free": ([ctypes.c_void_p], None),
        "pn_log_open_write": ([ctypes.c_char_p, ctypes.c_int32], ctypes.c_void_p),
        "pn_log_append": ([ctypes.c_void_p, ctypes.c_uint8, ctypes.c_uint64, ctypes.c_uint64, u8p, ctypes.c_uint64], ctypes.c_int32),
        "pn_log_flush": ([ctypes.c_void_p], ctypes.c_int32),
        "pn_log_close_write": ([ctypes.c_void_p], None),
        "pn_log_open_read": ([ctypes.c_char_p], ctypes.c_void_p),
        "pn_log_next": ([ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64)], ctypes.c_int32),
        "pn_log_close_read": ([ctypes.c_void_p], None),
        "pn_store_snapshot": ([ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint8, ctypes.c_uint64], ctypes.c_int64),
        "pn_store_load": ([ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint8], ctypes.c_int64),
        "pn_hash64_batch": ([ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64)], None),
        "pn_shard_batch": ([ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint32)], None),
        "pn_blake2b8_batch": (
            [u8p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64, u8p,
             ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint64)],
            None,
        ),
        "pn_tok_new": ([ctypes.c_char_p, ctypes.c_uint32, ctypes.c_int32, ctypes.c_int32], ctypes.c_void_p),
        "pn_tok_free": ([ctypes.c_void_p], None),
        "pn_tok_info": ([ctypes.c_void_p] + [ctypes.POINTER(ctypes.c_int32)] * 5, None),
        "pn_tok_encode_batch": (
            [ctypes.c_void_p, u8p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
             ctypes.c_int32, ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)],
            None,
        ),
        "pn_tok_encode_shard": (
            [ctypes.c_void_p, u8p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
             ctypes.c_uint64, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
             ctypes.POINTER(ctypes.c_int32)],
            None,
        ),
        "pn_version": ([], ctypes.c_char_p),
    }
    try:
        for name, (argtypes, restype) in sigs.items():
            fn = getattr(lib, name)
            fn.argtypes = argtypes
            fn.restype = restype
    except AttributeError as e:
        # stale/foreign .so missing a symbol: fall back to python paths
        print(f"pathway_tpu: native lib missing symbol ({e}); using python fallbacks", file=sys.stderr)
        return None
    return lib


NATIVE: ctypes.CDLL | None = _load()


def is_available() -> bool:
    return NATIVE is not None


_u8p = ctypes.POINTER(ctypes.c_uint8)


def _as_u8p(b: bytes):
    return ctypes.cast(ctypes.c_char_p(b), _u8p)


class NativeStore:
    """dict-like uint64 -> python-object store backed by the C++ blob
    store; values are pickled. Snapshottable to a SnapshotLog without
    per-row Python (pn_store_snapshot)."""

    __slots__ = ("_h",)

    def __init__(self):
        self._h = NATIVE.pn_store_new()

    def __del__(self):
        if NATIVE is not None and getattr(self, "_h", None):
            NATIVE.pn_store_free(self._h)
            self._h = None

    def __len__(self) -> int:
        return NATIVE.pn_store_len(self._h)

    def __contains__(self, key: int) -> bool:
        return bool(NATIVE.pn_store_contains(self._h, ctypes.c_uint64(int(key) & 0xFFFFFFFFFFFFFFFF)))

    def __setitem__(self, key: int, value: Any) -> None:
        blob = pickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)
        NATIVE.pn_store_upsert(self._h, ctypes.c_uint64(int(key) & 0xFFFFFFFFFFFFFFFF), _as_u8p(blob), len(blob))

    def __getitem__(self, key: int) -> Any:
        v = self.get(key, _MISSING)
        if v is _MISSING:
            raise KeyError(key)
        return v

    def get(self, key: int, default: Any = None) -> Any:
        ptr = _u8p()
        length = ctypes.c_uint64()
        ok = NATIVE.pn_store_get(self._h, ctypes.c_uint64(int(key) & 0xFFFFFFFFFFFFFFFF), ctypes.byref(ptr), ctypes.byref(length))
        if not ok:
            return default
        return pickle.loads(ctypes.string_at(ptr, length.value))

    def pop(self, key: int, default: Any = None) -> Any:
        ok = NATIVE.pn_store_remove(self._h, ctypes.c_uint64(int(key) & 0xFFFFFFFFFFFFFFFF))
        if not ok:
            return default
        ptr = _u8p()
        length = ctypes.c_uint64()
        NATIVE.pn_store_scratch(self._h, ctypes.byref(ptr), ctypes.byref(length))
        return pickle.loads(ctypes.string_at(ptr, length.value))

    def clear(self) -> None:
        NATIVE.pn_store_clear(self._h)

    def items(self) -> Iterator[tuple[int, Any]]:
        it = NATIVE.pn_store_iter_new(self._h)
        try:
            key = ctypes.c_uint64()
            ptr = _u8p()
            length = ctypes.c_uint64()
            while NATIVE.pn_store_iter_next(it, ctypes.byref(key), ctypes.byref(ptr), ctypes.byref(length)):
                yield key.value, pickle.loads(ctypes.string_at(ptr, length.value))
        finally:
            NATIVE.pn_store_iter_free(it)

    def keys(self) -> Iterator[int]:
        for k, _ in self.items():
            yield k

    def __iter__(self) -> Iterator[int]:
        return self.keys()

    def snapshot_to(self, log: "SnapshotLogWriter", kind: int, time: int) -> int:
        n = NATIVE.pn_store_snapshot(self._h, log._h, kind, ctypes.c_uint64(time))
        if n < 0:
            raise OSError("native snapshot write failed")
        return n

    def load_from(self, log: "SnapshotLogReader", kind: int) -> int:
        return NATIVE.pn_store_load(self._h, log._h, kind)


class _Missing:
    pass


_MISSING = _Missing()


class SnapshotLogWriter:
    """CRC-checked append-only log (native). Record: (kind, time, key, blob)."""

    def __init__(self, path: str, append: bool = True):
        os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
        self._h = NATIVE.pn_log_open_write(path.encode(), 1 if append else 0)
        if not self._h:
            raise OSError(f"cannot open snapshot log for write: {path}")

    def append(self, kind: int, time: int, key: int, blob: bytes) -> None:
        ok = NATIVE.pn_log_append(
            self._h, kind, ctypes.c_uint64(time), ctypes.c_uint64(int(key) & 0xFFFFFFFFFFFFFFFF), _as_u8p(blob), len(blob)
        )
        if not ok:
            raise OSError("snapshot log append failed")

    def append_obj(self, kind: int, time: int, key: int, obj: Any) -> None:
        self.append(kind, time, key, pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL))

    def flush(self) -> None:
        NATIVE.pn_log_flush(self._h)

    def close(self) -> None:
        if getattr(self, "_h", None):
            NATIVE.pn_log_close_write(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class SnapshotLogReader:
    """Reads records until EOF or the first torn/corrupt record."""

    def __init__(self, path: str):
        self._h = NATIVE.pn_log_open_read(path.encode())
        if not self._h:
            raise FileNotFoundError(path)

    def __iter__(self) -> Iterator[tuple[int, int, int, bytes]]:
        kind = ctypes.c_uint8()
        time = ctypes.c_uint64()
        key = ctypes.c_uint64()
        ptr = _u8p()
        length = ctypes.c_uint64()
        while NATIVE.pn_log_next(self._h, ctypes.byref(kind), ctypes.byref(time), ctypes.byref(key), ctypes.byref(ptr), ctypes.byref(length)):
            yield kind.value, time.value, key.value, ctypes.string_at(ptr, length.value)

    def iter_objects(self) -> Iterator[tuple[int, int, int, Any]]:
        for kind, time, key, blob in self:
            yield kind, time, key, pickle.loads(blob)

    def close(self) -> None:
        if getattr(self, "_h", None):
            NATIVE.pn_log_close_read(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def consolidate_native(updates: list) -> list | None:
    """Native consolidation. `updates` is a list of (key, row, diff);
    returns the consolidated list, or None if native is unavailable or a
    row is not exactly byte-serializable (arbitrary-object fallback —
    caller must then use the python path, whose `rows_equal` honors
    user-defined __eq__). On exact rows, byte equality of the canonical
    serialization coincides with values_equal, so grouping matches."""
    if NATIVE is None:
        return None
    from .engine.value import _serialize_for_hash

    packed = bytearray()
    import struct

    for idx, (key, row, diff) in enumerate(updates):
        canon = bytearray()
        if not _serialize_for_hash(row, canon):
            return None
        packed += struct.pack("<QqII", int(key) & 0xFFFFFFFFFFFFFFFF, diff, idx, len(canon))
        packed += canon
    buf = NATIVE.pn_consolidate(_as_u8p(bytes(packed)), len(packed))
    ptr = _u8p()
    length = ctypes.c_uint64()
    NATIVE.pn_buf_read(buf, ctypes.byref(ptr), ctypes.byref(length))
    raw = ctypes.string_at(ptr, length.value)
    NATIVE.pn_buf_free(buf)
    (n,) = struct.unpack_from("<I", raw, 0)
    out = []
    off = 4
    for _ in range(n):
        idx, diff = struct.unpack_from("<Iq", raw, off)
        off += 12
        key, row, _ = updates[idx]
        out.append((key, row, diff))
    return out


def blake2b8_batch(buf: bytes, offsets, key: bytes):
    """Keyed blake2b-8 digests over n messages packed in `buf` at
    `offsets` (uint64 ndarray, n+1 entries) -> uint64 ndarray, or None
    when the native lib is unavailable (caller falls back to hashlib)."""
    if NATIVE is None:
        return None
    import numpy as np

    offs = np.ascontiguousarray(offsets, np.uint64)
    n = len(offs) - 1
    out = np.empty(n, np.uint64)
    NATIVE.pn_blake2b8_batch(
        _as_u8p(buf),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n,
        _as_u8p(key),
        len(key),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return out


class NativeTokenizer:
    """Batched WordPiece tokenizer backed by pn_tok_encode_batch — the
    embedder host hot path (the reference leans on HF fast tokenizers'
    Rust core the same way; embedders.py:270)."""

    __slots__ = ("_h", "cls_id", "sep_id", "pad_id", "unk_id", "has_vocab")

    def __init__(
        self,
        vocab_file: str | None,
        vocab_size: int,
        lowercase: bool,
        max_chars: int = 100,
    ):
        self._h = NATIVE.pn_tok_new(
            (vocab_file or "").encode(), vocab_size, 1 if lowercase else 0, max_chars
        )
        vals = [ctypes.c_int32() for _ in range(5)]
        NATIVE.pn_tok_info(self._h, *[ctypes.byref(v) for v in vals])
        self.cls_id, self.sep_id, self.pad_id, self.unk_id = (
            v.value for v in vals[:4]
        )
        self.has_vocab = bool(vals[4].value)

    def __del__(self):
        if NATIVE is not None and getattr(self, "_h", None):
            NATIVE.pn_tok_free(self._h)
            self._h = None

    def encode_batch(self, texts: list[str], max_len: int):
        """-> (ids [n, max_len] int32 ndarray, lens [n] int32 ndarray)"""
        import numpy as np

        blobs = [t.encode("utf-8") for t in texts]
        n = len(blobs)
        offsets = np.zeros(n + 1, np.uint64)
        np.cumsum([len(b) for b in blobs], out=offsets[1:])
        concat = b"".join(blobs)
        out_ids = np.empty((n, max_len), np.int32)
        out_lens = np.empty(n, np.int32)
        NATIVE.pn_tok_encode_batch(
            self._h,
            _as_u8p(concat),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            n,
            max_len,
            out_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            out_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        return out_ids, out_lens

    @staticmethod
    def prepare_blob(texts: list[str]):
        """-> (concat utf-8 bytes, [n+1] uint64 offsets) for shard calls."""
        import numpy as np

        blobs = [t.encode("utf-8") for t in texts]
        offsets = np.zeros(len(blobs) + 1, np.uint64)
        np.cumsum([len(b) for b in blobs], out=offsets[1:])
        return b"".join(blobs), offsets

    def encode_shard(self, blob, offsets, row_begin: int, row_end: int,
                     max_len: int, out_ids, out_lens) -> None:
        """Encode rows [row_begin, row_end) of a prepared blob into the
        shared (n, max_len) matrix. ctypes drops the GIL for the call,
        so ingest workers calling disjoint shards run in parallel."""
        NATIVE.pn_tok_encode_shard(
            self._h,
            _as_u8p(blob),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            row_begin,
            row_end,
            max_len,
            out_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            out_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
