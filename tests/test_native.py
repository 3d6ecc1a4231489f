"""C++ native runtime tests: blob store, consolidation kernel parity,
snapshot log durability (incl. torn-tail crash tolerance), shard routing.

Mirrors the role of the reference's Rust integration tests
(/root/reference/tests/integration/test_file_kv.rs, test_stream_snapshot.rs)."""

import os
import struct

import numpy as np
import pytest

from pathway_tpu import native
from pathway_tpu.engine.value import hash_int_array, ref_scalar, shard_of

pytestmark = pytest.mark.skipif(not native.is_available(), reason="native lib not built")


def test_store_basic():
    s = native.NativeStore()
    assert len(s) == 0
    s[1] = ("a", 1.5, None)
    s[2**63 + 5] = {"nested": [1, 2]}
    assert len(s) == 2
    assert s[1] == ("a", 1.5, None)
    assert s.get(999) is None
    assert 1 in s and 999 not in s
    s[1] = ("b",)  # overwrite
    assert s[1] == ("b",)
    assert len(s) == 2
    assert s.pop(1) == ("b",)
    assert s.pop(1, "dflt") == "dflt"
    assert len(s) == 1
    items = dict(s.items())
    assert items == {2**63 + 5: {"nested": [1, 2]}}
    s.clear()
    assert len(s) == 0


def test_consolidate_parity():
    from pathway_tpu.engine.dataflow import consolidate

    updates = []
    rng = np.random.default_rng(0)
    for i in range(500):
        key = int(rng.integers(0, 50))
        row = (int(rng.integers(0, 5)), "v")
        updates.append((key, row, int(rng.choice([-1, 1]))))
    native_out = native.consolidate_native(updates)
    # python reference path (below the native threshold we call it directly)
    by = {}
    for k, r, d in updates:
        by[(k, r)] = by.get((k, r), 0) + d
    expect = {kr: d for kr, d in by.items() if d != 0}
    got = {}
    for k, r, d in native_out:
        got[(k, r)] = got.get((k, r), 0) + d
    assert got == expect
    # and the engine's consolidate() (which routes through native for >=64)
    engine_out = consolidate(updates)
    got2 = {}
    for k, r, d in engine_out:
        got2[(k, r)] = got2.get((k, r), 0) + d
    assert got2 == expect


def test_consolidate_numeric_tower():
    # 1.0 and 1 are equal values → must cancel (canonical serialization)
    out = native.consolidate_native([(7, (1.0,), 1), (7, (1,), -1)])
    assert out == []


def test_consolidate_path_parity_bool_nan():
    """Python and native paths must group identically (bool != int,
    NaN == NaN, NaN payloads canonicalized)."""
    from pathway_tpu.engine.dataflow import consolidate

    nan1 = float("nan")
    nan2 = np.float64("nan") * -1.0  # different payload sign bit
    cases = [
        [(1, (True,), 1), (1, (1,), -1)],  # bool vs int: distinct, no cancel
        [(2, (nan1,), 1), (2, (float(nan2),), -1)],  # NaNs cancel
    ]
    for updates in cases:
        small = consolidate(list(updates))
        big = consolidate(list(updates) + [(100 + i, ("pad",), 1) for i in range(70)])
        big_wo_pad = [u for u in big if u[0] < 100]
        assert small == big_wo_pad, f"batch-size-dependent result for {updates}"
    assert consolidate([(1, (True,), 1), (1, (1,), -1)]) == [(1, (1,), -1), (1, (True,), 1)]
    assert consolidate([(2, (nan1,), 1), (2, (float(nan2),), -1)]) == []


def test_consolidate_fallback_on_opaque_objects():
    """Rows with arbitrary objects (inexact serialization) must take the
    python path honoring __eq__."""

    class Obj:
        def __eq__(self, other):
            return isinstance(other, Obj)

        def __hash__(self):
            return 42

    assert native.consolidate_native([(1, (Obj(),), 1), (1, (Obj(),), -1)]) is None
    from pathway_tpu.engine.dataflow import consolidate

    ups = [(1, (Obj(),), 1), (1, (Obj(),), -1)] + [(100 + i, (Obj(),), 1) for i in range(70)]
    out = consolidate(ups)
    assert all(k >= 100 for k, _, _ in out) and len(out) == 70


def test_consolidate_retract_before_insert():
    out = native.consolidate_native([(5, ("new",), 1), (5, ("old",), -1)])
    assert out == [(5, ("old",), -1), (5, ("new",), 1)]


def test_log_roundtrip(tmp_path):
    p = str(tmp_path / "snap.log")
    w = native.SnapshotLogWriter(p, append=False)
    w.append_obj(1, 10, 111, {"offset": 5})
    w.append_obj(2, 11, 222, ("row", 3.5))
    w.flush()
    w.close()
    # append mode continues an existing log
    w = native.SnapshotLogWriter(p, append=True)
    w.append_obj(1, 12, 333, "third")
    w.close()
    r = native.SnapshotLogReader(p)
    recs = list(r.iter_objects())
    assert recs == [(1, 10, 111, {"offset": 5}), (2, 11, 222, ("row", 3.5)), (1, 12, 333, "third")]


def test_log_torn_tail_tolerated(tmp_path):
    p = str(tmp_path / "torn.log")
    w = native.SnapshotLogWriter(p, append=False)
    w.append_obj(1, 1, 1, "good")
    w.append_obj(1, 2, 2, "also good")
    w.close()
    # simulate crash mid-append: truncate the file inside the last record
    size = os.path.getsize(p)
    with open(p, "r+b") as f:
        f.truncate(size - 3)
    r = native.SnapshotLogReader(p)
    assert [obj for _, _, _, obj in r.iter_objects()] == ["good"]
    r.close()
    # append after a torn tail must truncate it so post-crash records are
    # reachable (crash-recovery path)
    w = native.SnapshotLogWriter(p, append=True)
    w.append_obj(1, 3, 3, "post-crash")
    w.close()
    r = native.SnapshotLogReader(p)
    assert [obj for _, _, _, obj in r.iter_objects()] == ["good", "post-crash"]


def test_store_snapshot_load(tmp_path):
    p = str(tmp_path / "state.log")
    s = native.NativeStore()
    for i in range(100):
        s[i] = (i, f"row{i}")
    w = native.SnapshotLogWriter(p, append=False)
    n = s.snapshot_to(w, kind=7, time=42)
    assert n == 100
    w.close()
    s2 = native.NativeStore()
    r = native.SnapshotLogReader(p)
    assert s2.load_from(r, kind=7) == 100
    assert dict(s2.items()) == dict(s.items())


def test_hash_batch_matches_python():
    lib = native.NATIVE
    import ctypes

    vals = np.arange(1000, dtype=np.uint64)
    out = np.zeros(1000, dtype=np.uint64)
    lib.pn_hash64_batch(
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        1000,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    np.testing.assert_array_equal(out, hash_int_array(vals))


def test_shard_batch_matches_python():
    lib = native.NATIVE
    import ctypes
    from pathway_tpu.engine.value import SHARD_MASK

    keys = np.array([int(ref_scalar(i)) for i in range(200)], dtype=np.uint64)
    out = np.zeros(200, dtype=np.uint32)
    lib.pn_shard_batch(
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        200,
        SHARD_MASK,
        8,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
    )
    expect = np.array([shard_of(int(k), 8) for k in keys], dtype=np.uint32)
    np.testing.assert_array_equal(out, expect)


def test_loaded_library_is_built_from_the_current_source(tmp_path, monkeypatch):
    """A stale artefact, however new its mtime, is never what loads: the
    library is looked up under a hash of the source on disk. Here the
    "current" source is a copy whose version string was edited, and the
    stale artefact is the library this process is running (built from
    the unedited source), planted under the old fixed name and under
    another hash's name, both stamped an hour into the future."""
    import shutil
    import time

    src = tmp_path / "pathway_native.cc"
    with open(native._SRC) as f:
        text = f.read()
    assert '"pathway-native 1.0"' in text
    src.write_text(text.replace('"pathway-native 1.0"', '"pathway-native edited"'))
    out_dir = tmp_path / "_native"
    out_dir.mkdir()
    future = time.time() + 3600
    for name in ("libpathway_native.so", "libpathway_native-0123456789abcdef.so"):
        shutil.copy(native._lib_path(), out_dir / name)
        os.utime(out_dir / name, (future, future))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_OUT_DIR", str(out_dir))

    lib = native._load()
    assert lib is not None
    assert lib.pn_version() == b"pathway-native edited"
    # and the stale artefacts are gone, so nothing can pick them up later
    assert os.listdir(out_dir) == [os.path.basename(native._lib_path())]
