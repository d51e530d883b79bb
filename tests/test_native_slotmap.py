"""Native C++ slotmap: behavior parity against the pure-Python SlotMap."""

import numpy as np
import pytest

from gubernator_tpu.ops.engine import SlotMap

native = pytest.importorskip("gubernator_tpu.native")
if native.load_library() is None:
    pytest.skip("native slotmap library unavailable", allow_module_level=True)

from gubernator_tpu.native import NativeSlotMap  # noqa: E402


@pytest.fixture(params=["python", "native"])
def sm(request):
    if request.param == "python":
        return SlotMap(256)
    return NativeSlotMap(256)


def test_assign_get_release_roundtrip(sm):
    s = sm.assign("a")
    assert s is not None
    assert sm.get("a") == s
    assert sm.assign("a") == s  # idempotent
    assert sm.key_of(s) == "a"
    assert len(sm) == 1
    sm.release(s)
    assert sm.get("a") is None
    assert sm.key_of(s) is None
    assert len(sm) == 0


def test_fills_to_capacity_and_reuses_released(sm):
    slots = [sm.assign(f"k{i}") for i in range(256)]
    assert None not in slots
    assert len(set(slots)) == 256
    assert sm.assign("overflow") is None
    sm.release(sm.get("k0"))
    assert sm.assign("overflow") is not None


def test_mapped_mask(sm):
    for i in range(10):
        sm.assign(f"k{i}")
    mask = sm.mapped_mask()
    assert mask.sum() == 10
    sm.release(sm.get("k0"))
    assert sm.mapped_mask().sum() == 9


def test_resolve_batch_matches_single_ops(sm):
    keys = [f"batch-{i % 50}".encode() for i in range(100)]
    slots, known = sm.resolve_batch(keys)
    assert (slots >= 0).all()
    # First 50 are fresh, second 50 are repeats mapping to the same slots.
    assert known[:50].sum() == 0
    assert known[50:].sum() == 50
    assert (slots[:50] == slots[50:]).all()
    for i in range(50):
        assert sm.get(f"batch-{i}") == slots[i]


def test_resolve_batch_full_table_returns_minus_one(sm):
    keys = [f"full-{i}".encode() for i in range(300)]
    slots, known = sm.resolve_batch(keys)
    assert (slots[:256] >= 0).all()
    assert (slots[256:] == -1).all()


def test_native_tombstone_rehash_stays_correct():
    """Churn far past capacity to exercise tombstone cleanup."""
    sm = NativeSlotMap(64)
    for round_ in range(200):
        keys = [f"r{round_}-{i}" for i in range(64)]
        for k in keys:
            assert sm.assign(k) is not None
        assert len(sm) == 64
        for k in keys:
            s = sm.get(k)
            assert s is not None and sm.key_of(s) == k
            sm.release(s)
        assert len(sm) == 0


@pytest.mark.parametrize("n_shards", [1, 3, 4])
def test_sharded_window_pass_routes_by_crc32(n_shards):
    """The sharded window pass sends every key to ``zlib.crc32(key) %
    n_shards`` (keys of length 0, 1 and 4,096 among them) and maps it in
    that shard's map alone: ``routing_parity_errors`` stays 0."""
    import threading
    import zlib

    from gubernator_tpu.native import ShardedWindowPass
    from gubernator_tpu.ops.reqcols import ReqColumns, pack_blob
    from gubernator_tpu.parallel.mesh_engine import MeshTickEngine

    rng = np.random.default_rng(n_shards)
    keys = [b"", b"a", b"k" * 4096, b"name_key"] + [
        b"k%d" % int(rng.integers(0, 1 << 40)) for _ in range(60)]
    n, cap = len(keys), 64
    maps = [NativeSlotMap(cap) for _ in range(n_shards)]
    blob, offsets = pack_blob(keys)
    ones = np.ones(n, np.int64)
    cols = ReqColumns(blob, offsets, hits=ones, limit=ones * 10,
                      duration=ones * 60_000, algorithm=ones * 0,
                      behavior=ones * 0, created_at=ones * 5, burst=ones * 0)
    status, sh, slots, known, inv, n_miss, counts, route_s = ShardedWindowPass(
        maps, cap).pack_window(
            cols, np.empty((19, 64), np.int32), 5, False,
            np.zeros(n_shards * cap, np.int64), 1)
    want = [zlib.crc32(k) % n_shards for k in keys]
    assert status == NativeSlotMap.PACK_UNIQUE and n_miss == n
    assert sh.tolist() == want and not known.any()
    assert counts.tolist() == np.bincount(want, minlength=n_shards).tolist()
    for k, d, slot in zip(keys, want, slots.tolist()):
        assert [sm.get(k.decode()) for sm in maps] == [
            slot if i == d else None for i in range(n_shards)]
    # the audit the reshard coordinator runs, on an engine that is only
    # its routing state (no device program)
    eng = MeshTickEngine.__new__(MeshTickEngine)
    eng.n_shards, eng.local_capacity, eng.slots = n_shards, cap, maps
    eng._lock = threading.RLock()
    assert eng.routing_parity_errors([k.decode() for k in keys]) == 0


# ----------------------------------------------------------------------
# The loader: ``*.so`` is git-ignored, so the library on disk is whatever
# an earlier checkout built — it must be rebuilt when its source is
# newer, and a fallback to pure Python must never be silent.
# ----------------------------------------------------------------------
@pytest.fixture
def native_copy(tmp_path, monkeypatch):
    """A private copy of the native sources, with the loader's
    per-process memos cleared."""
    import os
    import shutil

    for f in ("Makefile", "slotmap.cc", "wirecodec.cc"):
        shutil.copy(os.path.join(native._DIR, f), tmp_path / f)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_paths", {})
    monkeypatch.setattr(native, "_build_attempted", False)
    return tmp_path


def test_library_is_rebuilt_when_source_is_newer(native_copy, monkeypatch):
    import os

    so = native.library_path("libguber_wire.so")
    assert so == str(native_copy / "libguber_wire.so") and os.path.exists(so)
    # Age the library below its source: the next process must rebuild it.
    src_mtime = os.path.getmtime(native_copy / "wirecodec.cc")
    os.utime(so, (src_mtime - 100, src_mtime - 100))
    monkeypatch.setattr(native, "_paths", {})
    monkeypatch.setattr(native, "_build_attempted", False)
    assert native._stale("libguber_wire.so")
    assert native.library_path("libguber_wire.so") == so
    assert os.path.getmtime(so) >= src_mtime


def test_fallback_without_a_toolchain_warns(native_copy, monkeypatch, caplog):
    import logging

    monkeypatch.setenv("PATH", str(native_copy / "no-such-bin"))
    with caplog.at_level(logging.WARNING, logger="gubernator.native"):
        assert native.library_path("libguber_slotmap.so") is None
    assert "pure-Python fallback" in caplog.text
    assert "libguber_slotmap.so" in caplog.text


@pytest.mark.parametrize("toolchain", [True, False])
def test_library_without_the_newest_symbol_is_stale(
        native_copy, monkeypatch, caplog, toolchain):
    """A library an older checkout built can be newer than its source and
    still lack ``guber_slotmap_pack_window_sharded``: it is rebuilt, or
    refused with the WARNING, never bound half-way."""
    import logging
    import os
    import shutil

    # A sound library that exports none of the slotmap's symbols, newer
    # than slotmap.cc: make alone would leave it.
    so = native_copy / "libguber_slotmap.so"
    shutil.copy(native.library_path("libguber_wire.so"), so)
    monkeypatch.setattr(native, "_paths", {})
    monkeypatch.setattr(native, "_build_attempted", False)
    assert os.path.getmtime(so) >= os.path.getmtime(native_copy / "slotmap.cc")
    assert native._stale("libguber_slotmap.so")
    if not toolchain:
        monkeypatch.setenv("PATH", str(native_copy / "no-such-bin"))
    with caplog.at_level(logging.WARNING, logger="gubernator.native"):
        path = native.library_path("libguber_slotmap.so")
    if toolchain:
        assert path == str(so) and not native._stale("libguber_slotmap.so")
        assert b"guber_slotmap_pack_window_sharded" in so.read_bytes()
    else:
        assert path is None
        assert "guber_slotmap_pack_window_sharded" in caplog.text
        assert "pure-Python fallback" in caplog.text


def test_a_library_with_the_one_chip_pass_alone_is_stale(native_copy):
    """The library of the commit before the sharded window pass exports
    ``guber_slotmap_pack_window`` and not its sharded sibling: stale, so
    a sharded engine never falls to the numpy chain in silence."""
    so = native_copy / "libguber_slotmap.so"
    so.write_bytes(b"\x7fELF guber_slotmap_pack_window\0guber_crc32_batch\0")
    assert native._stale("libguber_slotmap.so")
    so.write_bytes(so.read_bytes() + b"guber_slotmap_pack_window_sharded\0")
    assert not native._stale("libguber_slotmap.so")
