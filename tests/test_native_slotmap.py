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
    still lack ``guber_slotmap_pack_window``: it is rebuilt, or refused
    with the WARNING, never bound half-way."""
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
        assert b"guber_slotmap_pack_window" in so.read_bytes()
    else:
        assert path is None
        assert "guber_slotmap_pack_window" in caplog.text
        assert "pure-Python fallback" in caplog.text
