"""Native (C++) runtime components, loaded via ctypes.

The compute path is JAX/XLA; the host runtime around it — here, the
key→slot table that front-ends every device tick — is C++ (built by the
Makefile in this directory).  When a shared library is absent or stale
and can't be built, callers fall back to the pure-Python path and a
WARNING says so.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import List, Optional

import numpy as np

log = logging.getLogger("gubernator.native")

_DIR = os.path.dirname(__file__)
# library -> its one source file (the Makefile's rules, mirrored here so
# a stale library is detected without needing make to be installed).
_SOURCES = {
    "libguber_slotmap.so": "slotmap.cc",
    "libguber_wire.so": "wirecodec.cc",
}
# library -> the newest symbol this checkout binds from it.  ``*.so`` is
# git-ignored and make compares only mtimes, so a library an older
# checkout built can be newer than its source and still lack it: that
# counts as stale too (rebuilt, or refused with the WARNING), so a
# library is never bound half-way.
_NEWEST_SYMBOL = {
    "libguber_slotmap.so": b"guber_slotmap_pack_window_sharded",
    "libguber_wire.so": b"guber_decode_req",
}
_lib: Optional[ctypes.CDLL] = None
_build_attempted = False
_paths: dict = {}   # library name -> resolved path / None, once per process


def _stale(name: str) -> bool:
    """True when library ``name`` is absent, older than its source, or
    lacks the newest symbol this checkout binds from it."""
    so = os.path.join(_DIR, name)
    src = os.path.join(_DIR, _SOURCES[name])
    if not os.path.exists(so):
        return True
    if os.path.exists(src) and os.path.getmtime(src) > os.path.getmtime(so):
        return True
    symbol = _NEWEST_SYMBOL.get(name)
    if symbol is None:
        return False
    # The dynamic string table holds every exported name; reading the
    # file (tens of KB) loads nothing, so a rebuild can still replace it.
    # guber: allow-G001(one read per library per process - library_path memoizes the answer, every later hot-path call hits _paths) # guber: allow-G002(same one-shot read at first load, memoized in _paths) # guber: allow-G007(same one-shot read - a cold-start cost beside the dlopen it guards, never steady-state)
    with open(so, "rb") as f:
        return symbol not in f.read()


def _try_build() -> None:
    global _build_attempted
    if _build_attempted:
        return
    _build_attempted = True
    try:
        # guber: allow-G001(one-shot memoized toolchain build at first use - every later hot-path call hits the cached .so) # guber: allow-G007(same one-shot build - serialized behind _build_attempted, a cold-start cost, never steady-state)
        subprocess.run(
            # -B, for the stale libraries alone: one that only lacks a
            # symbol is newer than its source, and make would leave it.
            ["make", "-C", _DIR, "-s", "-B",
             *(name for name in _SOURCES if _stale(name))],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (OSError, subprocess.SubprocessError) as e:
        # no toolchain / read-only install: library_path warns per library
        log.warning(
            "native build failed: %s %s", e,
            (getattr(e, "stderr", b"") or b"").decode(errors="replace")[-400:],
        )


def library_path(name: str) -> Optional[str]:
    """Path of native library ``name``, (re)built first when it is absent
    or older than its source — ``*.so`` is git-ignored, so what is on
    disk is whatever an earlier checkout built.  None, with a WARNING,
    when it cannot be brought up to date: callers then fall back to
    their pure-Python path, which is correct but several times slower,
    so the fallback is never silent."""
    if name not in _paths:
        if _stale(name):
            _try_build()
        if _stale(name):
            log.warning(
                "native library %s is missing or stale (older than %s, or "
                "without %s) and could not be built; using the slower "
                "pure-Python fallback",
                name, _SOURCES[name],
                _NEWEST_SYMBOL.get(name, b"its newest symbol").decode(),
            )
            _paths[name] = None
        else:
            _paths[name] = os.path.join(_DIR, name)
    return _paths[name]


def load_library() -> Optional[ctypes.CDLL]:
    """The slotmap shared library, built on first use if needed."""
    global _lib
    if _lib is not None:
        return _lib
    so = library_path("libguber_slotmap.so")
    if so is None:
        return None
    lib = ctypes.CDLL(so)
    lib.guber_slotmap_new.restype = ctypes.c_void_p
    lib.guber_slotmap_new.argtypes = [ctypes.c_int64]
    lib.guber_slotmap_free.argtypes = [ctypes.c_void_p]
    lib.guber_slotmap_get.restype = ctypes.c_int64
    lib.guber_slotmap_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.guber_slotmap_assign.restype = ctypes.c_int64
    lib.guber_slotmap_assign.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.guber_slotmap_release.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.guber_slotmap_size.restype = ctypes.c_int64
    lib.guber_slotmap_size.argtypes = [ctypes.c_void_p]
    lib.guber_slotmap_key_of.restype = ctypes.c_int64
    lib.guber_slotmap_key_of.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
    ]
    lib.guber_slotmap_resolve_batch.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
    ]
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    # The window pass is bound twice: through CDLL, which drops the GIL
    # for the call, and through PyDLL, which holds it.  Dropping it is
    # what lets the gRPC thread and the resolver run beside a wide
    # window's pass; taking it back costs a hand-off (0.1-0.3 ms a time
    # on the served path), which a narrow window's few microseconds of
    # work do not repay (NativeSlotMap.pack_window and
    # ShardedWindowPass.pack_window choose, by PACK_GIL_FREE_ROWS).
    held = ctypes.PyDLL(so)
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    window = [
        ctypes.c_char_p, i64p, ctypes.c_int64,     # key blob, offsets, n
        i64p, i64p, i64p, i64p, i64p, i64p, i64p,  # the request columns
        ctypes.c_int64, ctypes.c_int64,            # now, stop_on_miss
        i32p, ctypes.c_int64,                      # slab, its width
    ]
    lib.pack_window_gil_held = held.guber_slotmap_pack_window
    for fn in (lib.guber_slotmap_pack_window, lib.pack_window_gil_held):
        fn.restype = ctypes.c_int64
        fn.argtypes = [
            ctypes.c_void_p, *window,
            i64p, u8p, i64p,                           # slots, known, inv
            i64p, ctypes.c_int64,                      # last_access, tick
            np.ctypeslib.ndpointer(np.bool_, flags="C_CONTIGUOUS"),
            i32p, ctypes.c_int64, i64p,                # plan scratch, info
        ]
    lib.pack_window_sharded_gil_held = held.guber_slotmap_pack_window_sharded
    for fn in (lib.guber_slotmap_pack_window_sharded,
               lib.pack_window_sharded_gil_held):
        fn.restype = ctypes.c_int64
        fn.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64,  # maps, how many
            ctypes.c_int64, *window,                   # local_capacity
            i64p, i64p, u8p, i64p,                     # sh, slots, known, inv
            i64p, ctypes.c_int64,                      # last_access, tick
            i64p, i64p,                                # rows a shard, info
        ]
    lib.guber_slotmap_mapped.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
    ]
    lib.guber_slotmap_release_batch.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
    ]
    lib.guber_slotmap_keys_batch.restype = ctypes.c_int64
    lib.guber_slotmap_keys_batch.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
        ctypes.c_char_p,
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
    ]
    lib.guber_slotmap_assign_batch.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
    ]
    lib.guber_crc32_batch.argtypes = [
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS"),
    ]
    _lib = lib
    return lib


def as_char_p(blob):
    """A ``c_char_p``-compatible view of any bytes-like blob, copy-free
    for writable buffers (numpy views into a shared-memory slab, byte-
    arrays).  The native calls index strictly by (blob, offsets), so the
    missing NUL terminator of a raw buffer is irrelevant.  Read-only
    non-bytes buffers (rare: memoryview of bytes) fall back to one copy."""
    if isinstance(blob, (bytes, ctypes.Array)):
        return blob
    mv = memoryview(blob).cast("B")
    if mv.readonly:
        return mv.tobytes()
    return ctypes.cast(
        (ctypes.c_char * mv.nbytes).from_buffer(mv), ctypes.c_char_p
    )


def crc32_batch(blob, offsets: np.ndarray) -> np.ndarray:
    """zlib-compatible CRC-32 of every key in a packed (blob, offsets)
    pair — the mesh engine's vectorized key→shard router.  Falls back to
    a zlib loop when the native library is unavailable."""
    n = len(offsets) - 1
    lib = load_library()
    if lib is None:
        import zlib

        mv = memoryview(blob)
        return np.fromiter(
            (zlib.crc32(mv[offsets[i]:offsets[i + 1]]) for i in range(n)),
            np.uint32, count=n,
        )
    offsets = np.ascontiguousarray(offsets, np.int64)
    out = np.empty(n, np.uint32)
    lib.guber_crc32_batch(as_char_p(blob), offsets, n, out)
    return out


def _window_columns(cols, m32: np.ndarray) -> list:
    """A window's key offsets and seven request columns as the native
    window passes take them (contiguous int64), checked against each
    other and against the ``(19, b)`` slab they are to fill."""
    n = len(cols)
    columns = [
        np.ascontiguousarray(c, np.int64) for c in (
            cols.key_offsets, cols.hits, cols.limit, cols.duration,
            cols.algorithm, cols.behavior, cols.created_at, cols.burst)
    ]
    if len(columns[0]) != n + 1 or any(len(c) != n for c in columns[1:]):
        raise ValueError("request columns disagree on the row count")
    rows, b = m32.shape
    if rows != 19 or n > b:
        raise ValueError(f"a ({rows}, {b}) slab cannot hold {n} REQ32 lanes")
    return columns


class NativeSlotMap:
    """ctypes wrapper mirroring ops.engine.SlotMap, plus batch resolve."""

    def __init__(self, capacity: int):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native slotmap library unavailable")
        self._lib = lib
        self.capacity = int(capacity)
        self._h = lib.guber_slotmap_new(self.capacity)
        self._keybuf = ctypes.create_string_buffer(4096)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.guber_slotmap_free(h)
            self._h = None

    def __len__(self) -> int:
        return self._lib.guber_slotmap_size(self._h)

    def get(self, key: str) -> Optional[int]:
        b = key.encode()
        s = self._lib.guber_slotmap_get(self._h, b, len(b))
        return None if s < 0 else s

    def assign(self, key: str) -> Optional[int]:
        b = key.encode()
        s = self._lib.guber_slotmap_assign(self._h, b, len(b))
        return None if s < 0 else s

    def release(self, slot: int) -> None:
        self._lib.guber_slotmap_release(self._h, slot)

    def key_of(self, slot: int) -> Optional[str]:
        n = self._lib.guber_slotmap_key_of(
            self._h, slot, self._keybuf, len(self._keybuf)
        )
        return None if n < 0 else self._keybuf.raw[:n].decode()

    def mapped_mask(self) -> np.ndarray:
        """Boolean array over slots: True where a key is assigned."""
        out = np.empty(self.capacity, np.uint8)
        self._lib.guber_slotmap_mapped(self._h, out)
        return out.astype(bool)

    def resolve_batch(self, keys: List[bytes]):
        """(slots, known) for a batch of keys in one native call; slot -1
        means the table is full for that key."""
        from gubernator_tpu.ops.reqcols import pack_blob

        return self.resolve_blob(*pack_blob(keys))

    def resolve_blob(self, blob, offsets: np.ndarray):
        """resolve_batch on pre-packed (blob, offsets) — the columnar hot
        path's native call: no per-key Python at all.  ``blob`` may be any
        bytes-like buffer (a numpy view into a shared-memory slab included);
        non-bytes writable buffers are passed without copying."""
        n = len(offsets) - 1
        offsets = np.ascontiguousarray(offsets, np.int64)
        slots = np.empty(n, np.int64)
        known = np.empty(n, np.uint8)
        self._lib.guber_slotmap_resolve_batch(
            self._h, as_char_p(blob), offsets, n, slots, known
        )
        return slots, known

    # The window passes' statuses (slotmap.cc PackStatus).
    PACK_UNIQUE, PACK_GROUPED, PACK_DUPS_NO_PLAN = 0, 1, 2
    PACK_RESOLVED_ONLY, PACK_NOT_TAKEN = -1, -2
    # Rows from which a window pass drops the GIL for its call (numpy's
    # own ufunc loops drop it above 500 elements).
    PACK_GIL_FREE_ROWS = 512

    def pack_window(self, cols, m32: np.ndarray, now: int,
                    stop_on_miss: bool, last_access: np.ndarray, tick: int,
                    dirty: np.ndarray, upad_cap: int):
        """The host pack of one window in one native call (the GIL
        released for all of it from PACK_GIL_FREE_ROWS rows): keys ->
        slots, the leased ``(19, b)`` slab ``m32`` cleaned (zeros, the
        slot row at the sentinel, ``capacity``) and the REQ32 rows
        written into it in slot order, and the grouped plan where the
        window's duplicates qualify (the numpy chain ``resolve_blob`` -> ``engine.pack_cols_req32`` ->
        ``sort_packed_by_slot`` -> ``build_group_plan``, which it
        equals array for array).  Every packed slot is stamped ``tick``
        in ``last_access`` and marked in ``dirty`` where its row moves
        state (both ``capacity`` long).  ``upad_cap``: the widest head
        block the plan can need, ``engine.group_upad(b, n)``.

        Returns ``(status, slots, known, inv, n_miss, plan, n_leaky)``
        (``n_leaky``: rows packed with algorithm LEAKY).
        PACK_NOT_TAKEN: a row is Gregorian, nothing was done.
        PACK_RESOLVED_ONLY: a key found no slot, or ``stop_on_miss`` and
        a key was new; ``slots`` / ``known`` are ``resolve_blob``'s and
        the slab is untouched.  Otherwise the slab is packed and sorted
        and ``inv`` maps request order to sorted lanes; for PACK_GROUPED
        ``plan`` is ``build_group_plan``'s ``(mhead, count, uidx, rank,
        u, buf)``, else None: ``buf`` is the scratch the pass wrote, cut
        to the plan's own words, with ``now`` as its (lo, hi) pair
        behind them — the window's one upload (``engine.plan_views``)."""
        n = len(cols)
        columns = _window_columns(cols, m32)
        rows, b = m32.shape
        if len(last_access) != self.capacity or len(dirty) != self.capacity:
            raise ValueError("per-slot arrays must be capacity long")
        slots = np.empty(n, np.int64)
        known = np.empty(n, np.uint8)
        inv = np.empty(n, np.int64)
        # The plan's arrays, laid out uidx[b] rank[b] count[upad]
        # mhead[19][upad], and two words more for ``now``.  Fresh every
        # window: it is uploaded asynchronously, and jax may read it
        # until the copy is done.
        plan_cap = 2 * b + (rows + 1) * upad_cap
        scratch = np.empty(plan_cap + 2, np.int32)
        info = np.zeros(4, np.int64)
        call = (
            self._lib.guber_slotmap_pack_window
            if n >= self.PACK_GIL_FREE_ROWS else self._lib.pack_window_gil_held
        )
        status = call(
            self._h, as_char_p(cols.key_blob), columns[0], n, *columns[1:],
            now, stop_on_miss, m32, b, slots, known, inv,
            last_access, tick, dirty, scratch, plan_cap, info,
        )
        n_miss, u, upad, n_leaky = info.tolist()
        plan = None
        if status == self.PACK_GROUPED:
            at = 2 * b + upad
            end = at + rows * upad
            lo = now & 0xFFFFFFFF
            scratch[end] = lo - ((lo & 0x80000000) << 1)
            scratch[end + 1] = now >> 32
            plan = (
                scratch[at:end].reshape(rows, upad),
                scratch[2 * b:at], scratch[:b], scratch[b:2 * b], u,
                scratch[:end + 2],
            )
        return status, slots, known, inv, n_miss, plan, n_leaky

    def release_batch(self, slots: np.ndarray) -> None:
        """Release a batch of slots in one native call."""
        slots = np.ascontiguousarray(slots, np.int64)
        self._lib.guber_slotmap_release_batch(self._h, slots, len(slots))

    def keys_blob(self, slots: np.ndarray) -> tuple[bytes, np.ndarray]:
        """Keys of a batch of slots as one (blob, offsets) pair — the
        columnar snapshot format; unassigned slots span zero bytes."""
        slots = np.ascontiguousarray(slots, np.int64)
        n = len(slots)
        offsets = np.zeros(n + 1, np.int64)
        cap = max(4096, n * 64)
        while True:
            buf = ctypes.create_string_buffer(cap)
            need = self._lib.guber_slotmap_keys_batch(
                self._h, slots, n, buf, cap, offsets
            )
            if need <= cap:
                break
            cap = int(need)
        return buf.raw[: offsets[n]], offsets

    def keys_batch(self, slots: np.ndarray) -> List[bytes]:
        """Keys of a batch of slots (b"" for unassigned) in one native call."""
        blob, offsets = self.keys_blob(slots)
        mv = memoryview(blob)  # slice without copying the whole buffer
        return [bytes(mv[offsets[i] : offsets[i + 1]]) for i in range(len(slots))]

    def assign_blob(self, blob, offsets: np.ndarray) -> np.ndarray:
        """Assign keys packed as (blob, offsets); -1 = table full."""
        n = len(offsets) - 1
        offsets = np.ascontiguousarray(offsets, np.int64)
        out = np.empty(n, np.int64)
        self._lib.guber_slotmap_assign_batch(
            self._h, as_char_p(blob), offsets, n, out
        )
        return out

    def assign_batch(self, keys: List[bytes]) -> np.ndarray:
        """Assign a batch of keys in one native call; -1 = table full."""
        from gubernator_tpu.ops.reqcols import pack_blob

        return self.assign_blob(*pack_blob(keys))


class ShardedWindowPass:
    """The sharded window pass over one slot map a shard
    (slotmap.cc guber_slotmap_pack_window_sharded;
    ``MeshTickEngine._pack_window``): keys to shard, local slot and the
    slot-sorted slab in one native call, the GIL released for all of it
    from ``NativeSlotMap.PACK_GIL_FREE_ROWS`` rows."""

    def __init__(self, slot_maps, local_capacity: int):
        self._maps = list(slot_maps)    # their handles live as long as this
        self._lib = self._maps[0]._lib
        self.n_shards = len(self._maps)
        self.local_capacity = int(local_capacity)
        self._handles = (ctypes.c_void_p * self.n_shards)(
            *(sm._h for sm in self._maps))

    def pack_window(self, cols, m32: np.ndarray, now: int,
                    stop_on_miss: bool, last_access: np.ndarray, tick: int):
        """One window: CRC-32 of every key ``% n_shards`` -> ``sh``, the
        key resolved in that shard's map -> ``slots`` (LOCAL) and
        ``known``, then the leased ``(19, b)`` slab ``m32`` cleaned
        (zeros, the slot row at the sentinel, the GLOBAL capacity) and
        the REQ32 rows written into it sorted by global slot
        ``sh * local_capacity + slot``, each one stamped ``tick`` in
        ``last_access`` (global capacity long).  It equals the numpy
        chain of ``MeshTickEngine._pack_window_numpy`` array for array.

        Returns ``(status, sh, slots, known, inv, n_miss, counts,
        route_s)``: ``counts`` the rows a shard (``RaggedExtents.counts``),
        ``route_s`` the seconds to the end of the resolve.  Statuses as
        ``NativeSlotMap.pack_window``: PACK_NOT_TAKEN (a Gregorian row;
        nothing done), PACK_RESOLVED_ONLY (a key found no slot in its
        shard, or ``stop_on_miss`` and a key was new; ``sh`` / ``slots``
        / ``known`` stand, the slab and ``last_access`` are untouched),
        PACK_UNIQUE or PACK_DUPS_NO_PLAN (packed and sorted, ``inv``
        maps request order to sorted lanes; slots repeat or not)."""
        n = len(cols)
        columns = _window_columns(cols, m32)
        if len(last_access) != self.n_shards * self.local_capacity:
            raise ValueError("last_access must be the global capacity long")
        sh = np.empty(n, np.int64)
        slots = np.empty(n, np.int64)
        known = np.empty(n, np.uint8)
        inv = np.empty(n, np.int64)
        counts = np.empty(self.n_shards, np.int64)
        info = np.zeros(2, np.int64)
        call = (
            self._lib.guber_slotmap_pack_window_sharded
            if n >= NativeSlotMap.PACK_GIL_FREE_ROWS
            else self._lib.pack_window_sharded_gil_held
        )
        status = call(
            self._handles, self.n_shards, self.local_capacity,
            as_char_p(cols.key_blob), columns[0], n, *columns[1:],
            now, stop_on_miss, m32, m32.shape[1], sh, slots, known, inv,
            last_access, tick, counts, info,
        )
        n_miss, route_ns = info.tolist()
        return status, sh, slots, known, inv, n_miss, counts, route_ns * 1e-9
