"""Columnar request batches: the zero-object host hot path.

The round-2 profile put the end-to-end engine at ~10µs of host work per
request — nearly all of it constructing and walking per-item Python
objects (dataclass attribute reads, ``hash_key()`` string building, list
comprehensions) against a device kernel that does the actual decision in
~4ns.  The reference has the same shape of cost in Go (per-request
structs, channel hops, ``gubernator.go:272-294``) but Go's per-item
constant is ~30x smaller, so it can afford it; Python cannot.

This module is the fix: a request batch as a *struct of arrays* —
one contiguous key blob + int64 numpy columns — that flows from the
transport edge to the device with no per-request Python in between:

    wire bytes → (parse) → ReqColumns → native slotmap resolve (blob in,
    slots out) → vectorized matrix pack → device tick → (5, B) response
    matrix → wire bytes

Dataclass `RateLimitRequest` remains the API-edge type (tests, SDK,
Store hooks); :meth:`ReqColumns.from_requests` bridges.  The engine's
``process()`` keeps its object contract and routes through this path.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np

from gubernator_tpu.types import RateLimitRequest
from gubernator_tpu.utils.hotpath import hot_path
from gubernator_tpu.utils import sanitize

# `created_at` sentinel: proto3 optional presence maps to "server stamps
# now" (gubernator.proto:172-182).  0 is a legal (if silly) client value,
# so absence is encoded as -1.
CREATED_UNSET = -1

_EMPTY_I64 = np.empty(0, np.int64)


@dataclass
class ReqColumns:
    """One request batch as columns (see module docstring).

    ``key_blob``/``key_offsets`` hold the concatenated *hash keys*
    (``name + "_" + unique_key``, reference client.go:39-41): offsets are
    (n+1,) int64 with ``key j = blob[offsets[j]:offsets[j+1]]``, exactly
    the native slotmap's batch-resolve wire format (slotmap.cc
    guber_slotmap_resolve_batch).  The blob may be ``bytes`` or any
    bytes-like buffer — arena-backed batches carry a zero-copy numpy
    view into the decode slab (shared-memory slabs included); every
    consumer (native resolve, concat, per-key error paths) accepts the
    buffer form.

    ``refs`` optionally carries the originating request objects for the
    paths that genuinely need them (Store read/write-through hooks take a
    ``RateLimitRequest``); the hot path never touches it.
    """

    key_blob: "bytes | np.ndarray | memoryview"
    key_offsets: np.ndarray   # (n+1,) int64
    hits: np.ndarray          # all remaining columns: (n,) int64
    limit: np.ndarray
    duration: np.ndarray
    algorithm: np.ndarray
    behavior: np.ndarray
    created_at: np.ndarray    # CREATED_UNSET where the server stamps now
    burst: np.ndarray
    refs: Optional[Sequence[RateLimitRequest]] = None
    # Byte length of the *name* part of each packed key (the '_' split
    # position) — lets the wire codec re-emit the two proto string
    # fields from the packed key without re-splitting.  Optional: only
    # the transport paths that re-encode need it.
    name_len: Optional[np.ndarray] = None
    # Arena-backed batches (fastwire.parse_req decoding into a
    # ColumnArena slab) carry their lease here; the serving edge calls
    # :meth:`release` once the tick has consumed the columns so the slab
    # recycles.  Plain batches carry None and release() is a no-op.
    lease: Optional["ArenaLease"] = field(default=None, repr=False)
    # Count of each algorithm value 0..ALGORITHM_MAX over the batch, as
    # the native decode counted it (fastwire.parse_req); None where the
    # columns came from anywhere else, and the reader counts itself.
    algo_hist: Optional[Sequence[int]] = field(default=None, repr=False)

    def __len__(self) -> int:
        return len(self.hits)

    def release(self) -> None:
        """Return the backing arena slab (idempotent; no-op when the
        batch owns its arrays).  After release the column views may be
        overwritten by a later window — callers release only once the
        engine has packed the batch into its own request matrix."""
        lease, self.lease = self.lease, None
        if lease is not None:
            lease.release()

    def key_bytes(self, j: int) -> bytes:
        o = self.key_offsets
        b = self.key_blob[o[j] : o[j + 1]]
        # Buffer-backed blobs (arena/shm views) slice to a view; the
        # error/retry paths that call this expect real bytes.
        return b if type(b) is bytes else bytes(b)

    @classmethod
    def empty(cls) -> "ReqColumns":
        return cls(
            b"", np.zeros(1, np.int64), _EMPTY_I64, _EMPTY_I64, _EMPTY_I64,
            _EMPTY_I64, _EMPTY_I64, _EMPTY_I64, _EMPTY_I64,
        )

    @classmethod
    def from_requests(
        cls, requests: Sequence[RateLimitRequest], keep_refs: bool = False
    ) -> "ReqColumns":
        """Bridge from the dataclass API (one attribute pass, no copies
        beyond the columns themselves)."""
        n = len(requests)
        if n == 0:
            return cls.empty()
        names = [r.name for r in requests]
        blob, offsets = key_blob_from_parts(
            names, [r.unique_key for r in requests]
        )
        name_len = np.fromiter(
            (len(nm.encode()) for nm in names), np.int64, count=n
        )
        hits, limit, duration, algo, behav, created, burst = zip(*(
            (
                r.hits, r.limit, r.duration, int(r.algorithm),
                int(r.behavior),
                CREATED_UNSET if r.created_at is None else r.created_at,
                r.burst,
            )
            for r in requests
        ))
        a = lambda v: np.asarray(v, np.int64)  # noqa: E731
        return cls(
            blob, offsets, a(hits), a(limit), a(duration),
            a(algo), a(behav), a(created), a(burst),
            refs=requests if keep_refs else None,
            name_len=name_len,
        )

    def slice_chunk(self, s: int, e: int) -> "ReqColumns":
        """Contiguous sub-batch [s, e) — numpy views plus one blob slice
        (chunking by the engine's max_batch)."""
        o = self.key_offsets
        return ReqColumns(
            self.key_blob[o[s] : o[e]],
            o[s : e + 1] - o[s],
            self.hits[s:e], self.limit[s:e], self.duration[s:e],
            self.algorithm[s:e], self.behavior[s:e],
            self.created_at[s:e], self.burst[s:e],
            refs=None if self.refs is None else self.refs[s:e],
            name_len=None if self.name_len is None else self.name_len[s:e],
        )

    @classmethod
    def concat(cls, parts: List["ReqColumns"]) -> "ReqColumns":
        """Merge batches (the tick loop coalescing several waiters into
        one tick).  Refs survive only if every part carries them."""
        if len(parts) == 1:
            return parts[0]
        if not parts:
            return cls.empty()
        sizes = [len(p) for p in parts]
        offsets = np.zeros(sum(sizes) + 1, np.int64)
        base = 0
        at = 1
        for p, sz in zip(parts, sizes):
            offsets[at : at + sz] = p.key_offsets[1:] + base
            base += p.key_offsets[-1]
            at += sz
        cat = lambda f: np.concatenate([getattr(p, f) for p in parts])  # noqa: E731
        refs: Optional[list] = []
        for p in parts:
            if p.refs is None:
                refs = None
                break
            refs.extend(p.refs)
        name_len = (
            cat("name_len")
            if all(p.name_len is not None for p in parts) else None
        )
        return cls(
            b"".join(p.key_blob for p in parts), offsets,
            cat("hits"), cat("limit"), cat("duration"), cat("algorithm"),
            cat("behavior"), cat("created_at"), cat("burst"), refs=refs,
            name_len=name_len,
        )


def pack_blob(keys: Sequence[bytes]) -> tuple[bytes, np.ndarray]:
    """Concatenate keys into the (blob, (n+1,) int64 offsets) wire format
    every blob consumer here expects (native slotmap, snapshots)."""
    offsets = np.zeros(len(keys) + 1, np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    return b"".join(keys), offsets


def compact_blob(
    blob: bytes, offsets: np.ndarray, keep: np.ndarray
) -> tuple[bytes, np.ndarray]:
    """Filter a (blob, offsets) key pack down to the keep-masked rows,
    fully vectorized (snapshot restore drops expired rows without a
    per-key Python loop)."""
    arr = np.frombuffer(blob, np.uint8)
    lens = np.diff(offsets)
    starts = offsets[:-1][keep]
    ls = lens[keep]
    cum = np.zeros(len(ls) + 1, np.int64)
    np.cumsum(ls, out=cum[1:])
    pos = (
        np.arange(int(cum[-1]), dtype=np.int64)
        - np.repeat(cum[:-1], ls)
        + np.repeat(starts, ls)
    )
    return arr[pos].tobytes(), cum


def key_blob_from_parts(
    names: Sequence[str], unique_keys: Sequence[str]
) -> tuple[bytes, np.ndarray]:
    """Build (blob, offsets) for ``name_uniquekey`` hash keys from parallel
    name/key sequences (transport parse path)."""
    return pack_blob(
        [(nm + "_" + uk).encode() for nm, uk in zip(names, unique_keys)]
    )


# ----------------------------------------------------------------------
# Ingest column arena: preallocated per-window decode slabs
# ----------------------------------------------------------------------
class IngestOverloadError(RuntimeError):
    """Every arena slab is busy AND the per-window plain-allocation
    fallback budget (GUBER_INGEST_FALLBACK_LIMIT) is spent.  The ingest
    edge answers this as backpressure — a retriable RESOURCE_EXHAUSTED
    shed — instead of letting overload grow the heap unboundedly
    (docs/overload.md)."""


class ArenaLease:
    """One leased slab of a :class:`ColumnArena` (views handed to the
    decoder plus the release token).  Thread-safe release; idempotent.

    ``addr`` is what the native decode takes in place of the arrays:
    ``(ints address, ints row stride in elements, flags address, blob
    address, rows the slab holds, blob bytes)`` — plain integers, made
    once a slab when the arena is built."""

    __slots__ = ("arena", "index", "ints", "flags", "blob", "addr")

    def __init__(self, arena: "ColumnArena", index: int,
                 ints: np.ndarray, flags: np.ndarray, blob: np.ndarray,
                 addr: tuple):
        self.arena = arena
        self.index = index
        self.ints = ints
        self.flags = flags
        self.blob = blob
        self.addr = addr

    def release(self) -> None:
        arena, self.arena = self.arena, None
        if arena is not None:
            arena._release(self.index)

    def cancel(self) -> None:
        """Hand the slab back unused: the decode found the batch wider
        than the slab.  Counted as the size miss it is, not as a lease,
        and no window completed, so the fallback budget stands."""
        arena, self.arena = self.arena, None
        if arena is not None:
            arena._cancel(self.index)


def slab_addr(ints: np.ndarray, flags: np.ndarray, blob: np.ndarray) -> tuple:
    """:attr:`ArenaLease.addr` of one decode block: ``ints`` is (9, rows
    + 1) int64 with contiguous rows, ``flags`` and ``blob`` contiguous
    uint8."""
    return (
        ints.ctypes.data, ints.strides[0] // 8, flags.ctypes.data,
        blob.ctypes.data, ints.shape[1] - 1, blob.shape[0],
    )


class ColumnArena:
    """Reusable, capacity-bounded decode slabs for the wire→columns edge.

    The serving fast path (transport/fastwire.parse_req) used to
    allocate a fresh ``(9, n+1)`` int64 block, a flags vector, and a
    key-blob staging buffer per request batch — at serving batch rates
    the allocator (and the page-zeroing behind ``np.zeros``) is a
    measurable slice of the 0.15 ms/batch serve CPU.  The arena
    preallocates ``slabs`` fixed-size buffer sets once and leases them
    per window; a leased slab's numpy views become the
    :class:`ReqColumns` columns directly (zero copies besides the key
    blob's bytes materialization, which the native slotmap requires).

    Bounded by construction: a batch wider than ``max_batch`` (or a key
    blob larger than the slab) hands its lease back, a lease request
    while every slab is busy (more concurrent in-flight windows than
    ``slabs``) returns None, and either way the caller falls back to
    plain allocation — the arena is a fast path, never a correctness
    constraint.  ``slabs`` should cover
    the tick pipeline depth plus decode concurrency
    (GUBER_INGEST_ARENA_SLABS; see docs/architecture.md, "The serving
    edge").
    """

    # Key-blob staging bytes per request row.  parse_req needs
    # len(data) + n staging bytes for a batch of n; hash keys in the
    # wild run tens of bytes, and oversized batches just fall back.
    BLOB_PER_ROW = 128

    def __init__(self, max_batch: int, slabs: int = 8,
                 fallback_limit: int = 32):
        self.max_batch = int(max_batch)
        self.n_slabs = max(1, int(slabs))
        self.blob_cap = self.max_batch * self.BLOB_PER_ROW
        self._ints = np.zeros(
            (self.n_slabs, 9, self.max_batch + 1), np.int64)
        self._flags = np.zeros((self.n_slabs, self.max_batch), np.uint8)
        self._blob = np.empty((self.n_slabs, self.blob_cap), np.uint8)
        self._addr = [
            slab_addr(self._ints[i], self._flags[i], self._blob[i])
            for i in range(self.n_slabs)
        ]
        self._busy = [False] * self.n_slabs
        self._next = 0
        self._lock = sanitize.lock("ColumnArena._lock")
        # Busy-slab plain-allocation fallback budget, per window: the
        # counter resets whenever a slab recycles (a window completed),
        # so sustained exhaustion — not a transient burst — is what
        # exhausts the budget and triggers shed (docs/overload.md).
        # The budget counts ROWS — ``fallback_limit`` full batches'
        # worth — because rows are what the heap grows by: counted in
        # frames, 41 concurrent one-item callers (8 slabs + 32 frames)
        # were shed as "overload" with 41 rows in flight on a
        # 4,096-row window.
        self._fallback_rows = max(0, int(fallback_limit)) * self.max_batch
        self._window_fallback_rows = 0
        # Telemetry: misses (all slabs busy / batch too big) say whether
        # the bound is sized to the deployment's concurrency;
        # fallbacks count the budgeted plain allocations taken while
        # every slab was busy (gubernator_tpu_arena_fallbacks).
        self.metric_leases = 0
        self.metric_misses = 0
        self.metric_fallbacks = 0

    @hot_path
    def lease(self) -> Optional[ArenaLease]:
        """A free slab, or None when every one is busy (the caller
        allocates, within :meth:`try_fallback`'s budget).  The decoder
        leases before it knows the batch's size (one native call counts
        and parses) and cancels (:meth:`ArenaLease.cancel`) the lease of
        a batch it finds wider than the slab.  The slab is not cleaned:
        the decoder zeroes the region it uses."""
        with self._lock:
            idx = -1
            for k in range(self.n_slabs):
                j = (self._next + k) % self.n_slabs
                if not self._busy[j]:
                    idx = j
                    break
            if idx < 0:
                self.metric_misses += 1
                return None
            self._busy[idx] = True
            self._next = (idx + 1) % self.n_slabs
            self.metric_leases += 1
        return ArenaLease(self, idx, self._ints[idx], self._flags[idx],
                          self._blob[idx], self._addr[idx])

    def fits(self, n: int, blob_cap: int) -> bool:
        """Whether an ``n``-row decode could EVER lease here — False is a
        size miss (plain allocation is the only option and stays
        uncapped); True with a failed lease is busy-slab exhaustion,
        which is what the fallback budget governs."""
        return n <= self.max_batch and blob_cap <= self.blob_cap

    @hot_path
    def try_fallback(self, n: int) -> bool:
        """Spend ``n`` rows of the per-window plain-allocation budget.
        False means the budget cannot cover them: the caller sheds with
        :class:`IngestOverloadError` semantics instead of allocating."""
        with self._lock:
            if self._window_fallback_rows + n > self._fallback_rows:
                return False
            self._window_fallback_rows += n
            self.metric_fallbacks += 1
            return True

    def _release(self, index: int) -> None:
        with self._lock:
            self._busy[index] = False
            self._window_fallback_rows = 0

    def _cancel(self, index: int) -> None:
        with self._lock:
            self._busy[index] = False
            self.metric_leases -= 1
            self.metric_misses += 1

    def in_use(self) -> int:
        with self._lock:
            return sum(self._busy)
