"""Native wire codec bindings: serialized pb ⇄ columns with no message
objects.

The serving path's CPU cost is per-request Python object churn through
protobuf message objects (PERF.md, ``serve_cpu_us_per_decision``); the
C++ codec (:file:`native/wirecodec.cc`) parses ``GetRateLimitsReq``
bytes straight into :class:`~gubernator_tpu.ops.reqcols.ReqColumns` and
emits ``GetRateLimitsResp`` bytes straight from the engine's (5, n)
response matrix.  Every entry point degrades gracefully: ``None`` (or
the numpy fallback) when the shared library is unavailable or the input
needs the object path.

Request-side semantics match :func:`transport.convert.columns_from_pb`
exactly (empty-name/key per-item errors, metadata/GLOBAL → special,
``created_at`` 0-or-absent → server stamps now); response encoding is
byte-identical to protobuf for items without error/metadata — proven
against the protobuf library in tests/test_fastwire.py.
"""

from __future__ import annotations

import ctypes
import threading
from typing import Dict, Optional, Tuple

import numpy as np

from gubernator_tpu import native as native_mod
from gubernator_tpu.algos import algorithm_error, invalid_algorithm_mask
from gubernator_tpu.ops.reqcols import (
    CREATED_UNSET,
    ColumnArena,
    IngestOverloadError,
    ReqColumns,
    slab_addr,
)
from gubernator_tpu.types import ALGORITHM_MAX, Behavior
from gubernator_tpu.utils.hotpath import hot_path

_I64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_U8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")

# out_flags bits (wirecodec.cc).
_NAME_EMPTY = 1
_KEY_EMPTY = 2
_HAS_METADATA = 4
_HAS_CREATED = 8

# Behaviors that force the object-routing path: GLOBAL (owner routing +
# reconcile queues) and MULTI_REGION (federation validation — the edge
# must reject it per-item when federation is off, which the columns
# fast path cannot express).
_SPECIAL_BEHAVIOR = int(Behavior.GLOBAL) | int(Behavior.MULTI_REGION)

# guber_decode_req's summary (wirecodec.cc kSum*): n, the OR of the
# item flags, any algorithm out of range, any special item, the key
# blob's length, then the count of each algorithm 0..ALGORITHM_MAX.
_ALGORITHM_MAX = int(ALGORITHM_MAX)
_Summary = ctypes.c_int64 * (5 + _ALGORITHM_MAX + 1)
_TOO_WIDE = -2

_lib = None
_load_attempted = False


class _Scratch(threading.local):
    """The encode's output buffer and over-limit cell, one a thread, the
    buffer grown to the widest call."""

    cap = 0

    def __init__(self):
        self.over = ctypes.c_int64()
        self.over_ref = ctypes.byref(self.over)

    def grow(self, cap: int) -> None:
        self.cap = max(cap, 2 * self.cap)
        self.buf = ctypes.create_string_buffer(self.cap)
        self.addr = ctypes.addressof(self.buf)


_scratch = _Scratch()


def load() -> Optional[ctypes.CDLL]:
    """The wire codec library (built alongside the slotmap; None when the
    toolchain/build is unavailable — callers fall back to protobuf)."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    so = native_mod.library_path("libguber_wire.so")
    if so is None:
        return None
    lib = ctypes.CDLL(so)
    lib.guber_wire_count.restype = ctypes.c_int64
    lib.guber_wire_count.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    # The two calls of the serving edge take addresses and integers:
    # nothing is converted a call (an ndpointer argument costs ~4 us).
    # They are bound through PyDLL and keep the GIL: ~50 and ~45 us a
    # 1,000-item call, against a hand-over each way when it is dropped.
    # On the chip the two read alike (PERF.md, PR 31), and held is the
    # one that leaves this thread nothing to wait for.  The count (~5
    # us) goes with them: a decode that found every slab busy makes it.
    held = ctypes.PyDLL(so)
    lib.guber_wire_count = held.guber_wire_count
    lib.guber_decode_req = held.guber_decode_req
    lib.guber_encode_resp_mat = held.guber_encode_resp_mat
    lib.guber_decode_req.restype = ctypes.c_int64
    lib.guber_decode_req.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.guber_encode_resp_mat.restype = ctypes.c_int64
    lib.guber_encode_resp_mat.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.guber_parse_resp.restype = ctypes.c_int64
    lib.guber_parse_resp.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        _I64, _I64, _I64, _I64, _U8,
    ]
    lib.guber_encode_req.restype = ctypes.c_int64
    lib.guber_encode_req.argtypes = [
        ctypes.c_char_p, _I64, _I64,
        _I64, _I64, _I64, _I64, _I64, _I64, _I64, _U8,
        ctypes.c_int64, _U8, ctypes.c_int64,
    ]
    _lib = lib
    return lib


@hot_path
def parse_req(
    data: bytes, arena: Optional[ColumnArena] = None,
) -> Optional[Tuple[ReqColumns, Dict[int, str], bool]]:
    """Serialized ``GetRateLimitsReq`` → (cols, per-item errors, special).

    ``special`` is True when any item carries GLOBAL or MULTI_REGION
    behavior or metadata (those route through the object path, which
    re-parses with protobuf — the codec records metadata *presence*
    only).  Returns None when the
    native library is unavailable or the bytes are malformed (caller
    falls back to ``pb.GetRateLimitsReq.FromString``).

    One native call (``guber_decode_req``) counts the items, parses them
    and reports what the edge and the service would otherwise walk the
    columns for: ``special``, whether any item needs an error string,
    and the algorithm histogram that rides on ``cols.algo_hist``.  Only
    a batch the arena cannot hold (too wide, or every slab busy) costs
    a second call, to count before it allocates.

    With ``arena`` (ops.reqcols.ColumnArena) the decode lands in a
    preallocated slab and the returned columns — key blob included —
    are views into it: zero per-window allocation and zero copies
    (the native slotmap resolves the blob view in place).  The
    caller owns the lease: ``cols.release()`` once the engine has
    packed the batch (an unreleased lease just falls back to plain
    allocation when the arena runs dry, never corrupts).  Oversized
    batches silently skip the arena."""
    lib = load()
    if lib is None:
        return None
    ln = len(data)
    if ln == 0:
        return ReqColumns.empty(), {}, False
    summary = _Summary()
    got = None
    lease = arena.lease() if arena is not None else None
    if lease is not None:
        ints, flags_full, blob = lease.ints, lease.flags, lease.blob
        got = lib.guber_decode_req(
            data, ln, *lease.addr, _ALGORITHM_MAX, _SPECIAL_BEHAVIOR,
            CREATED_UNSET, summary)
        if got == _TOO_WIDE:
            lease.cancel()
            lease = None
    if lease is None:
        n = summary[0] if got == _TOO_WIDE else lib.guber_wire_count(data, ln)
        if n < 0:
            return None
        blob_cap = ln + n
        # Bounded fallback (docs/overload.md): a size miss (batch wider
        # than any slab) always plain-allocates, but busy-slab
        # exhaustion spends the arena's per-window fallback budget —
        # past it, the edge sheds instead of growing the heap.
        if (arena is not None and arena.fits(n, blob_cap)
                and not arena.try_fallback(n)):
            raise IngestOverloadError(
                "ingest arena exhausted and fallback budget spent")
        # One block for all int64 outputs; the decode zeroes it.
        blob = np.empty(blob_cap, np.uint8)
        ints = np.empty((9, n + 1), np.int64)
        flags_full = np.empty(n, np.uint8)
        got = lib.guber_decode_req(
            data, ln, *slab_addr(ints, flags_full, blob), _ALGORITHM_MAX,
            _SPECIAL_BEHAVIOR, CREATED_UNSET, summary)
    if got < 0:
        if lease is not None:
            lease.release()
        return None
    n = got
    if n == 0:
        if lease is not None:
            lease.release()
        return ReqColumns.empty(), {}, False
    off = ints[8, : n + 1]
    name_len, hits, limit, duration, algorithm, behavior, burst, created = (
        ints[i, :n] for i in range(8)
    )
    errors: Dict[int, str] = {}
    if summary[1] & (_NAME_EMPTY | _KEY_EMPTY):
        flags = flags_full[:n]
        for i in np.flatnonzero(flags & (_NAME_EMPTY | _KEY_EMPTY)):
            errors[int(i)] = (
                "field 'unique_key' cannot be empty"
                if flags[i] & _KEY_EMPTY
                else "field 'namespace' cannot be empty"
            )
    # Out-of-range algorithm values must fail loudly here: the kernels'
    # branchless per-lane dispatch would otherwise silently run an
    # unknown enum as a token bucket (algos/__init__.py).
    if summary[2]:
        for i in np.flatnonzero(invalid_algorithm_mask(algorithm)):
            errors.setdefault(int(i), algorithm_error(algorithm[i]))
    # The key blob stays a view into the decode buffer — the last copy
    # on the decode path is gone.  Arena-backed batches alias the slab
    # (valid until cols.release(), same lifetime as the other columns);
    # the plain-allocation branch aliases the freshly-built buffer the
    # columns already own.
    cols = ReqColumns(
        blob[: summary[4]], off, hits, limit, duration,
        algorithm, behavior, created, burst, name_len=name_len,
        lease=lease, algo_hist=summary[5:],
    )
    return cols, errors, summary[3] != 0


def parse_resp(data: bytes) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Serialized ``GetRateLimitsResp`` / ``GetPeerRateLimitsResp`` →
    ((4, n) int64 matrix of status/limit/remaining/reset_time, (n,) bool
    mask of items that carry an error string or metadata — re-parse those
    with protobuf for the strings).  None when unavailable/malformed."""
    lib = load()
    if lib is None:
        return None
    ln = len(data)
    n = lib.guber_wire_count(data, ln)
    if n < 0:
        return None
    mat = np.zeros((4, max(n, 1)), np.int64)
    special = np.zeros(max(n, 1), np.uint8)
    if n:
        got = lib.guber_parse_resp(
            data, ln, mat[0], mat[1], mat[2], mat[3], special
        )
        if got != n:
            return None
    return mat[:, :n], special[:n].astype(bool)


def encode_req(cols: ReqColumns, tag_peer: bool = False) -> Optional[bytes]:
    """Columns → serialized ``GetRateLimitsReq`` bytes (the identical
    outer shape serves ``GetPeerRateLimitsReq``; ``tag_peer`` is accepted
    for call-site clarity only).  Requires ``cols.name_len``; returns
    None when it (or the library) is missing — callers fall back to
    message objects."""
    n = len(cols)
    if n == 0:
        return b""
    lib = load()
    if lib is None or cols.name_len is None:
        return None
    has_created = (cols.created_at != CREATED_UNSET).astype(np.uint8)
    off = np.ascontiguousarray(cols.key_offsets, np.int64)
    name_len = np.ascontiguousarray(cols.name_len, np.int64)
    cap = int(off[n]) + 16 * n + 128
    while True:
        out = np.empty(cap, np.uint8)
        wrote = lib.guber_encode_req(
            native_mod.as_char_p(cols.key_blob), off, name_len,
            np.ascontiguousarray(cols.hits, np.int64),
            np.ascontiguousarray(cols.limit, np.int64),
            np.ascontiguousarray(cols.duration, np.int64),
            np.ascontiguousarray(cols.algorithm, np.int64),
            np.ascontiguousarray(cols.behavior, np.int64),
            np.ascontiguousarray(cols.burst, np.int64),
            np.ascontiguousarray(cols.created_at, np.int64),
            has_created, n, out, cap,
        )
        if wrote >= 0:
            return out[:wrote].tobytes()
        if wrote == -1:
            return None
        cap = -wrote


@hot_path
def encode_resp(mat: np.ndarray) -> Tuple[bytes, int]:
    """(5, n) response matrix → (serialized ``GetRateLimitsResp`` bytes,
    the sum of row 4: the call's over-limit count, error lanes and all —
    a caller with per-item errors does not encode).  Native when
    available, else the vectorized numpy encoder
    (:func:`transport.wire.encode_get_rate_limits_resp`) — identical
    bytes either way.

    One native call reads the matrix where it lies: the tick loop hands
    out column slices of one int64 matrix, whose rows are contiguous
    and one row stride apart.  Anything else is copied into that shape
    first."""
    n = mat.shape[1]
    if n == 0:
        return b"", 0
    lib = load()
    if lib is None:
        from gubernator_tpu.transport.wire import encode_get_rate_limits_resp

        return encode_get_rate_limits_resp(mat), int(mat[4].sum())
    if mat.dtype != np.int64 or mat.strides[1] != 8:
        mat = np.ascontiguousarray(mat, np.int64)
    # Worst case per item: 44 B payload (4 fields x (1 tag + 10 B
    # varint)) + 2 B item header (1 B tag + 1 B length varint, since
    # payload <= 44 < 128) = 46 B, so the buffer cannot be too small.
    scratch = _scratch
    if scratch.cap < 46 * n:
        scratch.grow(46 * n)
    wrote = lib.guber_encode_resp_mat(
        mat.__array_interface__["data"][0], mat.strides[0] // 8, n,
        scratch.addr, scratch.cap, scratch.over_ref)
    return ctypes.string_at(scratch.addr, wrote), scratch.over.value


# ----------------------------------------------------------------------
# Quota-lease frames (docs/leases.md).
#
# Lease traffic happens at lease EDGES (grant, expiry, exhaustion,
# release) — orders of magnitude rarer than decisions — so these frames
# are pure-Python struct codecs, not native: the codec cost is
# irrelevant, while the native library must stay optional.  All frames
# are little-endian with a 4-byte magic + u32 count header; parsers
# return None on a magic/length mismatch (callers treat that exactly
# like a malformed protobuf: reject the RPC).

import struct as _struct

# Request frames are v2: they carry the leaseholder identity (the
# server accounts per-holder slices — docs/leases.md).  Parsers still
# accept the v1 frames (no holder field → the shared "" identity) so a
# not-yet-upgraded client keeps working against a v2 server.
_LEASE_GRANT_REQ_MAGIC = b"GLR2"
_LEASE_GRANT_REQ_MAGIC_V1 = b"GLR1"
_LEASE_GRANT_RESP_MAGIC = b"GLT1"
_LEASE_SYNC_REQ_MAGIC = b"GSY2"
_LEASE_SYNC_REQ_MAGIC_V1 = b"GSY1"
_LEASE_SYNC_RESP_MAGIC = b"GSA1"


def _pack_str(s: str) -> bytes:
    b = s.encode()
    return _struct.pack("<H", len(b)) + b


def _unpack_str(data: bytes, off: int):
    (ln,) = _struct.unpack_from("<H", data, off)
    off += 2
    return data[off : off + ln].decode(), off + ln


def encode_lease_grant_req(specs) -> bytes:
    """[LeaseSpec] → LeaseGrant request frame."""
    parts = [_LEASE_GRANT_REQ_MAGIC, _struct.pack("<I", len(specs))]
    for s in specs:
        parts.append(_struct.pack(
            "<qqqqq", s.limit, s.duration, s.algorithm, s.burst, s.want))
        parts.append(_pack_str(s.name))
        parts.append(_pack_str(s.key))
        parts.append(_pack_str(s.holder))
    return b"".join(parts)


def parse_lease_grant_req(data: bytes):
    """LeaseGrant request frame → [LeaseSpec] (None when malformed)."""
    from gubernator_tpu.leases.protocol import LeaseSpec

    try:
        magic = data[:4]
        if magic not in (_LEASE_GRANT_REQ_MAGIC,
                         _LEASE_GRANT_REQ_MAGIC_V1):
            return None
        v1 = magic == _LEASE_GRANT_REQ_MAGIC_V1
        (n,) = _struct.unpack_from("<I", data, 4)
        off = 8
        out = []
        for _ in range(n):
            limit, duration, algo, burst, want = _struct.unpack_from(
                "<qqqqq", data, off)
            off += 40
            name, off = _unpack_str(data, off)
            key, off = _unpack_str(data, off)
            holder = ""
            if not v1:
                holder, off = _unpack_str(data, off)
            out.append(LeaseSpec(
                name=name, key=key, limit=limit, duration=duration,
                algorithm=algo, burst=burst, want=want, holder=holder))
        return out if off == len(data) else None
    except (_struct.error, IndexError, UnicodeDecodeError):
        return None


def encode_lease_grant_resp(tokens) -> bytes:
    """[Optional[LeaseToken]] → LeaseGrant response frame (a None slot
    is an explicit declined marker: the bucket was too hot to delegate
    and the client must fall back to per-request decisions)."""
    parts = [_LEASE_GRANT_RESP_MAGIC, _struct.pack("<I", len(tokens))]
    for t in tokens:
        if t is None:
            parts.append(b"\x00")
            continue
        parts.append(b"\x01")
        parts.append(_struct.pack("<qqq", t.budget, t.expires_ms,
                                  t.generation))
        parts.append(_pack_str(t.name))
        parts.append(_pack_str(t.key))
        parts.append(_struct.pack("<H", len(t.signature)))
        parts.append(t.signature)
    return b"".join(parts)


def parse_lease_grant_resp(data: bytes):
    """LeaseGrant response frame → [Optional[LeaseToken]]."""
    from gubernator_tpu.leases.protocol import LeaseToken

    try:
        if data[:4] != _LEASE_GRANT_RESP_MAGIC:
            return None
        (n,) = _struct.unpack_from("<I", data, 4)
        off = 8
        out = []
        for _ in range(n):
            present = data[off]
            off += 1
            if not present:
                out.append(None)
                continue
            budget, expires_ms, gen = _struct.unpack_from("<qqq", data, off)
            off += 24
            name, off = _unpack_str(data, off)
            key, off = _unpack_str(data, off)
            (siglen,) = _struct.unpack_from("<H", data, off)
            off += 2
            sig = data[off : off + siglen]
            off += siglen
            out.append(LeaseToken(
                name=name, key=key, budget=budget, expires_ms=expires_ms,
                generation=gen, signature=sig))
        return out if off == len(data) else None
    except (_struct.error, IndexError, UnicodeDecodeError):
        return None


def encode_lease_sync_req(syncs) -> bytes:
    """[LeaseSync] → LeaseSync request frame."""
    parts = [_LEASE_SYNC_REQ_MAGIC, _struct.pack("<I", len(syncs))]
    for s in syncs:
        parts.append(_struct.pack(
            "<qqB", s.consumed, s.generation, 1 if s.release else 0))
        parts.append(_pack_str(s.name))
        parts.append(_pack_str(s.key))
        parts.append(_pack_str(s.holder))
    return b"".join(parts)


def parse_lease_sync_req(data: bytes):
    """LeaseSync request frame → [LeaseSync]."""
    from gubernator_tpu.leases.protocol import LeaseSync

    try:
        magic = data[:4]
        if magic not in (_LEASE_SYNC_REQ_MAGIC,
                         _LEASE_SYNC_REQ_MAGIC_V1):
            return None
        v1 = magic == _LEASE_SYNC_REQ_MAGIC_V1
        (n,) = _struct.unpack_from("<I", data, 4)
        off = 8
        out = []
        for _ in range(n):
            consumed, gen, release = _struct.unpack_from("<qqB", data, off)
            off += 17
            name, off = _unpack_str(data, off)
            key, off = _unpack_str(data, off)
            holder = ""
            if not v1:
                holder, off = _unpack_str(data, off)
            out.append(LeaseSync(
                name=name, key=key, consumed=consumed, generation=gen,
                release=bool(release), holder=holder))
        return out if off == len(data) else None
    except (_struct.error, IndexError, UnicodeDecodeError):
        return None


def encode_lease_sync_resp(acks) -> bytes:
    """[LeaseSyncAck] → LeaseSync response frame."""
    parts = [_LEASE_SYNC_RESP_MAGIC, _struct.pack("<I", len(acks))]
    for a in acks:
        parts.append(_struct.pack(
            "<Bqqq", 1 if a.accepted else 0, a.generation,
            a.credited, a.charged))
    return b"".join(parts)


def parse_lease_sync_resp(data: bytes):
    """LeaseSync response frame → [LeaseSyncAck]."""
    from gubernator_tpu.leases.protocol import LeaseSyncAck

    try:
        if data[:4] != _LEASE_SYNC_RESP_MAGIC:
            return None
        (n,) = _struct.unpack_from("<I", data, 4)
        off = 8
        out = []
        for _ in range(n):
            accepted, gen, credited, charged = _struct.unpack_from(
                "<Bqqq", data, off)
            off += 25
            out.append(LeaseSyncAck(
                accepted=bool(accepted), generation=gen,
                credited=credited, charged=charged))
        return out if off == len(data) else None
    except (_struct.error, IndexError, UnicodeDecodeError):
        return None


# ----------------------------------------------------------------------
# Multi-region federation frames (docs/federation.md).
#
# Envelope exchange happens once per GUBER_FEDERATION_INTERVAL per remote
# region — WAN cadence, not decision cadence — so like the lease frames
# these are pure-Python struct codecs.  The version rides the magic
# (GFE1/GFA1): a receiver that doesn't recognize the magic rejects the
# RPC, which the sender's breaker/redelivery path treats like any other
# failure — a mixed-version fleet degrades to intra-region-only instead
# of corrupting state.

_FED_ENVELOPE_MAGIC = b"GFE1"
_FED_ACK_MAGIC = b"GFA1"


def encode_federation_envelope(env) -> bytes:
    """FederationEnvelope → GFE1 frame."""
    parts = [
        _FED_ENVELOPE_MAGIC,
        _struct.pack("<q", env.seq),
        _pack_str(env.origin),
        _pack_str(env.region),
        _pack_str(env.epoch),
        _struct.pack("<I", len(env.records)),
    ]
    for rec in env.records:
        parts.append(_struct.pack(
            "<qqqqqqq", rec.hits, rec.limit, rec.duration, rec.algorithm,
            rec.behavior, rec.burst, rec.created_at))
        parts.append(_pack_str(rec.name))
        parts.append(_pack_str(rec.unique_key))
    return b"".join(parts)


def parse_federation_envelope(data: bytes):
    """GFE1 frame → FederationEnvelope (None when malformed)."""
    from gubernator_tpu.federation.envelope import (
        FederationEnvelope,
        FederationRecord,
    )

    try:
        if data[:4] != _FED_ENVELOPE_MAGIC:
            return None
        (seq,) = _struct.unpack_from("<q", data, 4)
        off = 12
        origin, off = _unpack_str(data, off)
        region, off = _unpack_str(data, off)
        epoch, off = _unpack_str(data, off)
        (n,) = _struct.unpack_from("<I", data, off)
        off += 4
        records = []
        for _ in range(n):
            hits, limit, duration, algo, behavior, burst, created = (
                _struct.unpack_from("<qqqqqqq", data, off))
            off += 56
            name, off = _unpack_str(data, off)
            key, off = _unpack_str(data, off)
            records.append(FederationRecord(
                name=name, unique_key=key, hits=hits, limit=limit,
                duration=duration, algorithm=algo, behavior=behavior,
                burst=burst, created_at=created))
        env = FederationEnvelope(
            origin=origin, region=region, epoch=epoch, seq=seq,
            records=records)
        return env if off == len(data) else None
    except (_struct.error, IndexError, UnicodeDecodeError):
        return None


def encode_federation_ack(ack) -> bytes:
    """FederationAck → GFA1 frame."""
    return b"".join([
        _FED_ACK_MAGIC,
        _struct.pack("<qq", ack.seq, ack.applied),
        _pack_str(ack.origin),
    ])


def parse_federation_ack(data: bytes):
    """GFA1 frame → FederationAck (None when malformed)."""
    from gubernator_tpu.federation.envelope import FederationAck

    try:
        if data[:4] != _FED_ACK_MAGIC:
            return None
        seq, applied = _struct.unpack_from("<qq", data, 4)
        off = 20
        origin, off = _unpack_str(data, off)
        ack = FederationAck(origin=origin, seq=seq, applied=applied)
        return ack if off == len(data) else None
    except (_struct.error, IndexError, UnicodeDecodeError):
        return None
