"""Shared-memory slab rings: the edge plane's cross-process wire format.

One shm segment per edge worker, fully described by ``(max_batch,
slabs, depth)`` so the owner and the (jax-free) child process map
byte-identical views:

    control block   16 int64   magic/version/shape, generation, stop flag
    counter block   32 float64 worker-written telemetry (owner reads)
    request ring    ``slabs``  REQ32 decode slabs, worker → owner (SPSC)
    response ring   ``depth``  (5, max_batch) response slots, owner → worker

A request slab mirrors a :class:`~gubernator_tpu.ops.reqcols.ColumnArena`
slab exactly — ``(9, max_batch+1)`` int64 (row 8 = key-blob offsets), a
flags vector, and ``max_batch * BLOB_PER_ROW`` staging bytes — so
``fastwire.parse_req`` decodes straight into shared memory and the owner
rebuilds :class:`ReqColumns` as zero-copy views, key blob included.

SPSC discipline: each ring has exactly one producer and one consumer,
both advancing a private cursor and communicating only through the
per-slot ``state`` word.  The producer writes the payload first and
flips ``state`` last; the consumer reads ``state`` first.  CPython's
eval loop orders the stores and x86-TSO keeps them ordered across
cores; slabs are only reused after the consumer flips the state back,
so a torn window cannot be observed.  Crash recovery never relies on
ring state: the owner zeroes both rings and bumps ``generation`` before
respawning a worker, and stale-generation traffic is dropped on read.
"""

from __future__ import annotations

import gc
from typing import Optional, Tuple

import numpy as np

from gubernator_tpu.ops.reqcols import ColumnArena
from gubernator_tpu.utils import sanitize
from gubernator_tpu.utils.hotpath import hot_path

MAGIC = 0x45444745  # "EDGE"
LAYOUT_VERSION = 1

BLOB_PER_ROW = ColumnArena.BLOB_PER_ROW

# Control block words (16 int64).
CTRL_MAGIC = 0
CTRL_VERSION = 1
CTRL_MAX_BATCH = 2
CTRL_SLABS = 3
CTRL_DEPTH = 4
CTRL_GENERATION = 5
CTRL_STOP = 6
CTRL_WORKER_PID = 7
CTRL_READY = 8    # worker: attached + warmed, waiting for GO
CTRL_GO = 9       # owner: start the drive clock (drive-mode start barrier)
CTRL_REQ_AT = 10  # respawn handoff: where the next publish must land
CTRL_RESP_AT = 11  # respawn handoff: where the next response will land
CTRL_WORDS = 16

# Worker-written counters (32 float64; the owner only ever reads, so no
# cross-process atomicity is needed — each index has a single writer).
C_DECODE_SECONDS = 0
C_DECODE_BATCHES = 1
C_ROWS_DECODED = 2
C_WIN_PUBLISHED = 3
C_ROWS_PUBLISHED = 4
C_HITS_PUBLISHED = 5
C_WIN_ACKED = 6
C_ROWS_ACKED = 7
C_HITS_ACKED = 8
C_ERR_ROWS = 9
C_DOUBLE_SERVED = 10
C_BACKPRESSURE_WAITS = 11
C_SHED_LOCAL = 12
C_WIRE_BYTES_IN = 13
C_WIRE_BYTES_OUT = 14
C_DRIVE_DONE = 15
N_COUNTERS = 32

# Request-slab header words (8 int64 per slab).
RQ_STATE = 0          # FREE / PUBLISHED
RQ_SEQNO = 1
RQ_ROWS = 2
RQ_BLOB_LEN = 3
RQ_DEADLINE_NS = 4    # absolute CLOCK_MONOTONIC ns (system-wide on Linux)
RQ_DECODE_NS = 5      # decode duration, stamped by the worker
RQ_GENERATION = 6
RQ_WORDS = 8

# Response-slot header words (8 int64 per slot).
RS_STATE = 0          # FREE / PUBLISHED
RS_SEQNO = 1
RS_ROWS = 2
RS_ERR_COUNT = 3
RS_ERR_LEN = 4
RS_GENERATION = 5
RS_STATUS = 6         # RESP_OK / RESP_SHED
RS_WORDS = 8

FREE = 0
PUBLISHED = 1
LEASED = 2  # request slabs only: popped by the owner, not yet released

RESP_OK = 0
RESP_SHED = 1         # window shed (retriable; every row carries an error)

# Per-row budget for encoded error records in a response slot: errors are
# the exception path (shed windows, table-full items), and records past
# the budget degrade to a truncated string, never a lost error.
ERR_RECORD_BYTES = 112


def _pad8(n: int) -> int:
    return (n + 7) & ~7


class EdgeSegment:
    """Typed numpy views over one worker's shm segment.

    The owner constructs with ``create=True`` (and owns unlink); the
    child attaches by name.  Attach in children goes through
    :func:`attach_segment`, which un-registers the mapping from the
    multiprocessing resource tracker so a worker exit (or SIGKILL — the
    chaos case) can never tear down a segment the owner still serves
    from.
    """

    def __init__(self, name: Optional[str], max_batch: int, slabs: int,
                 depth: int, create: bool, shm=None):
        from multiprocessing import shared_memory

        self.max_batch = int(max_batch)
        self.slabs = int(slabs)
        self.depth = int(depth)
        self.blob_cap = self.max_batch * BLOB_PER_ROW
        self.err_cap = _pad8(8 + self.max_batch * ERR_RECORD_BYTES)
        if shm is not None:
            self.shm = shm
        elif create:
            self.shm = shared_memory.SharedMemory(
                name=name, create=True, size=self.total_size()
            )
        else:
            self.shm = shared_memory.SharedMemory(name=name)
        buf = self.shm.buf
        at = 0

        def view(dtype, shape):
            nonlocal at
            count = int(np.prod(shape))
            a = np.frombuffer(buf, dtype, count=count, offset=at)
            at += a.nbytes
            at = _pad8(at)
            return a.reshape(shape)

        mb, sl, dp = self.max_batch, self.slabs, self.depth
        self.ctrl = view(np.int64, (CTRL_WORDS,))
        self.counters = view(np.float64, (N_COUNTERS,))
        self.req_hdr = view(np.int64, (sl, RQ_WORDS))
        self.req_ints = view(np.int64, (sl, 9, mb + 1))
        self.req_flags = view(np.uint8, (sl, _pad8(mb)))
        self.req_blob = view(np.uint8, (sl, self.blob_cap))
        self.resp_hdr = view(np.int64, (dp, RS_WORDS))
        self.resp_mat = view(np.int64, (dp, 5, mb))
        self.resp_err = view(np.uint8, (dp, self.err_cap))
        assert at <= self.shm.size
        if create:
            self.ctrl[CTRL_MAGIC] = MAGIC
            self.ctrl[CTRL_VERSION] = LAYOUT_VERSION
            self.ctrl[CTRL_MAX_BATCH] = mb
            self.ctrl[CTRL_SLABS] = sl
            self.ctrl[CTRL_DEPTH] = dp
            self.ctrl[CTRL_GENERATION] = 1
        else:
            if int(self.ctrl[CTRL_MAGIC]) != MAGIC or (
                int(self.ctrl[CTRL_MAX_BATCH]) != mb
                or int(self.ctrl[CTRL_SLABS]) != sl
                or int(self.ctrl[CTRL_DEPTH]) != dp
            ):
                raise ValueError(
                    f"edge segment {self.shm.name} layout mismatch"
                )

    def total_size(self) -> int:
        mb, sl, dp = self.max_batch, self.slabs, self.depth
        return (
            _pad8(CTRL_WORDS * 8)
            + _pad8(N_COUNTERS * 8)
            + sl * (RQ_WORDS * 8 + 9 * (mb + 1) * 8 + _pad8(mb)
                    + self.blob_cap)
            + dp * (RS_WORDS * 8 + 5 * mb * 8 + self.err_cap)
        )

    # Views hold exported pointers into shm.buf; drop them before close()
    # or BufferError ("cannot close exported pointers exist").
    def _drop_views(self) -> None:
        for f in ("ctrl", "counters", "req_hdr", "req_ints", "req_flags",
                  "req_blob", "resp_hdr", "resp_mat", "resp_err"):
            if hasattr(self, f):
                delattr(self, f)

    def close(self) -> None:
        self._drop_views()
        try:
            self.shm.close()
        except BufferError:
            # A ReqColumns view in an unreachable cycle (future ->
            # done-callback -> columns) can outlive its drop; collect,
            # then retry.  A genuinely live view still pins the mapping
            # — swallow, unlink below works regardless.
            gc.collect()
            try:
                self.shm.close()
            except BufferError:
                pass

    def unlink(self) -> None:
        try:
            self.shm.unlink()
        except FileNotFoundError:
            pass


def attach_segment(name: str, max_batch: int, slabs: int,
                   depth: int) -> EdgeSegment:
    """Child-side attach, un-registered from the resource tracker (the
    owner created the segment and owns its lifetime; without this, any
    worker death — including the deliberate SIGKILL chaos path — would
    let the tracker unlink a segment that is still serving).  The
    registration is suppressed around the attach rather than undone
    after it: the spawn child shares the owner's tracker process, whose
    name cache is a set, so a child-side unregister would erase the
    owner's own registration and turn the owner's unlink into a tracker
    KeyError."""
    from multiprocessing import resource_tracker, shared_memory

    orig_register = resource_tracker.register
    resource_tracker.register = lambda *a, **k: None
    try:
        shm = shared_memory.SharedMemory(name=name)
    finally:
        resource_tracker.register = orig_register
    return EdgeSegment(None, max_batch, slabs, depth, create=False, shm=shm)


class RequestRing:
    """The worker→owner slab ring of one segment (SPSC).

    Producer side (worker): :meth:`try_claim` a FREE slab at the write
    cursor, decode into its views, :meth:`publish`.  Consumer side
    (owner): :meth:`pop_published` in ring order marks the slab LEASED;
    it returns to FREE via :meth:`free` only after the tick loop has
    packed the window — exactly the ``TickLoop._flush``
    release-after-pack timing, carried by the :class:`ShmSlabLease`
    attached to the drained ``ReqColumns``.  Slab states move
    FREE → PUBLISHED (worker) → LEASED → FREE (owner), each transition
    single-writer.
    """

    def __init__(self, seg: EdgeSegment):
        self.seg = seg
        self.hdr = seg.req_hdr
        self.slabs = seg.slabs
        self.write_at = 0
        self.read_at = 0
        # None unless GUBER_SANITIZERS=1 (docs/concurrency.md): per-ring
        # single-writer checker, one attribute test on the off path.
        self._san = sanitize.ring_sanitizer(f"RequestRing[{seg.shm.name}]")

    # -- producer (worker process) -------------------------------------
    def try_claim(self) -> Optional[int]:
        """Index of the slab at the write cursor if FREE, else None
        (ring full — the worker's per-producer backpressure bound)."""
        idx = self.write_at
        if int(self.hdr[idx, RQ_STATE]) != FREE:
            return None
        return idx

    @hot_path
    def publish(self, idx: int, seqno: int, rows: int, blob_len: int,
                deadline_ns: int, decode_ns: int, generation: int) -> None:
        """Hand a decoded slab to the owner: header payload first, the
        state flip last (the SPSC ordering contract), cursor advance."""
        h = self.hdr[idx]
        h[RQ_SEQNO] = seqno
        h[RQ_ROWS] = rows
        h[RQ_BLOB_LEN] = blob_len
        h[RQ_DEADLINE_NS] = deadline_ns
        h[RQ_DECODE_NS] = decode_ns
        h[RQ_GENERATION] = generation
        if self._san is not None:
            self._san.note_publish(idx)
        h[RQ_STATE] = PUBLISHED
        self.write_at = (idx + 1) % self.slabs

    # -- consumer (owner process) --------------------------------------
    @hot_path
    def pop_published(self) -> Optional[Tuple[int, int, int, int, int, int, int]]:
        """The next published slab in ring order as ``(idx, seqno, rows,
        blob_len, deadline_ns, decode_ns, generation)``, or None when the
        ring is quiet.  The slab moves PUBLISHED → LEASED: still owned by
        the tick loop's zero-copy views, not claimable by the worker, and
        — critically — not poppable again when the read cursor wraps a
        full ring of in-flight slabs.  :meth:`free` returns it to FREE."""
        idx = self.read_at
        h = self.hdr[idx]
        if int(h[RQ_STATE]) != PUBLISHED:
            return None
        if self._san is not None:
            self._san.note_pop(idx)
        h[RQ_STATE] = LEASED
        self.read_at = (idx + 1) % self.slabs
        return (
            idx, int(h[RQ_SEQNO]), int(h[RQ_ROWS]), int(h[RQ_BLOB_LEN]),
            int(h[RQ_DEADLINE_NS]), int(h[RQ_DECODE_NS]),
            int(h[RQ_GENERATION]),
        )

    def free(self, idx: int) -> None:
        if self._san is not None:
            self._san.note_free(
                idx, int(self.hdr[idx, RQ_STATE]) == PUBLISHED
            )
        self.hdr[idx, RQ_STATE] = FREE

    def reset(self) -> None:
        """Crash recovery: drop every in-flight slab and rewind both
        cursors (the owner bumps the generation around this)."""
        if self._san is not None:
            self._san.note_reset()
        self.hdr[:] = 0
        self.write_at = 0
        self.read_at = 0

    def detach(self) -> None:
        """Drop the shm views so the segment's mmap can close."""
        self.hdr = None
        self.seg = None


class ShmSlabLease:
    """Release token carried by a drained window's ``ReqColumns.lease``
    slot — duck-typed to :class:`ops.reqcols.ArenaLease` so the tick
    loop's release-after-pack call returns the shm slab to the worker
    without knowing it crossed a process boundary.  Idempotent."""

    __slots__ = ("ring", "index")

    def __init__(self, ring: RequestRing, index: int):
        self.ring = ring
        self.index = index

    def release(self) -> None:
        ring, self.ring = self.ring, None
        if ring is not None:
            ring.free(self.index)


class ResponseRing:
    """The owner→worker response ring of one segment (SPSC at the slot
    level; the owner side serializes its writers — tick-resolver and
    shed paths both complete futures — behind the plane's per-worker
    lock)."""

    def __init__(self, seg: EdgeSegment):
        self.seg = seg
        self.hdr = seg.resp_hdr
        self.mat = seg.resp_mat
        self.err = seg.resp_err
        self.depth = seg.depth
        self.write_at = 0
        self.read_at = 0
        # Consumer-side pin only: the producer side is deliberately
        # multi-thread (tick-resolver and shed paths), serialized by
        # the plane's per-worker lock rather than a thread pin.
        self._san = sanitize.ring_sanitizer(f"ResponseRing[{seg.shm.name}]")

    # -- producer (owner process) --------------------------------------
    def try_publish(self, seqno: int, rows: int, mat: np.ndarray,
                    err_blob: bytes, err_count: int, generation: int,
                    status: int) -> bool:
        """Write one window's response; False when the slot at the write
        cursor is still unconsumed (only reachable when the worker died
        — the live worker bounds its outstanding windows to the ring
        depth — so the caller counts a dropped response and moves on)."""
        idx = self.write_at
        h = self.hdr[idx]
        if int(h[RS_STATE]) != FREE:
            return False
        self.mat[idx, :, :rows] = mat
        if err_blob:
            self.err[idx, : len(err_blob)] = np.frombuffer(err_blob, np.uint8)
        h[RS_SEQNO] = seqno
        h[RS_ROWS] = rows
        h[RS_ERR_COUNT] = err_count
        h[RS_ERR_LEN] = len(err_blob)
        h[RS_GENERATION] = generation
        h[RS_STATUS] = status
        h[RS_STATE] = PUBLISHED
        self.write_at = (idx + 1) % self.depth
        return True

    # -- consumer (worker process) -------------------------------------
    def poll(self):
        """The next response in ring order as ``(seqno, rows, mat_view,
        err_count, err_blob_bytes, generation, status)`` or None; the
        caller must finish with the views before :meth:`free_slot`."""
        idx = self.read_at
        h = self.hdr[idx]
        if int(h[RS_STATE]) != PUBLISHED:
            return None
        rows = int(h[RS_ROWS])
        err_len = int(h[RS_ERR_LEN])
        if self._san is not None:
            self._san.note_pop(idx)
        out = (
            int(h[RS_SEQNO]), rows, self.mat[idx, :, :rows],
            int(h[RS_ERR_COUNT]), bytes(self.err[idx, :err_len]),
            int(h[RS_GENERATION]), int(h[RS_STATUS]), idx,
        )
        self.read_at = (idx + 1) % self.depth
        return out

    def free_slot(self, idx: int) -> None:
        if self._san is not None:
            # A polled slot sits in the lease set; freeing a PUBLISHED
            # slot that was never polled drops a response on the floor.
            self._san.note_free(
                idx, int(self.hdr[idx, RS_STATE]) == PUBLISHED
            )
        self.hdr[idx, RS_STATE] = FREE

    def reset(self) -> None:
        if self._san is not None:
            self._san.note_reset()
        self.hdr[:] = 0
        self.write_at = 0
        self.read_at = 0

    def detach(self) -> None:
        """Drop the shm views so the segment's mmap can close."""
        self.hdr = None
        self.mat = None
        self.err = None
        self.seg = None


def encode_errors(errors: dict) -> Tuple[bytes, int]:
    """Pack a per-item error dict (``{row: message}``) into the response
    slot's record blob: ``count`` u32 little-endian records of
    ``(row u32, len u32, utf-8 bytes)``.  Messages survive byte-exact —
    the wire contract's per-item error strings (engine table-full, the
    PR 9 retriable shed messages) must not be lossy across the shm hop."""
    if not errors:
        return b"", 0
    parts = []
    for i, msg in errors.items():
        b = msg.encode()[: ERR_RECORD_BYTES - 8]
        parts.append(int(i).to_bytes(4, "little"))
        parts.append(len(b).to_bytes(4, "little"))
        parts.append(b)
    return b"".join(parts), len(errors)


def decode_errors(blob: bytes, count: int) -> dict:
    """Inverse of :func:`encode_errors`."""
    errors = {}
    at = 0
    for _ in range(count):
        row = int.from_bytes(blob[at : at + 4], "little")
        ln = int.from_bytes(blob[at + 4 : at + 8], "little")
        at += 8
        errors[row] = blob[at : at + ln].decode()
        at += ln
    return errors
