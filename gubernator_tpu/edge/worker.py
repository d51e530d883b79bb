"""Edge worker child process: fastwire decode into shared-memory slabs.

The worker owns the producer side of one segment's request ring and the
consumer side of its response ring (:mod:`gubernator_tpu.edge.shmring`).
It NEVER imports jax — the import chain is numpy + protobuf + the native
wire codec, so a child spawns in well under a second and its crash
surface is disjoint from the device runtime.

Two modes share the decode/publish/ack core:

* ``socket`` — the daemon-facing ingest surface: a Unix-domain listener
  speaking length-prefixed fastwire frames (4-byte LE length +
  serialized ``GetRateLimitsReq``; responses mirror the framing with
  ``GetRateLimitsResp`` bytes).  Many clients per worker; responses are
  routed back by publish order.
* ``drive`` — a self-generating loopback load source for
  tests/test_edge.py and the chaos tests: pre-encodes frames once,
  then decode→publish→ack as fast as the rings allow, accounting every
  window through the shm counter block so the owner can check the
  exact-work invariants (parity / double-serve / dropped-ack) without
  trusting the worker's stdout.

Backpressure is per-producer by construction: a worker blocks on its own
ring (slab exhaustion) and its own response depth, never on another
worker's traffic.
"""

from __future__ import annotations

import os
import selectors
import signal
import socket
import struct
import time
from typing import Dict, Optional

import numpy as np

from gubernator_tpu.edge import shmring
from gubernator_tpu.edge.shmring import (
    CTRL_REQ_AT,
    CTRL_RESP_AT,
    C_BACKPRESSURE_WAITS,
    C_DECODE_BATCHES,
    C_DECODE_SECONDS,
    C_DOUBLE_SERVED,
    C_DRIVE_DONE,
    C_ERR_ROWS,
    C_HITS_ACKED,
    C_HITS_PUBLISHED,
    C_ROWS_ACKED,
    C_ROWS_DECODED,
    C_ROWS_PUBLISHED,
    C_SHED_LOCAL,
    C_WIN_ACKED,
    C_WIN_PUBLISHED,
    C_WIRE_BYTES_IN,
    C_WIRE_BYTES_OUT,
    CTRL_GENERATION,
    CTRL_GO,
    CTRL_READY,
    CTRL_STOP,
    CTRL_WORKER_PID,
    RESP_OK,
)
from gubernator_tpu.ops.reqcols import (
    CREATED_UNSET,
    IngestOverloadError,
    ReqColumns,
    key_blob_from_parts,
    slab_addr,
)
from gubernator_tpu.transport import fastwire

_LEN = struct.Struct("<I")

# The worker's local shed message mirrors the PR 9 admission-plane
# convention (retriable, names the stage) without importing the serving
# stack into the child.
SHED_EDGE_MSG = "request shed: edge worker slab ring exhausted (retriable)"
OVERSIZE_MSG = "batch exceeds the edge plane's max_batch; use the gRPC path"


class _WorkerSlabLease:
    """ArenaLease stand-in for ``fastwire.parse_req`` decoding into a
    ring slab.  Claiming never touches shm state (the slab stays FREE
    until publish), so release — parse-failure cleanup — is a no-op and
    the cursor simply reuses the slab."""

    __slots__ = ("ints", "flags", "blob", "addr", "index")

    def __init__(self, ints, flags, blob, addr, index):
        self.ints = ints
        self.flags = flags
        self.blob = blob
        self.addr = addr
        self.index = index

    def release(self) -> None:
        pass

    cancel = release  # a batch wider than the slab: never published


class _WorkerArena:
    """Duck-typed ColumnArena over the request ring: ``parse_req`` leases
    the slab at the write cursor and decodes straight into shared memory.
    A busy ring raises IngestOverloadError through the normal
    fits/try_fallback protocol (the per-producer backpressure bound);
    oversized batches plain-allocate so the caller can reject them
    without publishing."""

    def __init__(self, seg: shmring.EdgeSegment, ring: shmring.RequestRing):
        self.seg = seg
        self.ring = ring
        self.max_batch = seg.max_batch
        self.blob_cap = seg.blob_cap
        self._addr = [
            slab_addr(seg.req_ints[i], seg.req_flags[i], seg.req_blob[i])
            for i in range(seg.slabs)
        ]
        self.last: Optional[_WorkerSlabLease] = None

    def lease(self) -> Optional[_WorkerSlabLease]:
        idx = self.ring.try_claim()
        if idx is None:
            return None
        seg = self.seg
        self.last = _WorkerSlabLease(
            seg.req_ints[idx], seg.req_flags[idx], seg.req_blob[idx],
            self._addr[idx], idx)
        return self.last

    def fits(self, n: int, blob_cap: int) -> bool:
        return n <= self.max_batch and blob_cap <= self.blob_cap

    def try_fallback(self, n: int) -> bool:
        return False  # busy ring = backpressure, never heap growth


class EdgeWorker:
    """One edge worker's event loop (child-process side)."""

    def __init__(self, seg: shmring.EdgeSegment, worker_id: int):
        self.seg = seg
        self.worker_id = worker_id
        self.req = shmring.RequestRing(seg)
        self.resp = shmring.ResponseRing(seg)
        # Respawn handoff: a fresh worker must publish where the owner
        # will read next, and read responses where the owner will write
        # next (the owner's cursors survive the crash; ours don't).
        self.req.write_at = int(seg.ctrl[CTRL_REQ_AT]) % seg.slabs
        self.resp.read_at = int(seg.ctrl[CTRL_RESP_AT]) % seg.depth
        self.arena = _WorkerArena(seg, self.req)
        self.counters = seg.counters
        self.generation = int(seg.ctrl[CTRL_GENERATION])
        self.next_seq = 1
        # seq → (hits copy, route) — hits survive slab reuse for the ack
        # accounting; route is the client connection (socket mode) or
        # None (drive mode).
        self.pending: Dict[int, tuple] = {}
        self.on_reply = None  # socket mode's routing callback
        self.stop = False
        seg.ctrl[CTRL_WORKER_PID] = os.getpid()
        signal.signal(signal.SIGTERM, self._on_term)

    def _on_term(self, *_):
        self.stop = True

    def detach(self) -> None:
        """Drop every shm view (rings, arena, counters) so the segment's
        mmap can close without a BufferError at exit."""
        self.req.detach()
        self.resp.detach()
        self.arena.last = None
        self.arena.seg = None
        self.arena.ring = None
        self.counters = None

    def should_stop(self) -> bool:
        return self.stop or int(self.seg.ctrl[CTRL_STOP]) != 0

    # -- decode/publish core -------------------------------------------
    def decode_publish(self, data: bytes, deadline_ns: int, route=None):
        """Parse one frame into the slab at the write cursor and publish
        it.  Returns (seq, None) on publish, (None, reply) when the
        frame must be answered locally (per-item errors, special
        routing, oversize), and raises IngestOverloadError on a full
        ring."""
        t0 = time.monotonic_ns()
        out = fastwire.parse_req(data, self.arena)
        if out is None:
            raise ValueError("malformed or non-decodable request frame")
        cols, errors, special = out
        n = len(cols)
        if cols.lease is None:
            # Oversized for the slab: never published, answered locally.
            cols.release()
            return None, _error_frame(n, OVERSIZE_MSG)
        if errors or special:
            # Per-item validation errors and GLOBAL/metadata routing need
            # the object path; the edge plane serves plain batches only
            # (docs/edge.md) — answer locally, slab stays unpublished.
            msg = errors or {i: OVERSIZE_MSG for i in range(n)}
            if special and not errors:
                msg = {
                    i: "edge plane serves plain batches only; "
                    "use the gRPC path for GLOBAL/metadata"
                    for i in range(n)
                }
            return None, _error_frame(n, None, per_item=msg)
        dt = time.monotonic_ns() - t0
        idx = self.arena.last.index
        seq = self.next_seq
        self.next_seq += 1
        hits = np.array(cols.hits)  # slab views die at release; copy
        self.pending[seq] = (hits, route)
        c = self.counters
        c[C_DECODE_SECONDS] += dt * 1e-9
        c[C_DECODE_BATCHES] += 1
        c[C_ROWS_DECODED] += n
        c[C_WIRE_BYTES_IN] += len(data) + _LEN.size
        c[C_WIN_PUBLISHED] += 1
        c[C_ROWS_PUBLISHED] += n
        c[C_HITS_PUBLISHED] += int(hits.sum())
        self.req.publish(
            idx, seq, n, int(cols.key_offsets[n]), deadline_ns, dt,
            self.generation,
        )
        return seq, None

    # -- ack side -------------------------------------------------------
    def consume_responses(self, on_reply=None) -> int:
        """Drain the response ring; per window, account and (socket
        mode) encode + route the reply.  Returns windows consumed."""
        if on_reply is None:
            on_reply = self.on_reply
        got = 0
        c = self.counters
        while True:
            r = self.resp.poll()
            if r is None:
                return got
            seqno, rows, mat, errc, errb, gen, status, idx = r
            if gen != self.generation:
                self.resp.free_slot(idx)
                continue
            entry = self.pending.pop(seqno, None)
            if entry is None:
                # The exact-work oracle: a response for a window already
                # answered (or never published) is a double-serve.
                c[C_DOUBLE_SERVED] += 1
                self.resp.free_slot(idx)
                continue
            hits, route = entry
            errors = shmring.decode_errors(errb, errc) if errc else {}
            c[C_WIN_ACKED] += 1
            c[C_ROWS_ACKED] += rows
            c[C_ERR_ROWS] += len(errors)
            if status == RESP_OK:
                ok = mat[0] == 0  # UNDER_LIMIT consumes; OVER_LIMIT doesn't
                if errors:
                    ok = ok.copy()
                    for i in errors:
                        ok[i] = False
                c[C_HITS_ACKED] += int(hits[: len(ok)][ok].sum())
            wire = _encode_reply(mat, errors)
            c[C_WIRE_BYTES_OUT] += len(wire) + _LEN.size
            self.resp.free_slot(idx)
            if on_reply is not None:
                on_reply(route, wire)
            got += 1

    # -- drive mode -----------------------------------------------------
    def run_drive(self, spec: dict) -> None:
        """Self-generating loopback load (see module docstring).

        spec: batch, windows (0 = until stop flag), keys, key_prefix,
        hits, limit, duration, frames, timeout_s.
        """
        batch = int(spec.get("batch", 512))
        target = int(spec.get("windows", 0))
        n_keys = int(spec.get("keys", 4096))
        prefix = spec.get("key_prefix", f"w{self.worker_id}_")
        hits = int(spec.get("hits", 1))
        limit = int(spec.get("limit", 1 << 40))
        duration = int(spec.get("duration", 3_600_000))
        n_frames = int(spec.get("frames", 16))
        timeout_ns = int(float(spec.get("timeout_s", 30.0)) * 1e9)
        rng = np.random.default_rng(1000 + self.worker_id)
        frames = []
        for _ in range(n_frames):
            ids = rng.integers(0, n_keys, batch)
            blob, off = key_blob_from_parts(
                ["edge"] * batch, [f"{prefix}{int(k)}" for k in ids]
            )
            z = np.zeros(batch, np.int64)
            cols = ReqColumns(
                blob, off, np.full(batch, hits, np.int64),
                np.full(batch, limit, np.int64),
                np.full(batch, duration, np.int64),
                z, z, np.full(batch, CREATED_UNSET, np.int64), z,
                name_len=np.full(batch, 4, np.int64),
            )
            data = fastwire.encode_req(cols)
            if data is None:
                raise RuntimeError("edge drive mode needs the native codec")
            frames.append(data)
        # Start barrier: spawn/import time must not pollute the owner's
        # throughput clock.
        self.seg.ctrl[CTRL_READY] = 1
        while not self.should_stop() and int(self.seg.ctrl[CTRL_GO]) == 0:
            time.sleep(0.0002)
        fi = 0
        depth = self.seg.depth
        c = self.counters
        published = 0
        while not self.should_stop() and (target == 0 or published < target):
            self.consume_responses()
            if len(self.pending) >= depth:
                c[C_BACKPRESSURE_WAITS] += 1
                time.sleep(0.00005)
                continue
            try:
                seq, _ = self.decode_publish(
                    frames[fi], time.monotonic_ns() + timeout_ns
                )
            except IngestOverloadError:
                c[C_BACKPRESSURE_WAITS] += 1
                time.sleep(0.00005)
                continue
            fi = (fi + 1) % n_frames
            published += 1
        # Final drain: every published window must come back (the
        # dropped-ack invariant) unless the owner is tearing us down.
        quiet_until = time.monotonic() + 5.0
        while self.pending and time.monotonic() < quiet_until:
            if self.consume_responses():
                quiet_until = time.monotonic() + 5.0
            if self.should_stop():
                break
            time.sleep(0.0002)
        c[C_DRIVE_DONE] = 1
        # Linger until told to stop so the counter block stays paired
        # with a live process for the owner's final sync.
        while not self.should_stop():
            time.sleep(0.002)

    # -- socket mode ----------------------------------------------------
    def run_socket(self, path: str, timeout_s: float = 30.0) -> None:
        """Unix-socket ingest: length-prefixed fastwire frames in,
        length-prefixed response frames out, responses in publish order
        per window."""
        sel = selectors.DefaultSelector()
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass
        srv.bind(path)
        srv.listen(64)
        srv.setblocking(False)
        sel.register(srv, selectors.EVENT_READ, None)
        conns: Dict[int, "_Conn"] = {}
        timeout_ns = int(timeout_s * 1e9)
        self.seg.ctrl[CTRL_READY] = 1

        def reply(route, wire):
            conn = conns.get(route)
            if conn is not None:
                conn.queue(_LEN.pack(len(wire)) + wire)

        self.on_reply = reply
        try:
            while not self.should_stop():
                self.consume_responses()
                for key, events in sel.select(timeout=0.0005):
                    if key.data is None:
                        try:
                            s, _ = srv.accept()
                        except OSError:
                            continue
                        s.setblocking(False)
                        conn = _Conn(s)
                        conns[conn.id] = conn
                        sel.register(s, selectors.EVENT_READ, conn)
                        continue
                    conn = key.data
                    if events & selectors.EVENT_READ:
                        if not conn.read():
                            self._drop_conn(sel, conns, conn)
                            continue
                        for frame in conn.frames():
                            self._serve_frame(conn, frame, timeout_ns)
                    if events & selectors.EVENT_WRITE:
                        conn.flush()
                for conn in list(conns.values()):
                    if conn.out and not conn.flush():
                        self._drop_conn(sel, conns, conn)
        finally:
            for conn in list(conns.values()):
                self._drop_conn(sel, conns, conn)
            sel.unregister(srv)
            srv.close()
            try:
                os.unlink(path)
            except FileNotFoundError:
                pass

    def _serve_frame(self, conn: "_Conn", frame: bytes,
                     timeout_ns: int) -> None:
        deadline = time.monotonic_ns() + timeout_ns
        # Bounded claim wait: the ring is this producer's own admission
        # bound, so a short spin then a retriable shed (the PR 9
        # convention) keeps one hot client from queueing unboundedly.
        for _ in range(40):
            if len(self.pending) >= self.seg.depth:
                # Outstanding bound: the response ring must always have a
                # free slot for a live worker's windows.
                self.counters[C_BACKPRESSURE_WAITS] += 1
                self.consume_responses()
                time.sleep(0.0002)
                continue
            try:
                seq, local = self.decode_publish(frame, deadline, conn.id)
            except IngestOverloadError:
                self.counters[C_BACKPRESSURE_WAITS] += 1
                self.consume_responses()
                time.sleep(0.0002)
                continue
            except ValueError:
                conn.queue(_LEN.pack(0))  # unparseable: empty response
                return
            if local is not None:
                conn.queue(_LEN.pack(len(local)) + local)
            return
        self.counters[C_SHED_LOCAL] += 1
        n = _frame_rows(frame)
        shed = _error_frame(n, SHED_EDGE_MSG)
        conn.queue(_LEN.pack(len(shed)) + shed)

    def _drop_conn(self, sel, conns, conn) -> None:
        conns.pop(conn.id, None)
        try:
            sel.unregister(conn.sock)
        except Exception:
            pass
        conn.sock.close()
        # Windows already published for this conn still complete; their
        # replies drop at routing (the conn is gone) but the accounting
        # in consume_responses still runs — never silently lost.


class _Conn:
    """One client connection's read/write buffers."""

    _next_id = 1

    def __init__(self, sock):
        self.sock = sock
        self.id = _Conn._next_id
        _Conn._next_id += 1
        self.buf = b""
        self.out = b""

    def read(self) -> bool:
        try:
            data = self.sock.recv(1 << 16)
        except BlockingIOError:
            return True
        except OSError:
            return False
        if not data:
            return False
        self.buf += data
        return True

    def frames(self):
        while len(self.buf) >= _LEN.size:
            (ln,) = _LEN.unpack_from(self.buf)
            if len(self.buf) < _LEN.size + ln:
                return
            frame = self.buf[_LEN.size : _LEN.size + ln]
            self.buf = self.buf[_LEN.size + ln :]
            yield frame

    def queue(self, data: bytes) -> None:
        self.out += data
        self.flush()

    def flush(self) -> bool:
        if not self.out:
            return True
        try:
            sent = self.sock.send(self.out)
            self.out = self.out[sent:]
            return True
        except BlockingIOError:
            return True
        except OSError:
            return False


class EdgeClient:
    """Minimal blocking client for the worker's Unix-socket framing
    (tests and operator smoke checks; production streaming clients speak
    the same four-byte little-endian length prefix)."""

    def __init__(self, path: str, timeout: float = 10.0):
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.settimeout(timeout)
        self.sock.connect(path)

    def call(self, req_bytes: bytes) -> bytes:
        self.sock.sendall(_LEN.pack(len(req_bytes)) + req_bytes)
        return self.recv()

    def send(self, req_bytes: bytes) -> None:
        self.sock.sendall(_LEN.pack(len(req_bytes)) + req_bytes)

    def recv(self) -> bytes:
        hdr = self._read(_LEN.size)
        (ln,) = _LEN.unpack(hdr)
        return self._read(ln) if ln else b""

    def _read(self, n: int) -> bytes:
        out = b""
        while len(out) < n:
            chunk = self.sock.recv(n - len(out))
            if not chunk:
                raise ConnectionError("edge socket closed mid-frame")
            out += chunk
        return out

    def close(self) -> None:
        self.sock.close()


def _frame_rows(frame: bytes) -> int:
    lib = fastwire.load()
    if lib is None:
        return 0
    n = lib.guber_wire_count(frame, len(frame))
    return max(0, int(n))


def _encode_reply(mat: np.ndarray, errors: dict) -> bytes:
    """Response matrix (+ per-item error strings) → wire bytes.  The
    no-error path is the native encoder (byte-identical to protobuf);
    error items take the pb object path, mirroring the daemon's
    fallback."""
    if not errors:
        return fastwire.encode_resp(mat)[0]
    from gubernator_tpu import pb

    status, limit, remaining, reset = (
        mat[r].tolist() for r in range(4)
    )
    return pb.GetRateLimitsResp(
        responses=[
            pb.RateLimitResp(error=errors[i])
            if i in errors
            else pb.RateLimitResp(
                status=status[i], limit=limit[i],
                remaining=remaining[i], reset_time=reset[i],
            )
            for i in range(mat.shape[1])
        ]
    ).SerializeToString()


def _error_frame(n: int, msg: Optional[str], per_item: dict = None) -> bytes:
    """A whole-batch (or per-item) error response, pb-encoded."""
    from gubernator_tpu import pb

    errs = per_item if per_item is not None else {i: msg for i in range(n)}
    return pb.GetRateLimitsResp(
        responses=[
            pb.RateLimitResp(error=errs.get(i, msg or "")) for i in range(n)
        ]
    ).SerializeToString()


def worker_main(seg_name: str, worker_id: int, max_batch: int, slabs: int,
                depth: int, mode: str, options: dict) -> None:
    """Spawn entry point (the supervisor's process target).  Attaches
    the segment untracked, then runs the mode loop until the stop flag
    or SIGTERM."""
    if fastwire.load() is None:
        raise RuntimeError(
            "edge worker needs the native wire codec (libguber_wire.so)"
        )
    seg = shmring.attach_segment(seg_name, max_batch, slabs, depth)
    w = None
    try:
        w = EdgeWorker(seg, worker_id)
        if mode == "drive":
            w.run_drive(options.get("drive", {}))
        elif mode == "socket":
            w.run_socket(
                options["socket_path"],
                timeout_s=float(options.get("timeout_s", 30.0)),
            )
        else:
            raise ValueError(f"unknown edge worker mode {mode!r}")
    finally:
        if w is not None:
            w.detach()
        seg.close()
