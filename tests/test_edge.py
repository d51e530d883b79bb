"""Multi-process streaming edge tests (docs/edge.md).

Ring-protocol units run in-process against one shm segment (both ends
mapped by this test, no children), so the SPSC state machine — FREE →
PUBLISHED → LEASED → FREE, cursor wrap over leased slabs, response slot
reuse — is exercised deterministically.  The cross-process tests spawn
the real worker fleet but keep it small (2 workers, tiny windows) so
the suite stays inside the tier-1 budget; the SIGKILL chaos scenario
lives in test_chaos.py.
"""

import os

import numpy as np
import pytest

from gubernator_tpu.edge import shmring
from gubernator_tpu.edge.plane import EdgeConfig, EdgePlane
from gubernator_tpu.edge.shmring import (
    FREE,
    LEASED,
    PUBLISHED,
    RESP_OK,
    RQ_STATE,
    EdgeSegment,
    RequestRing,
    ResponseRing,
    ShmSlabLease,
    decode_errors,
    encode_errors,
)
from gubernator_tpu.transport import fastwire

NATIVE = fastwire.load() is not None


def _segment(mb=8, slabs=3, depth=4):
    return EdgeSegment(
        f"guber_edge_test_{os.getpid()}_{os.urandom(3).hex()}",
        mb, slabs, depth, create=True,
    )


def _close(seg, *rings):
    # Ring views pin the shm mapping; drop them or SharedMemory.__del__
    # warns BufferError at GC time.
    for r in rings:
        r.detach()
    seg.close()
    seg.unlink()


# ---------------------------------------------------------------------
# Segment + ring protocol units
# ---------------------------------------------------------------------
def test_segment_attach_validates_layout():
    seg = _segment()
    try:
        # Same shape attaches; a different shape must refuse the map
        # instead of mis-striding every view.
        peer = shmring.attach_segment(seg.shm.name, 8, 3, 4)
        peer.close()
        with pytest.raises(ValueError):
            shmring.attach_segment(seg.shm.name, 16, 3, 4)
    finally:
        _close(seg)


def test_request_ring_publish_pop_free_cycle():
    seg = _segment(slabs=2)
    try:
        ring = RequestRing(seg)
        idx = ring.try_claim()
        assert idx == 0
        ring.publish(idx, seqno=7, rows=3, blob_len=64, deadline_ns=123,
                     decode_ns=456, generation=1)
        assert int(seg.req_hdr[0, RQ_STATE]) == PUBLISHED
        got = ring.pop_published()
        assert got == (0, 7, 3, 64, 123, 456, 1)
        # Popped = leased to the tick loop: not claimable, not
        # re-poppable, until free().
        assert int(seg.req_hdr[0, RQ_STATE]) == LEASED
        ring.free(0)
        assert int(seg.req_hdr[0, RQ_STATE]) == FREE
    finally:
        _close(seg, ring)


def test_request_ring_wrap_never_repops_leased_slab():
    """The double-serve regression: with every slab in flight the read
    cursor wraps back to slab 0 — which is LEASED, not PUBLISHED, so the
    owner must see an empty ring, not the same window again."""
    seg = _segment(slabs=2)
    try:
        ring = RequestRing(seg)
        for seq in (1, 2):
            idx = ring.try_claim()
            assert idx is not None
            ring.publish(idx, seq, 1, 0, 0, 0, 1)
        assert ring.try_claim() is None  # producer backpressure bound
        first = ring.pop_published()
        second = ring.pop_published()
        assert (first[1], second[1]) == (1, 2)
        # Cursor has wrapped to slab 0; both slabs still leased.
        assert ring.pop_published() is None
        ring.free(first[0])
        # Freed slab is claimable by the producer again.
        assert ring.try_claim() == first[0]
    finally:
        _close(seg, ring)


def test_shm_slab_lease_release_idempotent():
    seg = _segment(slabs=2)
    try:
        ring = RequestRing(seg)
        idx = ring.try_claim()
        ring.publish(idx, 1, 1, 0, 0, 0, 1)
        ring.pop_published()
        lease = ShmSlabLease(ring, idx)
        lease.release()
        seg.req_hdr[idx, RQ_STATE] = LEASED  # re-arm to catch a 2nd free
        lease.release()
        assert int(seg.req_hdr[idx, RQ_STATE]) == LEASED
    finally:
        _close(seg, ring)


def test_response_ring_roundtrip_and_depth_bound():
    seg = _segment(mb=8, depth=2)
    try:
        ring = ResponseRing(seg)
        mat = np.arange(5 * 3, dtype=np.int64).reshape(5, 3)
        blob, cnt = encode_errors({1: "boom"})
        assert ring.try_publish(9, 3, mat, blob, cnt, generation=1,
                                status=RESP_OK)
        assert ring.try_publish(10, 2, mat[:, :2], b"", 0, 1, RESP_OK)
        # Depth exhausted: the slot at the write cursor is unconsumed.
        assert not ring.try_publish(11, 1, mat[:, :1], b"", 0, 1, RESP_OK)
        seq, rows, got, errc, errb, gen, status, idx = ring.poll()
        assert (seq, rows, errc, gen, status) == (9, 3, 1, 1, RESP_OK)
        np.testing.assert_array_equal(got, mat)
        assert decode_errors(errb, errc) == {1: "boom"}
        del got  # shm view; must not outlive the segment teardown below
        ring.free_slot(idx)
        # Freed slot admits the bounced response.
        assert ring.try_publish(11, 1, mat[:, :1], b"", 0, 1, RESP_OK)
    finally:
        _close(seg, ring)


def test_encode_errors_roundtrip_and_truncation():
    msgs = {0: "table full", 4: "x" * 500, 7: ""}
    blob, cnt = encode_errors(msgs)
    out = decode_errors(blob, cnt)
    assert out[0] == "table full" and out[7] == ""
    # Oversized messages truncate to the per-record budget, never lost.
    assert out[4] == "x" * (shmring.ERR_RECORD_BYTES - 8)
    assert encode_errors({}) == (b"", 0)


def test_edge_config_clamps_depth_to_slabs():
    cfg = EdgeConfig(workers=1, slabs=8, ring_depth=2)
    assert cfg.ring_depth == 8


def test_plane_refuses_zero_workers():
    with pytest.raises(ValueError):
        EdgePlane(tick_loop=None, config=EdgeConfig(workers=0))


def test_disabled_plane_creates_no_shm(tmp_path):
    """GUBER_EDGE_WORKERS=0 (the default) must leave the serving path
    byte-identical — concretely: nothing of the edge plane exists, no
    shm segment is ever created."""
    from gubernator_tpu.config import setup_daemon_config

    before = set(os.listdir("/dev/shm")) if os.path.isdir("/dev/shm") else set()
    dconf = setup_daemon_config(environ={"GUBER_GRPC_ADDRESS": "127.0.0.1:0"})
    assert dconf.config.edge_workers == 0
    if os.path.isdir("/dev/shm"):
        created = set(os.listdir("/dev/shm")) - before
        assert not [n for n in created if n.startswith("guber_edge_")]


def test_config_validates_edge_knobs():
    from gubernator_tpu.config import setup_daemon_config

    with pytest.raises(ValueError):
        setup_daemon_config(environ={"GUBER_EDGE_WORKERS": "-1"})
    with pytest.raises(ValueError):
        setup_daemon_config(environ={"GUBER_EDGE_SHM_SLABS": "0"})
    with pytest.raises(ValueError):
        setup_daemon_config(environ={"GUBER_EDGE_RING_DEPTH": "0"})
    dconf = setup_daemon_config(environ={
        "GUBER_EDGE_WORKERS": "2",
        "GUBER_EDGE_SHM_SLABS": "4",
        "GUBER_EDGE_RING_DEPTH": "8",
    })
    assert dconf.config.edge_workers == 2
    assert dconf.config.edge_shm_slabs == 4
    assert dconf.config.edge_ring_depth == 8


# ---------------------------------------------------------------------
# Flight-recorder decode attribution (ManualClock)
# ---------------------------------------------------------------------
def test_flightrec_edge_decode_folds_into_next_window():
    from gubernator_tpu.utils.flightrec import FlightRecorder

    t = [100.0]
    fr = FlightRecorder(windows=8, clock=lambda: t[0])
    seen = []
    fr.observer = lambda stage, s: seen.append((stage, round(s, 6)))
    # The drain thread folds the worker-stamped decode duration exactly
    # like the in-process transport edge: it accumulates and lands in
    # the NEXT window begun (a window's decode is the CPU that fed it).
    fr.edge("decode", 0.004)
    fr.edge("decode", 0.002)
    wid = fr.begin(width=32, depth=1)
    fr.note(wid, "tick", 0.001)
    fr.finish(wid)
    pct = fr.snapshot()["stages"]
    assert pct["decode"]["p50_ms"] == pytest.approx(6.0)
    assert ("decode", 0.004) in seen and ("decode", 0.002) in seen
    # The next window starts clean: pending decode was consumed.
    wid2 = fr.begin(width=32, depth=1)
    fr.finish(wid2)
    assert fr.recent(2)[-1]["stages_ms"]["decode"] == 0.0


# ---------------------------------------------------------------------
# Worker-side decode into the ring (no child process; needs the codec)
# ---------------------------------------------------------------------
@pytest.mark.skipif(not NATIVE, reason="native wire codec not built")
def test_worker_arena_backpressure_raises_overload():
    from gubernator_tpu.edge.worker import EdgeWorker
    from gubernator_tpu.ops.reqcols import (
        CREATED_UNSET, IngestOverloadError, ReqColumns,
        key_blob_from_parts,
    )

    seg = _segment(mb=8, slabs=2, depth=4)
    try:
        w = EdgeWorker(shmring.attach_segment(seg.shm.name, 8, 2, 4), 0)
        n = 4
        blob, off = key_blob_from_parts(["edge"] * n,
                                        [f"k{i}" for i in range(n)])
        z = np.zeros(n, np.int64)
        cols = ReqColumns(
            blob, off, np.ones(n, np.int64), np.full(n, 10, np.int64),
            np.full(n, 1000, np.int64), z, z,
            np.full(n, CREATED_UNSET, np.int64), z,
            name_len=np.full(n, 4, np.int64),
        )
        frame = fastwire.encode_req(cols)
        seq1, _ = w.decode_publish(frame, deadline_ns=1)
        seq2, _ = w.decode_publish(frame, deadline_ns=1)
        assert seq1 != seq2 and len(w.pending) == 2
        with pytest.raises(IngestOverloadError):
            w.decode_publish(frame, deadline_ns=1)  # both slabs published
        assert int(seg.counters[shmring.C_WIN_PUBLISHED]) == 2
        assert int(seg.counters[shmring.C_ROWS_PUBLISHED]) == 2 * n
        w.detach()
        w.seg.close()
    finally:
        _close(seg)


# ---------------------------------------------------------------------
# Cross-process end-to-end (2 workers, tiny drive)
# ---------------------------------------------------------------------
@pytest.mark.skipif(not NATIVE, reason="native wire codec not built")
def test_edge_drive_two_workers_exact_parity():
    """The serve_multiproc invariants at test scale: every published
    window acked exactly once, zero double-serves, zero drops, and the
    engine-applied hits equal the workers' acked-hit accounting."""
    from gubernator_tpu.ops.engine import TickEngine
    from gubernator_tpu.ops.reqcols import (
        CREATED_UNSET, ReqColumns, key_blob_from_parts,
    )
    from gubernator_tpu.service.tickloop import TickLoop
    from gubernator_tpu.utils.metrics import Metrics

    windows, batch, n_keys, limit = 25, 16, 32, 1 << 40
    eng = TickEngine(capacity=512, max_batch=64)
    loop = TickLoop(eng, batch_limit=64)
    metrics = Metrics()
    plane = EdgePlane(loop, EdgeConfig(
        workers=2, slabs=4, ring_depth=8, max_batch=64, mode="drive",
        drive={"batch": batch, "windows": windows, "keys": n_keys,
               "limit": limit, "frames": 4},
    ), metrics=metrics)
    try:
        plane.start()
        assert plane.wait_ready(60), "workers never became ready"
        plane.go()
        assert plane.wait_drive_done(120), "drive did not finish"
        tot = plane.totals()
    finally:
        plane.close()
        # Exact-work oracle: zero-hit probe reads back applied hits.
        consumed = 0
        for wid in range(2):
            keys = [f"w{wid}_{k}" for k in range(n_keys)]
            blob, off = key_blob_from_parts(["edge"] * n_keys, keys)
            z = np.zeros(n_keys, np.int64)
            cols = ReqColumns(
                blob, off, z, np.full(n_keys, limit, np.int64),
                np.full(n_keys, 3_600_000, np.int64), z, z,
                np.full(n_keys, CREATED_UNSET, np.int64), z,
                name_len=np.full(n_keys, 4, np.int64),
            )
            mat, errs = loop.submit_columns(cols).result(timeout=60)
            assert not errs
            consumed += int((limit - mat[2]).sum())
        loop.close()
        eng.close()
    assert tot["windows_published"] == 2 * windows
    assert tot["windows_acked"] == 2 * windows
    assert tot["double_served"] == 0
    assert tot["dropped_responses"] == 0
    assert tot["err_rows"] == 0
    assert tot["hits_acked"] == tot["hits_published"] == consumed
    # Counter-block aggregation reached the owner's Prometheus families,
    # per-worker labelled (final sync runs inside close()).
    for wid in ("0", "1"):
        assert metrics.sample(
            "gubernator_tpu_edge_windows_total", {"worker": wid}
        ) == windows
        assert metrics.sample(
            "gubernator_tpu_edge_acked_windows_total", {"worker": wid}
        ) == windows
        assert metrics.sample(
            "gubernator_tpu_edge_rows_total", {"worker": wid}
        ) == windows * batch
        assert metrics.sample(
            "gubernator_tpu_edge_decode_seconds_total", {"worker": wid}
        ) > 0.0


@pytest.mark.skipif(not NATIVE, reason="native wire codec not built")
def test_edge_socket_mode_roundtrip(tmp_path):
    """Socket ingest: length-prefixed fastwire frames through a real
    worker process come back as parseable responses with correct
    remaining counts."""
    from gubernator_tpu.edge.worker import EdgeClient
    from gubernator_tpu.ops.engine import TickEngine
    from gubernator_tpu.ops.reqcols import (
        CREATED_UNSET, ReqColumns, key_blob_from_parts,
    )
    from gubernator_tpu.pb import gubernator_pb2 as pb
    from gubernator_tpu.service.tickloop import TickLoop

    eng = TickEngine(capacity=512, max_batch=64)
    loop = TickLoop(eng, batch_limit=64)
    plane = EdgePlane(loop, EdgeConfig(
        workers=1, slabs=4, ring_depth=8, max_batch=64, mode="socket",
        socket_dir=str(tmp_path),
    ))
    try:
        plane.start()
        assert plane.wait_ready(60)
        n = 8
        blob, off = key_blob_from_parts(["edge"] * n,
                                        [f"sock{i}" for i in range(n)])
        z = np.zeros(n, np.int64)
        cols = ReqColumns(
            blob, off, np.ones(n, np.int64), np.full(n, 100, np.int64),
            np.full(n, 3_600_000, np.int64), z, z,
            np.full(n, CREATED_UNSET, np.int64), z,
            name_len=np.full(n, 4, np.int64),
        )
        frame = fastwire.encode_req(cols)
        client = EdgeClient(plane.socket_paths()[0], timeout=30.0)
        try:
            for want_remaining in (99, 98):
                raw = client.call(frame)
                parsed = fastwire.parse_resp(raw)
                if parsed is not None:
                    remaining = parsed[0][2]
                else:
                    msg = pb.GetRateLimitsResp.FromString(raw)
                    remaining = [r.remaining for r in msg.responses]
                assert list(remaining) == [want_remaining] * n
        finally:
            client.close()
    finally:
        plane.close()
        loop.close()
        eng.close()
