"""Native wire codec tests: byte parity with the protobuf library.

The serving fast path (transport/fastwire.py + native/wirecodec.cc)
replaces protobuf message objects on the wire↔columns boundary; these
tests prove the replacement is invisible — same columns as
``convert.columns_from_pb``, same bytes as ``SerializeToString()``,
lossless roundtrips — including the awkward cases (negative int64
varints, empty names, explicit created_at=0, metadata presence, unknown
fields from a future schema).
"""

import numpy as np
import pytest

from gubernator_tpu.ops.reqcols import CREATED_UNSET
from gubernator_tpu.pb import gubernator_pb2 as pb
from gubernator_tpu.transport import convert, fastwire

pytestmark = pytest.mark.skipif(
    fastwire.load() is None, reason="native wire codec unavailable"
)


def _req_bytes(reqs):
    return pb.GetRateLimitsReq(requests=reqs).SerializeToString()


def _parity(reqs):
    data = _req_bytes(reqs)
    out = fastwire.parse_req(data)
    assert out is not None
    cols, errors, special = out
    ref_cols, ref_errors, ref_special = convert.columns_from_pb(
        pb.GetRateLimitsReq.FromString(data).requests
    )
    assert errors == ref_errors
    assert special == ref_special
    # parse_req blobs are buffer views (zero-copy decode); compare bytes.
    assert bytes(cols.key_blob) == bytes(ref_cols.key_blob)
    np.testing.assert_array_equal(cols.key_offsets, ref_cols.key_offsets)
    for f in ("hits", "limit", "duration", "algorithm", "behavior",
              "created_at", "burst"):
        np.testing.assert_array_equal(
            getattr(cols, f), getattr(ref_cols, f), err_msg=f
        )
    return cols, errors, special


def test_parse_req_basic_parity():
    reqs = [
        pb.RateLimitReq(name=f"svc{i % 3}", unique_key=f"key{i}",
                        hits=1 + i, limit=10 ** 6, duration=3_600_000)
        for i in range(257)
    ]
    cols, errors, special = _parity(reqs)
    assert not errors and not special
    assert cols.name_len is not None
    assert cols.name_len[0] == len("svc0")


def test_parse_req_edge_values():
    reqs = [
        pb.RateLimitReq(name="n", unique_key="k", hits=-3,  # 10-byte varint
                        limit=2 ** 62, duration=1, burst=7),
        pb.RateLimitReq(name="n", unique_key="k2", created_at=0),
        pb.RateLimitReq(name="n", unique_key="k3", created_at=123456789),
        pb.RateLimitReq(name="Ω≈", unique_key="ключ", hits=1),  # UTF-8
    ]
    cols, errors, special = _parity(reqs)
    # explicit created_at=0 means "server stamps now" (columns_from_pb
    # parity); the nonzero one survives.
    assert cols.created_at[1] == CREATED_UNSET
    assert cols.created_at[2] == 123456789


def test_parse_req_errors_and_special():
    reqs = [
        pb.RateLimitReq(name="", unique_key="k"),
        pb.RateLimitReq(name="n", unique_key=""),
        pb.RateLimitReq(name="ok", unique_key="ok", behavior=2),  # GLOBAL
    ]
    cols, errors, special = _parity(reqs)
    assert 0 in errors and 1 in errors
    assert special


def test_parse_req_rejects_unknown_algorithm():
    # Out-of-range algorithm values must not fall through the kernels'
    # branchless dispatch as token-bucket (docs/algorithms.md); empty-
    # key errors keep precedence, and all five valid values pass.
    reqs = [
        pb.RateLimitReq(name="n", unique_key="k", hits=1, algorithm=7),
        pb.RateLimitReq(name="n", unique_key="", algorithm=9),
    ] + [
        pb.RateLimitReq(name="n", unique_key=f"ok{a}", hits=1, algorithm=a)
        for a in range(5)
    ]
    cols, errors, special = _parity(reqs)
    assert "invalid algorithm '7'" in errors[0]
    assert errors[1] == "field 'unique_key' cannot be empty"
    assert set(errors) == {0, 1}


def test_parse_req_metadata_presence():
    r = pb.RateLimitReq(name="n", unique_key="k")
    r.metadata["trace"] = "abc"
    cols, errors, special = _parity([r])
    assert special


def test_parse_req_unknown_fields_skipped():
    # A future-schema message: append an unknown varint field (200) and an
    # unknown length-delimited field (201) to a valid RateLimitReq.
    inner = pb.RateLimitReq(name="n", unique_key="k", hits=5)

    def varint(v):
        out = b""
        while True:
            if v < 0x80:
                return out + bytes([v])
            out += bytes([(v & 0x7F) | 0x80])
            v >>= 7

    raw_inner = (
        inner.SerializeToString()
        + varint((200 << 3) | 0) + varint(42)
        + varint((201 << 3) | 2) + varint(3) + b"xyz"
    )
    data = varint((1 << 3) | 2) + varint(len(raw_inner)) + raw_inner
    out = fastwire.parse_req(data)
    assert out is not None
    cols, errors, special = out
    assert len(cols) == 1 and cols.hits[0] == 5 and not errors


def test_parse_req_malformed_returns_none():
    assert fastwire.parse_req(b"\x0a\xff\xff\xff\xff\xff") is None


def test_encode_req_roundtrip():
    reqs = [
        pb.RateLimitReq(name=f"name{i}", unique_key=f"uk{i}", hits=i,
                        limit=5 * i, duration=1000 + i, algorithm=i % 2,
                        behavior=0, burst=i % 7)
        for i in range(64)
    ]
    reqs[3].created_at = 777
    reqs[4].hits = -1
    data = _req_bytes(reqs)
    cols, _, _ = fastwire.parse_req(data)
    enc = fastwire.encode_req(cols)
    assert enc is not None
    back = pb.GetRateLimitsReq.FromString(enc)
    assert len(back.requests) == len(reqs)
    for a, b in zip(reqs, back.requests):
        for f in ("name", "unique_key", "hits", "limit", "duration",
                  "algorithm", "behavior", "burst"):
            assert getattr(a, f) == getattr(b, f), f
        assert a.HasField("created_at") == b.HasField("created_at")
        assert a.created_at == b.created_at


def test_encode_req_from_requests_bridge():
    from gubernator_tpu.ops.reqcols import ReqColumns
    from gubernator_tpu.types import RateLimitRequest

    cols = ReqColumns.from_requests([
        RateLimitRequest(name="a", unique_key="b", hits=2, limit=9,
                         duration=100),
        RateLimitRequest(name="c_d", unique_key="e_f", hits=1, limit=1,
                         duration=1, created_at=55),
    ])
    enc = fastwire.encode_req(cols)
    back = pb.GetRateLimitsReq.FromString(enc)
    assert back.requests[0].name == "a"
    assert back.requests[1].unique_key == "e_f"  # '_' in parts survives
    assert back.requests[1].created_at == 55


def test_encode_resp_byte_parity():
    rng = np.random.default_rng(11)
    n = 500
    mat = np.zeros((5, n), np.int64)
    mat[0] = rng.integers(0, 2, n)
    mat[1] = rng.integers(0, 2 ** 40, n)
    mat[2] = rng.integers(-5, 2 ** 40, n)  # negatives: 10-byte varints
    mat[3] = rng.integers(0, 2 ** 45, n)
    ref = pb.GetRateLimitsResp(responses=[
        pb.RateLimitResp(
            status=int(mat[0, i]), limit=int(mat[1, i]),
            remaining=int(mat[2, i]), reset_time=int(mat[3, i]),
        )
        for i in range(n)
    ]).SerializeToString()
    assert fastwire.encode_resp(mat) == (ref, 0)
    # and the numpy fallback agrees too
    from gubernator_tpu.transport.wire import encode_get_rate_limits_resp

    assert encode_get_rate_limits_resp(mat) == ref


def test_encode_resp_worst_case_cap():
    """All four fields negative — every varint takes its full 10 bytes,
    so each item costs the worst-case 46 B (44 B payload + 2 B item
    header).  The old `8 + 44 * n` budget under-sized exactly this
    matrix and leaned on the retry path; the corrected cap must fit it
    first try and still match protobuf byte-for-byte."""
    n = 64
    mat = np.full((5, n), -1, np.int64)
    mat[4] = 0  # error row: no special strings
    ref = pb.GetRateLimitsResp(responses=[
        pb.RateLimitResp(status=-1, limit=-1, remaining=-1, reset_time=-1)
        for _ in range(n)
    ]).SerializeToString()
    assert fastwire.encode_resp(mat) == (ref, 0)


def test_parse_resp_roundtrip_and_special():
    mat = np.array(
        [[0, 1], [10, 20], [5, -2], [111, 222], [0, 1]], np.int64
    )
    m, special = fastwire.parse_resp(fastwire.encode_resp(mat)[0])
    np.testing.assert_array_equal(m, mat[:4])
    assert not special.any()
    raw = pb.GetRateLimitsResp(responses=[
        pb.RateLimitResp(status=1, error="table full"),
        pb.RateLimitResp(limit=5),
    ]).SerializeToString()
    m2, sp2 = fastwire.parse_resp(raw)
    assert sp2[0] and not sp2[1]
    assert m2[0, 0] == 1 and m2[1, 1] == 5


def test_empty_batches():
    cols, errors, special = fastwire.parse_req(b"")
    assert len(cols) == 0 and not errors and not special
    assert fastwire.encode_resp(np.zeros((5, 0), np.int64)) == (b"", 0)
    m, sp = fastwire.parse_resp(b"")
    assert m.shape == (4, 0) and len(sp) == 0


def test_peer_raw_wire_end_to_end():
    """GetPeerRateLimits over raw bytes: the peer edge shares the public
    edge's wire shapes, so the codec serves relayed batches too (the
    daemon processes them as owner regardless of ring state)."""
    import asyncio

    import grpc as grpc_mod

    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.transport.daemon import spawn_daemon

    async def run():
        conf = DaemonConfig(
            grpc_listen_address="127.0.0.1:0",
            http_listen_address="",
            peer_discovery_type="none",
        )
        d = await spawn_daemon(conf)
        channel = grpc_mod.aio.insecure_channel(d.conf.grpc_listen_address)
        raw_peer = channel.unary_unary(
            "/pb.gubernator.PeersV1/GetPeerRateLimits",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )
        try:
            # The codec path must actually be live, or this test would
            # pass vacuously (codec bytes == protobuf bytes by design).
            assert d.instance.peer_columns_fast_path_ok()
            reqs = [
                pb.RateLimitReq(name="pw", unique_key=f"k{i}", hits=1,
                                limit=9, duration=60_000)
                for i in range(6)
            ]
            data = pb.GetRateLimitsReq(requests=reqs).SerializeToString()
            out = await raw_peer(data, timeout=30.0)
            mat, special = fastwire.parse_resp(out)
            assert mat.shape == (4, 6) and not special.any()
            assert (mat[1] == 9).all() and (mat[2] == 8).all()
            # Object-path parity through the real stub.
            from gubernator_tpu.transport.grpc_api import PeersV1Stub
            from gubernator_tpu.pb import peers_pb2 as ppb

            stub = PeersV1Stub(channel)
            resp = await stub.GetPeerRateLimits(
                ppb.GetPeerRateLimitsReq(requests=reqs), timeout=30.0
            )
            assert [r.remaining for r in resp.rate_limits] == [7] * 6
        finally:
            await channel.close()
            await d.close()

    asyncio.run(run())


def test_columnar_client_end_to_end():
    """Raw-bytes gRPC path: columnar client → native codec both ways →
    same decisions the object API returns (standalone daemon)."""
    import asyncio

    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.ops.reqcols import ReqColumns
    from gubernator_tpu.transport.daemon import DaemonClient, spawn_daemon
    from gubernator_tpu.types import RateLimitRequest, Status

    async def run():
        conf = DaemonConfig(
            grpc_listen_address="127.0.0.1:0",
            http_listen_address="",
            peer_discovery_type="none",
        )
        d = await spawn_daemon(conf)
        client = DaemonClient(d.advertise_address)
        try:
            assert d.instance.columns_fast_path_ok()
            reqs = [
                RateLimitRequest(name="fw", unique_key=f"k{i}", hits=1,
                                 limit=3, duration=60_000)
                for i in range(8)
            ] * 2  # duplicates: second half decrements further
            cols = ReqColumns.from_requests(reqs)
            mat, errors = await client.get_rate_limits_columns(
                cols, timeout=30.0
            )
            assert not errors
            assert mat.shape == (4, 16)
            assert (mat[1] == 3).all()
            assert (mat[2][:8] == 2).all()      # first hit: remaining 2
            assert (mat[2][8:] == 1).all()      # duplicate: remaining 1
            # Object API against the same daemon agrees on the next hit.
            out = await client.get_rate_limits(reqs[:8], timeout=30.0)
            assert all(r.remaining == 0 for r in out)
            assert all(r.status == Status.UNDER_LIMIT for r in out)
            # One more drains it past the limit.
            out = await client.get_rate_limits(reqs[:8], timeout=30.0)
            assert all(r.status == Status.OVER_LIMIT for r in out)
            # Malformed bytes: INVALID_ARGUMENT, not UNKNOWN (the
            # pass-through deserializer moved parsing into the handler).
            import grpc

            try:
                await client._raw_get_rate_limits(
                    b"\x0a\xff\xff\xff\xff\xff", timeout=10.0
                )
                raise AssertionError("malformed request should fail")
            except grpc.aio.AioRpcError as e:
                assert e.code() == grpc.StatusCode.INVALID_ARGUMENT
        finally:
            await client.close()
            await d.close()

    asyncio.run(run())


# ----------------------------------------------------------------------
# Zero-copy ingest arena: decode-into-slab parity + lease mechanics
# ----------------------------------------------------------------------
def _rand_reqs(rng, n):
    """Randomized request batch spanning the codec's edge cases:
    negative/huge varints, explicit created_at=0, absent fields,
    UTF-8 keys."""
    reqs = []
    for i in range(n):
        kw = {}
        if rng.random() < 0.5:
            kw["hits"] = int(rng.integers(-(2**40), 2**40))
        if rng.random() < 0.5:
            kw["limit"] = int(rng.integers(0, 2**62))
        if rng.random() < 0.5:
            kw["duration"] = int(rng.integers(-(2**31), 2**31))
        if rng.random() < 0.3:
            kw["burst"] = int(rng.integers(0, 2**31))
        if rng.random() < 0.3:
            kw["algorithm"] = int(rng.integers(0, 2))
        if rng.random() < 0.3:
            # Any behavior bits except GLOBAL (2): GLOBAL flips the
            # special flag, which is its own (covered) route.
            kw["behavior"] = int(rng.choice([1, 4, 8, 16]))
        if rng.random() < 0.3:
            kw["created_at"] = int(rng.integers(0, 2**50))
        name = rng.choice(["svc", "s" * int(rng.integers(1, 40)), "Ω≈"])
        reqs.append(pb.RateLimitReq(
            name=name, unique_key=f"k{i}-{rng.integers(0, 10**9)}", **kw
        ))
    return reqs


def test_arena_decode_fuzz_parity():
    """Fuzzed wire batches must decode into arena slabs identically to
    both the plain decode and the protobuf object path — the zero-copy
    ingest pipeline changes allocation, never values."""
    from gubernator_tpu.ops.reqcols import ColumnArena

    rng = np.random.default_rng(11)
    arena = ColumnArena(512, slabs=3)
    for trial in range(6):
        reqs = _rand_reqs(rng, int(rng.integers(1, 400)))
        data = _req_bytes(reqs)
        plain = fastwire.parse_req(data)
        slab = fastwire.parse_req(data, arena)
        assert plain is not None and slab is not None
        pc, pe, ps = plain
        sc, se, ss = slab
        assert sc.lease is not None, "arena lease was not used"
        assert pe == se and ps == ss
        assert bytes(pc.key_blob) == bytes(sc.key_blob)
        np.testing.assert_array_equal(pc.key_offsets, sc.key_offsets)
        for f in ("hits", "limit", "duration", "algorithm", "behavior",
                  "created_at", "burst", "name_len"):
            np.testing.assert_array_equal(
                getattr(pc, f), getattr(sc, f), err_msg=f"{f} trial {trial}"
            )
        # Object-path parity (columns_from_pb is the reference).
        ref_cols, ref_errors, ref_special = convert.columns_from_pb(
            pb.GetRateLimitsReq.FromString(data).requests
        )
        assert se == ref_errors and ss == ref_special
        assert bytes(sc.key_blob) == bytes(ref_cols.key_blob)
        for f in ("hits", "limit", "duration", "algorithm", "behavior",
                  "created_at", "burst"):
            np.testing.assert_array_equal(
                getattr(sc, f), getattr(ref_cols, f), err_msg=f
            )
        sc.release()
        sc.release()  # idempotent
    assert arena.in_use() == 0


def test_arena_exhaustion_and_oversize_fall_back():
    """The arena is a bounded fast path: all-slabs-busy and oversized
    batches fall back to plain allocation, never fail or block."""
    from gubernator_tpu.ops.reqcols import ColumnArena

    arena = ColumnArena(8, slabs=2)
    small = _req_bytes(_rand_reqs(np.random.default_rng(0), 4))
    big = _req_bytes(_rand_reqs(np.random.default_rng(1), 64))
    a = fastwire.parse_req(small, arena)[0]
    b = fastwire.parse_req(small, arena)[0]
    assert a.lease is not None and b.lease is not None
    c = fastwire.parse_req(small, arena)[0]  # both slabs busy
    assert c.lease is None
    np.testing.assert_array_equal(a.hits, c.hits)
    d = fastwire.parse_req(big, arena)[0]    # wider than the slab
    assert d.lease is None
    assert arena.metric_misses == 2
    a.release()
    e = fastwire.parse_req(small, arena)[0]  # the slab recycled
    assert e.lease is not None
    np.testing.assert_array_equal(e.hits, b.hits)


def test_arena_slab_reuse_does_not_alias_live_columns():
    """A released slab's next decode must not disturb a still-held
    fallback batch, and two live leases never alias each other."""
    from gubernator_tpu.ops.reqcols import ColumnArena

    arena = ColumnArena(64, slabs=2)
    rng = np.random.default_rng(5)
    d1 = _req_bytes(_rand_reqs(rng, 16))
    d2 = _req_bytes(_rand_reqs(rng, 16))
    c1 = fastwire.parse_req(d1, arena)[0]
    h1 = c1.hits.copy()
    c2 = fastwire.parse_req(d2, arena)[0]
    np.testing.assert_array_equal(c1.hits, h1)  # second lease: no alias
    c1.release()
    c3 = fastwire.parse_req(d2, arena)[0]       # reuses c1's slab
    np.testing.assert_array_equal(c3.hits, c2.hits)


# ----------------------------------------------------------------------
# One native crossing each way (docs/edge.md): the decode that counts,
# parses, checks and summarises in one call, against the decode it
# replaced; the encode that reads the matrix where it lies
# ----------------------------------------------------------------------
def _reference_parse_req(data):
    """``parse_req`` as it stood before the one-call decode, kept here
    as the reference: count, allocate, ``guber_parse_req``, then the
    numpy passes over the columns.  Adds the histogram ``_count_algorithms``
    would make."""
    import ctypes

    from gubernator_tpu.algos import algorithm_error, invalid_algorithm_mask
    from gubernator_tpu.types import ALGORITHM_MAX

    lib = fastwire.load()
    i64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    fn = lib.guber_parse_req
    fn.restype = ctypes.c_int64
    fn.argtypes = [ctypes.c_char_p, ctypes.c_int64, u8, ctypes.c_int64,
                   i64, i64, i64, i64, i64, i64, i64, i64, i64, u8]
    ln = len(data)
    n = lib.guber_wire_count(data, ln)
    if n < 0:
        return None
    if n == 0:
        return (), {}, False, None
    blob = np.empty(ln + n, np.uint8)
    ints = np.zeros((9, n + 1), np.int64)
    flags = np.zeros(n, np.uint8)
    off = ints[8, : n + 1]
    name_len, hits, limit, duration, algorithm, behavior, burst, created = (
        ints[i, :n] for i in range(8))
    if fn(data, ln, blob, len(blob), off, name_len, hits, limit, duration,
          algorithm, behavior, burst, created, flags) != n:
        return None
    created[created == 0] = CREATED_UNSET
    errors = {}
    for i in np.flatnonzero(flags & 3):
        errors[int(i)] = (
            "field 'unique_key' cannot be empty" if flags[i] & 2
            else "field 'namespace' cannot be empty")
    for i in np.flatnonzero(invalid_algorithm_mask(algorithm)):
        errors.setdefault(int(i), algorithm_error(algorithm[i]))
    special = bool((flags & 4).any()) or bool((behavior & (2 | 16)).any())
    ok = (algorithm >= 0) & (algorithm <= int(ALGORITHM_MAX))
    hist = np.bincount(algorithm[ok], minlength=int(ALGORITHM_MAX) + 1)
    cols = (bytes(blob[: off[n]]), off, hits, limit, duration, algorithm,
            behavior, created, burst, name_len)
    return cols, errors, special, hist


def _seeded(n):
    return lambda: _rand_reqs(np.random.default_rng(1000 + n), n)


def _with(**kw):
    """Seven plain items with one odd one in the middle."""
    def build():
        reqs = _rand_reqs(np.random.default_rng(77), 7)
        fields = {"name": "svc", "unique_key": "odd", "hits": 1, "limit": 9,
                  **kw}
        metadata = fields.pop("metadata", None)
        reqs[3] = pb.RateLimitReq(**fields)
        if metadata:
            reqs[3].metadata["trace"] = metadata
        return reqs
    return build


_DECODE_FRAMES = {
    "n0": _seeded(0), "n1": _seeded(1), "n7": _seeded(7),
    "n1000": _seeded(1000), "n4097": _seeded(4097),
    "empty_name": _with(name=""), "empty_key": _with(unique_key=""),
    "algorithm_5": _with(algorithm=5), "algorithm_neg1": _with(algorithm=-1),
    "metadata": _with(metadata="abc"),
    "global": _with(behavior=2), "multi_region": _with(behavior=16),
    "created_0": _with(created_at=0), "created_absent": _with(),
    "truncated": None,
}


@pytest.mark.parametrize("arena_mode", ["absent", "leased", "exhausted"])
@pytest.mark.parametrize("frame", list(_DECODE_FRAMES))
def test_decode_parity_with_the_reference(frame, arena_mode):
    """Same columns, errors and special as the decode it replaced, and a
    histogram equal to numpy's count, whatever the arena does."""
    from gubernator_tpu.ops.reqcols import ColumnArena

    build = _DECODE_FRAMES[frame]
    if build is None:
        data = _req_bytes(_seeded(7)())[:-3]
    else:
        data = _req_bytes(build())
    arena = None
    if arena_mode != "absent":
        arena = ColumnArena(4096, slabs=2)
    held = []
    if arena_mode == "exhausted":
        held = [arena.lease(), arena.lease()]
        assert arena.lease() is None
    in_use = arena.in_use() if arena is not None else 0
    want = _reference_parse_req(data)
    got = fastwire.parse_req(data, arena)
    if want is None:
        assert got is None
        assert arena is None or arena.in_use() == in_use
        return
    cols, errors, special = got
    ref_cols, ref_errors, ref_special, ref_hist = want
    assert errors == ref_errors
    assert special is ref_special
    n = len(cols)
    if not ref_cols:
        assert n == 0 and cols.lease is None
        assert arena is None or arena.in_use() == in_use
        return
    leased = arena_mode == "leased" and n <= 4096
    assert (cols.lease is not None) is leased
    assert bytes(cols.key_blob) == ref_cols[0]
    for name, ref in zip(
            ("key_offsets", "hits", "limit", "duration", "algorithm",
             "behavior", "created_at", "burst", "name_len"), ref_cols[1:]):
        np.testing.assert_array_equal(getattr(cols, name), ref, err_msg=name)
    assert list(cols.algo_hist) == ref_hist.tolist()
    if arena is not None and not leased and arena_mode == "leased":
        # too wide for the slab: counted as the size miss it is
        assert arena.metric_misses == 1 and arena.metric_leases == 0
    cols.release()
    for lease in held:
        lease.release()
    assert arena is None or arena.in_use() == 0


def _wide_mat(rng, n, width, off):
    """A column slice of a wider matrix, as the tick loop hands out."""
    whole = rng.integers(-(2 ** 62), 2 ** 62, (5, width))
    whole[0] = rng.integers(0, 2, width)
    whole[4] = rng.integers(0, 2, width)
    whole[:, ::7] = 0  # proto3 omits zeros
    return whole[:, off : off + n]


@pytest.mark.parametrize("shape", [
    "whole", "column_offset", "four_10_byte_varints", "int32", "transposed",
])
def test_encode_parity_and_over_limit(shape):
    """Bytes identical to the numpy encoder (itself proven against
    protobuf above) and the over-limit count beside them, from the
    matrix where it lies or from a copy of any other layout."""
    from gubernator_tpu.ops.engine import masked_over_limit
    from gubernator_tpu.transport.wire import encode_get_rate_limits_resp

    rng = np.random.default_rng(31)
    if shape == "whole":
        mat = _wide_mat(rng, 1000, 1000, 0)
    elif shape == "column_offset":
        mat = _wide_mat(rng, 1000, 4096, 1861)
    elif shape == "four_10_byte_varints":
        mat = np.full((5, 300), -1, np.int64)[:, 17:217]
    elif shape == "int32":
        mat = rng.integers(0, 2 ** 31, (5, 64)).astype(np.int32)
    else:
        mat = np.ascontiguousarray(_wide_mat(rng, 64, 64, 0).T).T
        assert mat.strides[1] != 8
    out, over = fastwire.encode_resp(mat)
    assert out == encode_get_rate_limits_resp(mat)
    assert over == masked_over_limit(mat, {}) == int(mat[4].sum())
    back = pb.GetRateLimitsResp.FromString(out)
    assert len(back.responses) == mat.shape[1]
    assert back.responses[5].remaining == int(mat[2, 5])


class _CountingLib:
    """Stands in for the codec library: every native entry counts."""

    def __init__(self, lib):
        self._lib = lib
        self.calls = []

    def __getattr__(self, name):
        fn = getattr(self._lib, name)

        def counted(*args):
            self.calls.append(name)
            return fn(*args)

        return counted


def test_one_native_crossing_each_way(monkeypatch):
    """A plain call decodes in one native call and encodes in one, with
    nothing converted by ``numpy.ctypeslib`` on the way."""
    from gubernator_tpu.ops.reqcols import ColumnArena

    def refuse(cls, obj):
        raise AssertionError("numpy.ctypeslib from_param on the serving path")

    lib = _CountingLib(fastwire.load())
    monkeypatch.setattr(fastwire, "_lib", lib)
    monkeypatch.setattr(np.ctypeslib._ndptr, "from_param", classmethod(refuse))
    arena = ColumnArena(4096, slabs=2)
    data = _req_bytes([
        pb.RateLimitReq(name="svc", unique_key=f"k{i}", hits=1, limit=100,
                        duration=60_000, algorithm=i % 5)
        for i in range(1000)
    ])
    for use in (arena, None):
        lib.calls.clear()
        cols, errors, special = fastwire.parse_req(data, use)
        assert not errors and not special and len(cols) == 1000
        assert list(cols.algo_hist) == [200] * 5
        # no arena: one more call, to count before it allocates
        assert lib.calls == (
            ["guber_decode_req"] if use is not None
            else ["guber_wire_count", "guber_decode_req"])
    lib.calls.clear()
    mat = _wide_mat(np.random.default_rng(3), 1000, 4096, 24)
    out, over = fastwire.encode_resp(mat)
    assert lib.calls == ["guber_encode_resp_mat"]
    assert len(pb.GetRateLimitsResp.FromString(out).responses) == 1000
