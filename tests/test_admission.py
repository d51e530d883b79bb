"""Overload control plane unit tests (docs/overload.md).

Everything deadline-driven runs on :class:`ManualClock` — no wall-clock
sleeps anywhere near the shed decisions.  The TickLoop tests inject the
clock for *deadline math only* (the batch window stays on real time, so
the dispatch thread never wedges on a frozen clock) and use stub
engines, so the whole file is device-free and near-instant.
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from gubernator_tpu.admission import (
    CLASS_CLIENT,
    CLASS_PEER,
    POLICY_FAIL_CLOSED,
    POLICY_FAIL_OPEN,
    SHED_EXPIRED_MSG,
    SHED_SHUTDOWN_MSG,
    AdmissionConfig,
    AdmissionQueue,
    AimdLimiter,
    BudgetExhaustedError,
    QueueItem,
    batch_deadline,
    budget_header_value,
    deadline_from_header,
)
from gubernator_tpu.resilience.clock import ManualClock
from gubernator_tpu.service.tickloop import TickLoop
from gubernator_tpu.types import (
    PeerInfo,
    RateLimitRequest,
    RateLimitResponse,
    Status,
)


def _item(n=1, deadline=None, klass=CLASS_CLIENT, kind="obj", payload=None):
    if payload is None:
        payload = [
            RateLimitRequest(name="a", unique_key=str(i), hits=1,
                             limit=100, duration=60_000)
            for i in range(n)
        ]
    return QueueItem(kind, payload, n, Future(), deadline, klass)


# ----------------------------------------------------------------------
# Deadline helpers
# ----------------------------------------------------------------------

def test_budget_header_round_trip_reanchors():
    # Sender at now=100 with 250ms left; receiver at its own now=7.
    hdr = budget_header_value(100.25, now=100.0)
    assert hdr == "250"
    d = deadline_from_header(hdr, now=7.0)
    assert d == pytest.approx(7.25)


def test_budget_header_spent_renders_zero_not_negative():
    assert budget_header_value(99.0, now=100.0) == "0"
    # A zero budget re-anchors to "already expired", not "no deadline".
    d = deadline_from_header("0", now=5.0)
    assert d == 5.0


def test_malformed_budget_header_never_fails_the_request():
    assert deadline_from_header(None, now=1.0) is None
    assert deadline_from_header("nope", now=1.0) is None
    assert deadline_from_header("-5", now=1.0) is None


def test_batch_deadline_is_earliest_member():
    rs = [
        RateLimitRequest(name="a", unique_key="1"),
        RateLimitRequest(name="a", unique_key="2", deadline=9.0),
        RateLimitRequest(name="a", unique_key="3", deadline=4.0),
    ]
    assert batch_deadline(rs) == 4.0
    assert batch_deadline(rs[:1]) is None


# ----------------------------------------------------------------------
# AdmissionQueue
# ----------------------------------------------------------------------

def test_queue_peer_class_drains_before_client():
    q = AdmissionQueue(limit=100)
    a = _item(klass=CLASS_CLIENT)
    b = _item(klass=CLASS_PEER)
    c = _item(klass=CLASS_CLIENT)
    for it in (a, b, c):
        assert q.push(it) == []
    out = q.pop_window(100)
    assert out == [b, a, c]  # peer first, then client FIFO


def test_queue_overflow_sheds_soonest_expiring_client():
    q = AdmissionQueue(limit=3)
    far = _item(deadline=50.0)
    soon = _item(deadline=10.0)
    none = _item(deadline=None)  # deadline-less ranks last
    assert q.push(far) == []
    assert q.push(soon) == []
    assert q.push(none) == []
    newcomer = _item(deadline=40.0)
    shed = q.push(newcomer)
    assert shed == [soon]
    assert q.requests == 3
    assert newcomer in q.pop_window(100)


def test_queue_client_arrival_never_evicts_peer_work():
    q = AdmissionQueue(limit=2)
    p1 = _item(klass=CLASS_PEER)
    p2 = _item(klass=CLASS_PEER)
    assert q.push(p1) == []
    assert q.push(p2) == []
    client = _item(klass=CLASS_CLIENT, deadline=1.0)
    shed = q.push(client)
    assert shed == [client]  # the arrival sheds itself
    assert q.pop_window(100) == [p1, p2]


def test_queue_peer_arrival_may_evict_peer_when_no_client_queued():
    q = AdmissionQueue(limit=2)
    p1 = _item(klass=CLASS_PEER, deadline=5.0)
    p2 = _item(klass=CLASS_PEER, deadline=1.0)
    assert q.push(p1) == []
    assert q.push(p2) == []
    p3 = _item(klass=CLASS_PEER, deadline=9.0)
    assert q.push(p3) == [p2]


def test_queue_oversized_item_admitted_when_empty_and_popped():
    q = AdmissionQueue(limit=4)
    big = _item(n=10)
    assert q.push(big) == []  # never deadlocks a legal batch
    assert q.pop_window(4) == [big]  # always at least one item
    assert q.requests == 0


def test_queue_pop_window_respects_request_bound():
    q = AdmissionQueue(limit=100)
    items = [_item(n=3) for _ in range(4)]
    for it in items:
        q.push(it)
    out = q.pop_window(7)
    assert out == items[:2]  # 3+3 fits, +3 would exceed 7
    assert q.requests == 6


# ----------------------------------------------------------------------
# AIMD limiter
# ----------------------------------------------------------------------

def test_limiter_disabled_at_zero_target():
    lim = AimdLimiter(0.0, max_limit=1000)
    assert not lim.enabled
    for _ in range(100):
        lim.record(1e9)
    assert lim.window_limit == 1000  # untouched


def test_limiter_backs_off_multiplicatively_then_recovers():
    lim = AimdLimiter(10.0, max_limit=1000, adjust_every=4)
    assert lim.window_limit == 1000  # starts wide open
    for _ in range(4):
        lim.record(50.0)  # p99 over target
    assert lim.window_limit == 800
    assert lim.metric_decreases == 1
    for _ in range(4):
        lim.record(50.0)
    assert lim.window_limit == 640
    # Healthy windows: additive recovery, one step per adjustment.
    for _ in range(4):
        lim.record(1.0)
    assert lim.window_limit == 640 + lim.step
    assert lim.metric_increases == 1


def test_limiter_converges_within_bounds():
    lim = AimdLimiter(10.0, max_limit=1000, adjust_every=4)
    for _ in range(200):
        lim.record(50.0)
    assert lim.window_limit == lim.min_limit == max(1, 1000 // 32)
    for _ in range(100_000 // 4):
        lim.record(1.0)
    assert lim.window_limit == 1000  # clamped at max


# ----------------------------------------------------------------------
# AdmissionConfig
# ----------------------------------------------------------------------

def test_admission_config_from_env(monkeypatch):
    monkeypatch.setenv("GUBER_REQUEST_TIMEOUT", "2s")
    monkeypatch.setenv("GUBER_TARGET_P99_MS", "7.5")
    monkeypatch.setenv("GUBER_PENDING_LIMIT", "123")
    monkeypatch.setenv("GUBER_SHED_POLICY", "fail-closed")
    c = AdmissionConfig.from_env()
    assert c.request_timeout == 2.0
    assert c.target_p99_ms == 7.5
    assert c.pending_limit == 123
    assert c.shed_policy == POLICY_FAIL_CLOSED
    assert c.effective_pending_limit(1000) == 123


def test_admission_config_junk_falls_back(monkeypatch):
    monkeypatch.setenv("GUBER_REQUEST_TIMEOUT", "soon")
    monkeypatch.setenv("GUBER_TARGET_P99_MS", "fast")
    monkeypatch.setenv("GUBER_PENDING_LIMIT", "many")
    monkeypatch.setenv("GUBER_SHED_POLICY", "fail-sideways")
    c = AdmissionConfig.from_env()
    assert c.request_timeout == 30.0
    assert c.target_p99_ms == 0.0
    assert c.pending_limit == 0
    assert c.shed_policy == POLICY_FAIL_OPEN
    assert c.effective_pending_limit(1000) == 8000  # auto: 8x window


# ----------------------------------------------------------------------
# TickLoop admission behavior (stub engines, ManualClock deadlines)
# ----------------------------------------------------------------------

class _StubBatch:
    def __init__(self, reqs):
        self._reqs = reqs

    def handles(self):
        return []

    def responses(self):
        return [
            RateLimitResponse(
                status=Status.UNDER_LIMIT, limit=r.limit,
                remaining=r.limit - r.hits,
            )
            for r in self._reqs
        ]


class _StubEngine:
    """Counts submissions; optionally blocks inside submit so tests can
    deterministically fill the admission queue behind a busy device."""

    def __init__(self, gate: threading.Event = None):
        self.batches = []
        self.gate = gate
        self.entered = threading.Event()

    def submit(self, reqs):
        self.entered.set()
        if self.gate is not None:
            self.gate.wait(timeout=10)
        self.batches.append(list(reqs))
        return _StubBatch(reqs)


def _reqs(n, limit=100):
    return [
        RateLimitRequest(name="t", unique_key=str(i), hits=1, limit=limit,
                         duration=60_000, created_at=1_000)
        for i in range(n)
    ]


def test_tickloop_sheds_expired_before_pack():
    clk = ManualClock(start=100.0)
    eng = _StubEngine()
    loop = TickLoop(eng, admission=AdmissionConfig(), clock=clk)
    try:
        fut = loop.submit(_reqs(3), deadline=99.0)  # already past
        out = fut.result(timeout=5)
        assert len(out) == 3
        assert all(r.error == SHED_EXPIRED_MSG for r in out)
        assert eng.batches == []  # never reached the device
        assert loop.metric_shed_admission["expired"] == 3
        assert loop.metric_expired_served == 0  # the invariant
    finally:
        loop.close()


def test_tickloop_mixed_window_serves_live_sheds_dead():
    clk = ManualClock(start=100.0)
    eng = _StubEngine()
    loop = TickLoop(eng, admission=AdmissionConfig(), clock=clk)
    try:
        dead = loop.submit(_reqs(2), deadline=50.0)
        live = loop.submit(_reqs(1), deadline=200.0)
        assert [r.error for r in dead.result(timeout=5)] == (
            [SHED_EXPIRED_MSG] * 2)
        out = live.result(timeout=5)
        assert out[0].error == "" and out[0].status == Status.UNDER_LIMIT
        assert sum(len(b) for b in eng.batches) == 1
        assert loop.metric_expired_served == 0
    finally:
        loop.close()


def test_tickloop_deadline_none_is_never_shed():
    clk = ManualClock(start=1e9)  # absurdly late clock
    eng = _StubEngine()
    loop = TickLoop(eng, admission=AdmissionConfig(), clock=clk)
    try:
        out = loop.submit(_reqs(2)).result(timeout=5)
        assert all(r.error == "" for r in out)
        assert loop.metric_shed_admission == {}
    finally:
        loop.close()


def _overflow_shed(policy):
    """Wedge the engine on a gate, overfill the bounded queue, and
    return the overflow victim's answered responses."""
    gate = threading.Event()
    eng = _StubEngine(gate=gate)
    adm = AdmissionConfig(pending_limit=2, shed_policy=policy)
    loop = TickLoop(eng, admission=adm)
    try:
        first = loop.submit(_reqs(1))  # dispatch thread blocks in submit
        assert eng.entered.wait(timeout=5)
        victim = loop.submit(_reqs(2), deadline=time.monotonic() + 5.0)
        # Overflow: the queued victim (soonest deadline) is answered
        # synchronously in the caller's thread — no timing involved.
        survivor = loop.submit(_reqs(2), deadline=time.monotonic() + 50.0)
        out = victim.result(timeout=1)
        gate.set()
        assert survivor.result(timeout=5)
        assert first.result(timeout=5)
        assert loop.metric_shed_admission["overflow"] == 2
        return out
    finally:
        gate.set()
        loop.close()


def test_tickloop_overflow_fail_open_answers_under_limit():
    out = _overflow_shed(POLICY_FAIL_OPEN)
    assert all(r.status == Status.UNDER_LIMIT for r in out)
    assert all(r.remaining == r.limit == 100 for r in out)
    assert all(r.error == "" for r in out)


def test_tickloop_overflow_fail_closed_answers_over_limit():
    out = _overflow_shed(POLICY_FAIL_CLOSED)
    assert all(r.status == Status.OVER_LIMIT for r in out)
    assert all(r.remaining == 0 for r in out)
    assert all(r.limit == 100 for r in out)


def test_tickloop_policy_matrix_shapes():
    class _Cols:
        limit = np.array([10, 20], np.int64)
        created_at = np.array([100, 100], np.int64)
        duration = np.array([5, 5], np.int64)

    loop = TickLoop(_StubEngine(), admission=AdmissionConfig(
        shed_policy=POLICY_FAIL_CLOSED))
    try:
        mat = loop._policy_matrix(_Cols(), 2)
        assert mat.shape == (5, 2)
        assert (mat[0] == int(Status.OVER_LIMIT)).all()
        assert (mat[2] == 0).all() and (mat[4] == 1).all()
        assert (mat[1] == [10, 20]).all() and (mat[3] == 105).all()
        loop.shed_policy = POLICY_FAIL_OPEN
        mat = loop._policy_matrix(_Cols(), 2)
        assert (mat[0] == 0).all() and (mat[2] == [10, 20]).all()
        assert (mat[4] == 0).all()
    finally:
        loop.close()


def test_tickloop_wedged_close_answers_queued_with_retriable_shed():
    """Satellite: close() on a wedged dispatch thread must answer every
    queued future with a retriable shed status, not abandon them behind
    the old fixed join timeout."""
    gate = threading.Event()
    eng = _StubEngine(gate=gate)
    loop = TickLoop(eng, admission=AdmissionConfig(pending_limit=100))
    stuck = None
    try:
        first = loop.submit(_reqs(1))
        assert eng.entered.wait(timeout=5)
        stuck = loop.submit(_reqs(3))  # queued behind the wedged window
        # Make close() take the wedged branch immediately instead of
        # burning the real 5s join timeout.
        real_join = loop._thread.join
        loop._thread.join = lambda timeout=None: None
        loop.close()
        out = stuck.result(timeout=1)
        assert [r.error for r in out] == [SHED_SHUTDOWN_MSG] * 3
        assert loop.metric_shed_admission["shutdown"] == 3
    finally:
        # Unwedge so the real threads exit; first window still resolves.
        gate.set()
        if stuck is not None:
            loop._thread.join = real_join
        loop._thread.join(timeout=5)
        assert first.result(timeout=5)


def test_tickloop_limiter_narrows_admitted_window():
    clk = ManualClock(start=0.0)
    eng = _StubEngine()
    adm = AdmissionConfig(target_p99_ms=5.0)
    loop = TickLoop(eng, batch_limit=100, admission=adm, clock=clk)
    try:
        assert loop.limiter.enabled
        # Saturation evidence recorded out-of-band (as _metrics_sync
        # would): the next window must be admitted narrower.
        for _ in range(loop.limiter.adjust_every):
            loop.limiter.record(50.0)
        assert loop.limiter.window_limit == 80
        out = loop.submit(_reqs(5)).result(timeout=5)
        assert len(out) == 5
    finally:
        loop.close()


# ----------------------------------------------------------------------
# PeerClient budget propagation
# ----------------------------------------------------------------------

def _peer_client(clk):
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.service.peer_client import PeerClient

    return PeerClient(
        PeerInfo(grpc_address="127.0.0.1:1"),
        behaviors=BehaviorConfig(batch_timeout=0.5),
        clock=clk,
    )


def test_rpc_budget_no_deadline_uses_batch_timeout():
    pc = _peer_client(ManualClock(start=10.0))
    timeout, hdr = pc.rpc_budget(_reqs(2))
    assert timeout == 0.5 and hdr is None


def test_rpc_budget_forwards_remaining_not_original():
    clk = ManualClock(start=10.0)
    pc = _peer_client(clk)
    rs = _reqs(2)
    rs[0].deadline = 10.3  # 300ms left
    timeout, hdr = pc.rpc_budget(rs)
    assert timeout == pytest.approx(0.3)
    assert hdr == "300"
    clk.advance(0.2)  # budget drains as time passes
    timeout, hdr = pc.rpc_budget(rs)
    assert timeout == pytest.approx(0.1)
    assert hdr == "100"


def test_rpc_budget_floor_and_cap():
    clk = ManualClock(start=0.0)
    pc = _peer_client(clk)
    rs = _reqs(1)
    rs[0].deadline = 0.001  # 1ms left: floored, one real wire attempt
    timeout, hdr = pc.rpc_budget(rs)
    assert timeout == pc.timeout_floor == pytest.approx(0.05)
    assert hdr == "1"  # the header still tells the peer the truth
    rs[0].deadline = 60.0  # huge budget: capped at batch_timeout
    timeout, hdr = pc.rpc_budget(rs)
    assert timeout == 0.5
    assert hdr == "60000"


def test_rpc_budget_spent_raises_before_the_wire():
    clk = ManualClock(start=100.0)
    pc = _peer_client(clk)
    rs = _reqs(1)
    rs[0].deadline = 99.0
    with pytest.raises(BudgetExhaustedError):
        pc.rpc_budget(rs)


# ----------------------------------------------------------------------
# Edge deadline derivation + arena fallback budget
# ----------------------------------------------------------------------

def test_edge_deadline_precedence():
    from gubernator_tpu.transport.daemon import _edge_deadline

    class _Ctx:
        def __init__(self, md=(), rem=None):
            self._md = md
            self._rem = rem

        def invocation_metadata(self):
            return self._md

        def time_remaining(self):
            return self._rem

    t0 = time.monotonic()
    # Header wins over the gRPC context deadline.
    d = _edge_deadline(
        _Ctx(md=(("guber-deadline-ms", "250"),), rem=9.0), 30.0)
    assert d is not None and 0.2 <= d - t0 <= 0.3
    # No header: the context deadline.
    d = _edge_deadline(_Ctx(rem=2.0), 30.0)
    assert d is not None and 1.9 <= d - time.monotonic() + 0.1 <= 2.1
    # Neither: the configured default budget.
    d = _edge_deadline(_Ctx(), 30.0)
    assert d is not None and d - time.monotonic() > 29.0
    # Malformed header falls through to the next source, never errors.
    d = _edge_deadline(_Ctx(md=(("guber-deadline-ms", "junk"),)), 0.0)
    assert d is None  # default 0 = no deadline


@pytest.mark.parametrize("rows", [8, 1])
def test_arena_fallback_budget_is_per_window(rows):
    """The budget is fallback_limit full batches' worth of ROWS: two
    8-row frames spend it, and so do sixteen one-row frames — a crowd
    of small callers is not overload just for being many frames."""
    from gubernator_tpu.ops.reqcols import ColumnArena

    arena = ColumnArena(max_batch=8, slabs=1, fallback_limit=2)
    lease = arena.lease()
    assert lease is not None
    # Slab busy: fits-but-unleasable → budgeted fallbacks, then shed.
    assert arena.fits(4, 64)
    assert arena.lease() is None
    frames = 16 // rows
    for _ in range(frames):
        assert arena.try_fallback(rows)
    assert not arena.try_fallback(rows)  # budget spent
    assert arena.metric_fallbacks == frames
    lease.release()  # window completed: budget resets
    lease2 = arena.lease()
    assert arena.try_fallback(rows)
    lease2.release()
