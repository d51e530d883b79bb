"""Reshard protocol tests: admission freeze, quiesce, the coordinator
state machine (commit / abort / rollback), and the crash journal.

Nearly everything here runs against stub engines or the single-chip
TickLoop — the mesh-engine relayout itself is covered by
test_mesh_engine.py.  The ONE mesh build in this module is the
reshard × ragged composition case at the bottom (a deliberately tiny
8→3→6 engine, the second leg actuated by the autoscaler), because what
it pins is the coordinator-visible outcome: extent offsets recomputed
against the new ``cap_to`` keep ``state_loss`` / ``double_served`` /
``parity_errors`` at zero under Zipf-skewed ragged dispatch.
"""

import threading

import pytest

from gubernator_tpu.admission import (
    CLASS_PEER,
    SHED_RESHARD_MSG,
    AdmissionConfig,
)
from gubernator_tpu.parallel.reshard import (
    PHASE_IDLE,
    ReshardCoordinator,
    ReshardError,
)
from gubernator_tpu.persistence import (
    TransitionLog,
    TransitionRecord,
    check_interrupted,
)
from gubernator_tpu.service.tickloop import TickLoop
from gubernator_tpu.types import RateLimitRequest, RateLimitResponse, Status
from gubernator_tpu.utils.metrics import Metrics


class _StubBatch:
    def __init__(self, reqs):
        self._reqs = reqs

    def handles(self):
        return []

    def responses(self):
        return [
            RateLimitResponse(status=Status.UNDER_LIMIT, limit=r.limit,
                              remaining=r.limit - r.hits)
            for r in self._reqs
        ]


class _StubEngine:
    """No-device engine: records batches, carries a fake key census for
    the coordinator's degenerate path + verify phase."""

    def __init__(self, items=()):
        self.batches = []
        self.items = list(items)

    def submit(self, reqs):
        self.batches.append(list(reqs))
        return _StubBatch(reqs)

    def cache_size(self):
        return len(self.items)

    def export_items(self):
        return [dict(it) for it in self.items]


def _reqs(n):
    return [
        RateLimitRequest(name="rs", unique_key=str(i), hits=1, limit=100,
                         duration=60_000, created_at=1_000)
        for i in range(n)
    ]


class _StubLoop:
    """Records the freeze protocol a coordinator drives."""

    def __init__(self, quiesce_ok=True):
        self.calls = []
        self.quiesce_ok = quiesce_ok

    def freeze(self, shed_peers=False):
        self.calls.append(("freeze", shed_peers))

    def unfreeze(self):
        self.calls.append(("unfreeze",))

    def quiesce(self, timeout):
        self.calls.append(("quiesce", timeout))
        return self.quiesce_ok


# ---------------------------------------------------------------------------
# TickLoop freeze / quiesce
# ---------------------------------------------------------------------------
def test_freeze_sheds_clients_retriable_peers_drain():
    """Level-1 freeze: CLIENT windows answer the retriable reshard shed
    without touching the queue; PEER reconcile traffic keeps flowing
    (it must land before the cutover).  Level 2 sheds both; unfreeze
    restores normal service."""
    eng = _StubEngine()
    m = Metrics()
    loop = TickLoop(eng, admission=AdmissionConfig(), metrics=m)
    try:
        loop.freeze()
        out = loop.submit(_reqs(2)).result(timeout=5)
        assert [r.error for r in out] == [SHED_RESHARD_MSG] * 2
        peer_out = loop.submit(_reqs(1), klass=CLASS_PEER).result(timeout=5)
        assert peer_out[0].error == ""
        assert sum(len(b) for b in eng.batches) == 1  # only the peer window
        loop.freeze(shed_peers=True)
        out = loop.submit(_reqs(1), klass=CLASS_PEER).result(timeout=5)
        assert out[0].error == SHED_RESHARD_MSG
        loop.unfreeze()
        out = loop.submit(_reqs(1)).result(timeout=5)
        assert out[0].error == ""
        assert m.sample("gubernator_tpu_admission_shed_total",
                        {"reason": "reshard"}) == 3
        assert loop.metric_shed_admission["reshard"] == 3
    finally:
        loop.close()


def test_freeze_never_downgrades_and_quiesce_idle():
    eng = _StubEngine()
    loop = TickLoop(eng, admission=AdmissionConfig())
    try:
        loop.freeze(shed_peers=True)
        loop.freeze()  # must not downgrade the escalated freeze
        out = loop.submit(_reqs(1), klass=CLASS_PEER).result(timeout=5)
        assert out[0].error == SHED_RESHARD_MSG
        loop.unfreeze()
        loop.submit(_reqs(2)).result(timeout=5)
        assert loop.quiesce(timeout=5.0)  # drained loop is idle
    finally:
        loop.close()


def test_quiesce_times_out_under_stuck_window():
    """A window wedged on the device keeps the loop non-idle: quiesce
    must report False inside its budget instead of hanging (the
    coordinator aborts on that answer)."""
    gate = threading.Event()

    class _GatedEngine(_StubEngine):
        def submit(self, reqs):
            gate.wait(timeout=10)
            return super().submit(reqs)

    eng = _GatedEngine()
    loop = TickLoop(eng, admission=AdmissionConfig())
    try:
        fut = loop.submit(_reqs(1))
        assert not loop.quiesce(timeout=0.2)
        gate.set()
        assert fut.result(timeout=5)[0].error == ""
        assert loop.quiesce(timeout=5.0)
    finally:
        gate.set()
        loop.close()


# ---------------------------------------------------------------------------
# Coordinator state machine
# ---------------------------------------------------------------------------
def _items(n):
    return [{"key": f"it-{i}", "remaining": 5, "expire_at": 1 << 60}
            for i in range(n)]


def test_coordinator_degenerate_commit_and_metrics(tmp_path):
    """Single-chip engines (no native reshard) run the full protocol —
    freeze, drain, journal, verify — around an identity transition; the
    journal holds begin+commit and the metrics record the outcome."""
    eng = _StubEngine(items=_items(7))
    tl = _StubLoop()
    m = Metrics()
    coord = ReshardCoordinator(
        eng, tick_loop=tl, transition_log=TransitionLog(str(tmp_path)),
        metrics=m, freeze_timeout=1.0,
    )
    res = coord.reshard(2)
    assert res["outcome"] == "committed" and res["degenerate"] is True
    assert res["live_items"] == 7
    assert res["state_loss"] == 0 and res["double_served"] == 0
    # Freeze protocol order: level-1 freeze, quiesce, escalate, unfreeze.
    assert tl.calls == [
        ("freeze", False), ("quiesce", 1.0), ("freeze", True),
        ("unfreeze",),
    ]
    recs = TransitionLog(str(tmp_path)).records()
    assert [(r.phase, r.from_shards, r.to_shards) for r in recs] == [
        ("begin", 1, 2), ("commit", 1, 2),
    ]
    assert m.sample("gubernator_tpu_reshard_transitions_total",
                    {"result": "committed"}) == 1
    assert m.sample("gubernator_tpu_reshard_phase") == 0  # back to idle
    assert coord.phase != PHASE_IDLE  # terminal phase retained in status
    assert coord.status()["last"]["outcome"] == "committed"
    # A committed journal is not an interruption.
    assert check_interrupted(TransitionLog(str(tmp_path))) is None


def test_coordinator_drain_timeout_aborts(tmp_path):
    eng = _StubEngine(items=_items(3))
    tl = _StubLoop(quiesce_ok=False)
    m = Metrics()
    coord = ReshardCoordinator(
        eng, tick_loop=tl, transition_log=TransitionLog(str(tmp_path)),
        metrics=m, freeze_timeout=0.1,
    )
    res = coord.reshard(4)
    assert res["outcome"] == "aborted" and "drain timeout" in res["reason"]
    assert ("unfreeze",) in tl.calls           # admission always restored
    assert ("freeze", True) not in tl.calls    # never escalated
    assert TransitionLog(str(tmp_path)).records() == []  # pre-journal abort
    assert m.sample("gubernator_tpu_reshard_transitions_total",
                    {"result": "aborted"}) == 1


def test_coordinator_breaker_abort():
    """An open breaker (mid-transfer peer death) aborts before the
    cutover; admission unfreezes."""
    tl = _StubLoop()
    coord = ReshardCoordinator(
        _StubEngine(items=_items(2)), tick_loop=tl,
        breaker_check=lambda: True,
    )
    res = coord.reshard(3)
    assert res["outcome"] == "aborted" and "breaker" in res["reason"]
    assert tl.calls[-1] == ("unfreeze",)


def test_coordinator_engine_failure_rolls_back(tmp_path):
    """An engine that raises mid-relayout (it restores the old layout
    before raising) lands as an aborted transition with begin+abort in
    the journal — a crash *between* those records is what the startup
    interruption check catches."""

    class _ExplodingEngine(_StubEngine):
        n_shards = 4

        def reshard(self, new_shards):
            raise RuntimeError("device fell over")

    coord = ReshardCoordinator(
        _ExplodingEngine(items=_items(2)), tick_loop=_StubLoop(),
        transition_log=TransitionLog(str(tmp_path)),
    )
    res = coord.reshard(2)
    assert res["outcome"] == "aborted" and "rolled back" in res["reason"]
    recs = TransitionLog(str(tmp_path)).records()
    assert [r.phase for r in recs] == ["begin", "abort"]
    assert check_interrupted(TransitionLog(str(tmp_path))) is None


def test_coordinator_rejects_concurrent_and_bad_target():
    coord = ReshardCoordinator(_StubEngine())
    with pytest.raises(ReshardError):
        coord.reshard(0)
    assert coord._lock.acquire(blocking=False)  # simulate a running one
    try:
        with pytest.raises(ReshardError, match="already running"):
            coord.reshard(2)
    finally:
        coord._lock.release()
    assert coord.reshard(1)["outcome"] == "noop"  # 1 -> 1


def test_coordinator_verify_counts_damage():
    """A lossy/double-resident post-cutover table is counted, never
    silent (test_reshard_ragged_zipf_round_trip_zero_loss holds both
    at zero on a real mesh)."""

    class _DamagedEngine(_StubEngine):
        n_shards = 2

        def reshard(self, new_shards):
            return {"live_items": 4}

        def export_items(self):  # 2 unique keys, one resident twice
            return [{"key": "a"}, {"key": "a"}, {"key": "b"}]

    m = Metrics()
    coord = ReshardCoordinator(_DamagedEngine(), metrics=m)
    res = coord.reshard(1)
    assert res["outcome"] == "committed"
    assert res["state_loss"] == 2      # 4 expected, 2 unique survived
    assert res["double_served"] == 1
    assert m.sample("gubernator_tpu_reshard_state_loss_total") == 2
    assert m.sample("gubernator_tpu_reshard_double_served_total") == 1


def test_coordinator_pauses_global_mesh_reconcile():
    class _Pausable:
        def __init__(self):
            self.paused = 0
            self.log = []

        def pause_reconcile(self):
            self.paused += 1
            self.log.append("pause")

        def resume_reconcile(self):
            self.paused -= 1
            self.log.append("resume")

    gm = _Pausable()
    coord = ReshardCoordinator(
        _StubEngine(items=_items(1)), tick_loop=_StubLoop(),
        global_engine=gm,
    )
    assert coord.reshard(2)["outcome"] == "committed"
    assert gm.log == ["pause", "resume"] and gm.paused == 0


# ---------------------------------------------------------------------------
# Transition journal
# ---------------------------------------------------------------------------
def test_transition_log_crash_detection(tmp_path):
    log = TransitionLog(str(tmp_path))
    log.append(TransitionRecord("begin", 8, 4, epoch=1))
    log.append(TransitionRecord("commit", 8, 4, epoch=1))
    log.append(TransitionRecord("begin", 4, 8, epoch=2))  # died here
    rec = check_interrupted(TransitionLog(str(tmp_path)))
    assert rec is not None
    assert (rec.from_shards, rec.to_shards, rec.epoch) == (4, 8, 2)
    # check_interrupted clears the journal: the record matters across
    # exactly one restart.
    assert TransitionLog(str(tmp_path)).records() == []


def test_transition_log_torn_tail_tolerated(tmp_path):
    log = TransitionLog(str(tmp_path))
    log.append(TransitionRecord("begin", 2, 4, epoch=1))
    with open(log.path, "ab") as f:
        f.write(b"\x00garbage-torn-write")
    rec = check_interrupted(TransitionLog(str(tmp_path)))
    assert rec is not None and rec.to_shards == 4


def test_transition_log_disabled_is_noop():
    log = TransitionLog(None)
    log.append(TransitionRecord("begin", 1, 2, epoch=1))
    assert log.records() == []
    assert check_interrupted(log) is None


def test_interrupted_detection_counts_metric():
    m = Metrics()
    coord = ReshardCoordinator(_StubEngine(), metrics=m)
    coord.record_interrupted(TransitionRecord("begin", 8, 4, epoch=3))
    assert m.sample("gubernator_tpu_reshard_transitions_total",
                    {"result": "interrupted"}) == 1


# ---------------------------------------------------------------------------
# Reshard × ragged dispatch composition (the one mesh build here; see
# the module docstring)
# ---------------------------------------------------------------------------
def test_reshard_ragged_zipf_round_trip_zero_loss():
    """8→3→6 through the full coordinator protocol with Zipf-skewed
    traffic served by the ragged dispatch on every layout: the extent
    offsets are recomputed against each layout's ``cap_to``, so
    ``state_loss`` / ``double_served`` / ``parity_errors`` stay 0 and
    decisions keep matching a single-chip replay across both cutovers.
    The overflow canary must never move — skew has no fallback."""
    import asyncio

    import jax
    import numpy as np

    from gubernator_tpu.autoscale import (
        Autoscaler, AutoscalePolicy, PolicyConfig, SignalSnapshot)
    from gubernator_tpu.autoscale.controller import ACT
    from gubernator_tpu.ops.engine import TickEngine
    from gubernator_tpu.parallel.mesh_engine import MeshTickEngine, make_mesh
    from gubernator_tpu.resilience import ManualClock
    from gubernator_tpu.utils import timeutil

    # Wall-clock base: the coordinator's cutover stamps load_items with
    # the real clock, so synthetic epochs would expire every item at
    # the relayout boundary.
    NOW = timeutil.now_ms()
    eng = MeshTickEngine(
        mesh=make_mesh(jax.devices()), local_capacity=16, max_batch=32
    )
    ref = TickEngine(capacity=8 * 16, max_batch=32)
    coord = ReshardCoordinator(eng, verify=True)
    rng = np.random.default_rng(29)

    def zipf_window(width):
        return [
            RateLimitRequest(
                name="zr", unique_key=f"z{int(rng.zipf(1.2)) % 40}",
                hits=1, limit=10_000, duration=3_600_000,
            )
            for _ in range(width)
        ]

    def serve_and_compare(t):
        reqs = zipf_window(int(rng.integers(8, 33)))
        a = eng.process(reqs, now=NOW + t)
        b = ref.process(reqs, now=NOW + t)
        assert [(r.status, r.remaining, r.error) for r in a] == \
               [(r.status, r.remaining, r.error) for r in b]

    # The second leg is autonomous: the autoscaler's own step, under
    # sustained pressure, actuates it through the executor the service
    # wires in (coord.try_reshard), doubling 3 to 6.  Its audit is held
    # to the same zeros as the operator's leg.
    auto = []

    def executor(target):
        auto.append(coord.try_reshard(target))
        return auto[-1]

    clock = ManualClock()
    scaler = Autoscaler(
        lambda: SignalSnapshot(p99_ms=50.0, queue_depth=0,
                               hot_occupancy=0.9, shards=eng.n_shards),
        executor,
        policy=AutoscalePolicy(PolicyConfig(
            windows=1, target_p99_ms=5.0, min_shards=3, max_shards=8)),
        dry_run=False, clock=clock, sleep=clock.sleep,
    )

    def autonomous(target):
        assert asyncio.run(scaler.step()).action == ACT
        return auto[-1]

    for t in range(2):
        serve_and_compare(t)
    for target, t0, actuate in [(3, 100, coord.reshard),
                                (6, 200, autonomous)]:
        res = actuate(target)
        assert res["outcome"] == "committed", res
        assert res["to_shards"] == target == eng.n_shards
        assert res["state_loss"] == 0 and res["double_served"] == 0
        assert res["parity_errors"] == 0
        for t in range(2):
            serve_and_compare(t0 + t)
    assert eng.metric_routed_overflows == 0


def test_try_reshard_busy_dict_is_single_source_of_truth():
    """The concurrent-call outcome is one defined dict from the
    coordinator (BUSY_RESULT) — Instance.reshard, /debug/reshard's 409,
    and the autoscaler's reshard_busy veto all consume it instead of
    string-matching the error."""
    from gubernator_tpu.parallel.reshard import BUSY_RESULT

    coord = ReshardCoordinator(_StubEngine(items=_items(1)))
    assert not coord.is_busy()
    assert coord._lock.acquire(blocking=False)  # simulate a running one
    try:
        assert coord.is_busy()
        out = coord.try_reshard(2)
        assert out == BUSY_RESULT
        assert out is not BUSY_RESULT  # a copy; callers can't mutate it
        # the raising wrapper stays the compat surface
        with pytest.raises(ReshardError, match="already running"):
            coord.reshard(2)
    finally:
        coord._lock.release()
    assert not coord.is_busy()
    # bad targets still raise on BOTH entry points — busy is the only
    # non-raising outcome
    with pytest.raises(ReshardError):
        coord.try_reshard(0)
    assert coord.try_reshard(2)["outcome"] == "committed"


def test_coordinator_pauses_federation_sends():
    """Mirror of the global-mesh pause: federation envelope sends stop
    at FREEZE and resume after commit AND after abort (the finally)."""

    class _Pausable:
        def __init__(self):
            self.paused = 0
            self.log = []

        def pause(self):
            self.paused += 1
            self.log.append("pause")

        def resume(self):
            self.paused -= 1
            self.log.append("resume")

    fed = _Pausable()
    coord = ReshardCoordinator(
        _StubEngine(items=_items(1)), tick_loop=_StubLoop(), federation=fed,
    )
    assert coord.reshard(2)["outcome"] == "committed"
    assert fed.log == ["pause", "resume"] and fed.paused == 0

    # abort path: drain timeout — the finally must still resume
    fed2 = _Pausable()
    coord2 = ReshardCoordinator(
        _StubEngine(items=_items(1)), tick_loop=_StubLoop(quiesce_ok=False),
        federation=fed2, freeze_timeout=0.01,
    )
    assert coord2.reshard(2)["outcome"] == "aborted"
    assert fed2.log == ["pause", "resume"] and fed2.paused == 0
