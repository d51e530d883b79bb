"""Chaos suite: fault-injected clusters proving the peer path degrades
instead of lying (docs/resilience.md acceptance runs).

Scenarios: one peer at 100% injected RPC failure (breaker opens, GLOBAL
still answers locally, hits redeliver with zero loss on recovery), a peer
killed mid-flush and restarted, and degraded-mode limit enforcement
(DRAIN_OVER_LIMIT preserved).  All runs are seeded, use sub-100ms
breaker/sync windows, and end by asserting no background loop died —
metrics are the oracle (functional_test.go:2184-2276 pattern), never bare
sleeps.
"""

import asyncio

import pytest

from gubernator_tpu.cluster import Cluster
from gubernator_tpu.config import BehaviorConfig, Config, DaemonConfig
from gubernator_tpu.resilience import FaultInjector, ResilienceConfig
from gubernator_tpu.transport.daemon import Daemon
from gubernator_tpu.types import Behavior, RateLimitRequest, Status
from tests.helpers import global_req as req
from tests.helpers import poll_consumed, warm_global_path


def fast_chaos_conf():
    behaviors = BehaviorConfig(global_sync_wait=0.02, batch_wait=0.001)
    resilience = ResilienceConfig(
        breaker_open_for=0.05,
        breaker_open_cap=0.1,
        breaker_min_requests=3,
        forward_backoff_base=0.002,
        forward_backoff_cap=0.02,
    )
    return behaviors, resilience


def assert_no_loop_dead(cluster):
    """Acceptance (c): after the run, every background loop — GLOBAL hits,
    broadcast, and each peer's batch loop — is still alive."""
    for d in cluster.daemons:
        for t in d.instance.global_mgr._tasks:
            assert not t.done(), f"dead loop {t.get_name()} on {d.advertise_address}"
        for p in d.instance.get_peer_list():
            if p._batch_task is not None:
                assert not p._batch_task.done(), (
                    f"dead batch loop for {p.info.grpc_address}"
                )


async def test_chaos_100pct_failure_degrades_then_redelivers():
    """The ISSUE's acceptance run: one peer at 100% injected RPC failure.
    (a) the breaker opens within the configured threshold and GLOBAL
    requests still answer locally; (b) zero hits are lost once the peer
    recovers; (c) no background loop is dead at the end."""
    behaviors, resilience = fast_chaos_conf()
    inj = FaultInjector(seed=7)
    c = await Cluster.start(3, behaviors=behaviors, resilience=resilience,
                            fault_injector=inj)
    try:
        name, key = "chaos", "ck"
        owner = c.find_owning_daemon(name, key)
        non_owner = c.list_non_owning_daemons(name, key)[0]
        ni = c.daemons.index(non_owner)
        owner_addr = owner.conf.grpc_listen_address
        await warm_global_path(c, name, owner, non_owner)
        inj.set_fault(owner_addr, partition=True)

        client = non_owner.client()
        sent = 0
        for _ in range(30):
            out = await client.get_rate_limits([req(name, key)])
            # (a) degraded mode: local answers, never errors.
            assert out[0].error == ""
            assert out[0].status == Status.UNDER_LIMIT
            sent += 1
            await asyncio.sleep(0.005)
        await client.close()

        # (a) the breaker opened (metrics oracle, not sleeps) and flushes
        # were re-enqueued instead of dropped.
        await c.wait_for_metric(
            ni, "gubernator_breaker_transitions_total",
            labels={"peerAddr": owner_addr, "to": "open"}, timeout=30,
        )
        await c.wait_for_metric(
            ni, "gubernator_global_redelivered_hits_total", timeout=30)
        assert c.metric_value(ni, "gubernator_degraded_answers_total") >= 1
        assert c.metric_value(ni, "gubernator_global_dropped_hits_total") == 0

        # Recovery: (b) every hit lands on the owner — zero loss.
        inj.clear()
        await poll_consumed(owner, name, key, sent, timeout=60)
        assert c.metric_value(ni, "gubernator_global_dropped_hits_total") == 0
        # The breaker closed again after a successful probe.  Generous
        # budget: the half-open probe rides the backoff schedule, and the
        # suite shares one CPU core.
        await c.wait_for_metric(
            ni, "gubernator_breaker_transitions_total",
            labels={"peerAddr": owner_addr, "to": "closed"}, timeout=30,
        )
        # (c) nothing died.
        assert_no_loop_dead(c)
    finally:
        await c.stop()


async def test_chaos_drain_over_limit_preserved_in_degraded_mode():
    """Degraded GLOBAL answers still enforce the limit locally, and the
    redelivered hits drain the owner's bucket (DRAIN_OVER_LIMIT is forced
    on the owner's relay path) instead of erroring or going negative."""
    behaviors, resilience = fast_chaos_conf()
    inj = FaultInjector(seed=11)
    c = await Cluster.start(3, behaviors=behaviors, resilience=resilience,
                            fault_injector=inj)
    try:
        name, key = "chaos-drain", "dk"
        owner = c.find_owning_daemon(name, key)
        non_owner = c.list_non_owning_daemons(name, key)[0]
        await warm_global_path(c, name, owner, non_owner)
        inj.set_fault(owner.conf.grpc_listen_address, partition=True)

        client = non_owner.client()
        statuses = []
        for _ in range(7):
            out = await client.get_rate_limits(
                [req(name, key, hits=1, limit=5, duration=300_000)]
            )
            assert out[0].error == ""
            statuses.append(out[0].status)
            await asyncio.sleep(0.005)
        await client.close()
        # Local degraded enforcement: 5 under, then over — the partition
        # never turns the limiter into an allow-all.
        assert statuses[:5] == [Status.UNDER_LIMIT] * 5
        assert statuses[5:] == [Status.OVER_LIMIT] * 2

        inj.clear()
        # All 7 queued hits redeliver; DRAIN_OVER_LIMIT on the owner's
        # relay path pins the bucket at 0 rather than erroring/negative.
        oc = owner.client()

        async def owner_drained():
            while True:
                r = (await oc.get_rate_limits(
                    [req(name, key, hits=0, limit=5, duration=300_000)]
                ))[0]
                if r.remaining == 0:
                    return r
                await asyncio.sleep(0.02)

        await asyncio.wait_for(owner_drained(), timeout=60)
        # One more hit against the drained bucket is OVER_LIMIT (a zero-hit
        # query reports UNDER — nothing was requested).
        oc2 = owner.client()
        r = (await oc2.get_rate_limits(
            [req(name, key, hits=1, limit=5, duration=300_000)]
        ))[0]
        await oc2.close()
        assert r.status == Status.OVER_LIMIT
        assert r.remaining == 0
        assert_no_loop_dead(c)
    finally:
        await c.stop()


async def test_chaos_kill_peer_mid_flush_redelivers_after_restart():
    """A peer that actually dies (daemon closed, not injected) mid-flush:
    hits buffer locally and land once the peer comes back on the same
    address."""
    behaviors, resilience = fast_chaos_conf()
    c = await Cluster.start(2, behaviors=behaviors, resilience=resilience)
    try:
        name, key = "chaos-kill", "kk"
        owner = c.find_owning_daemon(name, key)
        non_owner = c.list_non_owning_daemons(name, key)[0]
        owner_idx = c.daemons.index(owner)
        ni = c.daemons.index(non_owner)

        # Kill the owner BEFORE any flush can land, then drive traffic:
        # every flush of these hits happens against a dead peer.
        await warm_global_path(c, name, owner, non_owner)
        await owner.close()
        client = non_owner.client()
        sent = 0
        for _ in range(20):
            out = await client.get_rate_limits([req(name, key)])
            assert out[0].error == ""
            sent += 1
            await asyncio.sleep(0.005)
        await client.close()
        await c.wait_for_metric(
            ni, "gubernator_global_redelivered_hits_total", timeout=10,
        )

        # Resurrect the owner on its old port; redelivery drains into it.
        owner = await c.restart(owner_idx)
        await poll_consumed(owner, name, key, sent, timeout=60)
        assert c.metric_value(ni, "gubernator_global_dropped_hits_total") == 0
        assert_no_loop_dead(c)
    finally:
        await c.stop()


async def test_chaos_intermittent_errors_recover_without_loss():
    """50% injected error rate (seeded), *asymmetric*: only the
    non-owner → owner direction fails (the directional WAN-style
    schedule); the owner's own outbound broadcasts are clean.  Slower,
    flappier — but the accounting still converges to zero loss and the
    loops survive."""
    behaviors, resilience = fast_chaos_conf()
    inj = FaultInjector(seed=23)
    c = await Cluster.start(2, behaviors=behaviors, resilience=resilience,
                            fault_injector=inj)
    try:
        name, key = "chaos-flap", "fk"
        owner = c.find_owning_daemon(name, key)
        non_owner = c.list_non_owning_daemons(name, key)[0]
        await warm_global_path(c, name, owner, non_owner)
        inj.set_fault(owner.conf.grpc_listen_address,
                      from_peer=non_owner.advertise_address,
                      error_rate=0.5)
        # The reverse direction is untouched: broadcasts owner → non_owner
        # must never be counted against this schedule.
        assert inj.spec_for(
            non_owner.conf.grpc_listen_address,
            from_peer=owner.advertise_address) is None

        client = non_owner.client()
        sent = 0
        for _ in range(25):
            out = await client.get_rate_limits([req(name, key)], timeout=30.0)
            assert out[0].error == ""
            sent += 1
            await asyncio.sleep(0.004)
        await client.close()

        inj.clear()
        # Generous budget: at 50% injected errors the flush can need
        # several backoff rounds, the poll client pays a fresh channel +
        # first-compile on its first RPC, and the suite shares one core.
        await poll_consumed(owner, name, key, sent, timeout=60)
        ni = c.daemons.index(non_owner)
        assert c.metric_value(ni, "gubernator_global_dropped_hits_total") == 0
        assert_no_loop_dead(c)
    finally:
        await c.stop()


async def test_chaos_peer_death_mid_reshard_defined_state():
    """Reshard acceptance run (docs/resharding.md failure matrix): a peer
    dies (100% partition) while a shard transition is requested.  The
    open breaker aborts the transition *before* the cutover — a defined
    state, zero bucket loss, zero double-serves — and admission
    unfreezes so the daemon keeps serving.  Once the peer recovers the
    same transition commits, the full protocol (freeze → drain →
    journal → verify) runs on the live cluster, and the buffered GLOBAL
    hits still redeliver with zero loss."""
    behaviors, resilience = fast_chaos_conf()
    inj = FaultInjector(seed=31)
    c = await Cluster.start(3, behaviors=behaviors, resilience=resilience,
                            fault_injector=inj)
    try:
        name, key = "chaos-reshard", "rk"
        owner = c.find_owning_daemon(name, key)
        non_owner = c.list_non_owning_daemons(name, key)[0]
        ni = c.daemons.index(non_owner)
        owner_addr = owner.conf.grpc_listen_address
        await warm_global_path(c, name, owner, non_owner)
        inj.set_fault(owner_addr, partition=True)

        # Drive GLOBAL traffic into the dead owner until the breaker
        # opens (metrics oracle) — this is the "peer died mid-transfer"
        # precondition the coordinator must observe.
        client = non_owner.client()
        sent = 0
        for _ in range(30):
            out = await client.get_rate_limits([req(name, key)])
            assert out[0].error == ""
            sent += 1
            await asyncio.sleep(0.005)
        await client.close()
        await c.wait_for_metric(
            ni, "gubernator_breaker_transitions_total",
            labels={"peerAddr": owner_addr, "to": "open"}, timeout=30,
        )
        # Pin the breaker open across the abort check: fast_chaos_conf's
        # 50ms open window can slip to HALF_OPEN between the metric wait
        # and the coordinator's breaker_check on a loaded host, and
        # is_open() is False in HALF_OPEN.
        for p in non_owner.instance.get_peer_list():
            if p._info.grpc_address == owner_addr:
                p.breaker.force_open(10.0)

        # The transition aborts on the open breaker, before any state
        # moves: a defined outcome, never an exception.
        res = await non_owner.instance.reshard(2)
        assert res["outcome"] == "aborted"
        assert "breaker" in res["reason"]
        assert res["state_loss"] == 0 and res["double_served"] == 0
        assert c.metric_value(
            ni, "gubernator_tpu_reshard_transitions_total",
            labels={"result": "aborted"},
        ) == 1
        # Admission unfroze: the daemon still answers (degraded, local).
        assert not non_owner.instance.tick_loop.frozen
        client = non_owner.client()
        out = await client.get_rate_limits([req(name, key)])
        assert out[0].error == ""
        sent += 1
        await client.close()

        # Recovery: breaker closes, the same transition commits — the
        # degenerate identity cutover runs the full freeze/drain/verify
        # protocol on this single-chip engine.
        inj.clear()
        await c.wait_for_metric(
            ni, "gubernator_breaker_transitions_total",
            labels={"peerAddr": owner_addr, "to": "closed"}, timeout=30,
        )
        before = non_owner.instance.engine.cache_size()
        res = await non_owner.instance.reshard(2)
        assert res["outcome"] == "committed"
        assert res.get("degenerate") is True
        assert res["state_loss"] == 0 and res["double_served"] == 0
        assert res["live_items"] == before
        assert c.metric_value(
            ni, "gubernator_tpu_reshard_state_loss_total") == 0
        assert c.metric_value(
            ni, "gubernator_tpu_reshard_double_served_total") == 0
        assert c.metric_value(
            ni, "gubernator_tpu_reshard_transitions_total",
            labels={"result": "committed"},
        ) == 1

        # The in-flight GLOBAL state rode through both transitions: every
        # buffered hit redelivers to the recovered owner — zero loss,
        # zero double-serves on the bucket itself.
        await poll_consumed(owner, name, key, sent, timeout=60)
        assert c.metric_value(ni, "gubernator_global_dropped_hits_total") == 0
        assert_no_loop_dead(c)
    finally:
        await c.stop()


def _isolate_regions(inj, c, a="us", b="eu"):
    """Cut every cross-region link with directional schedules — intra-
    region traffic keeps flowing, exactly what a WAN partition does."""
    for da in c.daemons:
        for db in c.daemons:
            if da.conf.data_center == a and db.conf.data_center == b:
                inj.set_fault(db.conf.grpc_listen_address,
                              from_peer=da.advertise_address,
                              partition=True)
                inj.set_fault(da.conf.grpc_listen_address,
                              from_peer=db.advertise_address,
                              partition=True)


async def test_chaos_region_isolation_degrades_heals_zero_loss():
    """The federation acceptance run (docs/federation.md): two regions,
    healthy exchange first, then a full WAN partition (directional
    schedules — intra-region links stay up), bounded degraded serving
    on both sides, then heal.  After the heal both regions converge on
    the union of all hits: zero hit loss, no double-counts."""
    behaviors, resilience = fast_chaos_conf()
    inj = FaultInjector(seed=13)
    c = await Cluster.start(
        4, datacenters=["us", "us", "eu", "eu"], behaviors=behaviors,
        resilience=resilience, fault_injector=inj, federation=True,
        federation_interval=0.02,
    )
    try:
        name, key = "chaos-fed", "gk"
        us_owner = c.find_owning_daemon_in_region(name, key, "us")
        eu_owner = c.find_owning_daemon_in_region(name, key, "eu")
        ui, ei = c.daemons.index(us_owner), c.daemons.index(eu_owner)

        def mr_req(hits=1):
            return RateLimitRequest(
                name=name, unique_key=key, hits=hits, limit=1_000_000,
                duration=3_600_000, behavior=Behavior.MULTI_REGION,
            )

        async def drive(daemon, n):
            client = daemon.client()
            for _ in range(n):
                # Generous RPC deadline: four engines JIT their first
                # programs during this test on a shared CPU host.
                out = await client.get_rate_limits([mr_req()], timeout=30.0)
                assert out[0].error == ""
                await asyncio.sleep(0.002)
            await client.close()

        # Healthy path: us hits show up in eu via the envelope stream.
        await drive(us_owner, 5)
        await c.wait_for_metric(
            ei, "gubernator_tpu_federation_envelopes_total",
            labels={"result": "applied"}, timeout=30)
        await poll_consumed(eu_owner, name, key, 5, timeout=60)

        # WAN partition: both regions keep serving, deltas buffer.
        _isolate_regions(inj, c)
        await drive(us_owner, 10)
        await drive(eu_owner, 7)
        # The sender noticed (redelivery attempts on the same envelope)
        # and flags MULTI_REGION answers as degraded.
        await c.wait_for_metric(
            ui, "gubernator_tpu_federation_redeliveries_total", timeout=30)
        await drive(us_owner, 3)
        assert c.metric_value(
            ui, "gubernator_tpu_federation_degraded_answers_total") >= 1
        # A contended key while the regions cannot talk: each side stops
        # at its own limit, so the split admits at most one limit more
        # than a healthy cluster would (an over-admission ratio of 1.0,
        # the staleness budget of docs/federation.md), never more.
        small = 5
        admitted = 0
        for region in ("us", "eu"):
            client = c.find_owning_daemon_in_region(
                name, "over", region).client()
            for _ in range(2 * small):
                out = await client.get_rate_limits([RateLimitRequest(
                    name=name, unique_key="over", hits=1, limit=small,
                    duration=3_600_000, behavior=Behavior.MULTI_REGION,
                )], timeout=30.0)
                assert out[0].error == ""
                if out[0].status == Status.OVER_LIMIT:
                    break
                admitted += 1
            await client.close()
        assert small <= admitted <= 2 * small
        # Degraded, never down: each region still answers from local
        # state — drift is bounded by staleness × local rate, which the
        # staleness gauge now exports.
        assert c.metric_value(
            ui, "gubernator_tpu_federation_staleness_seconds") > 0

        # Heal: buffered envelopes replay; the receive ledger dedupes
        # redeliveries; both regions converge on the union of all hits.
        inj.clear()
        total = 5 + 10 + 7 + 3
        await poll_consumed(us_owner, name, key, total, timeout=60)
        await poll_consumed(eu_owner, name, key, total, timeout=60)
        # Exactly-once: nothing pending, nothing lost, nothing doubled —
        # poll_consumed above asserted the == (over-admission would
        # overshoot, loss would undershoot).
        for d in (us_owner, eu_owner):
            fed = d.instance.federation
            assert fed is not None
            assert fed.pending_keys() == 0
            assert not fed._task.done()
        assert_no_loop_dead(c)
    finally:
        await c.stop()


def _snapshot_daemon_conf(tmp_path, interval=0.05):
    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="",
        peer_discovery_type="none",
    )
    conf.config = Config(
        # 1024 is a capacity the suite already compiles for — new table
        # shapes would pay fresh JIT programs in tier-1.
        cache_size=1024,
        snapshot_dir=str(tmp_path),
        snapshot_interval=interval,
    )
    return conf


def _local_req(key, hits, limit=1_000):
    return RateLimitRequest(
        name="crash", unique_key=key, hits=hits, limit=limit,
        duration=3_600_000,
    )


async def test_chaos_graceful_sigterm_restart_zero_loss(tmp_path):
    """The persistence acceptance run, graceful half: traffic, then the
    SIGTERM path (daemon.close == what the signal handler runs), then a
    restart from the same snapshot directory — every hit must still be
    accounted.  Zero loss, not bounded loss: close writes a final full
    base."""
    d = Daemon(_snapshot_daemon_conf(tmp_path, interval=60))
    await d.start()
    await d.wait_for_connect()
    client = d.client()
    for i in range(12):
        out = await client.get_rate_limits([_local_req(f"g{i}", hits=3)])
        assert out[0].error == ""
    await client.close()
    await d.close()  # graceful drain: readiness flips, final base written

    d2 = Daemon(_snapshot_daemon_conf(tmp_path, interval=60))
    await d2.start()
    await d2.wait_for_connect()
    try:
        assert d2.instance.restore_stats["restored_items"] >= 12
        c2 = d2.client()
        out = await c2.get_rate_limits(
            [_local_req(f"g{i}", hits=0) for i in range(12)]
        )
        await c2.close()
        loss = sum(1 for r in out if 1_000 - r.remaining != 3)
        assert loss == 0
    finally:
        await d2.close()


async def test_chaos_hard_kill_loss_bounded_by_one_delta_interval(tmp_path):
    """Hard kill (no final snapshot): a second daemon restores from the
    same directory while the first still runs — exactly what a kill -9
    leaves on disk.  Hits flushed by the delta loop must all be there;
    total loss is bounded by the traffic of one snapshot interval."""
    d = Daemon(_snapshot_daemon_conf(tmp_path, interval=0.05))
    await d.start()
    await d.wait_for_connect()
    client = d.client()
    n_flushed = 10
    for i in range(n_flushed):
        out = await client.get_rate_limits([_local_req(f"h{i}", hits=2)])
        assert out[0].error == ""
    # Wait until the delta loop has durably persisted the first batch.
    writer = d.instance._snapshot_writer
    deadline = asyncio.get_running_loop().time() + 10
    while writer.metric_items_written < n_flushed:
        assert asyncio.get_running_loop().time() < deadline, "no delta flush"
        await asyncio.sleep(0.02)
    # One more interval's worth of traffic that may or may not flush.
    n_tail = 5
    for i in range(n_tail):
        await client.get_rate_limits([_local_req(f"t{i}", hits=2)])
    await client.close()

    # "Kill": restore from disk NOW, first daemon still running (its
    # final base never happens for this read).
    d2 = Daemon(_snapshot_daemon_conf(tmp_path / "ignored", interval=60))
    d2.conf.config.snapshot_dir = str(tmp_path)
    await d2.start()
    await d2.wait_for_connect()
    try:
        c2 = d2.client()
        out = await c2.get_rate_limits(
            [_local_req(f"h{i}", hits=0) for i in range(n_flushed)]
            + [_local_req(f"t{i}", hits=0) for i in range(n_tail)]
        )
        await c2.close()
        flushed_lost = sum(
            1 for r in out[:n_flushed] if 1_000 - r.remaining != 2
        )
        tail_lost = sum(
            1 for r in out[n_flushed:] if 1_000 - r.remaining != 2
        )
        assert flushed_lost == 0, "fsync'd delta records must survive"
        assert tail_lost <= n_tail  # bounded by one interval's traffic
    finally:
        await d2.close()
        await d.close()


async def test_chaos_forward_path_faults_surface_as_retries():
    """Non-GLOBAL forwards against an injected-faulty owner: drops
    (DEADLINE_EXCEEDED) retry with backoff and eventually exhaust into the
    reference's 'not connected' error — the caller always gets an answer,
    never a hang."""
    behaviors, resilience = fast_chaos_conf()
    inj = FaultInjector(seed=5)
    c = await Cluster.start(2, behaviors=behaviors, resilience=resilience,
                            fault_injector=inj)
    try:
        name, key = "chaos-fwd", "wk"
        owner = c.find_owning_daemon(name, key)
        non_owner = c.list_non_owning_daemons(name, key)[0]
        inj.set_fault(owner.conf.grpc_listen_address, drop_rate=1.0)

        out = await asyncio.wait_for(
            non_owner.instance.get_rate_limits(
                [RateLimitRequest(name=name, unique_key=key, hits=1,
                                  limit=10, duration=60_000)]
            ),
            timeout=10,
        )
        assert "not connected" in out[0].error
        ni = c.daemons.index(non_owner)
        assert c.metric_value(
            ni, "gubernator_batch_send_retries_total"
        ) >= resilience.forward_max_attempts

        # Clear the fault: the next forward works again (breaker probes
        # through half-open within its 50ms open window).
        inj.clear()

        async def forward_recovers():
            while True:
                out = await non_owner.instance.get_rate_limits(
                    [RateLimitRequest(name=name, unique_key=key, hits=1,
                                      limit=10, duration=60_000)]
                )
                if out[0].error == "":
                    return out[0]
                await asyncio.sleep(0.05)

        r = await asyncio.wait_for(forward_recovers(), timeout=10)
        assert r.status == Status.UNDER_LIMIT
        assert_no_loop_dead(c)
    finally:
        await c.stop()


async def test_chaos_overload_spent_budget_sheds_not_hangs(tmp_path):
    """Overload scenario (docs/overload.md): a caller whose propagated
    budget is already spent gets an immediate retriable shed answer —
    the daemon never queues or serves work nobody is waiting for — and
    healthy traffic through the same daemon is untouched."""
    from gubernator_tpu.admission import SHED_EXPIRED_MSG

    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="",
        peer_discovery_type="none",
    )
    conf.config = Config(cache_size=1024)
    d = Daemon(conf)
    await d.start()
    await d.wait_for_connect()
    try:
        client = d.client()
        # Zero remaining budget rides guber-deadline-ms: expired on
        # arrival, shed before the device ever sees it.
        out = await client.get_rate_limits(
            [_local_req("ov-dead", hits=1)], budget_ms=0)
        assert out[0].error == SHED_EXPIRED_MSG
        shed = d.instance.tick_loop.metric_shed_admission
        assert shed.get("expired", 0) >= 1
        assert d.instance.tick_loop.metric_expired_served == 0

        # A generous budget and a budget-less request both serve.
        out = await client.get_rate_limits(
            [_local_req("ov-live", hits=1)], budget_ms=30_000)
        assert out[0].error == "" and out[0].status == Status.UNDER_LIMIT
        out = await client.get_rate_limits([_local_req("ov-live", hits=1)])
        assert out[0].error == ""
        assert 1_000 - out[0].remaining == 2  # shed never consumed hits
        await client.close()
    finally:
        await d.close()


# ---------------------------------------------------------------------
# Edge worker SIGKILL (docs/edge.md crash semantics)
# ---------------------------------------------------------------------
def test_chaos_edge_worker_sigkill_respawns_without_double_serve():
    """SIGKILL one edge worker mid-drive.  The supervisor must respawn
    it (fresh process, bumped generation), the in-flight slabs shed
    retriably — counted, never silently dropped — and no acked window
    may ever be double-served.  The respawned life resumes publishing
    into the same segment, so C_WIN_ACKED keeps climbing."""
    import os
    import signal
    import time

    from gubernator_tpu.edge import shmring
    from gubernator_tpu.edge.plane import EdgeConfig, EdgePlane
    from gubernator_tpu.ops.engine import TickEngine
    from gubernator_tpu.service.tickloop import TickLoop
    from gubernator_tpu.transport import fastwire
    from gubernator_tpu.utils.metrics import Metrics

    if fastwire.load() is None:
        pytest.skip("native wire codec not built")

    def wait_for(cond, timeout, what):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            time.sleep(0.02)
        raise AssertionError(f"timed out waiting for {what}")

    eng = TickEngine(capacity=1024, max_batch=64)
    loop = TickLoop(eng, batch_limit=64)
    metrics = Metrics()
    plane = EdgePlane(loop, EdgeConfig(
        workers=2, slabs=4, ring_depth=8, max_batch=64, mode="drive",
        drive={"batch": 32, "windows": 0, "keys": 64, "frames": 4},
    ), metrics=metrics)
    try:
        plane.start()
        assert plane.wait_ready(60), "workers never became ready"
        plane.go()
        victim = plane.workers[0]
        pid = victim.proc.pid
        wait_for(
            lambda: plane.counters(0)[shmring.C_WIN_ACKED] > 0,
            30, "worker 0 to ack its first window",
        )
        os.kill(pid, signal.SIGKILL)
        wait_for(
            lambda: victim.proc.pid != pid and victim.proc.is_alive(),
            30, "supervisor respawn",
        )
        acked_at_respawn = int(plane.counters(0)[shmring.C_WIN_ACKED])
        wait_for(
            lambda: plane.counters(0)[shmring.C_WIN_ACKED] > acked_at_respawn,
            30, "respawned worker to make progress",
        )
        tot = plane.totals()
    finally:
        plane.close()
        loop.close()
        eng.close()
    assert tot["restarts"] == 1, tot
    assert tot["double_served"] == 0, tot
    # Zero hit loss for acked windows: every window the workers counted
    # as acked was served exactly once, so acked accounting never
    # exceeds what was published; the crash gap is *accounted* (shed
    # slabs + dropped stale responses), not silent.
    assert tot["windows_acked"] <= tot["windows_published"], tot
    assert victim.generation == 2  # stale in-flight responses can't land
    assert metrics.sample(
        "gubernator_tpu_edge_worker_restarts_total", {"worker": "0"}
    ) == 1
