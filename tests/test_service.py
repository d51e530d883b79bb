"""Service-stack functional tests: daemon, routing, forwarding, gateway.

The behavioral spec comes from the reference's functional_test.go (run
against an in-process cluster, cluster/cluster.go); these tests exercise
the same surfaces over real loopback gRPC.
"""

import asyncio

import pytest

from gubernator_tpu.cluster import Cluster
from gubernator_tpu.config import BehaviorConfig
from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    RateLimitRequest,
    Status,
)

@pytest.fixture(scope="module")
def event_loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture(scope="module")
def cluster(event_loop):
    c = event_loop.run_until_complete(Cluster.start(3))
    yield c
    event_loop.run_until_complete(c.stop())


def req(name="test", key="k", hits=1, limit=5, duration=60_000, **kw):
    return RateLimitRequest(
        name=name, unique_key=key, hits=hits, limit=limit, duration=duration, **kw
    )


async def test_single_daemon_token_bucket(cluster):
    client = cluster.daemons[0].client()
    out = await client.get_rate_limits([req(key="single")])
    assert out[0].error == ""
    assert out[0].status == Status.UNDER_LIMIT
    assert out[0].limit == 5
    assert out[0].remaining == 4
    out = await client.get_rate_limits([req(key="single", hits=4)])
    assert out[0].remaining == 0
    out = await client.get_rate_limits([req(key="single", hits=1)])
    assert out[0].status == Status.OVER_LIMIT
    await client.close()


async def test_forwarding_owner_state_shared(cluster):
    """Hitting the same key via different daemons must share one bucket."""
    owner = cluster.find_owning_daemon("fwd", "shared")
    non_owner = cluster.list_non_owning_daemons("fwd", "shared")[0]
    c1 = owner.client()
    c2 = non_owner.client()
    out = await c1.get_rate_limits([req(name="fwd", key="shared", limit=10)])
    assert out[0].error == ""
    assert out[0].remaining == 9
    out = await c2.get_rate_limits([req(name="fwd", key="shared", limit=10)])
    assert out[0].error == ""
    assert out[0].remaining == 8
    # Forwarded response carries the owner's address in metadata.
    assert out[0].metadata.get("owner") == owner.conf.grpc_listen_address
    await c1.close()
    await c2.close()


async def test_batch_order_preserved(cluster):
    """Responses must line up with requests across batch sizes
    (functional_test.go:1638-1686 order-stability contract)."""
    client = cluster.daemons[0].client()
    for size in (1, 7, 64, 250):
        reqs = [
            req(name="order", key=f"key-{i}", hits=0, limit=100 + i)
            for i in range(size)
        ]
        out = await client.get_rate_limits(reqs)
        assert len(out) == size
        for i, r in enumerate(out):
            assert r.error == ""
            assert r.limit == 100 + i, f"size={size} idx={i}"
    await client.close()


async def test_batch_too_large_rejected(cluster):
    import grpc

    client = cluster.daemons[0].client()
    reqs = [req(key=f"big-{i}") for i in range(1001)]
    with pytest.raises(grpc.aio.AioRpcError) as exc:
        await client.get_rate_limits(reqs)
    assert exc.value.code() == grpc.StatusCode.OUT_OF_RANGE
    await client.close()


async def test_missing_fields(cluster):
    """Per-item validation errors; RPC still succeeds
    (functional_test.go:896 missing-field table)."""
    client = cluster.daemons[0].client()
    out = await client.get_rate_limits(
        [
            RateLimitRequest(name="test", unique_key="", hits=1, limit=10,
                             duration=1000),
            RateLimitRequest(name="", unique_key="akey", hits=1, limit=10,
                             duration=1000),
            req(key="ok"),
        ]
    )
    assert "unique_key" in out[0].error
    assert "namespace" in out[1].error
    assert out[2].error == ""
    await client.close()


async def test_health_check(cluster):
    client = cluster.daemons[0].client()
    h = await client.health_check()
    assert h.status == "healthy"
    assert h.peer_count == 3
    await client.close()


async def test_leaky_bucket_over_grpc(cluster):
    client = cluster.daemons[0].client()
    out = await client.get_rate_limits(
        [req(name="leaky", key="lk", hits=5, limit=10, duration=10_000,
             algorithm=Algorithm.LEAKY_BUCKET)]
    )
    assert out[0].error == ""
    assert out[0].remaining == 5
    await client.close()


async def test_global_behavior_reconciles():
    """GLOBAL: non-owner answers locally; hits flow to the owner and the
    owner broadcasts authoritative state back (global.go protocol)."""
    behaviors = BehaviorConfig(global_sync_wait=0.05, batch_wait=0.002)
    c = await Cluster.start(3, behaviors=behaviors)
    try:
        name, key = "global", "gk"
        owner = c.find_owning_daemon(name, key)
        non_owner = c.list_non_owning_daemons(name, key)[0]
        client = non_owner.client()
        g = req(name=name, key=key, hits=2, limit=100,
                behavior=Behavior.GLOBAL)
        out = await client.get_rate_limits([g])
        assert out[0].error == ""
        assert out[0].remaining == 98  # local answer
        assert out[0].metadata.get("owner") == owner.conf.grpc_listen_address

        # Metrics are the oracle, not sleeps (functional_test.go:2184-2276):
        # the non-owner must flush its hit batch to the owner, and the owner
        # must complete a broadcast — both observed only after the RPCs land.
        await c.wait_for_update(c.daemons.index(non_owner))
        await c.wait_for_broadcast(c.daemons.index(owner))
        await client.close()

        async def owner_saw_hits():
            while True:
                o = owner.client()
                resp = await o.get_rate_limits(
                    [req(name=name, key=key, hits=0, limit=100,
                         behavior=Behavior.GLOBAL)]
                )
                await o.close()
                if resp[0].remaining == 98:
                    return
                await asyncio.sleep(0.02)

        await asyncio.wait_for(owner_saw_hits(), timeout=5.0)

        # The broadcast reached the third daemon (neither owner nor hitter).
        # Still a bounded poll: _broadcast observes its metric even if one
        # peer push failed (it retries on the next interval), so the metric
        # alone doesn't prove THIS peer got the state.
        third = [d for d in c.daemons if d is not owner and d is not non_owner][0]

        async def third_synced():
            while True:
                t = third.client()
                resp = await t.get_rate_limits(
                    [req(name=name, key=key, hits=0, limit=100,
                         behavior=Behavior.GLOBAL)]
                )
                await t.close()
                if resp[0].remaining == 98:
                    return
                await asyncio.sleep(0.02)

        await asyncio.wait_for(third_synced(), timeout=5.0)
    finally:
        await c.stop()


async def test_global_hits_apply_locally_when_owner():
    """Hits queued for a key this node turns out to own must still land
    (the reference forwards to whatever GetPeer resolves, global.go:153-168;
    dropping them loses accounting for good)."""
    from gubernator_tpu.service.instance import InstanceConfig, V1Instance

    behaviors = BehaviorConfig(global_sync_wait=0.02, batch_wait=0.001)
    inst = await V1Instance.create(
        InstanceConfig(behaviors=behaviors, cache_size=256)
    )
    try:
        # The instance is new: its first window traces and lowers the
        # tick, which must not run against the wait below.
        await inst.apply_local([req(name="gl", key="warm", hits=0, limit=10)])
        r = req(name="gl", key="own", hits=3, limit=10,
                behavior=Behavior.GLOBAL)
        inst.global_mgr.queue_hit(r)

        async def settled():
            while True:
                out = await inst.apply_local(
                    [req(name="gl", key="own", hits=0, limit=10)]
                )
                if out[0].remaining == 7:
                    return
                await asyncio.sleep(0.01)

        await asyncio.wait_for(settled(), timeout=30)
    finally:
        await inst.close()


async def test_http_gateway_snake_case():
    """JSON gateway with snake_case fields (daemon.go:245-261 parity)."""
    import aiohttp

    c = await Cluster.start(1, http_gateway=True)
    try:
        addr = c.daemons[0].conf.http_listen_address
        async with aiohttp.ClientSession() as s:
            body = {
                "requests": [
                    {
                        "name": "http",
                        "unique_key": "hk",
                        "hits": "1",
                        "limit": "10",
                        "duration": "60000",
                    }
                ]
            }
            async with s.post(
                f"http://{addr}/v1/GetRateLimits", json=body
            ) as resp:
                assert resp.status == 200
                out = await resp.json()
            item = out["responses"][0]
            assert item["limit"] == "10"
            assert item["remaining"] == "9"
            assert "reset_time" in item
            async with s.get(f"http://{addr}/v1/HealthCheck") as resp:
                health = await resp.json()
            assert health["status"] == "healthy"
            async with s.get(f"http://{addr}/metrics") as resp:
                text = await resp.text()
            assert "gubernator_grpc_request_counts" in text
            assert "gubernator_cache_size" in text
    finally:
        await c.stop()


async def _wait_replica(daemon, name, key, limit, want_remaining,
                        timeout=5.0):
    """Poll one daemon's GLOBAL replica until it reports ``want_remaining``.

    The broadcast metric alone can't prove delivery to a *specific* peer
    (push failures are swallowed and retried next interval), so state
    assertions poll the replica itself."""
    async def poll():
        while True:
            cl = daemon.client()
            r = (await cl.get_rate_limits(
                [req(name=name, key=key, hits=0, limit=limit,
                     duration=6_000_000, behavior=Behavior.GLOBAL)]
            ))[0]
            await cl.close()
            if r.remaining == want_remaining:
                return
            await asyncio.sleep(0.02)

    await asyncio.wait_for(poll(), timeout=timeout)


async def test_global_peer_over_limit():
    """Non-owner replica drains to OVER_LIMIT through owner broadcasts
    (functional_test.go:1093 TestGlobalRateLimitsPeerOverLimit)."""
    behaviors = BehaviorConfig(global_sync_wait=0.05, batch_wait=0.002)
    c = await Cluster.start(3, behaviors=behaviors)
    try:
        name, key = "global-over", "pk"
        peer = c.list_non_owning_daemons(name, key)[0]
        client = peer.client()

        async def send_hit(hits, want_status, want_remaining):
            r = (await client.get_rate_limits(
                [req(name=name, key=key, hits=hits, limit=2,
                     duration=300_000, behavior=Behavior.GLOBAL)]
            ))[0]
            assert r.error == ""
            assert (r.status, r.remaining) == (want_status, want_remaining), r

        await send_hit(1, Status.UNDER_LIMIT, 1)
        await send_hit(1, Status.UNDER_LIMIT, 0)
        # Wait for the authoritative drained state to land on THIS peer
        # (broadcasts may split across windows and pushes may retry).
        await _wait_replica(peer, name, key, 2, 0)
        await send_hit(1, Status.OVER_LIMIT, 0)
        await send_hit(1, Status.OVER_LIMIT, 0)
        await client.close()
    finally:
        await c.stop()


async def test_global_negative_hits():
    """Negative GLOBAL hits credit tokens back across the cluster
    (functional_test.go:1204 TestGlobalNegativeHits)."""
    behaviors = BehaviorConfig(global_sync_wait=0.05, batch_wait=0.002)
    c = await Cluster.start(4, behaviors=behaviors)
    try:
        name, key = "global-neg", "nk"
        peers = c.list_non_owning_daemons(name, key)

        async def send_hit(daemon, hits, want_remaining):
            cl = daemon.client()
            r = (await cl.get_rate_limits(
                [req(name=name, key=key, hits=hits, limit=2,
                     duration=6_000_000, behavior=Behavior.GLOBAL)]
            ))[0]
            await cl.close()
            assert r.error == ""
            assert r.status == Status.UNDER_LIMIT
            assert r.remaining == want_remaining, (hits, r)

        # Negative hit on an empty bucket: remaining grows past the limit.
        await send_hit(peers[0], -1, 3)
        # Wait for the credit to replicate to the NEXT peer we'll hit —
        # the broadcast metric can't prove per-peer delivery.
        await _wait_replica(peers[1], name, key, 2, 3)
        # That peer sees the credited 3, credits one more.
        await send_hit(peers[1], -1, 4)
        await _wait_replica(peers[2], name, key, 2, 4)
        # A third peer can spend all 4 credits at once.
        await send_hit(peers[2], 4, 0)
        await _wait_replica(peers[0], name, key, 2, 0)
        # Query reflects the drained state everywhere.
        await send_hit(peers[0], 0, 0)
    finally:
        await c.stop()


async def test_forward_retry_exhaustion_and_self_upgrade():
    """The ≤5-retry forward loop (gubernator.go:311-391): a dead owner
    exhausts retries into the reference's "peers that are not connected"
    error; once ownership re-resolves to this node, the retry self-
    upgrades to local handling instead of forwarding."""
    c = await Cluster.start(2)
    try:
        d_owner = c.find_owning_daemon("retrytest", "rk")
        d_other = next(d for d in c.daemons if d is not d_owner)

        # Kill the owner: forwards now fail UNAVAILABLE and re-resolution
        # keeps returning the same dead peer.
        await d_owner.close()
        out = await d_other.instance.get_rate_limits(
            [req(name="retrytest", key="rk")]
        )
        assert "not connected" in out[0].error
        assert d_other.metrics.registry.get_sample_value(
            "gubernator_batch_send_retries_total"
        ) >= 5

        # Self-upgrade: ownership moves to the surviving node; the retry
        # path must answer locally (attempts != 0 and peer.is_owner).
        dead_peer = d_other.instance.get_peer("retrytest_rk")
        from gubernator_tpu.config import PeerInfo

        d_other.set_peers(
            [PeerInfo(grpc_address=d_other.advertise_address)]
        )
        resp = await d_other.instance._async_request(
            dead_peer, req(name="retrytest", key="rk"), "retrytest_rk"
        )
        assert resp.error == ""
        assert resp.remaining == 4
    finally:
        await c.stop()


def test_columns_fast_path_matches_object_path():
    """The wire→columns fast path must answer exactly like the object
    path, and flip off the moment the instance stops being standalone."""
    import asyncio

    import numpy as np

    from gubernator_tpu.ops.reqcols import ReqColumns
    from gubernator_tpu.service.instance import InstanceConfig, V1Instance
    from gubernator_tpu.types import PeerInfo, RateLimitRequest

    async def run():
        conf = InstanceConfig(cache_size=256, tpu_max_batch=64)
        inst = await V1Instance.create(conf)
        assert inst.columns_fast_path_ok()
        reqs = [
            RateLimitRequest(name="fp", unique_key=str(i % 5), hits=1,
                             limit=9, duration=60_000)
            for i in range(20)
        ]
        obj = await inst.get_rate_limits(reqs)
        mat, errors = await inst.get_rate_limits_columns(
            ReqColumns.from_requests(reqs)
        )
        assert not errors
        # Second pass over the same keys: columns observed object ticks.
        assert mat[2].tolist() == [r.remaining - 4 for r in obj]

        # Clustered instance: fast path must disable.
        inst.set_peers([PeerInfo(grpc_address="10.0.0.1:81")])
        assert not inst.columns_fast_path_ok()
        await inst.close()

    asyncio.run(run())


def test_columns_from_pb_validation_and_special():
    from gubernator_tpu.pb import gubernator_pb2 as pb
    from gubernator_tpu.transport.convert import columns_from_pb
    from gubernator_tpu.types import Behavior

    ms = [
        pb.RateLimitReq(name="a", unique_key="k", hits=1, limit=5,
                        duration=1000),
        pb.RateLimitReq(name="", unique_key="k2", hits=1),
        pb.RateLimitReq(name="b", unique_key="", hits=1),
    ]
    cols, errors, special = columns_from_pb(ms)
    assert not special
    assert errors == {
        1: "field 'namespace' cannot be empty",
        2: "field 'unique_key' cannot be empty",
    }
    assert cols.key_bytes(0) == b"a_k"

    ms2 = [pb.RateLimitReq(name="g", unique_key="k", hits=1,
                           behavior=int(Behavior.GLOBAL))]
    _, _, special = columns_from_pb(ms2)
    assert special
