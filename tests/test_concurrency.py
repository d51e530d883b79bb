"""Concurrency stress: exact accounting under parallel load.

The reference leans on Go's race detector plus mutex/channel discipline
(SURVEY §5.2); here safety is by construction (engine lock + event-loop
serialization + rank-ordered device application), so the tests assert the
*observable* invariant instead: with hits=1 requests against a bucket of
limit L, exactly L requests win UNDER_LIMIT no matter how many clients
race — any lost update, double count, or torn read shows up as a wrong
total.
"""

import asyncio
import threading

from gubernator_tpu.config import BehaviorConfig, Config, DaemonConfig
from gubernator_tpu.ops.engine import TickEngine
from gubernator_tpu.transport.daemon import DaemonClient, spawn_daemon
from gubernator_tpu.types import RateLimitRequest, Status


def _req(key, name="stress", hits=1, limit=100):
    return RateLimitRequest(
        name=name, unique_key=key, hits=hits, limit=limit, duration=60_000
    )


def test_engine_threads_exact_accounting():
    """8 threads × 50 calls × 4 hits on one key: exactly limit wins."""
    eng = TickEngine(capacity=1 << 12, max_batch=512)
    limit = 137
    wins = []
    lock = threading.Lock()

    def worker():
        got = 0
        for _ in range(50):
            rs = eng.process([_req("hot", hits=1, limit=limit)] * 4)
            got += sum(1 for r in rs if r.status == Status.UNDER_LIMIT)
        with lock:
            wins.append(got)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(wins) == limit  # 1600 hits total, exactly `limit` admitted


def test_engine_threads_disjoint_keys_no_crosstalk():
    eng = TickEngine(capacity=1 << 12, max_batch=512)

    def worker(tid, out):
        under = 0
        for i in range(40):
            rs = eng.process([_req(f"k{tid}", limit=25)])
            under += rs[0].status == Status.UNDER_LIMIT
        out[tid] = under

    out = {}
    threads = [
        threading.Thread(target=worker, args=(t, out)) for t in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(v == 25 for v in out.values()), out


async def test_service_concurrent_clients_exact_accounting():
    """64 concurrent gRPC clients racing on one bucket through the full
    daemon stack (tick loop batching + duplicate-key serialization)."""
    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="",
        peer_discovery_type="none",
    )
    conf.config = Config(
        behaviors=BehaviorConfig(batch_wait=0.002), cache_size=4096
    )
    d = await spawn_daemon(conf)
    limit, n_clients, per_client = 200, 64, 8
    try:
        # The grouped program's first trace-and-lower, with no deadline
        # and on a key of its own: the race below meets no compile.
        warm = DaemonClient(d.advertise_address)
        await warm.get_rate_limits(
            [_req("svc-warm", limit=limit)] * 2, timeout=None)
        await warm.close()

        async def one_client():
            c = DaemonClient(d.advertise_address)
            under = 0
            for _ in range(per_client):
                rs = await c.get_rate_limits([_req("svc-hot", limit=limit)])
                assert rs[0].error == ""
                under += rs[0].status == Status.UNDER_LIMIT
            await c.close()
            return under

        wins = await asyncio.gather(*(one_client() for _ in range(n_clients)))
        assert sum(wins) == limit  # 512 racing hits, exactly 200 admitted
    finally:
        await d.close()


async def test_snapshot_during_traffic_is_consistent():
    """export_items racing live traffic must snapshot a consistent table:
    every racing snapshot restores to a bucket that admits exactly its
    remaining budget, and total admissions across snapshot + replay equal
    the limit."""
    eng = TickEngine(capacity=1 << 12, max_batch=512)
    limit = 300
    stop = threading.Event()
    snaps = []

    def snapshotter():
        while not stop.is_set():
            snaps.append(eng.export_items())

    t = threading.Thread(target=snapshotter)
    t.start()
    try:
        admitted = 0
        # 400 hits > limit: snapshots race both contended and exhausted
        # states of the bucket.
        for _ in range(40):
            rs = eng.process([_req("snap-key", limit=limit)] * 10)
            admitted += sum(1 for r in rs if r.status == Status.UNDER_LIMIT)
    finally:
        stop.set()
        t.join()
    assert admitted == limit
    assert snaps, "snapshotter never ran"

    def drain(snapshot):
        """Restore a snapshot and count how many more hits it admits."""
        e = TickEngine(capacity=1 << 12, max_batch=512)
        e.load_items(snapshot)
        more = 0
        for _ in range(2 * limit // 100):
            rs = e.process([_req("snap-key", limit=limit)] * 100)
            more += sum(1 for r in rs if r.status == Status.UNDER_LIMIT)
        return more

    # A torn export (remaining disagreeing with status, half-written item)
    # breaks the invariant: snapshot-admitted + replayed == limit.
    for snapshot in [s for s in snaps if s][:: max(1, len(snaps) // 3)]:
        item = next(i for i in snapshot if i["key"].endswith("snap-key"))
        snapshot_admitted = limit - item["remaining"]
        assert 0 <= item["remaining"] <= limit
        assert drain(snapshot) == limit - snapshot_admitted

    # The final snapshot restores to an exhausted bucket.
    final = eng.export_items()
    eng2 = TickEngine(capacity=1 << 12, max_batch=512)
    eng2.load_items(final)
    r = eng2.process([_req("snap-key", limit=limit)])[0]
    assert r.status == Status.OVER_LIMIT
    assert r.remaining == 0
