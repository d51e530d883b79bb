"""Tiered bucket state (docs/tiering.md): churn continuity, cold-tier
bounds, the Store.remove eviction contract, write-behind, and full-table
graceful degradation.

The headline property: with a cold tier configured, a key that cycles
out of the device table and back in KEEPS its consumed budget — the old
blind-zeroing reclaim gave every returning key a fresh bucket, a
rate-limit bypass any key-churning client could exploit.
"""

import threading
import time

import numpy as np

from gubernator_tpu.ops.engine import TickEngine
from gubernator_tpu.store import MockStore
from gubernator_tpu.tiering import ColdStore
from gubernator_tpu.types import Algorithm, RateLimitRequest, Status

NOW = 1_700_000_000_000


def req(key, hits=1, limit=10, duration=600_000, **kw):
    return RateLimitRequest(
        name="t", unique_key=key, hits=hits, limit=limit, duration=duration,
        algorithm=kw.pop("algorithm", Algorithm.TOKEN_BUCKET), **kw,
    )


def _slotmap_invariant(engine):
    """Mapped + free must always cover the table exactly — a demoted
    slot that leaked out of the free list would shrink capacity."""
    sm = engine.slots
    if hasattr(sm, "_free"):  # pure-Python SlotMap
        assert len(sm._free) + len(sm) == engine.capacity


# ---------------------------------------------------------------------------
# Churn correctness: working set 4x capacity
# ---------------------------------------------------------------------------

def test_churn_4x_capacity_keeps_consumed_budget():
    cap, ws = 16, 64  # working set 4x the device table
    e = TickEngine(capacity=cap, max_batch=16, cold_capacity=4 * ws)
    try:
        # Sweep 1: consume 6 of 10 on every key.  Each 16-key batch
        # fills the table, so later batches evict (and demote) earlier
        # keys — every key cycles hot -> cold at least once.
        for start in range(0, ws, 16):
            rs = e.process(
                [req(f"k{i}", hits=6) for i in range(start, start + 16)],
                now=NOW,
            )
            assert all(r.remaining == 4 for r in rs)
        # Sweep 2: one more hit per key.  A fresh bucket would report
        # remaining 9; continuity through the cold tier reports 3.
        for start in range(0, ws, 16):
            rs = e.process(
                [req(f"k{i}", hits=1) for i in range(start, start + 16)],
                now=NOW + 1,
            )
            assert all(r.remaining == 3 for r in rs), (
                "re-promoted keys must keep their consumed budget"
            )
        assert e.metric_cold_hits >= ws - cap  # every demoted key promoted
        # Promotion stays batched: exactly one restore scatter per tick
        # that had cold hits, never one per key.
        assert e.metric_promote_dispatches == e.metric_promote_ticks > 0
        # The demote readback runs once per reclaim round that chose LRU
        # victims, and nowhere else: a reclaim-free tick pays none.
        assert e.metric_demote_readbacks == e.metric_evict_reclaims > 0
        # Demoted slots leak nothing host-side.
        assert not e._pending
        _slotmap_invariant(e)
        assert len(e.cold) <= e.cold.capacity
    finally:
        e.close()


def test_churn_leaky_preserves_float_level():
    e = TickEngine(capacity=4, max_batch=8, cold_capacity=64)
    try:
        rs = e.process(
            [req("lk", hits=6, algorithm=Algorithm.LEAKY_BUCKET)], now=NOW
        )
        assert rs[0].remaining == 4
        for i in range(8):  # churn lk out of the hot tier
            e.process([req(f"f{i}")], now=NOW)
        rs = e.process(
            [req("lk", hits=1, algorithm=Algorithm.LEAKY_BUCKET)], now=NOW
        )
        assert rs[0].remaining == 3  # remaining_f survived the round trip
    finally:
        e.close()


def test_without_cold_tier_eviction_resets_budget():
    # The bypass the tier exists to close, pinned as the DOCUMENTED
    # behavior of cold_capacity=0 (strict reference LRU semantics).
    e = TickEngine(capacity=4, max_batch=8)
    try:
        assert e.process([req("a", hits=6)], now=NOW)[0].remaining == 4
        for i in range(8):
            e.process([req(f"f{i}")], now=NOW)
        assert e.process([req("a", hits=1)], now=NOW)[0].remaining == 9
    finally:
        e.close()


def test_promotion_is_one_scatter_for_many_hits():
    e = TickEngine(capacity=4, max_batch=8, cold_capacity=64)
    try:
        e.process([req(f"a{i}", hits=2) for i in range(4)], now=NOW)
        e.process([req(f"b{i}") for i in range(4)], now=NOW)  # demote a*
        before = e.metric_promote_dispatches
        rs = e.process([req(f"a{i}", hits=1) for i in range(4)], now=NOW)
        assert [r.remaining for r in rs] == [7, 7, 7, 7]
        assert e.metric_promote_dispatches == before + 1  # ONE scatter
        assert e.metric_promotions >= 4
    finally:
        e.close()


def test_duplicate_cold_key_in_one_batch_sequences():
    # Two hits on a demoted key in ONE batch: one promotion, sequential
    # semantics against the promoted state.
    e = TickEngine(capacity=4, max_batch=8, cold_capacity=64)
    try:
        e.process([req("dup", hits=4)], now=NOW)
        for i in range(8):
            e.process([req(f"f{i}")], now=NOW)
        rs = e.process([req("dup", hits=3), req("dup", hits=3)], now=NOW)
        assert [r.remaining for r in rs] == [3, 0]
        assert rs[1].status == Status.UNDER_LIMIT
    finally:
        e.close()


# ---------------------------------------------------------------------------
# Store contract: remove on eviction, write-behind on cold overflow
# ---------------------------------------------------------------------------

def test_store_remove_fired_on_eviction_without_cold_tier():
    st = MockStore()
    e = TickEngine(capacity=4, max_batch=4, store=st)
    try:
        for i in range(4):
            e.process([req(f"k{i}")], now=NOW)
        assert st.called["Remove()"] == 0
        for i in range(4, 8):  # LRU-evict the first four
            e.process([req(f"k{i}")], now=NOW + i)
        assert e.metric_unexpired_evictions == 4
        assert st.called["Remove()"] == 4
        assert sorted(st.data) == [f"t_k{i}" for i in range(4, 8)]
    finally:
        e.close()


def test_store_remove_deferred_while_demoted():
    # With a cold tier the item is still cached after hot eviction, so
    # remove() must NOT fire on demote.
    st = MockStore()
    e = TickEngine(capacity=4, max_batch=4, store=st, cold_capacity=64)
    try:
        for i in range(8):
            e.process([req(f"k{i}")], now=NOW + i)
        assert e.metric_unexpired_evictions > 0
        assert st.called["Remove()"] == 0
        assert len(e.cold) > 0
    finally:
        e.close()


def test_cold_overflow_write_behind():
    st = MockStore()
    cold = ColdStore(capacity=4, store=st)
    cols = {
        f: np.arange(6, dtype=np.float64 if f == "remaining_f" else np.int64)
        for f in ("algorithm", "limit", "remaining", "remaining_f",
                  "duration", "created_at", "updated_at", "burst", "status")
    }
    cols["expire_at"] = np.full(6, NOW + 10_000, np.int64)
    put = cold.put_columns([f"w{i}".encode() for i in range(6)], cols, NOW)
    assert put == 6
    assert len(cold) == 4  # budget enforced by the tier's own LRU
    assert cold.metric_overflow_evictions == 2
    assert st.called["OnChange()"] == 2  # overflow write-behind
    assert all(k.startswith("w") for k in st.data)


def test_cold_ttl_expiry():
    st = MockStore()
    cold = ColdStore(capacity=8, store=st)
    cols = {
        f: np.zeros(2, np.float64 if f == "remaining_f" else np.int64)
        for f in ("algorithm", "limit", "remaining", "remaining_f",
                  "duration", "created_at", "updated_at", "burst", "status")
    }
    cols["expire_at"] = np.array([NOW + 50, NOW + 10_000], np.int64)
    cold.put_columns([b"short", b"long"], cols, NOW)
    assert len(cold) == 2
    # Expired entry is a miss at take() time and is dropped + removed.
    pos, _ = cold.take([b"short"], NOW + 100)
    assert len(pos) == 0
    assert st.called["Remove()"] == 1
    # The sweep drops nothing else until `long` expires too.
    assert cold.expire(NOW + 100) == 0
    assert cold.expire(NOW + 20_000) == 1
    assert len(cold) == 0


def _cols(n, expire):
    cols = {
        f: np.arange(n, dtype=np.float64 if f == "remaining_f" else np.int64)
        for f in ("algorithm", "limit", "remaining", "remaining_f",
                  "duration", "created_at", "updated_at", "burst", "status")
    }
    cols["expire_at"] = np.full(n, expire, np.int64)
    return cols


def test_slow_sink_never_blocks_concurrent_take():
    # Regression: overflow write-behind used to run INSIDE the cold
    # store's lock, so a slow sink (network store, SSD under fsync)
    # stalled every concurrent reader.  Sink calls now happen after the
    # lock is released.
    class SlowSink:
        def __init__(self):
            self.entered = threading.Event()

        def put_columns(self, keys, cols, now):
            self.entered.set()
            time.sleep(0.5)

    sink = SlowSink()
    cold = ColdStore(capacity=4, store=sink)
    cold.put_columns([f"a{i}".encode() for i in range(4)],
                     _cols(4, NOW + 10_000), NOW)
    t = threading.Thread(
        target=cold.put_columns,
        args=([f"b{i}".encode() for i in range(4)],
              _cols(4, NOW + 10_000), NOW),
    )
    t.start()
    assert sink.entered.wait(5.0)  # overflow shed is inside the sink now
    t0 = time.monotonic()
    pos, _ = cold.take([b"b0"], NOW)
    elapsed = time.monotonic() - t0
    t.join(5.0)
    assert len(pos) == 1
    assert elapsed < 0.25, (
        f"take blocked {elapsed:.2f}s behind a slow sink — sink calls "
        "must run outside the cold store's lock"
    )


def test_cold_overflow_prefers_batched_sink():
    # A sink advertising put_batch/remove_batch gets ONE call per shed
    # sweep / expiry sweep, not one per item.
    class BatchSink:
        def __init__(self):
            self.put_calls = []
            self.remove_calls = []

        def put_batch(self, items):
            self.put_calls.append(items)

        def remove_batch(self, keys):
            self.remove_calls.append(keys)

    sink = BatchSink()
    cold = ColdStore(capacity=4, store=sink)
    put = cold.put_columns([f"w{i}".encode() for i in range(6)],
                           _cols(6, NOW + 10_000), NOW)
    assert put == 6
    assert len(sink.put_calls) == 1  # one batched call for both victims
    assert len(sink.put_calls[0]) == 2
    assert cold.metric_overflow_evictions == 2
    # Expiry sweep batches removals the same way.
    cols = _cols(2, NOW + 50)
    cold.put_columns([b"s0", b"s1"], cols, NOW)
    assert cold.expire(NOW + 100) == 2
    assert len(sink.remove_calls) == 1  # one batched removal call
    assert sorted(sink.remove_calls[0]) == ["s0", "s1"]


def test_cold_put_drops_already_expired_rows():
    cold = ColdStore(capacity=8)
    cols = {
        f: np.zeros(1, np.float64 if f == "remaining_f" else np.int64)
        for f in ("algorithm", "limit", "remaining", "remaining_f",
                  "duration", "created_at", "updated_at", "burst", "status")
    }
    cols["expire_at"] = np.array([NOW - 1], np.int64)
    assert cold.put_columns([b"dead"], cols, NOW) == 0
    assert len(cold) == 0


# ---------------------------------------------------------------------------
# Graceful degradation: full table sheds per-item errors
# ---------------------------------------------------------------------------

def test_full_table_sheds_per_item_errors_not_raise():
    e = TickEngine(capacity=4, max_batch=16)
    try:
        rs = e.process([req(f"k{i}") for i in range(10)], now=NOW)
        served = [r for r in rs if not r.error]
        shed = [r for r in rs if r.error]
        assert len(served) == 4 and len(shed) == 6
        assert all("table full" in r.error for r in shed)
        assert all(r.remaining == 9 for r in served)
        assert e.metric_shed_requests == 6
        # The engine keeps serving afterwards.
        rs = e.process([req("k0")], now=NOW + 1)
        assert rs[0].error == "" and rs[0].remaining == 8
    finally:
        e.close()


def test_shed_keeps_store_write_through_consistent():
    st = MockStore()
    e = TickEngine(capacity=2, max_batch=8, store=st)
    try:
        rs = e.process([req(f"k{i}") for i in range(5)], now=NOW)
        ok = [i for i, r in enumerate(rs) if not r.error]
        assert len(ok) == 2
        assert len(st.data) == 2  # only the served items were persisted
    finally:
        e.close()


def test_occupancy_surface():
    e = TickEngine(capacity=8, max_batch=8, cold_capacity=16)
    try:
        e.process([req(f"k{i}") for i in range(4)], now=NOW)
        assert e.hot_occupancy() == 0.5
        assert e.cold_size() == 0
        for i in range(4, 16):
            e.process([req(f"k{i}")], now=NOW + i)
        assert e.cold_size() > 0
    finally:
        e.close()


# ---------------------------------------------------------------------------
# Snapshots: demoted state survives Loader save/restore
# ---------------------------------------------------------------------------

def test_snapshot_includes_cold_entries_and_restores():
    e = TickEngine(capacity=4, max_batch=8, cold_capacity=64)
    try:
        for i in range(8):  # 8 keys through a 4-slot table: 4 demote
            e.process([req(f"k{i}", hits=i + 1)], now=NOW)
        assert e.cold_size() > 0
        snap = e.export_columns()
        assert len(snap["key_offsets"]) - 1 == 8  # hot + cold, disjoint
        assert e.last_export_stats["cold_items"] == e.cold_size()
    finally:
        e.close()
    e2 = TickEngine(capacity=4, max_batch=8, cold_capacity=64)
    try:
        e2.load_columns(snap, now=NOW)
        # The 4-slot table can't hold 8 restored keys; the overflow lands
        # cold and every key keeps its consumed budget through the cycle.
        assert e2.cache_size() <= 4 and e2.cold_size() >= 4
        for i in range(8):
            rs = e2.process([req(f"k{i}", hits=0)], now=NOW)
            assert rs[0].remaining == 10 - (i + 1), f"k{i} lost its budget"
    finally:
        e2.close()


def test_dirty_delta_includes_fresh_demotions():
    e = TickEngine(capacity=4, max_batch=8, cold_capacity=64)
    try:
        for i in range(4):
            e.process([req(f"k{i}", hits=2)], now=NOW)
        e.export_columns()  # full export drains both dirty sets
        # Churn k0..k3 out; the demotions are the only new state.
        for i in range(4, 8):
            e.process([req(f"k{i}")], now=NOW)
        delta = e.export_columns(dirty_only=True)
        keys = set()
        blob, offs = delta["key_blob"], delta["key_offsets"]
        for j in range(len(offs) - 1):
            keys.add(bytes(blob[offs[j]: offs[j + 1]]).decode())
        assert {f"t_k{i}" for i in range(4)} <= keys  # demoted rows present
    finally:
        e.close()
