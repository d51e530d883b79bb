"""Row-layout table tests: Pallas kernels (interpret mode on CPU) and
row-vs-column engine parity.

The row layout (ops/rowtable.py) is the TPU production path; on the CPU
test backend its kernels run in Pallas interpret mode, so everything here
checks semantics, and benchmarks/ on the chip checks speed.
"""

import numpy as np
import pytest

from gubernator_tpu.ops import rowtable
from gubernator_tpu.ops.engine import TickEngine
from gubernator_tpu.ops.rowtable import (
    FIELD_OFFSETS,
    ROW_W,
    RowState,
    gather_rows,
    scatter_rows,
)
from gubernator_tpu.store import MockStore
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest, Status

import jax.numpy as jnp


def req(key="k", hits=1, limit=10, duration=60_000, **kw):
    return RateLimitRequest(
        name="t", unique_key=key, hits=hits, limit=limit, duration=duration,
        algorithm=kw.pop("algorithm", Algorithm.TOKEN_BUCKET), **kw,
    )


# ----------------------------------------------------------------------
# Kernel correctness (interpret mode)
# ----------------------------------------------------------------------
def test_scatter_gather_round_trip():
    cap, b = 256, 32
    rng = np.random.default_rng(7)
    slots = np.sort(rng.permutation(cap)[:b]).astype(np.int32)
    rows = rng.integers(0, 1 << 30, (b, ROW_W)).astype(np.int32)
    table = jnp.zeros((cap + 1, ROW_W), jnp.int32)

    out = np.asarray(scatter_rows(table, jnp.asarray(slots), jnp.asarray(rows)))
    ref = np.zeros((cap + 1, ROW_W), np.int32)
    ref[slots] = rows
    assert np.array_equal(out, ref)

    got = np.asarray(gather_rows(jnp.asarray(out), jnp.asarray(slots)))
    assert np.array_equal(got, rows)


def test_scatter_guard_row_absorbs_masked_lanes():
    cap = 64
    table = jnp.zeros((cap + 1, ROW_W), jnp.int32)
    slots = jnp.asarray(np.array([3, cap, cap, 7], np.int32))
    rows = jnp.asarray(np.full((4, ROW_W), 9, np.int32))
    out = np.asarray(scatter_rows(table, slots, rows))
    assert (out[3] == 9).all() and (out[7] == 9).all()
    # nothing besides rows 3, 7 and the guard row was touched
    touched = np.zeros(cap + 1, bool)
    touched[[3, 7, cap]] = True
    assert (out[~touched] == 0).all()


def test_logical_matrix_round_trip():
    from gubernator_tpu.ops.buckets import BucketState

    b = 8
    rows = BucketState(
        algorithm=jnp.arange(b, dtype=jnp.int32) % 2,
        limit=jnp.asarray(np.arange(b) * (1 << 40) + 5, jnp.int64),
        remaining=jnp.asarray(np.arange(b) - 3, jnp.int64),
        remaining_f=jnp.asarray(np.linspace(-2.5, 1e12, b), jnp.float64),
        duration=jnp.full(b, 60_000, jnp.int64),
        created_at=jnp.full(b, 1_700_000_000_123, jnp.int64),
        updated_at=jnp.full(b, 1_700_000_000_456, jnp.int64),
        burst=jnp.full(b, 7, jnp.int64),
        status=jnp.ones(b, jnp.int32),
        expire_at=jnp.full(b, 1_700_000_060_000, jnp.int64),
        in_use=jnp.asarray(np.arange(b) % 2 == 0),
        tat=jnp.asarray(1_700_000_000_789 + np.arange(b) * (1 << 33), jnp.int64),
        prev_count=jnp.asarray(np.arange(b) * (1 << 35) + 11, jnp.int64),
    )
    m = rowtable.logical_to_matrix(rows)
    back = rowtable.matrix_to_logical(m)
    for f in rows._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(back, f)), np.asarray(getattr(rows, f)), err_msg=f
        )


# ----------------------------------------------------------------------
# Engine parity: row layout must be observably identical to columns
# ----------------------------------------------------------------------
def make_engines(**kw):
    return (
        TickEngine(capacity=64, max_batch=64, table_layout="columns", **kw),
        TickEngine(capacity=64, max_batch=64, table_layout="row", **kw),
    )


def run_parity(batches, now0=1_700_000_000_000, **engine_kw):
    col, row = make_engines(**engine_kw)
    assert row.layout == "row" and col.layout == "columns"
    now = now0
    for batch in batches:
        a = col.process(batch, now=now)
        b = row.process(batch, now=now)
        assert [
            (r.status, r.limit, r.remaining, r.reset_time, r.error) for r in a
        ] == [
            (r.status, r.limit, r.remaining, r.reset_time, r.error) for r in b
        ]
        now += 1_000
    return col, row


def test_engine_parity_token_and_leaky():
    run_parity([
        [req(key=f"k{i}", hits=2, limit=5) for i in range(8)],
        [req(key=f"k{i}", hits=2, limit=5) for i in range(8)],
        [req(key=f"k{i}", hits=2, limit=5) for i in range(8)],  # over limit
        [req(key=f"l{i}", hits=1, limit=10, duration=10_000,
             algorithm=Algorithm.LEAKY_BUCKET) for i in range(8)],
        [req(key=f"l{i}", hits=3, limit=10, duration=10_000,
             algorithm=Algorithm.LEAKY_BUCKET) for i in range(8)],
    ])


def test_engine_parity_duplicates_and_behaviors():
    run_parity([
        # thundering herd: one key many times (merge fast path)
        [req(key="hot", hits=1, limit=10) for _ in range(32)],
        # mixed-parameter duplicates (rank-round fallback)
        [req(key="hot", hits=1, limit=10 + (i % 2)) for i in range(8)],
        # queries + RESET_REMAINING + DRAIN_OVER_LIMIT + negative hits
        [
            req(key="hot", hits=0, limit=10),
            req(key="hot", hits=-2, limit=10),
            req(key="hot", hits=1, limit=10,
                behavior=Behavior.RESET_REMAINING),
            req(key="hot", hits=100, limit=10,
                behavior=Behavior.DRAIN_OVER_LIMIT),
        ],
    ])


def test_engine_parity_eviction_pressure():
    # capacity 64 engines; 3 generations of 60 distinct short-TTL keys
    # force TTL reclaim and LRU eviction on both layouts.
    gens = [
        [req(key=f"g{g}-{i}", hits=1, limit=3, duration=1_500)
         for i in range(60)]
        for g in range(3)
    ]
    col, row = run_parity(
        [gens[0], gens[1], gens[2]],
    )
    assert col.cache_size() == row.cache_size()


def test_engine_parity_store_write_through():
    col_store, row_store = MockStore(), MockStore()
    col = TickEngine(capacity=64, max_batch=64, table_layout="columns",
                     store=col_store)
    row = TickEngine(capacity=64, max_batch=64, table_layout="row",
                     store=row_store)
    now = 1_700_000_000_000
    batch = [req(key=f"k{i}", hits=1, limit=5) for i in range(4)]
    assert [r.remaining for r in col.process(batch, now=now)] == \
           [r.remaining for r in row.process(batch, now=now)]
    assert sorted(col_store.data) == sorted(row_store.data)
    for k in col_store.data:
        assert col_store.data[k] == row_store.data[k], k


def test_engine_parity_snapshot_and_globals():
    from gubernator_tpu.types import GlobalUpdate, RateLimitResponse

    col, row = run_parity([
        [req(key=f"k{i}", hits=1, limit=9, duration=120_000) for i in range(6)],
    ])
    a = sorted(col.export_items(), key=lambda d: d["key"])
    b = sorted(row.export_items(), key=lambda d: d["key"])
    assert a == b

    upd = [
        GlobalUpdate(
            key="t_gk",
            algorithm=Algorithm.TOKEN_BUCKET,
            duration=60_000,
            created_at=1_700_000_000_000,
            status=RateLimitResponse(
                status=Status.UNDER_LIMIT, limit=50, remaining=44,
                reset_time=1_700_000_060_000,
            ),
        )
    ]
    col.install_globals(upd, now=1_700_000_001_000)
    row.install_globals(upd, now=1_700_000_001_000)
    a = sorted(col.export_items(), key=lambda d: d["key"])
    b = sorted(row.export_items(), key=lambda d: d["key"])
    assert a == b

    # load_items round trip into fresh row engine
    fresh = TickEngine(capacity=64, max_batch=64, table_layout="row")
    fresh.load_items(a, now=1_700_000_001_500)
    c = sorted(fresh.export_items(), key=lambda d: d["key"])
    assert [d["key"] for d in c] == [d["key"] for d in a]
