"""Thundering-herd fast path: merged duplicate application must be bit-exact
with the sequential rank rounds.

The reference's headline scenario is many clients hammering one key
(docs/architecture.md, benchmark_test.go:122-147).  The tick kernel merges
uniform duplicate groups into closed-form prefix arithmetic
(engine._apply_merged_followers); these tests prove the merged kernel and
the pure rank-round kernel (merge_uniform=False) produce identical
responses *and* identical final table state across the branch space:
under/over, exact remainder, DRAIN_OVER_LIMIT, persisted status, mixed
groups (fallback), leaky herds (fraction preservation, exact-zero, drain),
RESET_REMAINING (never merged).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu.ops.buckets import BucketState, get_slot, set_slot
from gubernator_tpu.ops.engine import REQ_ROW_INDEX, REQ_ROWS, make_tick_fn
from gubernator_tpu.types import Algorithm, Behavior, Status

CAP = 256


# Module-scoped jitted kernels: jax.jit caches per (function, shapes), and
# make_tick_fn returns a fresh closure per call — building them once lets
# every same-shape batch across the suite reuse one compiled program.
FAST = jax.jit(make_tick_fn(CAP, merge_uniform=True))
SLOW = jax.jit(make_tick_fn(CAP, merge_uniform=False))


def run_both(m: np.ndarray, state: BucketState | None = None, now: int = 1_000):
    """Run one packed batch through the merged and unmerged kernels."""
    if state is None:
        state = BucketState.zeros(CAP)
    st_f, r_f = FAST(state, jnp.asarray(m), jnp.int64(now))
    st_s, r_s = SLOW(state, jnp.asarray(m), jnp.int64(now))
    return (st_f, np.asarray(r_f)), (st_s, np.asarray(r_s))


def assert_identical(fast, slow):
    (st_f, r_f), (st_s, r_s) = fast, slow
    np.testing.assert_array_equal(r_f, r_s, err_msg="responses diverge")
    for name in BucketState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_f, name)),
            np.asarray(getattr(st_s, name)),
            err_msg=f"state.{name} diverges",
        )


def packed(rows, b=None):
    """rows: list of dicts of REQ_ROWS fields; padding aims out of bounds."""
    b = b or len(rows)
    m = np.zeros((len(REQ_ROWS), b), np.int64)
    m[REQ_ROW_INDEX["slot"]] = CAP
    for c, r in enumerate(rows):
        for k, v in r.items():
            m[REQ_ROW_INDEX[k], c] = v
        m[REQ_ROW_INDEX["valid"], c] = 1
    return m


def uniform_rows(n, slot=3, hits=1, limit=10, behavior=0, known_head=0,
                 duration=60_000, created_at=1_000, algorithm=0, burst=0):
    rows = []
    for i in range(n):
        rows.append(dict(
            slot=slot, known=(1 if i else known_head), hits=hits, limit=limit,
            duration=duration, algorithm=algorithm, behavior=behavior,
            created_at=created_at, burst=burst,
        ))
    return rows


def test_herd_fresh_key_drains_then_over():
    m = packed(uniform_rows(64, hits=1, limit=10))
    f, s = run_both(m)
    assert_identical(f, s)
    # Sanity against the spec, not just self-consistency:
    r = f[1]
    status, _, remaining = r[0], r[1], r[2]
    assert list(remaining[:10]) == list(range(9, -1, -1))
    assert (status[:10] == Status.UNDER_LIMIT).all()
    assert (status[10:64] == Status.OVER_LIMIT).all()
    assert int(get_slot(f[0], "remaining", 3)) == 0
    # At-zero branch persisted OVER into the stored item (algorithms.go:162-169).
    assert int(get_slot(f[0], "status", 3)) == Status.OVER_LIMIT


def test_herd_nondivisible_no_drain_keeps_remainder():
    # hits=3 into limit=10: 7,4,1 under, then over-ask forever; remaining
    # parks at 1 and stored status never flips (over-ask isn't persisted).
    m = packed(uniform_rows(32, hits=3, limit=10))
    f, s = run_both(m)
    assert_identical(f, s)
    r = f[1]
    assert list(r[2][:3]) == [7, 4, 1]
    assert (r[0][3:32] == Status.OVER_LIMIT).all()
    assert (r[2][3:32] == 1).all()
    assert int(get_slot(f[0], "remaining", 3)) == 1
    assert int(get_slot(f[0], "status", 3)) == Status.UNDER_LIMIT


def test_herd_nondivisible_drain_zeroes():
    m = packed(uniform_rows(32, hits=3, limit=10,
                            behavior=Behavior.DRAIN_OVER_LIMIT))
    f, s = run_both(m)
    assert_identical(f, s)
    r = f[1]
    assert list(r[2][:3]) == [7, 4, 1]
    assert (r[2][3:32] == 0).all()
    assert int(get_slot(f[0], "remaining", 3)) == 0
    # Drain → at-zero from rank q+2 on → OVER persisted.
    assert int(get_slot(f[0], "status", 3)) == Status.OVER_LIMIT


def test_herd_on_existing_bucket_with_persisted_over():
    # Stored status OVER with remaining bumped back up (limit-delta path):
    # follower responses must echo the *persisted* status while under.
    st = BucketState.zeros(CAP)
    st = set_slot(st, 3, algorithm=0, limit=10, remaining=5,
                  duration=60_000, created_at=500, status=int(Status.OVER_LIMIT),
                  expire_at=60_500, in_use=True)
    m = packed(uniform_rows(8, hits=1, limit=10, known_head=1))
    f, s = run_both(m, state=st)
    assert_identical(f, s)
    assert (f[1][0][:5] == Status.OVER_LIMIT).all()  # echo of stored status


def test_mixed_hits_group_falls_back_identically():
    rows = uniform_rows(16, hits=2, limit=20)
    rows[7]["hits"] = 5  # one non-uniform member → whole group sequential
    m = packed(rows)
    f, s = run_both(m)
    assert_identical(f, s)


def test_reset_and_query_groups_never_merge_wrongly():
    rows = (
        uniform_rows(8, slot=2, hits=1, limit=10,
                     behavior=Behavior.RESET_REMAINING)
        + uniform_rows(8, slot=4, hits=0, limit=10)  # queries
    )
    m = packed(rows)
    f, s = run_both(m)
    assert_identical(f, s)


def test_leaky_herd_fresh_key_drains_then_over():
    m = packed(uniform_rows(
        64, hits=1, limit=10, algorithm=Algorithm.LEAKY_BUCKET))
    f, s = run_both(m)
    assert_identical(f, s)
    r = f[1]
    # burst defaults to limit; head takes 1, followers drain the rest.
    assert list(r[2][:10]) == list(range(9, -1, -1))
    assert (r[0][:10] == Status.UNDER_LIMIT).all()
    assert (r[0][10:64] == Status.OVER_LIMIT).all()
    assert float(get_slot(f[0], "remaining_f", 3)) == 0.0


def test_leaky_herd_preserves_fraction_through_decrements():
    # A stored fractional remaining (mid-drip) must survive integer
    # decrements bit-exactly — the closed form subtracts from the float,
    # not the truncation.
    st = BucketState.zeros(CAP)
    st = set_slot(st, 3, algorithm=int(Algorithm.LEAKY_BUCKET), limit=10,
                  remaining_f=7.625, duration=60_000, burst=10,
                  updated_at=1_000, expire_at=61_000, in_use=True)
    m = packed(uniform_rows(4, hits=2, limit=10, known_head=1,
                            algorithm=Algorithm.LEAKY_BUCKET))
    f, s = run_both(m, state=st)
    assert_identical(f, s)
    # 7.625 → head 5.625 → followers 3.625, 1.625, then over-ask parks it.
    assert float(get_slot(f[0], "remaining_f", 3)) == 1.625


def test_leaky_herd_exact_remainder_zeroes_float():
    # algorithms.go:392-397: the exact-remainder branch sets the *float*
    # remaining to exactly 0.0, dropping any fraction.
    st = BucketState.zeros(CAP)
    st = set_slot(st, 3, algorithm=int(Algorithm.LEAKY_BUCKET), limit=10,
                  remaining_f=6.5, duration=60_000, burst=10,
                  updated_at=1_000, expire_at=61_000, in_use=True)
    m = packed(uniform_rows(8, hits=2, limit=10, known_head=1,
                            algorithm=Algorithm.LEAKY_BUCKET))
    f, s = run_both(m, state=st)
    assert_identical(f, s)
    assert float(get_slot(f[0], "remaining_f", 3)) == 0.0


def test_leaky_herd_drain_zeroes_and_at_zero_reset_time():
    # Non-divisible remainder + DRAIN_OVER_LIMIT: the first over-ask zeroes
    # the float; later followers take the at-zero branch, whose reset_time
    # is computed from zero remaining, not the parked remainder.
    m = packed(uniform_rows(32, hits=3, limit=10,
                            algorithm=Algorithm.LEAKY_BUCKET,
                            behavior=Behavior.DRAIN_OVER_LIMIT))
    f, s = run_both(m)
    assert_identical(f, s)
    assert float(get_slot(f[0], "remaining_f", 3)) == 0.0


def test_leaky_herd_zero_remaining_keeps_fraction():
    # trunc(remaining)=0 with a live fraction: every follower is at-zero
    # and the fraction must survive (no exact/drain step ever fires).
    st = BucketState.zeros(CAP)
    st = set_slot(st, 3, algorithm=int(Algorithm.LEAKY_BUCKET), limit=10,
                  remaining_f=0.875, duration=60_000, burst=10,
                  updated_at=1_000, expire_at=61_000, in_use=True)
    m = packed(uniform_rows(6, hits=2, limit=10, known_head=1,
                            algorithm=Algorithm.LEAKY_BUCKET))
    f, s = run_both(m, state=st)
    assert_identical(f, s)
    assert float(get_slot(f[0], "remaining_f", 3)) == 0.875


def test_leaky_herd_4096_one_key():
    n = 4096
    m = packed(uniform_rows(n, hits=1, limit=100,
                            algorithm=Algorithm.LEAKY_BUCKET), b=n)
    f, s = run_both(m)
    assert_identical(f, s)
    r = f[1]
    assert (r[0][:100] == Status.UNDER_LIMIT).all()
    assert (r[0][100:n] == Status.OVER_LIMIT).all()


def test_negative_hits_group_falls_back():
    m = packed(uniform_rows(8, hits=-2, limit=10))
    f, s = run_both(m)
    assert_identical(f, s)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_randomized_parity(seed):
    rng = np.random.default_rng(seed)
    rows = []
    # ~20 slot groups, random sizes/params; some groups uniform, some mixed,
    # some leaky, some with behaviors; shuffled into one batch.
    for g in range(20):
        slot = int(rng.integers(0, 40))
        size = int(rng.integers(1, 12))
        uniform = rng.random() < 0.6
        base = dict(
            slot=slot,
            hits=int(rng.integers(0, 6)),
            limit=int(rng.integers(1, 12)),
            duration=60_000,
            algorithm=int(rng.random() < 0.2),
            behavior=int(rng.choice(
                [0, 0, 0, Behavior.DRAIN_OVER_LIMIT, Behavior.RESET_REMAINING]
            )),
            created_at=1_000,
            burst=0,
        )
        for i in range(size):
            r = dict(base)
            if not uniform and i and rng.random() < 0.5:
                r["hits"] = int(rng.integers(0, 6))
            r["known"] = 0  # first occurrence per slot fixed below
            rows.append(r)
    rng.shuffle(rows)
    seen = set()
    for r in rows:
        r["known"] = 1 if r["slot"] in seen else 0
        seen.add(r["slot"])
    m = packed(rows, b=256)
    f, s = run_both(m)
    assert_identical(f, s)


def test_herd_4096_one_key_matches_and_is_single_round():
    # The benchmark_test.go:122-147 scenario at full batch width:
    # correctness here, speed in benchmarks/ (the Zipf cells).
    n = 4096
    m = packed(uniform_rows(n, hits=1, limit=100), b=n)
    f, s = run_both(m)
    assert_identical(f, s)
    r = f[1]
    assert (r[0][:100] == Status.UNDER_LIMIT).all()
    assert (r[0][100:n] == Status.OVER_LIMIT).all()
