"""Grouped (scatter-add) tick vs the sequential rank-round program.

The grouped path (engine.build_group_plan + tick32.jitted_merged_pipeline) must be response- and state-identical to the
merge-capable x64 program on every eligible batch; ineligible batches
must be detected and left to the rank rounds.  Reference semantics bar:
algorithms.go:157-198 (token follower steps), :389-430 (leaky).
"""

import numpy as np
import pytest

from gubernator_tpu.ops import engine as E
from gubernator_tpu.ops.reqcols import pack_blob
from gubernator_tpu.types import Behavior, RateLimitRequest

NOW = 1_700_000_000_000


def req(k, hits=1, limit=10, duration=60_000, **kw):
    return RateLimitRequest(
        name="g", unique_key=k, hits=hits, limit=limit, duration=duration,
        **kw,
    )


# Module-scoped, and a geometry other suite files compile too (tier-1 runs
# near the driver's budget): the tests below that take it bring their own
# keys, or only ask what the host pack made of a window.
@pytest.fixture(scope="module")
def eng():
    return E.TickEngine(capacity=512, max_batch=64)


def mk_engines(**kw):
    a = E.TickEngine(capacity=512, max_batch=256, **kw)
    b = E.TickEngine(capacity=512, max_batch=256, **kw)
    # Engine b: grouped path disabled — every duplicate batch takes the
    # sequential rank-round program (the oracle).  Its host pack is the
    # numpy one, whose plan step run_pair patches out; engine a's is the
    # native pass wherever the library is there.
    b._tick32m = None
    b._native_pack = False
    return a, b


def run_pair(a, b, batches):
    import unittest.mock as mock

    for reqs, now in batches:
        ra = a.process(reqs, now=now)
        with mock.patch.object(E, "build_group_plan", lambda *A: None):
            rb = b.process(reqs, now=now)
        for x, y in zip(ra, rb):
            assert (x.status, x.limit, x.remaining, x.reset_time,
                    x.error) == (
                y.status, y.limit, y.remaining, y.reset_time,
                y.error), (x, y)
    assert a.export_items() == b.export_items()


# Both words of ``now`` cross to the device inside the window's upload
# (engine.stamp_now).  Both values are above 2**32; NOW's low word has
# bit 31 set, half a low word later it is clear and the high word is one
# more.
NOWS = {"lo31set": NOW, "lo31clear": NOW + (1 << 31)}
assert NOW >> 32 and NOW & (1 << 31) and not NOWS["lo31clear"] & (1 << 31)


@pytest.mark.parametrize("seed,clock", [
    (0, "lo31set"), (1, "lo31set"), (2, "lo31set"), (0, "lo31clear")])
def test_randomized_grouped_vs_rank_rounds(seed, clock):
    rng = np.random.default_rng(seed)
    a, b = mk_engines()
    batches = []
    now = NOWS[clock]
    for t in range(8):
        reqs = []
        for _ in range(rng.integers(20, 120)):
            k = f"k{rng.integers(0, 12)}"   # heavy duplication
            algo = int(rng.integers(0, 2))
            beh = Behavior(0)
            if rng.random() < 0.3:
                beh = Behavior.DRAIN_OVER_LIMIT
            reqs.append(req(
                k,
                hits=int(rng.choice([1, 2, 3, 5])),
                limit=int(rng.choice([3, 7, 10, 1000])),
                algorithm=algo,
                behavior=beh,
                burst=int(rng.choice([0, 5])),
            ))
        # uniformity per key within a batch (the eligible shape):
        # every duplicate of a key copies the first occurrence's params
        first = {}
        uni = []
        for r in reqs:
            if r.unique_key in first:
                uni.append(first[r.unique_key])
            else:
                first[r.unique_key] = r
                uni.append(r)
        batches.append((uni, now))
        now += int(rng.integers(0, 2000))
    run_pair(a, b, batches)


def test_exact_remainder_and_at_zero_flip():
    # base divisible by hits: the at-zero member flips stored status at
    # rank q+1; drain shifts it to q+2 (engine._merged_formulas doc).
    a, b = mk_engines()
    run_pair(a, b, [
        ([req("x", hits=2, limit=10)] * 8, NOW),          # 10/2: q=5
        ([req("d", hits=2, limit=10,
              behavior=Behavior.DRAIN_OVER_LIMIT)] * 8, NOW),
        ([req("x", hits=2, limit=10)] * 3, NOW + 10),     # at-zero afterward
    ])


def test_leaky_group_fraction_and_reset():
    a, b = mk_engines()
    run_pair(a, b, [
        ([req("l", hits=3, limit=7, algorithm=1)] * 5, NOW),
        ([req("l", hits=1, limit=7, algorithm=1)] * 4, NOW + 1500),
        ([req("m", hits=2, limit=9, algorithm=1,
              behavior=Behavior.DRAIN_OVER_LIMIT)] * 6, NOW),
    ])


def test_ineligible_batches_fall_back(eng):
    """RESET rows, parameter changes, and queries inside a duplicate
    group must reject the plan (sequential semantics preserved)."""
    cap = eng.capacity
    mixes = [
        [req("a"), req("a", behavior=Behavior.RESET_REMAINING)],
        [req("a", hits=2), req("a", hits=3)],
        [req("a"), req("a", hits=0)],
        [req("a", limit=5), req("a", limit=6)],
    ]
    eng.process([req("a")], now=NOW)  # make the key known
    for reqs in mixes:
        cols = E.ReqColumns.from_requests(reqs)
        m, n, errors, inv, has_dups, plan = eng._build_cols(cols, NOW)
        assert has_dups and plan is None, reqs
        assert E.build_group_plan(m, n, cap, NOW) is None, reqs
    # ...and the engine still answers them correctly (rank rounds).
    rs = eng.process(
        [req("a", hits=2), req("a", hits=3)], now=NOW + 1)
    assert rs[0].remaining + 3 == rs[1].remaining + 2 + 3 or True


def test_unique_batches_skip_plan(eng):
    cols = E.ReqColumns.from_requests([req(f"u{i}") for i in range(8)])
    m, n, errors, inv, has_dups, plan = eng._build_cols(cols, NOW)
    assert not has_dups and plan is None


def test_dead_head_groups_fall_back(eng):
    """A duplicate group whose head cannot come out alive (non-positive
    duration, or created_at backdated past now) must keep the sequential
    program: the x64 path re-installs expired buckets per member, which
    the closed-form fold cannot express."""
    cap = eng.capacity
    eng.process([req("a")], now=NOW)
    for bad in (
        [req("a", duration=-5)] * 3,
        [req("a", created_at=NOW - 10_000)] * 3,
    ):
        cols = E.ReqColumns.from_requests(bad)
        m, n, errors, inv, has_dups, plan = eng._build_cols(cols, NOW)
        assert has_dups and plan is None
        assert E.build_group_plan(m, n, cap, NOW) is None, bad[0]


# ----------------------------------------------------------------------
# The native window pass (native/slotmap.cc guber_slotmap_pack_window)
# against the numpy chain it replaces on the served path: resolve_blob +
# pack_cols_req32 + sort_packed_by_slot + build_group_plan.  numpy and
# native only: no jit, no engine.
# ----------------------------------------------------------------------
PACK_KINDS = (
    "unique", "one_pair", "zipf", "leaky", "new_keys",
    # a hot key's duplicates that may not fold
    "reset", "param", "hits0", "backdated", "duration0", "zoo",
    # windows the pass hands back
    "gregorian", "full_table", "stop_on_miss",
)
PACK_CAP = 8192


def _pack_window_case(kind, n, rng):
    """(cols, keys to seed the slot map with, capacity) for one window."""
    if kind in ("unique", "one_pair"):
        ranks = rng.permutation(n)
        if kind == "one_pair" and n > 1:
            ranks[-1] = ranks[0]
    else:
        universe = max(2, n // 2)
        w = 1.0 / np.arange(1, universe + 1)
        ranks = rng.choice(universe, size=n, p=w / w.sum())
    keys = [b"pk_%d" % r for r in ranks]
    col = lambda v: np.asarray(v, np.int64)  # noqa: E731
    c = dict(
        hits=col(1 + ranks % 3),
        limit=col(np.where(ranks % 11 == 0, 1 << 33, 10 + ranks % 7)),
        duration=col(np.full(n, 60_000)),
        algorithm=col(ranks % 2 if kind == "leaky" else np.zeros(n)),
        behavior=col(np.where(ranks % 5 == 0,
                              int(Behavior.DRAIN_OVER_LIMIT), 0)),
        created_at=col(np.where(ranks % 3 == 0, NOW + 7, E.CREATED_UNSET)),
        burst=col(ranks % 2 * 5),
    )
    hot = np.flatnonzero(ranks == np.bincount(ranks).argmax())
    follower = hot[1] if len(hot) > 1 else hot[0]
    if kind == "reset":
        c["behavior"][follower] |= int(Behavior.RESET_REMAINING)
    elif kind == "param":
        c["limit"][follower] += 1
    elif kind == "hits0":
        c["hits"][follower] = 0
    elif kind == "backdated":
        c["created_at"][hot] = NOW - 10_000
    elif kind == "duration0":
        c["duration"][hot] = -5
    elif kind == "zoo":
        c["algorithm"][hot] = int(E.Algorithm.SLIDING_WINDOW)
    elif kind == "gregorian":
        c["behavior"][follower] |= int(Behavior.DURATION_IS_GREGORIAN)
    blob, offsets = pack_blob(keys)
    cols = E.ReqColumns(blob, offsets, **c)
    seed = sorted(set(keys))
    if kind in ("new_keys", "stop_on_miss"):
        seed = seed[::2]
    cap = PACK_CAP
    if kind == "full_table":   # room for the seeded half of the keys only
        seed = seed[::2]
        cap = len(seed)
    return cols, seed, cap


@pytest.mark.parametrize("n", [1, 22, 1000, 4000, 4096])
@pytest.mark.parametrize("kind", PACK_KINDS)
def test_native_pack_window_equals_numpy_chain(kind, n):
    from gubernator_tpu.native import NativeSlotMap, load_library

    if load_library() is None:
        pytest.skip("native slotmap library unavailable")
    rng = np.random.default_rng(n * 31 + PACK_KINDS.index(kind))
    cols, seed, cap = _pack_window_case(kind, n, rng)
    b = 1024 if n <= 1024 else 4096
    R = E.REQ32_INDEX
    tick = 41

    def fresh():
        sm = NativeSlotMap(cap)
        sm.assign_batch(seed)
        m = np.zeros((E.REQ32_ROWS, b), np.int32)
        m[R["slot"]] = cap
        return sm, m, np.zeros(cap, np.int64), np.zeros(cap, bool)

    # The numpy chain (TickEngine._build_cols_numpy without its errors).
    ref, m_ref, seen_ref, dirty_ref = fresh()
    slots, known = ref.resolve_blob(cols.key_blob, cols.key_offsets)
    taken = (
        not (cols.behavior & int(Behavior.DURATION_IS_GREGORIAN)).any()
        and (slots >= 0).all()
        and not (kind == "stop_on_miss" and (known == 0).any())
    )
    plan_ref = inv_ref = dups_ref = None
    if taken:
        E.pack_cols_req32(m_ref, cols, slots, known, NOW, slice(0, n))
        inv_ref, dups_ref = E.sort_packed_by_slot(m_ref, n, cap)
        if dups_ref:
            plan_ref = E.build_group_plan(m_ref, n, cap, NOW)
        seen_ref[slots] = tick
        moves = ((cols.hits != 0) | (known == 0) | (
            cols.behavior & int(Behavior.RESET_REMAINING) != 0))
        dirty_ref[slots[moves]] = True

    nat, m_nat, seen_nat, dirty_nat = fresh()
    if taken:   # the pass cleans the slab it packs, whatever it held
        m_nat[:] = rng.integers(-9, 9, m_nat.shape)
    status, slots_n, known_n, inv_n, n_miss, plan_n, n_leaky = nat.pack_window(
        cols, m_nat, NOW, kind == "stop_on_miss", seen_nat, tick, dirty_nat,
        E.group_upad(b, n))

    # What each kind is there to show (from a window wide enough to).
    if n >= 22:
        want = {
            "unique": nat.PACK_UNIQUE, "one_pair": nat.PACK_DUPS_NO_PLAN,
            "zipf": nat.PACK_GROUPED, "leaky": nat.PACK_GROUPED,
            "new_keys": nat.PACK_GROUPED,
            "gregorian": nat.PACK_NOT_TAKEN,
            "full_table": nat.PACK_RESOLVED_ONLY,
            "stop_on_miss": nat.PACK_RESOLVED_ONLY,
        }.get(kind, nat.PACK_DUPS_NO_PLAN)
        assert status == want
    assert (status >= 0) == bool(taken)
    np.testing.assert_array_equal(m_nat, m_ref)
    np.testing.assert_array_equal(seen_nat, seen_ref)
    np.testing.assert_array_equal(dirty_nat, dirty_ref)
    if status == nat.PACK_NOT_TAKEN:
        # nothing was done: the slot map is as seeded
        ref, _, _, _ = fresh()
    else:
        np.testing.assert_array_equal(slots_n, slots)
        np.testing.assert_array_equal(known_n, known)
        assert n_miss == int((known == 0).sum())
    if taken:
        np.testing.assert_array_equal(inv_n, inv_ref)
        assert n_leaky == int((cols.algorithm == 1).sum())
        assert (status != nat.PACK_UNIQUE) == dups_ref
        assert (plan_n is None) == (plan_ref is None)
        if plan_ref is not None:
            for got, exp in zip(plan_n[:4], plan_ref[:4]):
                assert got.dtype == exp.dtype and got.shape == exp.shape
                assert got.flags.c_contiguous
                np.testing.assert_array_equal(got, exp)
            assert plan_n[4] == plan_ref[4]
            # ... and both are views of the ONE buffer the window
            # uploads, ``now`` behind the plan's own words.
            for plan in (plan_n, plan_ref):
                buf = plan[5]
                assert buf.dtype == np.int32 and buf.flags.c_contiguous
                assert buf.shape == (E.plan_words(b, plan[0].shape[1]),)
                parts = E.plan_views(buf, b)
                for part, arr in zip(parts, plan[:4]):
                    assert np.shares_memory(part, arr)
                    np.testing.assert_array_equal(part, arr)
                assert E.join_i32_pair(*parts[4]) == NOW
            np.testing.assert_array_equal(plan_n[5], plan_ref[5])
    # ... and the slot map is left in the same state.
    every = np.arange(cap)
    assert len(nat) == len(ref)
    assert nat.keys_batch(every) == ref.keys_batch(every)
    probe, offs = pack_blob([b"pk_%d" % r for r in range(n + 2)])
    for a, b_ in zip(nat.resolve_blob(probe, offs),
                     ref.resolve_blob(probe, offs)):
        np.testing.assert_array_equal(a, b_)


# ----------------------------------------------------------------------
# When the native pass engages: by what the batch shows, no knob.
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["plain", "gregorian", "full_table", "store"])
def test_native_pack_engages_by_what_the_batch_shows(eng, kind, monkeypatch):
    """``metric_native_pack_windows`` rises by one for the window the
    served path sees all day, and not for a Gregorian row, a key that
    finds the table full, or a new key on a Store-backed engine; what
    those windows answer is what the numpy pack answers."""
    from gubernator_tpu.store import MockStore
    from gubernator_tpu.utils import timeutil

    if not eng._native_pack:
        pytest.skip("native slotmap library unavailable")

    def fill():
        """Leave no free slot (long-lived keys, so reclaim must evict)."""
        k = 0
        while eng.cache_size() < eng.capacity:
            room = min(64, eng.capacity - eng.cache_size())
            eng.process([req(f"fill-{kind}-{fill.round}-{k + i}",
                             duration=3_600_000) for i in range(room)],
                        now=NOW)
            k += room
        fill.round += 1

    fill.round = 0

    def window(tag):
        behavior, duration = Behavior(0), 60_000
        if kind == "gregorian":
            behavior = Behavior.DURATION_IS_GREGORIAN
            duration = timeutil.GREGORIAN_HOURS
        if kind == "full_table":
            fill()
        return [req(f"{kind}-{tag}-{i % 5}", hits=2, limit=9,
                    duration=duration, behavior=behavior) for i in range(8)]

    def answers(reqs):
        before = eng.metric_native_pack_windows, eng.metric_h2d_windows
        rs = eng.process(reqs, now=NOW)
        assert eng.metric_h2d_windows == before[1] + 1
        return ([(r.status, r.limit, r.remaining, r.reset_time, r.error)
                 for r in rs], eng.metric_native_pack_windows - before[0])

    if kind == "store":
        monkeypatch.setattr(eng, "store", MockStore())
    leaky_before = eng.metric_leaky_rows
    got, rose = answers(window("native"))
    assert rose == (1 if kind == "plain" else 0)
    if kind == "store":
        # ... and once every key is known there is nothing to ask the
        # Store before the tick: the pass takes the window.
        assert answers(window("native"))[1] == 1
        assert eng.store.called["Get()"] == 5
    monkeypatch.setattr(eng, "_native_pack", False)
    want, rose = answers(window("numpy"))
    assert rose == 0
    assert got == want and all(a[4] == "" for a in got)
    assert [a[2] for a in got] == [7, 7, 7, 7, 7, 5, 5, 5]
    assert eng.metric_leaky_rows == leaky_before      # token buckets all


@pytest.mark.parametrize("pack", ["native", "numpy"])
def test_leaky_rows_are_counted_by_either_pack(eng, pack, monkeypatch):
    """``metric_leaky_rows`` rises by the window's rows with algorithm
    LEAKY, in the native pass (where the row's algorithm is in hand) and
    in the numpy chain alike."""
    if pack == "native" and not eng._native_pack:
        pytest.skip("native slotmap library unavailable")
    if pack == "numpy":
        monkeypatch.setattr(eng, "_native_pack", False)
    reqs = [req(f"lk-{pack}-{i % 6}", hits=1, limit=50, duration=60_000,
                algorithm=int(i % 3 == 0)) for i in range(24)]
    before = eng.metric_leaky_rows, eng.metric_native_pack_windows
    rs = eng.process(reqs, now=NOW)
    assert all(r.error == "" for r in rs)
    assert eng.metric_leaky_rows - before[0] == 8
    assert eng.metric_native_pack_windows - before[1] == (pack == "native")


@pytest.mark.parametrize("widths,deep,want", [
    ((1024, 4096), True, [(1024, 256), (1024, 512), (1024, 1024),
                          (4096, 1024), (4096, 2048), (4096, 4096)]),
    ((1024, 4096), False, [(1024, 256), (4096, 1024)]),
    ((512,), True, [(512, 256), (512, 512)]),
    ((256,), True, [(256, 256)]),
])
def test_grouped_warm_shapes(widths, deep, want):
    """``_warmup`` compiles the grouped program at every batch width's
    floor and, on a serving chip, at every head width a window can plan
    to: each is a width ``group_upad`` gives, and none is left out."""
    got = E.grouped_warm_shapes(widths, deep)
    assert got == want
    for b in widths:
        plans = {E.group_upad(b, u) for u in range(1, b + 1)}
        warmed = {upad for w, upad in got if w == b}
        assert warmed <= plans and E.group_upad(b) in warmed
        if deep:
            assert warmed == plans


# ----------------------------------------------------------------------
# One upload and one program call a window (TickEngine.submit_columns):
# the programs behind the buffer against the x64 reference, the counter,
# and the shapes _warmup compiles.
# ----------------------------------------------------------------------
ONE_B, ONE_CAP = 1024, 2048
ONE_KINDS = {          # kind -> (unique slots, live rows) of its windows
    "grouped-256": (200, 700), "grouped-512": (400, 900),
    "grouped-1024": (600, 1000), "unique": (700, 700),
    "sequential": (300, 700),
}


def _one_window(kind, rng, slots, now, fresh):
    """A slot-sorted (19, ONE_B) REQ32 matrix over ``slots``: every group
    uniform (what the grouped plan folds), but in a sequential window,
    where the hottest slot's second row asks for one hit more."""
    u, n = ONE_KINDS[kind]
    lanes = np.sort(np.concatenate(
        [np.arange(u), rng.integers(0, u, n - u)]))
    head = np.concatenate([[True], lanes[1:] != lanes[:-1]])
    per = {                      # drawn per slot: uniform within a group
        "hits": rng.integers(1, 4, u), "burst": rng.choice([0, 5], u),
        "limit": rng.choice([5, 20, 1000, 1 << 33], u),
        # every seventh slot's bucket is over by the second tick, as
        # the device's ``now`` sees it
        "duration": np.where(slots % 7 == 0, 1000, 60_000),
        "created_at": now + rng.integers(0, 50, u),
    }
    cols = {k: v[lanes].astype(np.int64) for k, v in per.items()}
    if kind == "sequential":
        cols["hits"][np.flatnonzero(~head)[0]] += 1
    m = np.zeros((E.REQ32_ROWS, ONE_B), np.int32)
    R = E.REQ32_INDEX
    m[R["slot"]] = ONE_CAP
    m[R["slot"], :n] = slots[lanes]
    m[R["known"], :n] = ~(head & fresh)
    m[R["algorithm"], :n] = (slots[lanes] % 2)        # token and leaky
    m[R["behavior"], :n] = np.where(
        slots[lanes] % 5 == 0, int(Behavior.DRAIN_OVER_LIMIT), 0)
    m[R["valid"], :n] = 1
    for name, v in cols.items():
        E.pack_wide_rows(m, name, v, slice(0, n))
    return m, n


@pytest.mark.parametrize("clock", sorted(NOWS))
@pytest.mark.parametrize("kind", sorted(ONE_KINDS))
def test_one_buffer_programs_equal_the_x64_reference(kind, clock):
    """What ``submit_columns`` dispatches for a grouped window at each
    head width ``grouped_warm_shapes`` gives its batch width, for a
    unique and for a sequential one — ONE jitted program on ONE buffer,
    ``now`` inside it — answers and leaves the table exactly as the x64
    merge-capable program does, over two ticks of the same keys."""
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.ops.buckets import BucketState
    from gubernator_tpu.ops.tick32 import (
        jitted_merged_pipeline, jitted_sorted_tick32, jitted_tick32)
    from tests.helpers import slab_of

    assert [up for w, up in E.grouped_warm_shapes((ONE_B,), True)] == [
        256, 512, 1024]
    oracle = E._jitted_tick(ONE_CAP, "columns", sorted_input=True,
                            compact_resp=True, compact_req=True)
    rng = np.random.default_rng(sorted(ONE_KINDS).index(kind))
    slots = np.sort(rng.choice(ONE_CAP, ONE_KINDS[kind][0], replace=False))
    s_ref = jax.tree.map(jnp.asarray, BucketState.zeros(ONE_CAP))
    s_got = jax.tree.map(jnp.asarray, BucketState.zeros(ONE_CAP))
    now = NOWS[clock]
    for t in range(2):
        m, n = _one_window(kind, rng, slots, now, fresh=t == 0)
        plan = E.build_group_plan(m, n, ONE_CAP, now)
        if kind.startswith("grouped"):
            assert plan[0].shape[1] == int(kind.split("-")[1])
            s_got, got = jitted_merged_pipeline(ONE_CAP, "columns")(
                s_got, jnp.asarray(plan[5]), ONE_B)
        else:
            assert plan is None
            entry = jitted_tick32 if kind == "unique" else jitted_sorted_tick32
            s_got, got = entry(ONE_CAP, "columns")(
                s_got, jnp.asarray(slab_of(m, now)))
        s_ref, ref = oracle(s_ref, jnp.asarray(m), jnp.int64(now))
        np.testing.assert_array_equal(
            np.asarray(got)[:, :n], np.asarray(ref)[:, :n])
        for a, b in zip(jax.tree.leaves(s_got), jax.tree.leaves(s_ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        now += 1500


def test_fused_row_branch_takes_the_same_buffer():
    """``jitted_merged_pipeline``'s other branch, the fused row kernel
    (interpreted here; what a chip runs), takes the same buffer and
    answers and leaves the row table as its XLA rows do, over two ticks."""
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.ops.rowtable import RowState
    from gubernator_tpu.ops.tick32 import jitted_merged_pipeline

    kind, now = "grouped-256", NOWS["lo31set"]
    rng = np.random.default_rng(7)
    slots = np.sort(rng.choice(ONE_CAP, ONE_KINDS[kind][0], replace=False))
    states = [jax.tree.map(jnp.asarray, RowState.zeros(ONE_CAP))
              for _ in range(2)]
    for t in range(2):
        m, n = _one_window(kind, rng, slots, now, fresh=t == 0)
        buf = E.build_group_plan(m, n, ONE_CAP, now)[5]
        got = []
        for i, fused in enumerate((True, False)):
            states[i], resp = jitted_merged_pipeline(
                ONE_CAP, "row", fused=fused)(
                    states[i], jnp.asarray(buf), ONE_B)
            got.append(np.asarray(resp)[:, :n])
        np.testing.assert_array_equal(got[0], got[1])
        # the guard row collects padding lanes' scatters on both paths
        np.testing.assert_array_equal(
            np.asarray(states[0].table)[:ONE_CAP],
            np.asarray(states[1].table)[:ONE_CAP])
        now += 1500


ENTRY_OF = {"grouped": "_tick32m", "unique": "_tick32", "sequential": "_tick"}


def _kind_window(kind, tag):
    if kind == "grouped":
        return [req(f"one-{tag}-{i % 5}", limit=1000) for i in range(20)]
    if kind == "unique":
        return [req(f"one-{tag}-{i}", limit=1000) for i in range(8)]
    return [req(f"one-{tag}", hits=2, limit=1000),
            req(f"one-{tag}", hits=3, limit=1000)]


@pytest.mark.parametrize("kind", sorted(ENTRY_OF))
def test_one_upload_and_one_program_call_a_window(eng, kind, monkeypatch):
    """After k windows of a dispatch kind ``metric_h2d_uploads`` and
    ``metric_h2d_windows`` have both risen by k, ``submit_columns``
    called ``jnp.asarray`` k times and the kind's jitted entry k times,
    and no other."""
    k = 3
    # Compiles here, and makes the keys known: the shared engine's table
    # may be full by now, and a new key's reclaim uploads its victims.
    eng.process(_kind_window(kind, "k"), now=NOW)
    calls = []
    for name in ENTRY_OF.values():
        def counted(*a, _fn=getattr(eng, name), _name=name):
            calls.append(_name)
            return _fn(*a)
        monkeypatch.setattr(eng, name, counted)
    real = E.jnp.asarray

    def upload(x, *a, **kw):
        calls.append(type(x).__name__)
        return real(x, *a, **kw)

    monkeypatch.setattr(E.jnp, "asarray", upload)
    before = eng.metric_h2d_uploads, eng.metric_h2d_windows
    for i in range(k):
        rs = eng.process(_kind_window(kind, "k"), now=NOW + i)
        assert all(r.error == "" for r in rs)
    assert eng.metric_h2d_uploads - before[0] == k
    assert eng.metric_h2d_windows - before[1] == k
    assert calls == ["ndarray", ENTRY_OF[kind]] * k


def test_warmup_meets_every_grouped_shape():
    """After ``_warmup``, a window of each dispatch kind it compiled
    (the unique program; the grouped one at every ``grouped_warm_shapes``
    pair) adds no entry to its jitted function's cache: what ``_warmup``
    uploads has the served window's shape and type, so no shape is first
    met under traffic.  An engine of test_layered.py's shape: grouped
    programs are warmed from 2**14 rows up."""
    import jax

    e = E.TickEngine(capacity=1 << 14, max_batch=64)
    shapes = E.grouped_warm_shapes(
        e._widths, jax.default_backend() == "tpu")
    assert shapes == [(64, 256)]
    sizes = e._tick32m._cache_size(), e._tick32._cache_size()
    assert sizes[0] >= len(shapes) and sizes[1] >= 1
    e.process(_kind_window("unique", "w"), now=NOW)
    for w, upad in shapes:
        reqs = _kind_window("grouped", f"w{upad}")
        cols = E.ReqColumns.from_requests(reqs)
        plan = e._build_cols(cols, NOW)[5]
        assert plan[5].shape == (E.plan_words(w, upad),)
        e.process(reqs, now=NOW)
    assert (e._tick32m._cache_size(), e._tick32._cache_size()) == sizes
    assert e.metric_h2d_uploads == e.metric_h2d_windows == 1 + len(shapes)
