"""Unit-layer plan + layered tick parity vs the x64 oracle.

engine.build_layer_plan decomposes a mixed-duplicate batch into unit
layers; tick32.jitted_layered_pipeline applies one narrow merged tick
per layer, chained through the table.  Responses AND final table state
must match the sequential oracle bit-for-bit on every eligible batch;
ineligible shapes must return None (the engine then keeps the
sequential program).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu.ops.buckets import BucketState
from gubernator_tpu.ops.engine import (
    REQ32_INDEX as R32,
    REQ32_ROWS,
    _jitted_tick,
    build_layer_plan,
    group_upad,
    pack_wide_rows,
)
from gubernator_tpu.ops.tick32 import jitted_layered_pipeline
from gubernator_tpu.types import Behavior
from tests.helpers import slab_of

CAP = 1 << 10
B = 256
NOW = 1_700_000_000_000

ORACLE = _jitted_tick(CAP, "columns", sorted_input=True, compact_resp=True,
                      compact_req=True)

# Every plan of this module runs through ONE layered program.  The
# plan's layer count is quantized (2, 4, then multiples of 4) and each
# new (w0, k_pad) is a new trace and compile, which the batches below
# used to pay one by one; w0 is group_upad(B) for every batch this
# narrow, and the layer stack pads to the planner's own ceiling with
# the same all-padding layers it pads with (slot = CAP heads, count 1).
KPAD = 32   # build_layer_plan's max_layers


def _layered(state, packed, plan, now):
    mh0, cnt0, mhk, cntk, uidx, rank, kpad = plan
    assert mh0.shape[1] == group_upad(B) and kpad <= KPAD
    extra = KPAD - kpad
    mhk = np.concatenate(
        [mhk, np.zeros((extra,) + mhk.shape[1:], np.int32)])
    mhk[kpad - 1:, R32["slot"], :] = CAP
    cntk = np.concatenate(
        [cntk, np.ones((extra, cntk.shape[1]), np.int32)])
    fn = jitted_layered_pipeline(CAP, "columns", group_upad(B), KPAD)
    return fn(
        state, jnp.asarray(mh0), jnp.asarray(cnt0), jnp.asarray(mhk),
        jnp.asarray(cntk), jnp.asarray(slab_of(packed, now)),
        jnp.asarray(uidx), jnp.asarray(rank),
    )


def _mixed_batch(rng, reset_frac=0.1, now=NOW):
    """Slot-sorted batch with deep hot groups broken by RESET rows and
    parameter changes — the layered plan's home turf.  All durations
    positive and created_at == now so count>1 heads are provably alive
    (the plan's eligibility)."""
    n = int(rng.integers(60, B))
    # Enough duplicate depth to clear the plan's min_dup_frac gate, but
    # shallow enough unit structure to stay under max_layers (the
    # param-share probability below bounds expected units per segment).
    lo = min(max(16, n // 3), 70)
    hot_n = int(rng.integers(lo, min(80, n - 2)))
    slots = np.sort(np.concatenate([
        np.zeros(hot_n, np.int64),
        np.full(int(rng.integers(1, 10)), 7, np.int64),  # 2nd hot key
        rng.integers(8, CAP, max(1, n - hot_n - 9)),
    ]))[:n]
    n = len(slots)
    m = np.zeros((REQ32_ROWS, B), np.int32)
    m[R32["slot"], :n] = slots
    m[R32["slot"], n:] = CAP
    m[R32["known"], :n] = 1
    m[R32["valid"], :n] = 1
    hits = rng.integers(1, 4, n)
    limit = rng.integers(1, 30, n)
    behavior = np.where(
        rng.random(n) < reset_frac, int(Behavior.RESET_REMAINING),
        np.where(rng.random(n) < 0.2, int(Behavior.DRAIN_OVER_LIMIT), 0),
    ).astype(np.int64)
    algo = rng.integers(0, 2, n)
    # Duplicates usually share params so multi-member units form.
    for i in range(1, n):
        if slots[i] == slots[i - 1] and rng.random() < 0.85:
            hits[i], limit[i] = hits[i - 1], limit[i - 1]
            behavior[i], algo[i] = behavior[i - 1], algo[i - 1]
    m[R32["algorithm"], :n] = algo
    m[R32["behavior"], :n] = behavior
    for name, v in (("hits", hits), ("limit", limit),
                    ("duration", np.full(n, 60_000)),
                    ("created_at", np.full(n, now))):
        full = np.zeros(B, np.int64)
        full[:n] = v
        pack_wide_rows(m, name, full, slice(None))
    return m, n


@pytest.mark.parametrize("seed", [3, 11])
def test_layered_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    for _ in range(2):
        m, n = _mixed_batch(rng)
        plan = build_layer_plan(m, n, CAP, NOW)
        assert plan is not None, "eligible batch must plan"
        packed = jnp.asarray(m)
        s1 = jax.tree.map(jnp.asarray, BucketState.zeros(CAP))
        s2 = jax.tree.map(jnp.asarray, BucketState.zeros(CAP))
        s1, r1 = ORACLE(s1, packed, jnp.int64(NOW))
        s2, r2 = _layered(s2, packed, plan, NOW)
        np.testing.assert_array_equal(
            np.asarray(r1)[:, :n], np.asarray(r2)[:, :n])
        for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layered_chains_across_ticks():
    """Sequential layered ticks keep state in step with the oracle."""
    rng = np.random.default_rng(5)
    s1 = jax.tree.map(jnp.asarray, BucketState.zeros(CAP))
    s2 = jax.tree.map(jnp.asarray, BucketState.zeros(CAP))
    for t in range(2):
        m, n = _mixed_batch(rng, now=NOW + t)
        plan = build_layer_plan(m, n, CAP, NOW + t)
        assert plan is not None
        packed = jnp.asarray(m)
        s1, r1 = ORACLE(s1, packed, jnp.int64(NOW + t))
        s2, r2 = _layered(s2, packed, plan, NOW + t)
        np.testing.assert_array_equal(
            np.asarray(r1)[:, :n], np.asarray(r2)[:, :n])
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_rejects_dead_multi_unit_heads():
    """A count>1 unit under a backdated/negative-duration head can't be
    proven alive — the plan must decline (sequential program handles
    it)."""
    m = np.zeros((REQ32_ROWS, B), np.int32)
    n = 4
    m[R32["slot"], :n] = 0
    m[R32["slot"], n:] = CAP
    m[R32["known"], :n] = 1
    m[R32["valid"], :n] = 1
    for name, v in (("hits", 1), ("limit", 5), ("duration", -5),
                    ("created_at", NOW)):
        full = np.zeros(B, np.int64)
        full[:n] = v
        pack_wide_rows(m, name, full, slice(None))
    assert build_layer_plan(m, n, CAP, NOW) is None


def test_plan_rejects_overdeep_segments():
    """More units on one segment than max_layers → None."""
    rng = np.random.default_rng(1)
    n = 80
    m = np.zeros((REQ32_ROWS, B), np.int32)
    m[R32["slot"], :n] = 0            # one segment
    m[R32["slot"], n:] = CAP
    m[R32["known"], :n] = 1
    m[R32["valid"], :n] = 1
    hits = rng.integers(1, 1000, n)   # params differ row to row →
    for name, v in (("hits", hits),   # every row its own unit
                    ("limit", np.full(n, 5)),
                    ("duration", np.full(n, 60_000)),
                    ("created_at", np.full(n, NOW))):
        full = np.zeros(B, np.int64)
        full[:n] = v
        pack_wide_rows(m, name, full, slice(None))
    assert build_layer_plan(m, n, CAP, NOW, max_layers=32) is None


def test_plan_invariants_fuzz():
    """Host-only structural invariants over many random eligible plans:
    every live row's uidx lands inside the flat journal, rank is its
    offset from its unit head, unit heads occupy distinct journal
    positions, and per-unit counts sum back to the live row count."""
    rng = np.random.default_rng(123)
    checked = 0
    for _ in range(40):
        m, n = _mixed_batch(rng)
        plan = build_layer_plan(m, n, CAP, NOW)
        if plan is None:
            continue
        checked += 1
        mh0, cnt0, mhk, cntk, uidx, rank, kpad = plan
        w0 = mh0.shape[1]
        flat_w = w0 + (kpad - 1) * mhk.shape[2]
        live = m[R32["slot"], :n] < CAP
        nl = int(live.sum())
        assert (uidx[:nl] >= 0).all() and (uidx[:nl] < flat_w).all()
        # Heads are the rank-0 rows; their journal positions are unique,
        # every member shares its head's position, and rank is exactly
        # the member's offset from its head row.
        heads = np.flatnonzero(rank[:nl] == 0)
        pos = uidx[:nl][heads]
        assert len(np.unique(pos)) == len(pos)
        head_of = heads[
            np.searchsorted(heads, np.arange(nl), side="right") - 1]
        assert (rank[:nl] == np.arange(nl) - head_of).all()
        assert (uidx[:nl] == uidx[:nl][head_of]).all()
        # Live counts across all layers sum to the live row count.
        total = int(
            cnt0[mh0[R32["slot"]] < CAP].sum()
            + sum(
                cntk[k][mhk[k][R32["slot"]] < CAP].sum()
                for k in range(kpad - 1)
            )
        )
        assert total == nl
        # Every member's head shares its slot.
        assert (
            m[R32["slot"], :n][:nl] == m[R32["slot"], :n][head_of]
        ).all()
    assert checked >= 20  # the generator must mostly produce eligible plans


def test_engine_dispatches_layered():
    """TickEngine routes an eligible mixed batch through the layered
    pipeline and still matches object-path semantics.  (The layered
    dispatch is gated to serving-scale engines — capacity >= 2^14.)"""
    from gubernator_tpu.ops.engine import TickEngine
    from gubernator_tpu.types import RateLimitRequest, Status

    eng = TickEngine(capacity=1 << 14, max_batch=64)
    reqs = (
        [RateLimitRequest(name="h", unique_key="hot", hits=1, limit=100,
                          duration=60_000) for _ in range(10)]
        + [RateLimitRequest(name="h", unique_key="hot", hits=1, limit=100,
                            duration=60_000,
                            behavior=Behavior.RESET_REMAINING)]
        + [RateLimitRequest(name="h", unique_key="hot", hits=2, limit=100,
                            duration=60_000) for _ in range(5)]
        + [RateLimitRequest(name="h", unique_key=f"c{i}", hits=1, limit=9,
                            duration=60_000) for i in range(6)]
    )
    out = eng.process(reqs, now=NOW)
    # The batch must actually have ridden the layered pipeline — the
    # sequential fallback produces identical responses, so without this
    # the test cannot catch the production path going dead.
    assert eng.metric_layered_ticks == 1
    assert all(r.error == "" for r in out)
    # Hot key: 10 singles, then RESET (back to 100), then 5x2 = 90.
    assert out[9].remaining == 90
    assert out[10].remaining == 100          # the RESET row's response
    assert out[15].remaining == 90
    assert all(r.status == Status.UNDER_LIMIT for r in out)
    assert all(r.remaining == 8 for r in out[16:])
