"""Parity: the parts-native chained-unit-round program vs the x64 oracle.

``tick32.make_sorted_tick32_rows_fn`` is the program the engine runs for
mixed/ineligible duplicate batches (TickEngine ``self._tick``); the x64
``engine.make_tick_fn`` sorted tick is the oracle.  Responses AND final
table state must agree bit-for-bit on adversarial batches: duplicate
groups broken by RESET/DRAIN/parameter changes, query rows (hits=0),
dead heads (negative durations), backdated created_at, fresh vs known
rows, and both algorithms interleaved.
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
    pack_wide_rows,
)
from gubernator_tpu.ops.tick32 import jitted_sorted_tick32
from gubernator_tpu.types import Behavior
from tests.helpers import slab_of

CAP = 1 << 10
B = 256
NOW = 1_700_000_000_000

ORACLE = _jitted_tick(CAP, "columns", sorted_input=True, compact_resp=True,
                      compact_req=True)
SORTED32 = jitted_sorted_tick32(CAP, "columns")


def _random_batch(rng):
    n = int(rng.integers(50, B))
    hot_n = int(rng.integers(5, min(60, n - 1)))
    slots = np.sort(np.concatenate([
        np.zeros(hot_n, np.int64),           # deep hot group at slot 0
        rng.integers(1, CAP, n - hot_n),     # cold keys (some collide)
    ]))
    m = np.zeros((REQ32_ROWS, B), np.int32)
    m[R32["slot"], :n] = slots
    m[R32["slot"], n:] = CAP
    m[R32["known"], :n] = rng.integers(0, 2, n)
    m[R32["valid"], :n] = 1
    hits = rng.integers(0, 4, n)             # incl. queries
    limit = rng.integers(1, 20, n)
    dur = rng.choice([60_000, 60_000, 60_000, -5], n)   # incl. dead heads
    created = np.full(n, NOW)
    created[rng.random(n) < 0.1] = NOW - 10 ** 9        # backdated
    behavior = rng.choice(
        [0, 0, 0, int(Behavior.RESET_REMAINING),
         int(Behavior.DRAIN_OVER_LIMIT)], n)
    algo = rng.integers(0, 2, n)
    # Duplicates often share params so real units form; the rest break
    # groups into singleton units.
    for i in range(1, n):
        if slots[i] == slots[i - 1] and rng.random() < 0.6:
            hits[i], limit[i] = hits[i - 1], limit[i - 1]
            behavior[i], algo[i] = behavior[i - 1], algo[i - 1]
            dur[i], created[i] = dur[i - 1], created[i - 1]
    m[R32["algorithm"], :n] = algo
    m[R32["behavior"], :n] = behavior
    for name, v in (("hits", hits), ("limit", limit), ("duration", dur),
                    ("created_at", created)):
        full = np.zeros(B, np.int64)
        full[:n] = v
        pack_wide_rows(m, name, full, slice(None))
    return jnp.asarray(m), n


@pytest.mark.parametrize("seed", [5, 17, 99])
def test_sorted32_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    for _ in range(4):
        packed, n = _random_batch(rng)
        s1 = jax.tree.map(jnp.asarray, BucketState.zeros(CAP))
        s2 = jax.tree.map(jnp.asarray, BucketState.zeros(CAP))
        s1, r1 = ORACLE(s1, packed, jnp.int64(NOW))
        s2, r2 = SORTED32(s2, jnp.asarray(slab_of(packed, NOW)))
        np.testing.assert_array_equal(
            np.asarray(r1)[:, :n], np.asarray(r2)[:, :n])
        for a, b, name in zip(
            jax.tree.leaves(s1), jax.tree.leaves(s2),
            [str(i) for i in range(len(jax.tree.leaves(s1)))],
        ):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"state leaf {name}")


def test_sorted32_chains_across_ticks():
    """Sequential ticks through the program keep per-slot state exactly
    in step with the oracle (the chain touches the table, not just the
    responses)."""
    rng = np.random.default_rng(3)
    s1 = jax.tree.map(jnp.asarray, BucketState.zeros(CAP))
    s2 = jax.tree.map(jnp.asarray, BucketState.zeros(CAP))
    for t in range(3):
        packed, n = _random_batch(rng)
        s1, r1 = ORACLE(s1, packed, jnp.int64(NOW + t * 1000))
        s2, r2 = SORTED32(
            s2, jnp.asarray(slab_of(packed, NOW + t * 1000)))
        np.testing.assert_array_equal(
            np.asarray(r1)[:, :n], np.asarray(r2)[:, :n])
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trunc_to_pair_negative_rate():
    """Negative leaky rates (negative durations) convert Go-style —
    trunc toward zero, not floor (algorithms.go int64(rate))."""
    from gubernator_tpu.ops import b64
    from gubernator_tpu.ops import i64pair as p64

    for v in (-0.357, -5.0, -5.9, 0.9, 5.9, -(2.0 ** 40) - 0.5):
        t = b64.from_np(np.full(4, np.float32(v), np.float64))
        got = p64.to_np(b64.trunc_to_pair(t))[0]
        assert got == int(np.float32(v)), (v, got)
