"""The plain reference against the program's own x64 tick
(``make_tick_fn``, what chip_smoke.Oracle uses) on seeded histories with
duplicates: a cross-check, not the reference.  And the control: the
reference with one guarantee broken has to come out as not correct."""

import numpy as np
import pytest

from benchmarks.harness.reference import Reference

HOUR = 3_600_000


def histories(seed, keys=64, rounds=6, width=256):
    """Rounds of requests over few keys (so every round holds
    duplicates), time stepping between rounds."""
    rng = np.random.default_rng(seed)
    alg = rng.integers(0, 2, keys)
    limit = rng.choice([5, 20, 100, 1 << 33], keys)
    duration = rng.choice([HOUR, 2 * HOUR], keys)
    burst = np.where(alg == 1, rng.choice([0, 10, 50], keys), 0)
    t = 1_800_000_000_000
    for _ in range(rounds):
        t += int(rng.choice([1, 700, 90_000]))
        ids = rng.integers(0, keys, width)
        hits = rng.choice([0, 1, 1, 1, 2, 7], width)
        beh = rng.choice([0, 0, 0, 8, 32], width)
        yield t, ids, hits, beh, alg[ids], limit[ids], duration[ids], burst[ids]


def x64_answers(rounds, keys):
    """The program's x64 tick, column layout, slot == key id."""
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.ops.buckets import BucketState
    from gubernator_tpu.ops.engine import REQ_ROW_INDEX as R, REQ_ROWS, make_tick_fn

    state = BucketState.zeros(keys)
    tick = jax.jit(make_tick_fn(keys))
    known = np.zeros(keys, bool)
    out = []
    for t, ids, hits, beh, alg, limit, duration, burst in rounds:
        w = len(ids)
        first = np.zeros(w, bool)
        first[np.unique(ids, return_index=True)[1]] = True
        m = np.zeros((len(REQ_ROWS), w), np.int64)
        m[R["slot"]] = ids
        m[R["known"]] = known[ids] | ~first
        known[ids] = True
        for name, col in (("hits", hits), ("limit", limit), ("duration", duration),
                          ("algorithm", alg), ("behavior", beh), ("burst", burst)):
            m[R[name]] = col
        m[R["created_at"]] = t
        m[R["valid"]] = 1
        state, resp = tick(state, jnp.asarray(m), jnp.int64(t))
        out.append(np.asarray(resp)[:4])
    return out


def plain_answers(rounds, control=""):
    ref = Reference(control)
    buckets = {}
    out = []
    for t, ids, hits, beh, alg, limit, duration, burst in rounds:
        got = np.zeros((4, len(ids)), np.int64)
        for j, key in enumerate(ids.tolist()):
            buckets[key], ans = ref.apply(
                buckets.get(key),
                (int(hits[j]), int(limit[j]), int(duration[j]), int(burst[j]),
                 int(alg[j]), int(beh[j]), t))
            got[:, j] = ans
        out.append(got)
    return out


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_reference_agrees_with_the_x64_tick(seed):
    rounds = list(histories(seed))
    want = x64_answers(rounds, 64)
    got = plain_answers(rounds)
    for r, (a, b) in enumerate(zip(got, want)):
        bad = np.flatnonzero((a != b).any(axis=0))
        assert len(bad) == 0, (r, bad[:5], a[:, bad[:5]], b[:, bad[:5]])


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_control_comes_out_not_correct(seed, control="lost_hit"):
    rounds = [r for r in histories(seed, rounds=12)]
    # the benchmark's traffic: hits 1, no behaviour flags
    rounds = [(t, ids, np.ones_like(h), np.zeros_like(b), *rest)
              for t, ids, h, b, *rest in rounds]
    sound = plain_answers(rounds)
    broken = plain_answers(rounds, control)
    mismatched = sum(int((a != b).any(axis=0).sum()) for a, b in zip(sound, broken))
    assert mismatched > 0
