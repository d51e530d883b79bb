"""Differential test: transition32 (parts-native) vs bucket_transition
(the jax_enable_x64 oracle) across every branch of the decision tree.

Every output must match EXACTLY: status, remaining, reset_time,
over_limit, every integer state field, and the leaky float remaining bit
for bit (the parts path computes IEEE binary64 on the bit pattern,
ops/b64.py; on the CPU the x64 oracle is true float64).  Below the
differential: the served tick and the grouped fold against the plain
reference (algos/reference.py ``token_bucket`` / ``leaky_bucket``,
upstream algorithms.go in Python int and float).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gubernator_tpu.ops import i64pair as p64
from gubernator_tpu.ops import tfloat as tf
from gubernator_tpu.ops.buckets import (
    BucketState, ReqBatch, bucket_transition)
from gubernator_tpu.ops.transition32 import (
    PReq, PResp, PState, transition32)
from gubernator_tpu.types import Algorithm, Behavior
from tests.helpers import slab_of

NOW = 1_700_000_000_000


def gen_batch(rng, n):
    """Random state+request pairs exercising every branch combination."""
    # exact-quotient (duration, limit) pool: rate = d/l representable
    dl = [(30_000, 10), (60_000, 1000), (1_000, 4), (4_096, 1 << 12),
          (3_600_000, 1000), (5_000, 5), (1_000, 1), (0, 10)]
    d_l = [dl[i] for i in rng.integers(0, len(dl), n)]
    duration = np.array([d for d, _ in d_l], np.int64)
    limit = np.array([l for _, l in d_l], np.int64)

    hits = rng.choice([0, 1, 2, 5, 100, -1, -50, 10**12], n)
    algo = rng.integers(0, 2, n).astype(np.int64)
    behavior = np.zeros(n, np.int64)
    pick = rng.random(n)
    behavior[pick < 0.2] = int(Behavior.RESET_REMAINING)
    behavior[(pick >= 0.2) & (pick < 0.35)] = int(Behavior.DRAIN_OVER_LIMIT)
    greg = (pick >= 0.35) & (pick < 0.45)
    behavior[greg] |= int(Behavior.DURATION_IS_GREGORIAN)
    burst = rng.choice([0, 5, 20, 10**6], n)

    known = rng.random(n) < 0.8
    in_use = rng.random(n) < 0.85
    s_algo = np.where(rng.random(n) < 0.7, algo, 1 - algo).astype(np.int64)
    s_limit = np.where(rng.random(n) < 0.6, limit,
                       rng.choice([1, 7, 2000, 10**13], n))
    s_duration = np.where(rng.random(n) < 0.6, duration,
                          rng.choice([500, 2_000, 120_000], n))
    s_remaining = rng.integers(0, 30, n).astype(np.int64)
    s_remaining[rng.random(n) < 0.2] = 0
    # drip-accumulated float remainders: integer + k/8 fractions (exact)
    s_rem_f = (rng.integers(0, 25, n) + rng.integers(0, 8, n) / 8.0)
    s_created = NOW - rng.integers(0, 120_000, n)
    s_updated = NOW - rng.integers(-5_000, 120_000, n)
    s_burst = np.where(rng.random(n) < 0.6, np.where(burst == 0, limit, burst),
                       rng.choice([3, 50], n))
    s_status = (rng.random(n) < 0.2).astype(np.int64)
    s_expire = NOW + rng.choice([-10_000, -1, 0, 1, 60_000], n)
    created = NOW - rng.choice([0, 0, 0, 1_000, 3_000, 61_000, -500], n)
    greg_exp = np.where(greg, NOW + rng.choice([500, 3_600_000], n), 0)
    greg_dur = np.where(greg, rng.choice([3_600_000, 86_400_000], n), 0)

    state = dict(
        algorithm=s_algo, limit=s_limit, remaining=s_remaining,
        remaining_f=s_rem_f, duration=s_duration, created_at=s_created,
        updated_at=s_updated, burst=s_burst, status=s_status,
        expire_at=s_expire, in_use=in_use,
    )
    req = dict(
        slot=np.arange(n, dtype=np.int64), known=known, hits=hits,
        limit=limit, duration=duration, algorithm=algo, behavior=behavior,
        created_at=created, burst=burst, greg_exp=greg_exp,
        greg_dur=greg_dur, valid=np.ones(n, bool),
    )
    return state, req


def to_oracle(state, req):
    s = BucketState(
        algorithm=jnp.asarray(state["algorithm"], jnp.int32),
        limit=jnp.asarray(state["limit"]),
        remaining=jnp.asarray(state["remaining"]),
        remaining_f=jnp.asarray(state["remaining_f"], jnp.float64),
        duration=jnp.asarray(state["duration"]),
        created_at=jnp.asarray(state["created_at"]),
        updated_at=jnp.asarray(state["updated_at"]),
        burst=jnp.asarray(state["burst"]),
        status=jnp.asarray(state["status"], jnp.int32),
        expire_at=jnp.asarray(state["expire_at"]),
        in_use=jnp.asarray(state["in_use"]),
        # Zoo columns (PR 16): token/leaky lanes never read them.
        tat=jnp.zeros_like(jnp.asarray(state["expire_at"])),
        prev_count=jnp.zeros_like(jnp.asarray(state["expire_at"])),
    )
    r = ReqBatch(
        slot=jnp.asarray(req["slot"], jnp.int32),
        known=jnp.asarray(req["known"]),
        hits=jnp.asarray(req["hits"]),
        limit=jnp.asarray(req["limit"]),
        duration=jnp.asarray(req["duration"]),
        algorithm=jnp.asarray(req["algorithm"], jnp.int32),
        behavior=jnp.asarray(req["behavior"], jnp.int32),
        created_at=jnp.asarray(req["created_at"]),
        burst=jnp.asarray(req["burst"]),
        greg_exp=jnp.asarray(req["greg_exp"]),
        greg_dur=jnp.asarray(req["greg_dur"]),
        valid=jnp.asarray(req["valid"]),
    )
    return s, r


def to_parts(state, req):
    s = PState(
        algorithm=jnp.asarray(state["algorithm"], jnp.int32),
        limit=p64.from_np(state["limit"]),
        remaining=p64.from_np(state["remaining"]),
        remaining_f=tf.from_np(state["remaining_f"]),
        duration=p64.from_np(state["duration"]),
        created_at=p64.from_np(state["created_at"]),
        updated_at=p64.from_np(state["updated_at"]),
        burst=p64.from_np(state["burst"]),
        status=jnp.asarray(state["status"], jnp.int32),
        expire_at=p64.from_np(state["expire_at"]),
        in_use=jnp.asarray(state["in_use"]),
        # Zoo columns (PR 16): token/leaky lanes never read them.
        tat=p64.from_np(np.zeros_like(state["expire_at"])),
        prev_count=p64.from_np(np.zeros_like(state["expire_at"])),
    )
    r = PReq(
        slot=jnp.asarray(req["slot"], jnp.int32),
        known=jnp.asarray(req["known"]),
        hits=p64.from_np(req["hits"]),
        limit=p64.from_np(req["limit"]),
        duration=p64.from_np(req["duration"]),
        algorithm=jnp.asarray(req["algorithm"], jnp.int32),
        behavior=jnp.asarray(req["behavior"], jnp.int32),
        created_at=p64.from_np(req["created_at"]),
        burst=p64.from_np(req["burst"]),
        greg_exp=p64.from_np(req["greg_exp"]),
        greg_dur=p64.from_np(req["greg_dur"]),
        valid=jnp.asarray(req["valid"]),
    )
    return s, r


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_differential_vs_x64_oracle(seed):
    rng = np.random.default_rng(seed)
    state, req = gen_batch(rng, 2048)

    os_, or_ = to_oracle(state, req)
    want_state, want_resp = jax.jit(bucket_transition)(
        jnp.int64(NOW), os_, or_)

    ps, pr = to_parts(state, req)
    got_state, got_resp = jax.jit(transition32)(
        p64.from_np(np.int64(NOW)), ps, pr)

    # responses: exact
    np.testing.assert_array_equal(
        np.asarray(got_resp.status), np.asarray(want_resp.status))
    np.testing.assert_array_equal(
        p64.to_np(got_resp.remaining), np.asarray(want_resp.remaining))
    np.testing.assert_array_equal(
        p64.to_np(got_resp.reset_time), np.asarray(want_resp.reset_time))
    np.testing.assert_array_equal(
        np.asarray(got_resp.over_limit), np.asarray(want_resp.over_limit))

    # new state: integer fields exact
    for f in ("limit", "remaining", "duration", "created_at",
              "updated_at", "burst", "expire_at"):
        np.testing.assert_array_equal(
            p64.to_np(getattr(got_state, f)),
            np.asarray(getattr(want_state, f)), err_msg=f)
    for f in ("algorithm", "status", "in_use"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got_state, f)),
            np.asarray(getattr(want_state, f)), err_msg=f)
    # float remaining: IEEE binary64, every operation rounded as the
    # CPU's float64 rounds it
    np.testing.assert_array_equal(
        tf.to_np(got_state.remaining_f),
        np.asarray(want_state.remaining_f))


def test_rough_rate_consistency():
    """Non-representable rates (duration/limit with repeating binary
    expansion): the parts path keeps its invariants: response remaining
    == floor(stored remaining_f) for under-limit leaky decisions, and
    status consistent with remaining."""
    rng = np.random.default_rng(99)
    n = 1024
    state, req = gen_batch(rng, n)
    req["duration"] = rng.choice([1000, 900, 1234], n)
    req["limit"] = rng.choice([3, 7, 11, 13], n)
    req["algorithm"] = np.ones(n, np.int64)  # leaky
    state["algorithm"] = np.ones(n, np.int64)

    ps, pr = to_parts(state, req)
    got_state, got_resp = jax.jit(transition32)(
        p64.from_np(np.int64(NOW)), ps, pr)

    rem = p64.to_np(got_resp.remaining)
    stored = tf.to_np(got_state.remaining_f)
    status = np.asarray(got_resp.status)
    over = np.asarray(got_resp.over_limit)
    behavior = req["behavior"]
    drain = (behavior & int(Behavior.DRAIN_OVER_LIMIT)) != 0
    hits = req["hits"]

    # every decision: stored float remaining is finite and >= 0 unless
    # negative hits pushed it up; response remaining never negative for
    # positive-hit traffic
    assert np.isfinite(stored).all()
    pos = hits > 0
    assert (rem[pos] >= 0).all()
    # over_limit implies OVER status
    np.testing.assert_array_equal(status[over] != 0, over[over])
    # DRAIN over-limit zeroes response remaining
    assert (rem[over & drain & pos] == 0).all()


def test_preq_from_compact_roundtrip():
    from gubernator_tpu.ops.engine import (
        REQ32_ROWS, pack_request_matrix32)
    from gubernator_tpu.ops.transition32 import preq_from_compact
    from gubernator_tpu.types import RateLimitRequest

    reqs = [
        RateLimitRequest(
            name="t", unique_key=f"k{i}", hits=(-1) ** i * (i + 1) * 10**i,
            limit=(1 << 33) + i, duration=60_000 + i,
            algorithm=Algorithm(i % 2), behavior=Behavior(0),
            burst=i * 7, created_at=NOW + i)
        for i in range(8)
    ]
    m32 = np.zeros((REQ32_ROWS, 8), np.int32)
    pack_request_matrix32(
        m32, np.arange(8), reqs, np.arange(8), np.ones(8, bool), NOW)
    pr = preq_from_compact(jnp.asarray(m32))
    np.testing.assert_array_equal(
        p64.to_np(pr.hits), [r.hits for r in reqs])
    np.testing.assert_array_equal(
        p64.to_np(pr.limit), [r.limit for r in reqs])
    np.testing.assert_array_equal(
        p64.to_np(pr.created_at), [r.created_at for r in reqs])
    np.testing.assert_array_equal(np.asarray(pr.slot), np.arange(8))


def test_matrix_adapters_roundtrip():
    from gubernator_tpu.ops.rowtable import ROW_USED, logical_to_matrix
    from gubernator_tpu.ops.transition32 import (
        pstate_from_matrix, pstate_to_matrix)

    rng = np.random.default_rng(5)
    state, _ = gen_batch(rng, 256)
    os_, _ = to_oracle(state, gen_batch(rng, 256)[1])
    mat = jax.jit(logical_to_matrix)(os_)

    ps = pstate_from_matrix(mat)
    np.testing.assert_array_equal(p64.to_np(ps.limit), state["limit"])
    np.testing.assert_array_equal(
        tf.to_np(ps.remaining_f), state["remaining_f"])
    back = jax.jit(pstate_to_matrix)(ps)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(mat))


# ----------------------------------------------------------------------
# Against the plain reference (algos/reference.py)
# ----------------------------------------------------------------------
def _join(lo, hi):
    return (hi.astype(np.int64) << 32) | (lo.astype(np.int64) & 0xFFFFFFFF)


@pytest.mark.parametrize("seed", [2])
def test_leaky_probe_through_the_served_tick(seed):
    """4,000 leaky keys, limit x duration from the population's sets, 240
    touches about a second apart (each key on its own clock), hits 1,
    through ``tick32.jitted_tick32`` (what ``TickEngine`` dispatches for
    a unique window, on the CPU's column layout) against
    ``reference.leaky_bucket``.  Where the float64 sum ``remaining +
    leak`` rounds to a whole number the ~70-bit triple read one off for
    a few touches: 16 of these 960,000 answers (keys 984, 2037, 3165)
    before the leaky path became IEEE binary64; 0 now."""
    from gubernator_tpu.algos import reference
    from gubernator_tpu.ops import engine as E
    from gubernator_tpu.ops.tick32 import jitted_tick32

    n, touches, width = 4000, 240, 4096
    rng = np.random.default_rng(seed)
    limit = rng.choice([5, 20, 100, 1000, 2**33], n)
    duration = rng.choice([3_600_000, 7_200_000, 86_400_000], n)
    tick = jitted_tick32(width, "columns")
    zeros, _, _ = E._layout_ops("columns")
    state = jax.tree.map(jnp.asarray, zeros(width))
    R, lanes = E.REQ32_INDEX, slice(0, n)
    now = NOW
    created = np.full(n, now)
    ref = [None] * n
    bad = []
    for t in range(touches):
        now += 1000
        created = created + 1000 + rng.integers(-40, 41, n)
        m = np.zeros((E.REQ32_ROWS, width), np.int32)
        m[R["slot"]] = width
        m[R["slot"], lanes] = np.arange(n)
        m[R["known"], lanes] = t > 0
        m[R["algorithm"], lanes] = int(Algorithm.LEAKY_BUCKET)
        m[R["valid"], lanes] = 1
        E.pack_wide_rows(m, "hits", np.ones(n, np.int64), lanes)
        E.pack_wide_rows(m, "limit", limit, lanes)
        E.pack_wide_rows(m, "duration", duration, lanes)
        E.pack_wide_rows(m, "created_at", created, lanes)
        state, resp = tick(state, jnp.asarray(slab_of(m, now)))
        resp = np.asarray(resp)
        got = zip(resp[0, lanes].tolist(),
                  _join(resp[2, lanes], resp[3, lanes]).tolist(),
                  _join(resp[4, lanes], resp[5, lanes]).tolist())
        for i, g in enumerate(got):
            ref[i], r = reference.leaky_bucket(ref[i], {
                "hits": 1, "limit": int(limit[i]),
                "duration": int(duration[i]), "algorithm": 1, "burst": 0,
                "created_at": int(created[i])}, now)
            if g != (r["status"], r["remaining"], r["reset_time"]):
                bad.append((t, i, g, r))
    assert not bad, (len(bad), bad[:4])


@pytest.mark.parametrize("seed", [7, 8])
def test_fold_equals_sequential_float64_subtractions(seed):
    """A uniform group of ``count`` duplicates on a leaky row with a
    fractional ``remaining_f``: the closed-form fold stores what
    ``count - 1`` follower requests, one after another through
    ``reference.leaky_bucket``, leave: the float64, bit for bit."""
    from gubernator_tpu.algos import reference
    from gubernator_tpu.ops.transition32 import merged_fold32

    rng = np.random.default_rng(seed)
    n = 1024
    limit = rng.choice([20, 100, 1000, 2**33], n)
    duration = np.full(n, 3_600_000)
    hits = rng.choice([1, 2, 3, 7], n)
    count = rng.integers(1, 65, n)
    drain = rng.random(n) < 0.3
    # the row as the head's transition left it
    whole = np.where(rng.random(n) < 0.5, rng.integers(0, 40, n),
                     rng.integers(0, limit + 1))
    rem_f = np.minimum(whole + rng.random(n) * (rng.random(n) < 0.8),
                       limit.astype(np.float64))
    state, req = gen_batch(rng, n)
    state.update(
        algorithm=np.ones(n, np.int64), limit=limit, duration=duration,
        remaining_f=rem_f, burst=limit, created_at=np.full(n, NOW),
        updated_at=np.full(n, NOW), expire_at=NOW + duration,
        in_use=np.ones(n, bool))
    req.update(
        hits=hits, limit=limit, duration=duration,
        algorithm=np.ones(n, np.int64), burst=np.zeros(n, np.int64),
        behavior=np.where(drain, int(Behavior.DRAIN_OVER_LIMIT), 0),
        created_at=np.full(n, NOW), known=np.ones(n, bool),
        greg_exp=np.zeros(n, np.int64), greg_dur=np.zeros(n, np.int64))
    ps, pr = to_parts(state, req)
    folded, _ = jax.jit(merged_fold32)(
        p64.from_np(np.int64(NOW)), ps, pr, jnp.asarray(count, jnp.int32))
    got = tf.to_np(folded.remaining_f)
    for i in range(n):
        s = {k: (v[i].item() if hasattr(v[i], "item") else v[i])
             for k, v in state.items()}
        r = {k: int(req[k][i]) for k in (
            "hits", "limit", "duration", "algorithm", "behavior", "burst",
            "created_at")}
        for _ in range(int(count[i]) - 1):
            s, _resp = reference.leaky_bucket(s, r, NOW)
        assert got[i].tobytes() == np.float64(s["remaining_f"]).tobytes(), (
            i, rem_f[i], hits[i], count[i], got[i], s["remaining_f"])


@pytest.fixture(scope="module")
def served_engine():
    # a geometry other suite files compile too (ROADMAP's standing
    # constraint)
    from gubernator_tpu.ops import engine as E

    return E.TickEngine(capacity=512, max_batch=64)


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_mixed_histories_against_the_plain_reference(served_engine, seed):
    """Seeded histories of a mixed population (half leaky, bursts 0 / 10
    / 50, duplicates in a window, RESET_REMAINING and DRAIN_OVER_LIMIT,
    expiries) through the served tick (``TickEngine.process``: the
    unique, grouped and sequential programs, as the window's shape
    decides) against ``reference.token_bucket`` / ``leaky_bucket``:
    every answer equal."""
    from gubernator_tpu.algos import reference
    from gubernator_tpu.types import RateLimitRequest

    rng = np.random.default_rng(seed)
    eng = served_engine
    keys = 40
    algo = (np.arange(keys) % 2).tolist()
    limit = rng.choice([5, 20, 100, 1000, 2**33], keys).tolist()
    duration = rng.choice([3_000, 3_600_000, 7_200_000], keys).tolist()
    burst = rng.choice([0, 10, 50], keys).tolist()
    ref = {}
    now = NOW + seed * 100_000_000
    for _ in range(60):
        now += int(rng.integers(1, 2_500))
        ids = rng.choice(keys, int(rng.integers(1, 60)),
                         p=(w := 1 / np.arange(1, keys + 1)) / w.sum())
        reqs = []
        for k in ids.tolist():
            pick = rng.random()
            behavior = (Behavior.RESET_REMAINING if pick < 0.03 else
                        Behavior.DRAIN_OVER_LIMIT if pick < 0.3 else
                        Behavior(0))
            reqs.append(RateLimitRequest(
                name=f"mixed{seed}", unique_key=f"k{k}",
                hits=int(rng.choice([0, 1, 1, 1, 2, 5])), limit=limit[k],
                duration=duration[k], algorithm=Algorithm(algo[k]),
                behavior=behavior, burst=burst[k] if algo[k] else 0))
        for q, a in zip(reqs, eng.process(reqs, now=now)):
            ref[q.unique_key], r = reference.transition(
                ref.get(q.unique_key), {
                    "hits": q.hits, "limit": q.limit,
                    "duration": q.duration, "algorithm": int(q.algorithm),
                    "behavior": int(q.behavior), "burst": q.burst,
                    "created_at": now}, now)
            assert not a.error
            assert (int(a.status), a.limit, a.remaining, a.reset_time) == (
                r["status"], r["limit"], r["remaining"], r["reset_time"]
            ), (q, a, r)
