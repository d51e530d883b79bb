"""The generator: YCSB zipfian's head mass, lanes own disjoint keys, every
seed offers the same arrivals and sizes, keys are made as the wire wants
them, the byte count."""

import numpy as np

from benchmarks.harness import costs, population, traffic


def test_zipfian_head_mass():
    n = 8_000_000
    z = traffic.Zipfian(n, 0.99)
    r = z.ranks(np.random.default_rng(0).random(2_000_000))
    assert r.min() == 0 and r.max() < n
    # P(rank 0) = 1/zeta(n, 0.99): about one item in eighteen
    assert abs((r == 0).mean() - 1.0 / z.zetan) < 0.002
    assert 17.0 < z.zetan < 18.5
    # YCSB's generator gives rank 1 the mass 0.5**theta / zetan
    assert abs((r == 1).mean() - 0.5 ** 0.99 / z.zetan) < 0.002
    # the ten hottest keys carry about a sixth of all items
    assert 0.13 < (r < 10).mean() < 0.20


def test_lanes_own_disjoint_keys_and_calls_keep_their_sizes():
    mix = {"loop": "closed", "lanes": 16, "items": [1000, 1000],
           "keys": {"dist": "zipfian", "theta": 0.99, "scramble": 7919}}
    seen = {}
    for mine in ([0, 4, 8, 12], [1, 5, 9, 13]):
        for ln, (warm, plan) in traffic.plans(mix, 32000, 7, 2.0, mine).items():
            assert warm is plan and len(plan) == traffic.RING_CALLS   # one ring
            assert np.array_equal(plan.call_ids(traffic.RING_CALLS + 3), plan.call_ids(3))
            assert (plan.ids % 16 == ln).all()
            assert len(plan.call_ids(3)) == 1000
            seen[ln] = set(plan.ids.tolist())
    lanes = sorted(seen)
    for a in lanes:
        for b in lanes:
            assert a == b or not (seen[a] & seen[b])


def test_open_loop_plans_a_warm_up_of_its_own():
    mix = {"loop": "open", "lanes": 8, "items": [1, 10], "rate_calls_per_s": 100,
           "keys": {"dist": "uniform"}}
    got = traffic.plans(mix, 32000, 7, 4.0, [0, 4])
    for ln, (warm, main) in got.items():
        assert warm is not main
        assert (warm.ids % 8 == ln).all() and (main.ids % 8 == ln).all()
        assert main.due.max() < 4.0
        assert 0.9 < warm.due.max() / traffic.WARMUP_MAX_SECONDS <= 1.05
    assert sum(len(m) for _, m in traffic.plans(mix, 32000, 7, 4.0, list(range(8))).values()) == 400


def test_every_seed_offers_the_same_arrivals_and_sizes():
    mix = {"loop": "open", "lanes": 256, "items": [1, 10], "rate_calls_per_s": 500,
           "keys": {"dist": "uniform"}}
    a = traffic.open_schedule(mix, 1, 4.0)
    b = traffic.open_schedule(mix, 3_000_000_019, 4.0)
    assert len(a[0]) == 2000 and a[0][-1] < 4.0 and (np.diff(a[0]) >= 0).all()
    assert not np.array_equal(a[0], b[0])
    assert np.allclose(np.sort(np.diff(a[0], prepend=0)), np.sort(np.diff(b[0], prepend=0)))
    assert np.array_equal(np.sort(a[1]), np.sort(b[1]))
    assert np.array_equal(np.bincount(a[2], minlength=256), np.bincount(b[2], minlength=256))
    assert a[1].min() == 1 and a[1].max() == 10
    # and the same seed the same traffic
    c = traffic.open_schedule(mix, 1, 4.0)
    assert all(np.array_equal(x, y) for x, y in zip(a, c))


def test_key_blob_is_the_wire_key():
    blob, off = population.key_blob(np.asarray([0, 42, 7_999_999]))
    assert blob.tobytes() == b"bench_k00000000bench_k00000042bench_k07999999"
    assert off.tolist() == [0, 15, 30, 45]


def test_population_is_a_function_of_seed_and_id():
    spec = {"keys": 1000, "leaky_share": 0.5, "limit": [5, 20], "duration_ms": [1, 2],
            "leaky_burst": [0, 10]}
    p = population.Population(spec, 3_000_000_019)
    ids = np.arange(1000)
    a = p.params(ids)
    b = p.params(ids[::-1])
    assert all(np.array_equal(x, y[::-1]) for x, y in zip(a, b))
    assert 0.4 < a[0].mean() < 0.6
    st = p.state(ids, 10**12)
    cap = np.where(a[0] == 1, np.where(a[3] == 0, a[1], a[3]), a[1])
    assert (st["remaining"] >= 0).all() and (st["remaining"] <= cap).all()
    assert (st["remaining_f"] <= cap).all()
    other = population.Population(spec, 1).params(ids)
    assert not np.array_equal(a[1], other[1])


def test_decision_bytes():
    # state read and written once per decision: 2 x 24 words x 4 B
    assert costs.decision_bytes(1) == 192
    assert costs.decision_bytes(1_000_000) == 192_000_000
    assert abs(costs.least_seconds(819_000_000, "TPU v5 lite") - 0.192) < 1e-12
    try:
        costs.peaks("TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind has to be an error")
