"""Seeded randomized differential fuzz: row vs column layouts.

The hand-written parity suite (test_rowtable.py) covers chosen
scenarios; this drives both layouts through the same randomized mixed
workload — algorithms, behaviors, duplicates, queries, negative hits,
limit/duration churn, time advancement, TTL expiry and eviction
pressure — and requires bit-identical responses and exports at every
step.  Deterministic seeds keep failures reproducible.
"""

import numpy as np
import pytest

from gubernator_tpu.ops.engine import TickEngine
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest

BEHAVIOR_POOL = [
    Behavior.BATCHING,
    Behavior.NO_BATCHING,
    Behavior.RESET_REMAINING,
    Behavior.DRAIN_OVER_LIMIT,
]


def random_request(rng, keyspace):
    key = f"k{rng.integers(0, keyspace)}"
    # All five algorithms, zoo included (docs/algorithms.md) — keys are
    # shared across draws, so algorithm-switch restarts fuzz too.
    algorithm = Algorithm(int(rng.integers(0, 5)))
    behavior = Behavior(0)
    if rng.random() < 0.25:
        behavior = BEHAVIOR_POOL[rng.integers(0, len(BEHAVIOR_POOL))]
    hits = int(rng.choice([0, 1, 1, 1, 2, 5, -1, 100]))
    # Limit/duration drawn from a small pool so a key sees parameter
    # changes over its lifetime (the limit-delta / duration-change and
    # algorithm-switch reference flows).
    limit = int(rng.choice([3, 10, 100]))
    duration = int(rng.choice([1_000, 5_000, 60_000]))
    burst = int(rng.choice([0, limit, limit * 2]))
    return RateLimitRequest(
        name="fuzz", unique_key=key, hits=hits, limit=limit,
        duration=duration, algorithm=algorithm, behavior=behavior,
        burst=burst,
    )


def snap(resp):
    return [(r.status, r.limit, r.remaining, r.reset_time, r.error)
            for r in resp]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fuzz_layout_parity(seed):
    rng = np.random.default_rng(seed)
    col = TickEngine(capacity=96, max_batch=64, table_layout="columns")
    row = TickEngine(capacity=96, max_batch=64, table_layout="row")
    now = 1_700_000_000_000
    for step in range(40):
        # keyspace > capacity so eviction/reclaim runs under pressure
        batch = [random_request(rng, keyspace=160)
                 for _ in range(int(rng.integers(1, 48)))]
        a = col.process(batch, now=now)
        b = row.process(batch, now=now)
        assert snap(a) == snap(b), f"seed {seed} step {step}"
        now += int(rng.choice([0, 50, 400, 2_000, 61_000]))
    assert col.cache_size() == row.cache_size()
    ea = sorted(col.export_items(), key=lambda d: d["key"])
    eb = sorted(row.export_items(), key=lambda d: d["key"])
    assert ea == eb
