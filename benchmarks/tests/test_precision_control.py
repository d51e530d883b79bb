"""The comparison that decides ``correct`` refuses the next precision
down.  ``base3-mixed-10m`` states that a leaky bucket's remaining is
upstream's float64, every operation rounded as IEEE binary64.  Here the
plain reference with its leaky arithmetic rounded to float32 after every
operation stands in the program's place over a seeded leaky history of
that configuration's population, and the comparison (every answer equal,
limit 0) has to find it out.  On the CPU; the wrapper lives in this test
alone (as ``--control float32`` it would edit run.py and
harness/reference.py: a ``benchmark`` issue's to add)."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness.population import LEAKY, Population
from benchmarks.harness.reference import (
    DRAIN_OVER_LIMIT, OVER, RESET_REMAINING, UNDER, Reference)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(os.path.dirname(HERE), "configs", "base3-mixed-10m.json")
F32 = np.float32


class Float32Leaky(Reference):
    """``Reference._leaky`` with every floating operation rounded to
    float32 (the nearest precision below the one stated); token buckets
    and new leaky buckets (whole numbers) are the reference's own."""

    def _leaky(self, b, hits, limit, duration, burst, behavior, now):
        if burst == 0:
            burst = limit
        if b is None or b["algorithm"] != LEAKY:
            return self._leaky_new(hits, limit, duration, burst, now)
        rem = F32(b["remaining_f"])
        if behavior & RESET_REMAINING:
            rem = F32(burst)
        if b["burst"] != burst:
            if burst > int(rem):
                rem = F32(burst)
            b["burst"] = burst
        b["limit"], b["duration"] = limit, duration
        rate = F32(duration) / F32(limit)
        if hits != 0:
            b["expire_at"] = now + duration
        leak = F32(now - b["updated_at"]) / rate
        if int(leak) > 0:
            rem = rem + leak
            b["updated_at"] = now
        if int(rem) > burst:
            rem = F32(burst)
        whole, irate = int(rem), int(rate)
        reset = now + (limit - whole) * irate
        status, answer = UNDER, whole
        if whole == 0 and hits > 0:
            status = OVER
        elif whole == hits:
            rem, answer, reset = F32(0), 0, now + limit * irate
        elif hits > whole:
            status = OVER
            if behavior & DRAIN_OVER_LIMIT:
                rem, answer = F32(0), 0
        elif hits != 0:
            rem = rem - F32(hits)
            answer = int(rem)
            reset = now + (limit - answer) * irate
        b["remaining_f"] = float(rem)
        return b, (status, limit, answer, reset)


def leaky_history(seed, keys=400, touches=120):
    """(key id, request) in time order: leaky keys of the configuration's
    population (at its rehearsal size), each touched with hits 1 (the
    cell's traffic) at seeded gaps of up to a few seconds."""
    with open(CONFIG) as f:
        config = json.load(f)
    spec = dict(config["population"], keys=config["rehearse"]["keys"])
    pop = Population(spec, seed)
    ids = np.arange(pop.n)
    alg, limit, duration, burst = pop.params(ids)
    ids = ids[alg == LEAKY][:keys]
    t0 = 1_800_000_000_000
    state = pop.state(ids, t0)
    rng = np.random.default_rng(seed)
    start = {int(k): {f: (float if f == "remaining_f" else int)(v[j])
                      for f, v in state.items()}
             for j, k in enumerate(ids)}
    when = t0 + np.cumsum(rng.integers(1, 4000, (len(ids), touches)), axis=1)
    events = []
    for j, k in enumerate(ids.tolist()):
        req = (1, int(limit[k]), int(duration[k]), int(burst[k]), LEAKY, 0)
        events += [(int(t), k, req + (int(t),)) for t in when[j]]
    events.sort(key=lambda e: e[:2])
    return start, events


def answers(ref, start, events):
    buckets = {k: dict(b) for k, b in start.items()}
    out = []
    for _, k, req in events:
        buckets[k], ans = ref.apply(buckets[k], req)
        out.append(ans)
    return out


@pytest.mark.parametrize("seed", [1, 2147483777, 3000000019])
def test_float32_leaky_arithmetic_comes_out_not_correct(seed):
    start, events = leaky_history(seed)
    sound = answers(Reference(), start, events)
    assert sound == answers(Reference(), start, events)     # determinate
    lower = answers(Float32Leaky(), start, events)
    mismatched = sum(a != b for a, b in zip(sound, lower))
    print(f"float32 control, seed {seed}: {mismatched} of {len(sound)} answers differ")
    assert mismatched > 0
