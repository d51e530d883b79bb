"""The one general traffic generator: *lanes*.

A key belongs to one lane (``key_id % lanes``), a lane has one call in
flight, so every key's history is one ordered list and a replay is
determinate.  A traffic mix is a data file of parameters (see
``benchmarks/traffic/*.json``); this module turns (mix, population size,
seed, seconds) into each lane's calls, before the window:

  loop       "closed": a lane sends its next call when the last returns
             "open":   calls are due on a Poisson schedule at ``rate_calls_per_s``
  lanes      how many lanes (and so the most calls in flight)
  items      [lo, hi] items a call, uniform
  hits       hits of every item
  keys       {"dist": "zipfian", "theta": 0.99, "scramble": 7919}  (YCSB)
             {"dist": "uniform"}
  rate_calls_per_s   open loop: the rate offered, fixed in the file
  check      {"sample_mod": m, "hot_ranks": r}: the output check replays one
             key in m (by a hash of seed and id) and the r hottest

What every mix shares is fixed here, not in the files: a closed loop's
ring of RING_CALLS calls a lane (a lane that gets round it starts over)
and its ramp (one more lane joins every RAMP_SECONDS_PER_LANE seconds of
the warm-up's start, so that windows of every width up to the full one form,
and their programs are traced, before the window); an open loop's gaps
and sizes, which are drawn from SHAPE_SEED and only *ordered* by --seed,
so that every seed offers the same set of arrivals and sizes (the
builder's contract: a seed may not change the work); the generators'
clock lead over the server's, CREATED_AT_LEAD_MS (``created_at`` never
behind the server's clock, which would select the sequential program).

Imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

from . import population

GENERATORS = 4                  # child processes the lanes are dealt to
CREATED_AT_LEAD_MS = 3_600_000
RING_CALLS = 1024
RAMP_SECONDS_PER_LANE = 1.0
SHAPE_SEED = 20260930
WARMUP_MAX_SECONDS = 240.0      # the window opens then, steady or not


def zeta(n: int, theta: float) -> float:
    return float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -theta))


class Zipfian:
    """YCSB's ZipfianGenerator (Gray et al., "Quickly generating
    billion-record synthetic databases"), vectorised: ranks 0..n-1,
    rank 0 the hottest."""

    def __init__(self, n: int, theta: float):
        self.n, self.theta = n, theta
        self.zetan = zeta(n, theta)
        self.zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - self.zeta2 / self.zetan)

    def ranks(self, u: np.ndarray) -> np.ndarray:
        uz = u * self.zetan
        tail = (self.n * (self.eta * u - self.eta + 1.0) ** self.alpha).astype(np.int64)
        r = np.where(uz < 1.0, 0, np.where(uz < self.zeta2, 1, tail))
        return np.minimum(r, self.n - 1)


def key_ids(mix: dict, n_keys: int, rng, count: int) -> np.ndarray:
    """``count`` key ids drawn from the mix's distribution over all keys."""
    k = mix["keys"]
    if k["dist"] == "uniform":
        return rng.integers(0, n_keys, count)
    if k["dist"] == "zipfian":
        z = Zipfian(n_keys, float(k["theta"]))
        return (z.ranks(rng.random(count)) * int(k["scramble"])) % n_keys
    raise ValueError(f"unknown key distribution {k['dist']!r}")


def hot_ids(mix: dict, n_keys: int, ranks: int) -> np.ndarray:
    """The ids of the ``ranks`` hottest keys (none where keys are uniform)."""
    k = mix["keys"]
    if k["dist"] != "zipfian" or ranks <= 0:
        return np.zeros(0, np.int64)
    return (np.arange(ranks, dtype=np.int64) * int(k["scramble"])) % n_keys


def lane_keys(mix: dict, n_keys: int, seed: int, lanes_mine: list,
              need: dict) -> dict:
    """{lane: ids (need[lane],)}: each lane's stream of key ids, drawn
    from the mix's distribution conditioned on ``id % lanes == lane``
    (draw over all keys, keep the lane's own)."""
    lanes = int(mix["lanes"])
    out = {ln: [] for ln in lanes_mine}
    have = {ln: 0 for ln in lanes_mine}
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11, min(lanes_mine)])
    todo = max(need.values()) if need else 0
    while todo > 0:
        chunk = int(min(max(todo * lanes * 1.1, 1 << 16), 1 << 24))
        ids = key_ids(mix, n_keys, rng, chunk)
        lane = ids % lanes
        todo = 0
        for ln in lanes_mine:
            if have[ln] < need[ln]:
                got = ids[lane == ln][: need[ln] - have[ln]]
                out[ln].append(got)
                have[ln] += len(got)
                todo = max(todo, need[ln] - have[ln])
    return {ln: (np.concatenate(v) if v else np.zeros(0, np.int64))
            for ln, v in out.items()}


def open_schedule(mix: dict, seed: int, seconds: float) -> tuple:
    """(due (c,) seconds from the window's start, size (c,), lane (c,)):
    Poisson arrivals at the mix's rate.  Gaps and sizes come from
    SHAPE_SEED and are permuted by ``seed``; lanes are dealt evenly
    and permuted by ``seed``."""
    rate = float(mix["rate_calls_per_s"])
    lanes = int(mix["lanes"])
    lo, hi = mix["items"]
    c = int(rate * seconds)
    shape = np.random.default_rng(SHAPE_SEED)
    gaps = shape.exponential(1.0 / rate, c)
    gaps *= (seconds / gaps.sum()) * (c / (c + 1.0))   # the last call is due inside the window
    sizes = shape.integers(lo, hi + 1, c)
    order = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 12])
    due = np.cumsum(order.permutation(gaps))
    sizes = order.permutation(sizes)
    lane = order.permutation(np.arange(c) % lanes)
    return due, sizes, lane


class LanePlan:
    """Calls of one lane, drawn before they are sent: ``bounds`` (c+1,)
    into ``ids``; ``due`` (c,) seconds from the phase's start in an open
    loop, None in a closed one (where the plan is a ring: call k is
    ``k % len``)."""

    def __init__(self, lane, ids, bounds, due):
        self.lane, self.ids, self.bounds, self.due = lane, ids, bounds, due

    def __len__(self):
        return len(self.bounds) - 1

    def call_ids(self, k: int) -> np.ndarray:
        k %= len(self)
        return self.ids[self.bounds[k]: self.bounds[k + 1]]


def _lane_plans(mix, n_keys, seed, lanes_mine, sizes, dues) -> dict:
    need = {ln: int(sizes[ln].sum()) for ln in lanes_mine}
    ids = lane_keys(mix, n_keys, seed, lanes_mine, need)
    return {
        ln: LanePlan(ln, ids[ln],
                     np.concatenate([[0], np.cumsum(sizes[ln])]).astype(np.int64),
                     dues[ln])
        for ln in lanes_mine
    }


def plans(mix: dict, n_keys: int, seed: int, seconds: float,
          lanes_mine: list) -> dict:
    """{lane: (warm-up plan, window plan)} for the lanes one generator
    child owns.  Closed loop: one ring serves both (the warm-up starts
    it, the window goes on in it).  Open loop: the window's plan is the
    mix's schedule; the warm-up's is the same rate and sizes from a
    stream of its own, long enough for WARMUP_MAX_SECONDS."""
    lo, hi = mix["items"]
    if mix["loop"] == "closed":
        calls = RING_CALLS
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 13])
        sizes = {ln: (np.full(calls, lo, np.int64) if lo == hi
                      else rng.integers(lo, hi + 1, calls)) for ln in lanes_mine}
        ring = _lane_plans(mix, n_keys, seed, lanes_mine, sizes,
                           {ln: None for ln in lanes_mine})
        return {ln: (ring[ln], ring[ln]) for ln in lanes_mine}
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    due, size, lane = open_schedule(mix, seed, seconds)
    main = _lane_plans(mix, n_keys, seed, lanes_mine,
                       {ln: size[lane == ln] for ln in lanes_mine},
                       {ln: due[lane == ln] for ln in lanes_mine})
    rate, lanes = float(mix["rate_calls_per_s"]), int(mix["lanes"])
    c = int(rate * WARMUP_MAX_SECONDS)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 14])
    wdue = np.cumsum(rng.exponential(1.0 / rate, c))
    wsize = rng.integers(lo, hi + 1, c)
    wlane = rng.integers(0, lanes, c)
    warm = _lane_plans(mix, n_keys, seed + 1, lanes_mine,
                       {ln: wsize[wlane == ln] for ln in lanes_mine},
                       {ln: wdue[wlane == ln] for ln in lanes_mine})
    return {ln: (warm[ln], main[ln]) for ln in lanes_mine}


def sampled(ids: np.ndarray, seed: int, check: dict, hot: np.ndarray) -> np.ndarray:
    """Which ids the output check replays: one key in ``sample_mod`` by
    a hash of (seed, id), and the mix's hottest keys."""
    m = population.field(ids, seed, 21) % np.uint64(int(check["sample_mod"])) == 0
    return m | np.isin(ids, hot) if len(hot) else m
