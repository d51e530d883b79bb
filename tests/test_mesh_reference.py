"""The sharded engine held to the benchmark's plain reference (upstream
``algorithms.go`` in Python int and float; it imports nothing of the
program), on four of the eight virtual devices: a seeded mixed
population (``base3-mixed-10m-mesh4``'s, small) filled through
``load_columns``, then duplicate-bearing zipf windows through
``submit_columns``.  Every answer equal, limit 0, which is what the
cell's ``correct`` asks on the chip; the one-chip ``TickEngine`` gives
the same answers; the reference one precision below (leaky arithmetic
rounded to float32) does not.  And the fill itself: ``load_columns``
against ``load_items``.
"""

import jax
import numpy as np
import pytest

from benchmarks.harness import population, traffic
from benchmarks.harness.reference import Reference
from benchmarks.tests.test_precision_control import Float32Leaky
from gubernator_tpu.ops.engine import TickEngine, items_from_snapshot
from gubernator_tpu.ops.raggedtick import choose_tile
from gubernator_tpu.ops.reqcols import ReqColumns
from gubernator_tpu.parallel.mesh_engine import MeshTickEngine, make_mesh
from gubernator_tpu.utils import flightrec

SHARDS = 4
KEYS = 3000
B = 256
SPEC = {"keys": KEYS, "leaky_share": 0.5, "leaky_burst": [0, 10, 50],
        "limit": [5, 20, 100, 1000, 1 << 33],
        "duration_ms": [3_600_000, 7_200_000, 86_400_000]}
MIX = {"keys": {"dist": "zipfian", "theta": 0.99, "scramble": 7919}}
T0 = 1_800_000_000_000
HOT = int(traffic.hot_ids(MIX, KEYS, 1)[0])
# the hottest key by seed: a leaky bucket of limit 2^33 (never over), a
# leaky one of burst 50 (over its limit inside the wide group), a token
# bucket of limit 2^33 (test_the_hot_key_is_of_both_kinds holds that)
SEEDS = (11, 2147483777, 2147489003)


# One engine of each kind for the module (a MeshTickEngine is seconds of
# compile a program); a history's keys carry its seed, so the histories
# do not meet in the table.
@pytest.fixture(scope="module")
def mesh():
    return MeshTickEngine(mesh=make_mesh(jax.devices()[:SHARDS]),
                          local_capacity=4096, max_batch=B)


@pytest.fixture(scope="module")
def one_chip():
    return TickEngine(capacity=4 * 4096, max_batch=B)


def key_blob(ids, tag):
    keys = [b"bench%d_k%08d" % (tag, i) for i in np.asarray(ids).tolist()]
    offsets = np.zeros(len(keys) + 1, np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    return b"".join(keys), offsets


def snapshot(pop, ids, tag):
    snap = pop.state(ids, T0)
    snap["key_blob"], snap["key_offsets"] = key_blob(ids, tag)
    return snap


def history(seed, windows=7):
    """(population, [(clock ms, key ids)]): zipf 0.99 windows of varied
    width with the clock stepping between them; the fourth holds the
    hottest key 150 times, a group wider than the extent walk's tile."""
    pop = population.Population(SPEC, seed)
    rng = np.random.default_rng(seed)
    out, t = [], T0
    for w in range(windows):
        t += int(rng.choice([1, 700, 40_000]))
        ids = traffic.key_ids(MIX, KEYS, rng, B - 23 * (w % 3))
        if w == 3:
            ids[rng.permutation(len(ids))[:150]] = HOT
        out.append((t, ids))
    return pop, out


def columns(pop, ids, t, tag):
    alg, limit, duration, burst = pop.params(ids)
    blob, offsets = key_blob(ids, tag)
    n = len(ids)
    return ReqColumns(
        key_blob=blob, key_offsets=offsets, hits=np.ones(n, np.int64),
        limit=limit, duration=duration, algorithm=alg,
        behavior=np.zeros(n, np.int64), created_at=np.full(n, t, np.int64),
        burst=burst)


def served(eng, pop, wins, tag):
    """Fill ``eng`` with the whole population, then serve the windows:
    [(4, n) status, limit, remaining, reset_time]."""
    eng.load_columns(snapshot(pop, np.arange(KEYS), tag), now=T0)
    out = []
    for t, ids in wins:
        rm, errors = eng.submit_columns(columns(pop, ids, t, tag), now=T0).result()
        assert not errors
        out.append(np.asarray(rm)[:4])
    return out


def replayed(ref, pop, wins):
    state = pop.state(np.arange(KEYS), T0)
    buckets = {k: {f: (float if f == "remaining_f" else int)(v[k])
                   for f, v in state.items()} for k in range(KEYS)}
    alg, limit, duration, burst = pop.params(np.arange(KEYS))
    out = []
    for t, ids in wins:
        got = np.zeros((4, len(ids)), np.int64)
        for j, k in enumerate(ids.tolist()):
            buckets[k], ans = ref.apply(buckets[k], (
                1, int(limit[k]), int(duration[k]), int(burst[k]),
                int(alg[k]), 0, t))
            got[:, j] = ans
        out.append(got)
    return out


def mismatched(a, b):
    return sum(int((x != y).any(axis=0).sum()) for x, y in zip(a, b))


def test_the_hot_key_is_of_both_kinds():
    kinds = [int(population.Population(SPEC, s).params(np.asarray([HOT]))[0][0])
             for s in SEEDS]
    assert kinds == [population.LEAKY, population.LEAKY, 0]


@pytest.mark.parametrize("seed", SEEDS)
def test_duplicate_windows_equal_the_reference_and_one_chip(mesh, one_chip, seed):
    pop, wins = history(seed)
    tag = SEEDS.index(seed)
    # every window has duplicates, and the fourth's hot group is wider
    # than a tile of the extent walk, so it straddles two
    assert all(len(np.unique(ids)) < len(ids) for _, ids in wins)
    assert (wins[3][1] == HOT).sum() > choose_tile(B, SHARDS)
    dup0, uniq0 = mesh.metric_dup_windows, mesh.metric_unique_windows
    want = replayed(Reference(), pop, wins)
    got = served(mesh, pop, wins, tag)
    assert mismatched(got, want) == 0
    assert mesh.metric_dup_windows - dup0 == len(wins)
    assert mesh.metric_unique_windows == uniq0
    assert mismatched(served(one_chip, pop, wins, tag), want) == 0
    # one precision below the one the configuration states: not correct
    lower = mismatched(replayed(Float32Leaky(), pop, wins), want)
    print(f"float32 control, seed {seed}: {lower} of"
          f" {sum(len(i) for _, i in wins)} answers differ")
    assert lower > 0


def test_load_columns_equals_load_items(mesh):
    """The same snapshot through both fills: equal exports; a duplicate
    key's last row wins; an expired row is dropped."""
    pop = population.Population(SPEC, 11)
    ids = np.concatenate([np.arange(400), np.arange(40)])    # 40 keys twice
    snap = snapshot(pop, ids, 7)
    snap["remaining"] = snap["remaining"].copy()
    snap["remaining"][400:] = 3                   # the later rows differ
    snap["algorithm"] = snap["algorithm"].copy()
    snap["algorithm"][400:] = 0
    snap["expire_at"] = snap["expire_at"].copy()
    snap["expire_at"][100:110] = T0 - 1           # expired at the fill
    a = mesh
    b = MeshTickEngine(mesh=make_mesh(jax.devices()[:2]), local_capacity=512,
                       max_batch=16)
    a.load_columns(snap, now=T0)
    b.load_items(items_from_snapshot(snap), now=T0)

    def exported(eng):
        return sorted((it for it in eng.export_items()
                       if it["key"].startswith("bench7_")),
                      key=lambda it: it["key"])

    got, want = exported(a), exported(b)
    assert got == want and len(got) == 390
    by_key = {it["key"]: it for it in got}
    assert all(by_key["bench7_k%08d" % k]["remaining"] == 3 for k in range(40))
    assert "bench7_k00000105" not in by_key
    assert a.routing_parity_errors([it["key"] for it in got]) == 0


@pytest.mark.parametrize("layout", ["columns", "row"])
def test_load_columns_reclaims_a_full_shard_once(layout):
    """More live keys than a shard holds: the expired rows already there
    are reclaimed, once, and the new keys take their slots.  On the row
    layout the dead scan reads each shard's own buffer (a slice of the
    sharded table would gather all of it onto one chip)."""
    eng = MeshTickEngine(mesh=make_mesh(jax.devices()[:2]), local_capacity=64,
                         max_batch=16, table_layout=layout)
    pop = population.Population(SPEC, 5)
    old = snapshot(pop, np.arange(300), 8)       # more than both shards hold
    old["expire_at"] = np.full(300, T0 + 10, np.int64)
    eng.load_columns(old, now=T0)
    assert eng.cache_size() == 128               # both shards full
    calls = []
    reclaim = eng._reclaim
    eng._reclaim = lambda *a: (calls.append(a[0]), reclaim(*a))[1]
    fresh = snapshot(pop, np.arange(1000, 1100), 8)
    eng.load_columns(fresh, now=T0 + 1000)       # the old rows have expired
    assert sorted(calls) == [0, 1]               # once a shard
    keys = {it["key"] for it in eng.export_items()}
    assert {"bench8_k%08d" % k for k in range(1000, 1100)} <= keys
    assert eng.metric_unexpired_evictions == 0


def test_route_stage_and_dispatch_counters(mesh):
    """``route`` is in the recorder's totals after a mesh window, and the
    two dispatch counters add up to ``metric_h2d_windows``."""
    pop = population.Population(SPEC, 3)
    rec = flightrec.FlightRecorder(windows=8)
    flightrec.install(rec)
    try:
        for ids in (np.arange(50), np.asarray([1, 1, 2, 3])):
            wid = rec.begin(len(ids), 0)
            mesh.submit_columns(columns(pop, ids, T0 + 5, 9), now=T0).result()
            rec.end_dispatch(wid)
            rec.finish(wid)
    finally:
        flightrec.uninstall()
    stages = [w["stages_ms"] for w in rec.recent()]
    assert len(stages) == 2 and all(s["route"] > 0 for s in stages)
    assert "route" in flightrec.STAGES
    assert mesh.metric_dup_windows >= 1 and mesh.metric_unique_windows >= 1
    assert (mesh.metric_dup_windows + mesh.metric_unique_windows
            == mesh.metric_h2d_windows)
    assert mesh.metric_h2d_uploads == 3 * mesh.metric_h2d_windows


def test_the_x64_program_is_not_the_mesh_engines():
    """``make_tick_fn`` (x64: float32-pair emulation on a TPU) is the
    tests' reference only; the mesh serves the 32-bit programs."""
    from gubernator_tpu.parallel import mesh_engine

    assert not hasattr(mesh_engine, "make_tick_fn")
