"""The sharded engine held to the benchmark's plain reference (upstream
``algorithms.go`` in Python int and float; it imports nothing of the
program), on four of the eight virtual devices in the column layout: a
seeded mixed population (``base3-mixed-10m-mesh4``'s, small, and
``base5-100m-mesh4``'s, ids over its whole 100M) filled through
``load_columns``, then duplicate-bearing zipf windows through
``submit_columns``.  Every answer equal, limit 0, which is what the
cell's ``correct`` asks on the chip; the one-chip ``TickEngine`` gives
the same answers; the reference one precision below (leaky arithmetic
rounded to float32) does not.  And the fill itself: ``load_columns``
against ``load_items``.  And the host side of a window: the native
sharded window pass against the numpy chain, array for array.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import population, traffic
from benchmarks.harness.reference import Reference
from benchmarks.tests.test_precision_control import Float32Leaky
from gubernator_tpu.native import NativeSlotMap
from gubernator_tpu.ops.buckets import np_logical
from gubernator_tpu.ops.engine import (
    REQ32_INDEX, SLAB_ROWS, TickEngine, device_dead_mask, items_from_snapshot,
    join_i32_pair)
from gubernator_tpu.ops.raggedtick import choose_tile
from gubernator_tpu.ops.reqcols import CREATED_UNSET, ReqColumns, pack_blob
from gubernator_tpu.parallel import mesh_engine
from gubernator_tpu.parallel.mesh_engine import (
    MeshTickEngine, make_mesh, make_window_pass)
from gubernator_tpu.types import Behavior
from gubernator_tpu.utils import flightrec, timeutil

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
# base5-100m-mesh4's population: the same block but for its 100M keys
SPEC5 = dict(SPEC, keys=100_000_000)
SEED5 = 2147495011


# One engine of each kind for the module (a MeshTickEngine is seconds of
# compile a program); a history's keys carry its seed, so the histories
# do not meet in the table.  The column layout, which base5-100m-mesh4's
# 31,250,000 slots a shard take on the chip, is named, not left to auto.
@pytest.fixture(scope="module")
def mesh():
    return MeshTickEngine(mesh=make_mesh(jax.devices()[:SHARDS]),
                          local_capacity=4096, max_batch=B,
                          table_layout="columns")


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


def history(seed, spec=SPEC, windows=7):
    """(population, the KEYS ids filled, [(clock ms, key ids)]): zipf 0.99
    windows of varied width over the filled ids, with the clock stepping
    between them; the fourth holds the hottest key 150 times, a group
    wider than the extent walk's tile.  A population of more than KEYS
    keys has KEYS of its ids drawn over all of them, in order."""
    pop = population.Population(spec, seed)
    rng = np.random.default_rng(seed)
    space = np.arange(KEYS)
    if pop.n > KEYS:
        space = np.sort(rng.choice(pop.n, KEYS, replace=False))
    out, t = [], T0
    for w in range(windows):
        t += int(rng.choice([1, 700, 40_000]))
        ids = traffic.key_ids(MIX, KEYS, rng, B - 23 * (w % 3))
        if w == 3:
            ids[rng.permutation(len(ids))[:150]] = HOT
        out.append((t, space[ids]))
    return pop, space, out


def columns(pop, ids, t, tag):
    alg, limit, duration, burst = pop.params(ids)
    blob, offsets = key_blob(ids, tag)
    n = len(ids)
    return ReqColumns(
        key_blob=blob, key_offsets=offsets, hits=np.ones(n, np.int64),
        limit=limit, duration=duration, algorithm=alg,
        behavior=np.zeros(n, np.int64), created_at=np.full(n, t, np.int64),
        burst=burst)


def served(eng, pop, wins, tag, space=None):
    """Fill ``eng`` with the ids ``space`` (the first KEYS where None),
    then serve the windows: [(4, n) status, limit, remaining,
    reset_time]."""
    space = np.arange(KEYS) if space is None else space
    eng.load_columns(snapshot(pop, space, tag), now=T0)
    out = []
    for t, ids in wins:
        rm, errors = eng.submit_columns(columns(pop, ids, t, tag), now=T0).result()
        assert not errors
        out.append(np.asarray(rm)[:4])
    return out


def replayed(ref, pop, wins, space=None):
    space = np.arange(KEYS) if space is None else space
    state = pop.state(space, T0)
    at = {k: i for i, k in enumerate(space.tolist())}
    buckets = {k: {f: (float if f == "remaining_f" else int)(v[i])
                   for f, v in state.items()} for k, i in at.items()}
    alg, limit, duration, burst = pop.params(space)
    out = []
    for t, ids in wins:
        got = np.zeros((4, len(ids)), np.int64)
        for j, k in enumerate(ids.tolist()):
            i = at[k]
            buckets[k], ans = ref.apply(buckets[k], (
                1, int(limit[i]), int(duration[i]), int(burst[i]),
                int(alg[i]), 0, t))
            got[:, j] = ans
        out.append(got)
    return out


def mismatched(a, b):
    return sum(int((x != y).any(axis=0).sum()) for x, y in zip(a, b))


def test_the_hot_key_is_of_both_kinds():
    kinds = [int(population.Population(SPEC, s).params(np.asarray([HOT]))[0][0])
             for s in SEEDS]
    assert kinds == [population.LEAKY, population.LEAKY, 0]


@pytest.mark.parametrize("seed,spec,tag", [
    *(pytest.param(s, SPEC, i, id=str(s)) for i, s in enumerate(SEEDS)),
    pytest.param(SEED5, SPEC5, 5, id=f"base5-{SEED5}"),
])
def test_duplicate_windows_equal_the_reference_and_one_chip(
        mesh, one_chip, seed, spec, tag):
    pop, space, wins = history(seed, spec)
    assert mesh.layout == "columns"
    if pop.n > KEYS:     # ids from the whole space, its top quarter too
        assert space.max() > 3 * pop.n // 4 and len(np.unique(space)) == KEYS
    # every window has duplicates, and the fourth's hot group is wider
    # than a tile of the extent walk, so it straddles two
    assert all(len(np.unique(ids)) < len(ids) for _, ids in wins)
    assert (wins[3][1] == space[HOT]).sum() > choose_tile(B, SHARDS)
    dup0, uniq0 = mesh.metric_dup_windows, mesh.metric_unique_windows
    want = replayed(Reference(), pop, wins, space)
    got = served(mesh, pop, wins, tag, space)
    assert mismatched(got, want) == 0
    assert mesh.metric_dup_windows - dup0 == len(wins)
    assert mesh.metric_unique_windows == uniq0
    assert mismatched(served(one_chip, pop, wins, tag, space), want) == 0
    # one precision below the one the configuration states: not correct
    lower = mismatched(replayed(Float32Leaky(), pop, wins, space), want)
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


def test_a_fill_is_counted_in_describe(mesh, one_chip):
    """``load_seconds`` / ``load_rows`` in ``describe()`` (the daemon's
    ``engine:`` line, ``benchmarks/run.py``'s too) on both engines: a
    fill adds its seconds and the rows it landed, an expired row not
    among them."""
    pop = population.Population(SPEC, 13)
    snap = snapshot(pop, np.arange(500), 13)
    snap["expire_at"] = snap["expire_at"].copy()
    snap["expire_at"][:10] = T0 - 1
    for eng in (mesh, one_chip):
        before = eng.describe()
        eng.load_columns(snap, now=T0)
        d = eng.describe()
        assert d["load_rows"] - before["load_rows"] == 490
        assert d["load_seconds"] > before["load_seconds"]
        assert d["load_rows"] == eng.load_rows


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


@pytest.mark.parametrize("capacity", [1003, 4096])
def test_the_column_dead_scan_equals_the_host(capacity):
    """The column layout's dead scan (``engine._jitted_dead_scan``: the
    int32-pair compare, packed by stride) against the host's reading of
    the same columns, at a width that is no multiple of eight, with
    expiries either side of ``now`` in its high word and in its low one
    (bit 31 of it set)."""
    rng = np.random.default_rng(capacity)
    now = (5 << 32) | 0x9000_0000
    exp = rng.integers(now - (3 << 32), now + (3 << 32), capacity)
    exp[:8] = [now - 1, now, now + 1, now - (1 << 32), now + (1 << 32),
               5 << 32, (5 << 32) | 0xFFFF_FFFF, 0]
    in_use = rng.random(capacity) < 0.8
    lo = (exp & 0xFFFF_FFFF).astype(np.uint32).view(np.int32)
    hi = (exp >> 32).astype(np.int32)
    got = device_dead_mask(
        jnp.asarray(in_use), (jnp.asarray(lo), jnp.asarray(hi)), now, capacity)
    np.testing.assert_array_equal(got, ~in_use | (exp < now))


def test_the_dead_scan_reads_each_shards_own_columns(mesh, monkeypatch):
    """``MeshTickEngine._shard_dead_mask`` on the column layout scans a
    shard's own buffers on the shard's own device (a slice of the
    sharded arrays would gather them onto one first: 1.1 GB at
    base5-100m-mesh4's size), and finds what the host reads there: the
    filled keys of an hour's duration dead two hours on, the rest live."""
    devices = []
    scan = mesh_engine.device_dead_mask

    def spy(in_use, expire_at, now, capacity):
        devices.append({d for a in (in_use, *expire_at) for d in a.devices()})
        return scan(in_use, expire_at, now, capacity)

    monkeypatch.setattr(mesh_engine, "device_dead_mask", spy)
    cap, now = mesh.local_capacity, T0 + 7_200_001
    host = jax.tree.map(np.asarray, mesh.state)
    want = ~host.in_use | (np_logical(host.expire_at, "expire_at") < now)
    for s in range(SHARDS):
        np.testing.assert_array_equal(
            mesh._shard_dead_mask(s, now), want[s * cap:(s + 1) * cap])
    assert devices == [{d} for d in mesh.mesh.devices.flat]


def test_route_stage_and_dispatch_counters(mesh):
    """``route`` and ``pack`` are in the recorder's totals after a mesh
    window, the native pass on, and it answered every one of them; the
    two dispatch counters add up to ``metric_h2d_windows``."""
    pop = population.Population(SPEC, 3)
    rec = flightrec.FlightRecorder(windows=8)
    flightrec.install(rec)
    before = mesh.metric_native_pack_windows, mesh.metric_h2d_windows
    try:
        for ids in (np.arange(50), np.asarray([1, 1, 2, 3])):
            wid = rec.begin(len(ids), 0)
            mesh.submit_columns(columns(pop, ids, T0 + 5, 9), now=T0).result()
            rec.end_dispatch(wid)
            rec.finish(wid)
    finally:
        flightrec.uninstall()
    stages = [w["stages_ms"] for w in rec.recent()]
    assert len(stages) == 2
    assert all(s["route"] > 0 and s["pack"] > 0 for s in stages)
    # pack's CPU is its whole range's, the route pass's share included
    for s in stages:
        assert 0 < s["pack_cpu"] <= s["pack"] + s["route"] + 1e-3, s
    assert "route" in flightrec.STAGES
    if mesh._window_pass is not None:
        assert (mesh.metric_native_pack_windows - before[0]
                == mesh.metric_h2d_windows - before[1] == 2)
    assert mesh.metric_dup_windows >= 1 and mesh.metric_unique_windows >= 1
    assert (mesh.metric_dup_windows + mesh.metric_unique_windows
            == mesh.metric_h2d_windows)
    assert mesh.metric_h2d_uploads == mesh.metric_h2d_windows


# ----------------------------------------------------------------------
# One upload a window: ``now`` and the extent offsets ride in the slab's
# tail (partition.RaggedExtents.split), and both programs read them
# there.
# ----------------------------------------------------------------------
def tail_columns(keys, ranks):
    """Token and leaky buckets by turns, a third of them with a second's
    duration; ``created_at`` unset, so the tick's ``now`` is the clock."""
    n = len(keys)
    col = lambda v: np.asarray(v, np.int64)  # noqa: E731
    blob, offsets = pack_blob(keys)
    return ReqColumns(
        blob, offsets, hits=np.ones(n, np.int64), limit=col(10 + ranks % 7),
        duration=col(np.where(ranks % 3 == 0, 1_000, 3_600_000)),
        algorithm=col(ranks % 2), behavior=np.zeros(n, np.int64),
        created_at=np.full(n, CREATED_UNSET, np.int64),
        burst=np.zeros(n, np.int64))


@pytest.mark.parametrize("bit31", ["lo_bit31_clear", "lo_bit31_set"])
@pytest.mark.parametrize("program", ["unique", "dup"])
def test_now_and_offsets_ride_in_the_slab(mesh, one_chip, program, bit31):
    """Two ticks five seconds apart, at a ``now`` over 2^32 whose low
    word has bit 31 clear or set: the buckets of a second's duration
    expire between them and start anew, the others go on, which only a
    program that reads both of ``now``'s words from the tail answers;
    and every shard walks its own extent, which only one that reads its
    two offsets there does.  Answers and ``_last_access`` as the
    one-chip engine's, through both programs."""
    now = (T0 & ~0xFFFFFFFF) | (0x9000_0000 if bit31 == "lo_bit31_set"
                                else 0x1000_0000)
    assert now > 1 << 32 and bool(now & 0x8000_0000) == (bit31 == "lo_bit31_set")
    n = 120
    ranks = np.arange(n) if program == "unique" else np.arange(n) % 40
    keys = [b"tail_%s_%s_%d" % (program.encode(), bit31.encode(), r)
            for r in ranks.tolist()]
    assert len({zlib.crc32(k) % SHARDS for k in keys}) == SHARDS
    counter = f"metric_{program}_windows"
    count0 = getattr(mesh, counter)
    answers = {mesh: [], one_chip: []}
    ticks = {mesh: [], one_chip: []}
    # the second window holds the first two thirds of the rows
    for t, rows in ((now, n), (now + 5_000, 2 * n // 3)):
        cols = tail_columns(keys[:rows], ranks[:rows])
        for eng in (mesh, one_chip):
            rm, errors = eng.submit_columns(cols, now=t).result()
            assert not errors
            answers[eng].append(np.asarray(rm)[:4])
            ticks[eng].append(eng._tick_count)
    assert mismatched(answers[mesh], answers[one_chip]) == 0
    first, second = (a[2] for a in answers[mesh])        # remaining
    token = ranks[:len(second)] % 2 == 0
    expired = ranks[:len(second)] % 3 == 0
    np.testing.assert_array_equal(
        second[token & expired], first[:len(second)][token & expired])
    assert (second[~expired] < first[:len(second)][~expired]).all()
    assert getattr(mesh, counter) - count0 == 2

    def stamps(eng, slot_of):
        return [int(eng._last_access[slot_of(k.decode(), zlib.crc32(k) % SHARDS)])
                for k in keys]

    cap = mesh.local_capacity
    for eng, slot_of in (
            (mesh, lambda k, sh: sh * cap + mesh.slots[sh].get(k)),
            (one_chip, lambda k, sh: one_chip.slots.get(k))):
        again = set(keys[:2 * n // 3])
        want = [ticks[eng][1] if k in again else ticks[eng][0] for k in keys]
        assert stamps(eng, slot_of) == want


@pytest.mark.parametrize("program", ["tick_unique_ragged", "tick_ragged"])
def test_one_upload_and_one_program_call_a_window(mesh, monkeypatch, program):
    """After k windows of a program ``metric_h2d_uploads`` and
    ``metric_h2d_windows`` have both risen by k, and ``submit_columns``
    crossed into the runtime with host data k times (``jax.device_put``
    of the whole slab onto the replicated sharding; no ``jnp.asarray``,
    no ``jnp.int64``) and called that program's entry k times, and no
    other."""
    k = 3
    ranks = np.arange(30) if program == "tick_unique_ragged" else np.arange(30) % 7
    keys = [b"one_%s_%d" % (program.encode(), r) for r in ranks.tolist()]
    cols = tail_columns(keys, ranks)
    mesh.submit_columns(cols, now=T0).result()   # compiles here
    calls = []
    for name in ("tick_unique_ragged", "tick_ragged"):
        def counted(*a, _fn=getattr(mesh.ops, name), _name=name):
            calls.append(_name)
            return _fn(*a)
        monkeypatch.setattr(mesh.ops, name, counted)
    for mod, name in ((mesh_engine.jax, "device_put"),
                      (mesh_engine.jnp, "asarray"),
                      (mesh_engine.jnp, "int64")):
        def upload(x, *a, _fn=getattr(mod, name), _name=name, **kw):
            calls.append((_name, type(x).__name__, np.shape(x), a))
            return _fn(x, *a, **kw)
        monkeypatch.setattr(mod, name, upload)
    before = mesh.metric_h2d_uploads, mesh.metric_h2d_windows
    for i in range(k):
        _, errors = mesh.submit_columns(cols, now=T0 + 1 + i).result()
        assert not errors
    assert mesh.metric_h2d_uploads - before[0] == k
    assert mesh.metric_h2d_windows - before[1] == k
    slab = ("device_put", "ndarray", (SLAB_ROWS, B),
            (mesh.ops.slab_sharding,))
    assert mesh.ragged.slab_rows(B) == SLAB_ROWS
    assert mesh.ops.slab_sharding.is_fully_replicated
    assert calls == [slab, program] * k


# ----------------------------------------------------------------------
# The native sharded window pass (native/slotmap.cc
# guber_slotmap_pack_window_sharded) against the numpy chain it stands
# in for, array for array.  No device program runs: the shared engine's
# slot maps and host arrays are swapped for fresh ones a run.
# ----------------------------------------------------------------------
PASS_KINDS = ("unique", "zipf", "one_shard", "new_keys")


def on_shard(shard, count, tag):
    """``count`` keys that CRC-32 routes to ``shard`` of SHARDS."""
    keys, i = [], 0
    while len(keys) < count:
        k = b"%s_%d" % (tag, i)
        if zlib.crc32(k) % SHARDS == shard:
            keys.append(k)
        i += 1
    return keys


def window_case(kind, n, rng):
    """(ReqColumns of n rows, the keys mapped before the window)."""
    if kind == "unique":
        ranks = rng.permutation(n)
    else:   # a hot head: the hottest key is a tenth of the rows
        ranks = np.minimum(rng.zipf(1.2, n) - 1, 2 * n)
        ranks[rng.random(n) < 0.1] = 0
    if kind == "one_shard":
        pool = on_shard(2, int(ranks.max()) + 1, b"wp")
        keys = [pool[r] for r in ranks.tolist()]
    else:
        keys = [b"wp_%d" % r for r in ranks.tolist()]
    col = lambda v: np.asarray(v, np.int64)  # noqa: E731
    blob, offsets = pack_blob(keys)
    cols = ReqColumns(
        blob, offsets,
        hits=col(ranks % 3),
        limit=col(np.where(ranks % 11 == 0, 1 << 33, 10 + ranks % 7)),
        duration=col(np.full(n, 60_000)),
        algorithm=col(ranks % 2),
        behavior=col(np.where(ranks % 5 == 0,
                              int(Behavior.DRAIN_OVER_LIMIT), 0)),
        created_at=col(np.where(ranks % 3 == 0, T0 + 7, CREATED_UNSET)),
        burst=col(ranks % 2 * 5))
    seed = sorted(set(keys))
    if kind == "new_keys":
        seed = seed[::2]
    return cols, keys, seed


@pytest.mark.parametrize("n", [1, 50, 4000])
@pytest.mark.parametrize("kind", PASS_KINDS)
def test_native_window_pass_equals_numpy_chain(mesh, monkeypatch, kind, n):
    if mesh._window_pass is None:
        pytest.skip("native slotmap library unavailable")
    rng = np.random.default_rng(n * 31 + PASS_KINDS.index(kind))
    cols, keys, seed = window_case(kind, n, rng)
    cap = mesh.local_capacity
    b = B if n <= B else 4096
    R = REQ32_INDEX

    def run(native):
        slots = [NativeSlotMap(cap) for _ in range(SHARDS)]
        for d, sm in enumerate(slots):
            sm.assign_batch([k for k in seed if zlib.crc32(k) % SHARDS == d])
        monkeypatch.setattr(mesh, "slots", slots)
        monkeypatch.setattr(
            mesh, "_window_pass",
            make_window_pass(slots, cap) if native else None)
        monkeypatch.setattr(
            mesh, "_last_access", np.zeros(mesh.capacity, np.int64))
        monkeypatch.setattr(mesh, "_pending", set())
        before = (mesh.metric_hits, mesh.metric_misses,
                  mesh.metric_native_pack_windows)
        # the pass cleans the rows it packs, whatever they held; the
        # numpy chain is handed a clean slab; both write the whole tail
        slab = rng.integers(
            -9, 9, (mesh.ragged.slab_rows(b), b)).astype(np.int32)
        if not native:
            mesh._staging.clean(slab)
        errors = {}
        sh, sl, ix, inv, has_dups, route_s = mesh._pack_window(
            cols, T0, slab, errors)
        m, now_words, offs = mesh.ragged.split(slab)
        assert slab.shape[0] == SLAB_ROWS and offs.base is slab
        assert not errors and route_s > 0
        assert ix is None or (ix == np.arange(n)).all()
        counted = tuple(
            now - was for now, was in zip(
                (mesh.metric_hits, mesh.metric_misses,
                 mesh.metric_native_pack_windows), before))
        every = np.arange(cap)
        return dict(
            slab=m, now=now_words, sh=sh, slots=sl, inv=inv,
            has_dups=has_dups, offs=offs,
            known=m[R["known"], inv], last_access=mesh._last_access,
            pending=set(mesh._pending), counted=counted,
            maps=[sm.keys_batch(every) for sm in slots])

    nat = run(native=True)
    # the window's keys live where the host ring says, once each
    assert mesh.routing_parity_errors([k.decode() for k in keys]) == 0
    ref = run(native=False)
    assert nat.pop("counted")[2] == 1 and ref.pop("counted")[2] == 0
    for name, want in ref.items():
        if isinstance(want, np.ndarray):
            assert nat[name].dtype == want.dtype, name
            np.testing.assert_array_equal(nat[name], want, err_msg=name)
        else:
            assert nat[name] == want, name

    # ... and both are what the window says, read without either.
    want_sh = np.asarray([zlib.crc32(k) % SHARDS for k in keys])
    np.testing.assert_array_equal(nat["sh"], want_sh)
    seen, want_known = set(seed), []
    for k in keys:
        want_known.append(k in seen)
        seen.add(k)
    np.testing.assert_array_equal(nat["known"], want_known)
    np.testing.assert_array_equal(
        np.diff(nat["offs"]), np.bincount(want_sh, minlength=SHARDS))
    assert nat["offs"][0] == 0 and len(nat["offs"]) == SHARDS + 1
    assert int(join_i32_pair(*nat["now"])) == T0
    assert nat["has_dups"] == (len(set(keys)) < n)
    g = nat["sh"] * cap + nat["slots"]
    assert nat["pending"] == set(g[~np.asarray(want_known)].tolist())
    assert (nat["last_access"][g] == mesh._tick_count).all()
    if kind == "one_shard":
        assert (nat["sh"] == 2).all()
    if kind == "new_keys" and n > 1:
        assert nat["pending"]


@pytest.mark.parametrize("kind", ["plain", "gregorian", "shard_reclaimed",
                                  "shard_full"])
def test_native_pass_engages_by_what_the_batch_shows(mesh, one_chip, kind):
    """``metric_native_pack_windows`` rises by one for the window the
    served path sees all day, and not for a Gregorian row or a key that
    finds its shard full (reclaimed and retried; where nothing can be
    freed, the per-item error); those take the numpy chain and answer
    as before.  (A Store's new key: tests/test_mesh_engine.py, on its
    store-backed engines.)"""
    if mesh._window_pass is None:
        pytest.skip("native slotmap library unavailable")
    pop = population.Population(SPEC, 3)
    cap = mesh.local_capacity
    ids = np.arange(100, 140)
    cols = columns(pop, ids, T0 + 5, 10)
    n = len(cols)
    # three more rows, their keys new and all on shard 1
    fresh = on_shard(1, 3, b"bench10_%s" % kind.encode())
    blob, offsets = pack_blob(
        [cols.key_bytes(i) for i in range(n)] + fresh)
    grow = lambda c, v: np.concatenate([c, np.full(3, v, np.int64)])  # noqa: E731
    cols = ReqColumns(
        blob, offsets, hits=grow(cols.hits, 1), limit=grow(cols.limit, 10),
        duration=grow(cols.duration, 60_000),
        algorithm=grow(cols.algorithm, 0), behavior=grow(cols.behavior, 0),
        created_at=grow(cols.created_at, T0 + 5), burst=grow(cols.burst, 0))
    if kind == "gregorian":
        cols.behavior[n] = int(Behavior.DURATION_IS_GREGORIAN)
        cols.duration[n] = timeutil.GREGORIAN_MINUTES

    lo, fillers, reclaimed = cap, [], []
    if kind.startswith("shard_"):
        # Fill shard 1's map from the host: the device never saw these
        # keys, so a reclaim finds them dead and frees them ...
        sm = mesh.slots[1]
        room = cap - len(sm)
        fillers = sm.assign_batch(on_shard(1, room, b"filler_%s" % kind.encode()))
        assert len(sm) == cap and (fillers >= 0).all()
        stamps = mesh._last_access[lo:lo + cap].copy()
        if kind == "shard_full":
            # ... unless the shard's every slot counts as touched by the
            # coming tick: nothing to free, expired or least recent.
            mesh._last_access[lo:lo + cap] = mesh._tick_count + 1
        reclaim = mesh._reclaim
        mesh._reclaim = lambda *a: (reclaimed.append(a[0]), reclaim(*a))[1]
    before = mesh.metric_native_pack_windows, mesh.metric_h2d_windows
    try:
        rm, errors = mesh.submit_columns(cols, now=T0).result()
    finally:
        if len(fillers):
            del mesh._reclaim
            # the fillers a reclaim did not free (a freed one's slot may
            # be a fresh key's by now)
            left = np.asarray([s for s in fillers.tolist() if (
                sm.key_of(s) or "").startswith("filler_")], np.int64)
            sm.release_batch(left)
            mesh._last_access[lo:lo + cap] = stamps
    assert mesh.metric_h2d_windows - before[1] == 1
    assert (mesh.metric_native_pack_windows - before[0]
            == (kind == "plain"))
    want, want_errors = one_chip.submit_columns(cols, now=T0).result()
    assert not want_errors
    if kind == "shard_full":
        assert errors == {i: "rate-limit shard full; eviction failed"
                          for i in range(n, n + 3)}
        served_rows = np.arange(n)
    else:
        assert not errors
        served_rows = np.arange(n + 3)
    assert reclaimed == ([1] if kind.startswith("shard_") else [])
    np.testing.assert_array_equal(
        np.asarray(rm)[:4, served_rows], np.asarray(want)[:4, served_rows])
    if kind == "gregorian":
        assert rm[3, n] == timeutil.gregorian_expiration(
            T0, timeutil.GREGORIAN_MINUTES)


def test_the_x64_program_is_not_the_mesh_engines():
    """``make_tick_fn`` (x64: float32-pair emulation on a TPU) is the
    tests' reference only; the mesh serves the 32-bit programs."""
    from gubernator_tpu.parallel import mesh_engine

    assert not hasattr(mesh_engine, "make_tick_fn")
