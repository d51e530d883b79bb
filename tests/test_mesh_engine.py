"""Multi-chip sharded engine tests on the virtual 8-device CPU mesh."""

import jax
import numpy as np
import pytest

from gubernator_tpu.parallel.mesh_engine import MeshTickEngine, make_mesh
from gubernator_tpu.types import Algorithm, RateLimitRequest, Status

NOW = 1_700_000_000_000


# Every MeshTickEngine traces and compiles its own sharded programs
# (seconds each on 8 virtual devices), so the module builds ONE engine
# per distinct parameter set and the tests tell their state apart by
# key prefix and their metrics by difference.
@pytest.fixture(scope="module")
def engine():
    mesh = make_mesh(jax.devices())
    return MeshTickEngine(mesh=mesh, local_capacity=128, max_batch=64)


@pytest.fixture(scope="module")
def engine64():
    """A second, smaller full-mesh engine: the load side of the snapshot
    round trip and the sharded side of the single-chip comparison."""
    return MeshTickEngine(
        mesh=make_mesh(jax.devices()), local_capacity=64, max_batch=64)


@pytest.fixture(scope="module")
def row_engines():
    """Two row-layout engines (Pallas in interpret mode on the CPU):
    the second only ever loads what the first exported."""
    return tuple(
        MeshTickEngine(mesh=make_mesh(), local_capacity=32, max_batch=16,
                       table_layout="row")
        for _ in range(2))


@pytest.fixture(scope="module")
def store_engines():
    """Two engines over ONE MockStore: what the first writes through,
    the second (which never saw the key) reads through on its miss."""
    from gubernator_tpu.store import MockStore

    store = MockStore()
    return store, tuple(
        MeshTickEngine(mesh=make_mesh(), local_capacity=32, max_batch=16,
                       store=store)
        for _ in range(2))


def req(key, hits=1, limit=10, duration=60_000, **kw):
    return RateLimitRequest(
        name="mesh", unique_key=key, hits=hits, limit=limit,
        duration=duration, algorithm=Algorithm.TOKEN_BUCKET, **kw,
    )


def test_sharded_state_persists_across_ticks(engine):
    reqs = [req(str(i)) for i in range(100)]
    out1 = engine.process(reqs, now=NOW)
    assert [r.remaining for r in out1] == [9] * 100
    out2 = engine.process(reqs, now=NOW + 5)
    assert [r.remaining for r in out2] == [8] * 100


@pytest.mark.skipif(
    len(jax.devices()) < 6,
    reason="needs a >=6-shard mesh: the assertions require ~all of 8 "
           "shards populated and 200 keys exceed a small mesh's summed "
           "128-slot shard capacity (GUBER_TEST_TPU runs single-chip)",
)
def test_keys_spread_across_shards(engine):
    engine.process([req(f"spread-{i}") for i in range(200)], now=NOW)
    per_shard = [len(sm) for sm in engine.slots]
    assert sum(per_shard) >= 200
    assert sum(1 for n in per_shard if n > 0) >= 6  # ~all 8 shards populated


def test_over_limit_on_mesh(engine):
    r = req("exhaust", hits=10, limit=10)
    out = engine.process([r], now=NOW)
    assert out[0].remaining == 0
    out = engine.process([req("exhaust", hits=1, limit=10)], now=NOW + 1)
    assert out[0].status == Status.OVER_LIMIT


def test_reclaim_does_not_release_same_batch_slots():
    """Filling a shard then inserting more keys in ONE batch must not
    release slots assigned earlier in that same batch (pre-tick device
    state is stale for them)."""
    mesh = make_mesh(jax.devices()[:1])
    eng = MeshTickEngine(mesh=mesh, local_capacity=4, max_batch=16)
    # Fill the table with short-TTL keys, let them expire.
    eng.process([req(f"old{i}", duration=10) for i in range(4)], now=NOW)
    # One batch: 4 fresh long-lived keys exhaust the shard, then a straw
    # request forces a SECOND mid-batch reclaim whose view of device
    # in_use/expire_at is stale for the 4 slots just assigned.
    fresh = [req(f"new{i}", limit=10, duration=600_000) for i in range(4)]
    straw = [req("straw", limit=10, duration=600_000)]
    eng.process(fresh + straw, now=NOW + 1000)
    out = eng.process(fresh, now=NOW + 2000)
    # The straw's spill tick may LRU-evict at most one fresh key; the
    # pre-fix bug released every same-batch slot → ALL keys reset (=9).
    rems = sorted(r.remaining for r in out if not r.error)
    assert rems in ([8, 8, 8, 8], [8, 8, 8, 9]), out


def test_spill_chunking_beyond_tick_budget():
    mesh = make_mesh(jax.devices()[:2])
    eng = MeshTickEngine(mesh=mesh, local_capacity=512, max_batch=8)
    reqs = [req(f"spill{i}", limit=100) for i in range(100)]  # >> 2*8
    out = eng.process(reqs, now=NOW)
    assert len(out) == 100
    assert all(r.error == "" and r.remaining == 99 for r in out)


def test_mesh_snapshot_roundtrip(engine, engine64):
    """Loader.Save/Load over the sharded table (see TickEngine analog)."""
    e1, e2 = engine, engine64
    e1.process([req(f"snap{i}", hits=3, limit=9) for i in range(40)], now=NOW)
    items = [it for it in e1.export_items()
             if it["key"].startswith("mesh_snap")]
    assert len(items) == 40
    e2.load_items(items, now=NOW)
    out = e2.process(
        [req(f"snap{i}", hits=0, limit=9) for i in range(40)], now=NOW
    )
    assert all(r.remaining == 6 for r in out), out


def test_matches_single_device_engine(engine64):
    """The sharded tick must agree with the single-chip engine bit-for-bit
    — including same-tick duplicate keys: both engines sequence same-slot
    requests in arrival order (stable slot sorts on both paths), so even
    duplicate-bearing windows must match decision for decision."""
    from gubernator_tpu.ops.engine import TickEngine

    m_eng = engine64
    routed0 = m_eng.metric_routed_windows
    # the single-chip programs test_ragged_parity_fuzz_vs_single_chip
    # compiles too (they are cached by capacity and width)
    s_eng = TickEngine(capacity=2048, max_batch=64)
    rng = np.random.default_rng(7)
    for t in range(6):
        reqs = [
            RateLimitRequest(
                name="cmp",
                unique_key=str(int(rng.integers(0, 40))),
                hits=int(rng.integers(0, 4)),
                limit=20,
                duration=60_000,
                algorithm=int(rng.integers(0, 2)),
            )
            for _ in range(50)
        ]
        if t < 3:
            # Unique-key windows exercise the parts-native program...
            seen, uniq = set(), []
            for r in reqs:
                k = r.hash_key()
                if k not in seen:
                    seen.add(k)
                    uniq.append(r)
            reqs = uniq
        # ...and the rest keep their duplicates (the merge-capable
        # program, arrival-order sequencing across both engines).
        a = m_eng.process(reqs, now=NOW + t * 1000)
        b = s_eng.process(reqs, now=NOW + t * 1000)
        for x, y in zip(a, b):
            assert (x.status, x.remaining, x.reset_time, x.error) == (
                y.status,
                y.remaining,
                y.reset_time,
                y.error,
            )
    # The routed flat format served every window (no silent fallback).
    assert m_eng.metric_routed_windows - routed0 == 6
    assert m_eng.metric_routed_overflows == 0


def test_mesh_row_layout_matches_columns(row_engines):
    """The Pallas row layout on the sharded mesh (interpret mode on CPU)
    must agree with the column layout decision for decision."""
    row = row_engines[0]
    col = MeshTickEngine(
        mesh=make_mesh(), local_capacity=32, max_batch=16,
        table_layout="columns",
    )
    assert row.layout == "row" and col.layout == "columns"
    for t in range(3):
        reqs = [req(f"rl{i}", hits=1, limit=7) for i in range(24)]
        # ...and a duplicate-bearing window, token and leaky: the sorted
        # 32-bit program's row gathers inside the extent walk, which is
        # what a chip's sharded table runs
        if t == 2:
            reqs = [
                RateLimitRequest(
                    name="mesh", unique_key=f"rl{i % 5}", hits=1, limit=7,
                    duration=60_000, algorithm=(i % 5) % 2)
                for i in range(16)]
        a = row.process(reqs, now=NOW + t)
        b = col.process(reqs, now=NOW + t)
        assert [(r.status, r.remaining, r.reset_time) for r in a] == \
               [(r.status, r.remaining, r.reset_time) for r in b]
    assert row.metric_dup_windows == col.metric_dup_windows >= 1


def test_mesh_row_layout_snapshot_roundtrip(row_engines):
    eng, e2 = row_engines
    eng.process([req(f"snapr{i}", hits=2, limit=9) for i in range(20)], now=NOW)
    items = [it for it in eng.export_items()
             if it["key"].startswith("mesh_snapr")]
    assert len(items) == 20
    e2.load_items(items, now=NOW + 1)
    out = e2.process([req("snapr3", hits=0, limit=9)], now=NOW + 1)[0]
    assert out.remaining == 7


def test_routing_parity_fuzz_vs_host_ring(engine):
    """Device-derived ownership must agree with the host hash ring for
    every served key: the vectorized CRC-32 route, the scalar
    ``_shard_of`` ring, slotmap residency (exactly one shard), and the
    global-slot derivation (``slot // local_capacity``)."""
    rng = np.random.default_rng(11)
    keys = [
        f"parity-{int(rng.integers(0, 1 << 30))}-{'x' * int(rng.integers(0, 40))}"
        for _ in range(120)
    ]
    reqs = [req(k, limit=1000) for k in keys]
    for s in range(0, len(reqs), 60):
        engine.process(reqs[s:s + 60], now=NOW)
    assert engine.routing_parity_errors(
        [r.hash_key() for r in reqs]) == 0


def test_route_function_parity_shard_counts():
    """The vectorized CRC-32 router must be bit-identical to the scalar
    zlib route at every shard count — including 1, odd, prime, and >8
    (no engine builds: this is pure host routing math)."""
    import zlib

    from gubernator_tpu.native import crc32_batch

    rng = np.random.default_rng(13)
    keys = [b"", b"a", b"name_key", bytes(rng.integers(1, 255, 60).astype(np.uint8))] + [
        f"k{int(rng.integers(0, 1 << 40))}".encode() for _ in range(200)
    ]
    blob = b"".join(keys)
    offsets = np.zeros(len(keys) + 1, np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    crcs = crc32_batch(blob, offsets)
    for n_shards in (1, 2, 3, 5, 7, 8, 13):
        vec = (crcs % np.uint32(n_shards)).astype(np.int64)
        ref = [zlib.crc32(k) % n_shards for k in keys]
        assert vec.tolist() == ref, n_shards


def test_ragged_trace_stability_across_widths(engine):
    """One fixed-shape program per batch capacity: the ragged dispatch
    always uploads a (19, max_batch) slab + offsets, so varying the
    OBSERVED window width must never trace a new program (the routed
    path compiled one per width; a signature drift — e.g. a committed
    device_put where warmup used jnp.asarray — re-traces per tick at
    ~0.6 s each).  The ShardedOps trace counters only increment at
    trace time, so they must stay flat across the full width sweep,
    duplicate-bearing windows included."""
    # Unique window, then a duplicate-bearing window: both programs run.
    engine.process([req(f"tr-{i}") for i in range(20)], now=NOW)
    engine.process(
        [req("tr-dup", hits=1) for _ in range(8)]
        + [req(f"tr-{i}") for i in range(8)],
        now=NOW + 1,
    )
    before = dict(engine.ops.trace_counts)
    assert {"tick_ragged", "tick_unique_ragged"} <= set(before)
    # Width sweep 1 → max_batch (64 on the module engine): every width
    # reuses the two warmed programs.
    for t, width in enumerate((1, 7, 16, 33, 48, engine.max_batch)):
        engine.process(
            [req(f"tw-{t}-{i}") for i in range(width)], now=NOW + 2 + t)
        engine.process(
            [req(f"tw-dup-{t}", hits=1) for _ in range(max(1, width // 2))]
            + [req(f"tw-{t}-{i}") for i in range(width // 2)],
            now=NOW + 20 + t,
        )
    assert dict(engine.ops.trace_counts) == before


def test_ragged_skew_window_no_fallback(engine):
    """The adversarial window the routed path used to overflow on —
    every key hashing to ONE shard — is just another ragged extent now:
    one shard's count is the whole batch, the rest are zero, answers
    are exact, and the pinned-zero overflow canary never moves."""
    shard0 = [
        k for k in (f"ov{i}" for i in range(2000))
        if engine._shard_of(f"mesh_{k}") == 0
    ][:40]
    assert len(shard0) == 40
    over0 = engine.metric_routed_overflows
    out = engine.process([req(k, limit=50) for k in shard0], now=NOW)
    assert all(r.error == "" and r.remaining == 49 for r in out)
    # Second tick on the same skewed window: state persisted on-shard.
    out = engine.process([req(k, limit=50) for k in shard0], now=NOW + 1)
    assert all(r.remaining == 48 for r in out)
    assert engine.metric_routed_overflows == over0 == 0


def test_ragged_extent_math_shard_counts():
    """Pure-host extent math at every interesting shard count —
    including 1, odd, prime, and >8 (no engine builds): counts sum to
    the live rows, offsets are their exact cumsum, and each shard's
    extent covers precisely its own rows of a slot-sorted batch."""
    from gubernator_tpu.parallel.partition import RaggedExtents

    rng = np.random.default_rng(17)
    for n_shards in (1, 2, 3, 5, 7, 8, 13):
        spec = RaggedExtents(n_shards, 64)
        sh = rng.integers(0, n_shards, 200)
        ok = rng.random(200) < 0.8
        counts = spec.counts(sh, ok)
        assert counts.sum() == ok.sum(), n_shards
        offs = spec.offsets(counts)
        assert offs[0] == 0 and offs[-1] == ok.sum()
        assert (np.diff(offs) == counts).all(), n_shards
        # Sorting live lanes by shard makes each extent exactly that
        # shard's rows — the invariant the on-device walker relies on
        # (global-slot sort implies shard sort: slot // cap ascends).
        sorted_sh = np.sort(sh[ok])
        for s in range(n_shards):
            ext = sorted_sh[offs[s]:offs[s + 1]]
            assert (ext == s).all(), (n_shards, s)
        # All-dead window: zero counts, all-zero offsets (the warmup
        # shape), never an exception.
        zero = spec.counts(sh, np.zeros(200, bool))
        assert (spec.offsets(zero) == 0).all()


def test_ragged_parity_fuzz_vs_single_chip(engine):
    """Randomized ragged-vs-single-chip decision parity on the module
    engine — skewed key mixes, duplicates, mixed algorithms, and an
    adversarial all-rows-on-one-shard window (the regime that used to
    fall back).  Decisions must match bit-for-bit; the overflow canary
    must never move."""
    from gubernator_tpu.ops.engine import TickEngine

    s_eng = TickEngine(capacity=2048, max_batch=64)
    rng = np.random.default_rng(23)
    over0 = engine.metric_routed_overflows
    windows = []
    for t in range(4):
        windows.append([
            RateLimitRequest(
                name="rf", unique_key=f"z{int(rng.zipf(1.4)) % 30}",
                hits=int(rng.integers(0, 3)), limit=40, duration=60_000,
                algorithm=int(rng.integers(0, 2)),
            )
            for _ in range(int(rng.integers(20, 64)))
        ])
    # Adversarial window: every key owned by one shard.
    hot = [
        k for k in (f"rfhot{i}" for i in range(2000))
        if engine._shard_of(f"rf_{k}") == engine.n_shards - 1
    ][:30]
    windows.append([
        RateLimitRequest(name="rf", unique_key=k, hits=1, limit=40,
                         duration=60_000)
        for k in hot
    ])
    def sweep(base):
        for t, reqs in enumerate(windows):
            a = engine.process(reqs, now=base + t * 500)
            b = s_eng.process(reqs, now=base + t * 500)
            for x, y in zip(a, b):
                assert (x.status, x.remaining, x.reset_time, x.error) == (
                    y.status, y.remaining, y.reset_time, y.error)

    sweep(NOW)
    # The same skewed windows again, now that every program they need
    # has run once: serving reuses them (no retrace, whatever the
    # extents), and every decision issued resolves exactly once —
    # hits + misses neither short of the rows sent (a dropped key) nor
    # over them (one served twice).
    traces = dict(engine.ops.trace_counts)
    resolved0 = engine.metric_hits + engine.metric_misses
    sweep(NOW + 10_000)
    assert dict(engine.ops.trace_counts) == traces
    assert engine.metric_hits + engine.metric_misses - resolved0 == sum(
        len(w) for w in windows)
    assert engine.metric_routed_overflows == over0 == 0


def test_mesh_store_write_and_read_through(store_engines):
    """Store on the sharded engine: on_change after every mutation,
    get() consulted on miss, remove() on eviction-by-reset."""
    store, (eng, eng2) = store_engines
    changes0 = store.called["OnChange()"]
    eng.process([req("st1", hits=2, limit=10)], now=NOW)
    assert store.called["OnChange()"] - changes0 == 1
    item = store.data["mesh_st1"]
    assert item["remaining"] == 8

    # An engine that never saw the key read-throughs the persisted
    # state on its miss.
    gets0 = store.called["Get()"]
    out = eng2.process([req("st1", hits=1, limit=10)], now=NOW + 1)[0]
    assert out.remaining == 7
    assert store.called["Get()"] - gets0 >= 1


def test_native_pass_leaves_a_store_miss_to_the_numpy_chain(store_engines):
    """On a Store-backed engine a window with a new key stops the native
    window pass at the miss (the Store is asked first: the numpy chain
    and ``_read_through``, from the slots the pass resolved); a window
    of known keys is the pass's, write-through and all."""
    store, (eng, eng2) = store_engines
    if eng._window_pass is None:
        pytest.skip("native slotmap library unavailable")
    reqs = [req("np1", hits=1, limit=10), req("np2", hits=2, limit=10)]
    packed0, gets0 = eng.metric_native_pack_windows, store.called["Get()"]
    out = eng.process(reqs, now=NOW)
    assert [r.remaining for r in out] == [9, 8]
    assert eng.metric_native_pack_windows == packed0
    assert store.called["Get()"] - gets0 == 2
    out = eng.process(reqs, now=NOW + 1)
    assert [r.remaining for r in out] == [8, 6]
    assert eng.metric_native_pack_windows == packed0 + 1
    assert store.called["Get()"] - gets0 == 2
    assert store.data["mesh_np1"]["remaining"] == 8
    assert store.data["mesh_np2"]["remaining"] == 6
    # the engine that never saw the keys reads them through, in numpy
    packed2 = eng2.metric_native_pack_windows
    out = eng2.process(reqs[:1], now=NOW + 2)
    assert out[0].remaining == 7
    assert eng2.metric_native_pack_windows == packed2


def test_mesh_store_via_instance_config():
    """The service layer no longer refuses Store + mesh shards."""
    import asyncio

    from gubernator_tpu.service.instance import InstanceConfig, V1Instance
    from gubernator_tpu.store import MockStore

    async def run():
        conf = InstanceConfig(
            cache_size=256, tpu_mesh_shards=2, store=MockStore(),
            tpu_max_batch=16,
        )
        inst = await V1Instance.create(conf)
        out = await inst.get_rate_limits([req("svc1", hits=1, limit=5)])
        assert out[0].remaining == 4
        assert conf.store.called["OnChange()"] >= 1
        await inst.close()

    asyncio.run(run())


def test_mesh_store_read_through_for_spilled_rows():
    """A block-overflow spill's fresh slot re-resolves as known=1 on the
    retry tick, but the device never wrote it — persisted state must
    still read-through for those rows."""
    from gubernator_tpu.store import MockStore

    store = MockStore()
    eng = MeshTickEngine(
        mesh=make_mesh(), local_capacity=32, max_batch=2, store=store
    )
    # Four keys that all route to one shard: with max_batch=2, two spill.
    shard0 = [
        k for k in (f"sp{i}" for i in range(200))
        if eng._shard_of(f"mesh_{k}") == 0
    ][:4]
    assert len(shard0) == 4
    for k in shard0:
        store.data[f"mesh_{k}"] = {
            "key": f"mesh_{k}", "algorithm": 0, "limit": 10, "remaining": 3,
            "remaining_f": 0.0, "duration": 60_000, "created_at": NOW,
            "updated_at": 0, "burst": 10, "status": 0,
            "expire_at": NOW + 60_000,
        }
    out = eng.process([req(k, hits=1, limit=10) for k in shard0], now=NOW)
    # Every response reflects the persisted remaining=3 minus this hit —
    # including the two spilled into the retry tick.
    assert [r.remaining for r in out] == [2, 2, 2, 2]


# ----------------------------------------------------------------------
# Elastic live resharding (docs/resharding.md)
# ----------------------------------------------------------------------
def test_layout_transition_spec_shard_counts():
    """Pure-spec n→m remap parity at every interesting shard count —
    including 1, odd, prime, and >8 (no engine builds).  The flat remap
    ``owner*cap_to + local`` must be the identity on global slots (the
    invariant that makes the on-device scatter lossless), owners must be
    ``g // cap_to``, and every live slot must land exactly once."""
    from gubernator_tpu.parallel.partition import plan_transition

    for n_to in (1, 2, 3, 5, 7, 8, 13):
        tr = plan_transition(8, 128, n_to)
        assert tr.cap_to == -(-tr.live_slots // n_to)
        rm = tr.remap()
        assert rm.shape == (tr.live_slots, 3)
        g = np.arange(tr.live_slots)
        assert (rm[:, 0] == g // tr.cap_to).all(), n_to
        assert (rm[:, 1] == g % tr.cap_to).all(), n_to
        # Identity on flat slots == bijection: no loss, no double-serve.
        assert (rm[:, 2] == g).all(), n_to
        assert (rm[:, 0] < n_to).all() and (rm[:, 1] < tr.cap_to).all()


def test_layout_transition_round_trip_identity():
    """8→3→8 must be the identity transition: chaining through ``then``
    threads the live-slot count, so the return leg re-derives the
    original per-shard capacity and the composed remap is ``g → g``."""
    from gubernator_tpu.parallel.partition import plan_transition

    tr = plan_transition(8, 128, 3)
    back = tr.then(8)
    assert back.n_to == 8 and back.cap_to == 128
    assert back.live_slots == tr.live_slots == 8 * 128
    assert (back.remap()[:, 2] == np.arange(back.live_slots)).all()


def test_layout_transition_validation():
    from gubernator_tpu.parallel.partition import plan_transition

    with pytest.raises(ValueError):
        plan_transition(0, 128, 4)
    with pytest.raises(ValueError):
        plan_transition(8, 128, 0)
    with pytest.raises(ValueError):
        plan_transition(8, 0, 4)
    with pytest.raises(ValueError):
        plan_transition(8, 128, 4, live_slots=8 * 128 + 1)


def test_relayout_dispatch_lossless_and_trace_stable(engine):
    """Dispatching the relayout collective (no cutover) must produce a
    flat table carrying every live row with identical state, and must
    not retrace any warmed serving program — the transition runs its own
    per-transition jit, never touching the serving widths."""
    from gubernator_tpu.parallel.partition import plan_transition

    engine.process([req(f"rl-{i}", limit=50) for i in range(40)], now=NOW)
    before = {it["key"]: it for it in engine.export_items()}
    traces = dict(engine.ops.trace_counts)
    tr = plan_transition(engine.n_shards, engine.local_capacity,
                         max(1, engine.n_shards // 2))
    flat = engine._dispatch_relayout(tr)
    items, n_live = engine._transition_items(flat)
    assert n_live == len(before)
    after = {it["key"]: it for it in items}
    assert after.keys() == before.keys()
    for k, it in after.items():
        assert it["remaining"] == before[k]["remaining"], k
        assert it["expire_at"] == before[k]["expire_at"], k
    # Serving still on the old layout, and the relayout dispatch did not
    # retrace any serving-width program (the satellite trace pin).
    out = engine.process([req(f"rl-{i}", limit=50) for i in range(40)],
                         now=NOW + 5)
    assert all(r.error == "" for r in out)
    now_traces = dict(engine.ops.trace_counts)
    now_traces.pop("relayout", None)
    traces.pop("relayout", None)
    assert now_traces == traces


@pytest.mark.slow
def test_mesh_reshard_round_trip_under_state():
    """Full 8→4→8 cutover on a dedicated engine: zero loss, value
    parity, zero routing-parity errors, serving resumes on both sides.
    Slow: each transition builds + warms a fresh shard set."""
    eng = MeshTickEngine(
        mesh=make_mesh(jax.devices()), local_capacity=64, max_batch=64
    )
    reqs = [req(f"rs-{i}", limit=100, duration=3_600_000)
            for i in range(150)]
    for s in range(0, len(reqs), 50):
        eng.process(reqs[s:s + 50], now=NOW)
    keys = sorted(it["key"] for it in eng.export_items())
    info = eng.reshard(4, now=NOW + 10)
    assert info["live_items"] == len(keys) and eng.n_shards == 4
    assert sorted(it["key"] for it in eng.export_items()) == keys
    assert eng.routing_parity_errors(keys) == 0
    out = eng.process(reqs[:20], now=NOW + 20)
    assert all(r.error == "" for r in out)
    info = eng.reshard(8, now=NOW + 30)
    assert info["to_shards"] == 8
    assert sorted(it["key"] for it in eng.export_items()) == keys
    assert eng.routing_parity_errors(keys) == 0
    assert eng.reshard(8, now=NOW + 40)["noop"] is True
