"""GLOBAL mesh-collective data plane tests.

The reconcile step must reproduce the observable semantics of the
reference's sendHits + broadcastPeers loops (global.go:91-283) — hit
aggregation, DRAIN_OVER_LIMIT forcing, RESET_REMAINING OR-folding, owner
authority, replica overwrite — with psum/all_gather instead of RPC fans.
The final test proves parity against the real gRPC path on the in-process
cluster.
"""

import asyncio

import pytest

from gubernator_tpu.parallel.global_mesh import (
    MeshGlobalEngine,
    make_global_mesh,
)
from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    RateLimitRequest,
    Status,
)

NOW = 1_700_000_000_000


def req(key="gk", hits=1, limit=100, duration=60_000, **kw):
    kw.setdefault("behavior", Behavior.GLOBAL)
    return RateLimitRequest(
        name="gm", unique_key=key, hits=hits, limit=limit, duration=duration,
        created_at=NOW, **kw,
    )


@pytest.fixture(scope="module")
def engine():
    return MeshGlobalEngine(mesh=make_global_mesh(4), capacity=64, max_batch=32)


def owner_of(engine, key):
    slot = engine.slots.get(key)
    assert slot is not None
    return slot // (engine.capacity // engine.n_nodes)


def test_local_answers_then_reconcile_sums_hits(engine):
    # Two nodes observe hits on the same key; each answers from its own
    # replica (non-owner local answer, gubernator.go:395-421)...
    out1 = engine.process([req(key="sum", hits=3)], node_idx=1, now=NOW)
    assert out1[0].status == Status.UNDER_LIMIT and out1[0].remaining == 97
    out2 = engine.process([req(key="sum", hits=4)], node_idx=2, now=NOW)
    assert out2[0].remaining == 96  # node 2's replica never saw node 1's hits

    # ...and the collective reconcile lands the *sum* on the authority and
    # overwrites every replica with the authoritative result.
    engine.reconcile(now=NOW + 10)
    views = engine.peek(engine_key("sum"))
    assert all(v["in_use"] for v in views)
    assert [v["remaining"] for v in views] == [93] * engine.n_nodes


def engine_key(key):
    return "gm_" + key


def test_owner_direct_hits_are_authoritative(engine):
    # First touch assigns the slot; find the owning node.
    engine.process([req(key="own", hits=0)], node_idx=0, now=NOW)
    own = owner_of(engine, engine_key("own"))
    other = (own + 1) % engine.n_nodes

    out = engine.process([req(key="own", hits=5)], node_idx=own, now=NOW)
    assert out[0].remaining == 95
    engine.process([req(key="own", hits=3)], node_idx=other, now=NOW)
    engine.reconcile(now=NOW + 10)
    views = engine.peek(engine_key("own"))
    # Owner's direct drain (5) + psum'd remote hits (3).
    assert [v["remaining"] for v in views] == [92] * engine.n_nodes


def test_aggregate_overdraw_drains_to_zero(engine):
    # Forwarded GLOBAL hits are applied with DRAIN_OVER_LIMIT forced
    # (gubernator.go:510-512): an aggregate over-ask empties the bucket.
    engine.process([req(key="drain", hits=6, limit=10)], node_idx=1, now=NOW)
    engine.process([req(key="drain", hits=6, limit=10)], node_idx=2, now=NOW)
    engine.reconcile(now=NOW + 10)
    views = engine.peek(engine_key("drain"))
    assert [v["remaining"] for v in views] == [0] * engine.n_nodes
    assert all(v["in_use"] for v in views)


def test_reset_remaining_folds_across_nodes(engine):
    engine.process([req(key="rst", hits=9, limit=10)], node_idx=1, now=NOW)
    engine.reconcile(now=NOW + 10)
    assert engine.peek(engine_key("rst"))[0]["remaining"] == 1
    # A RESET_REMAINING hit queued on any node resets the authority
    # (global.go:105-110 ORs the behavior into the aggregated request).
    engine.process(
        [req(key="rst", hits=1, limit=10,
             behavior=Behavior.GLOBAL | Behavior.RESET_REMAINING)],
        node_idx=2, now=NOW + 20,
    )
    engine.reconcile(now=NOW + 30)
    views = engine.peek(engine_key("rst"))
    # Token-bucket RESET removes the item (algorithms.go:78-90).
    assert all(not v["in_use"] for v in views)


def test_leaky_bucket_global(engine):
    r = lambda h, n: req(key="lk", hits=h, limit=10, duration=10_000,
                         algorithm=Algorithm.LEAKY_BUCKET)
    engine.process([r(2, 1)], node_idx=1, now=NOW)
    engine.process([r(3, 2)], node_idx=2, now=NOW)
    engine.reconcile(now=NOW + 1)
    views = engine.peek(engine_key("lk"))
    assert [v["remaining_f"] for v in views] == [5.0] * engine.n_nodes


def test_new_key_created_at_owner_via_reconcile(engine):
    # The owner node never sees the request; reconcile must create the
    # bucket there from the psum'd hits (the reference owner creating the
    # item on first forwarded hit).
    engine.process([req(key="fresh", hits=2, limit=50)], node_idx=3, now=NOW)
    own = owner_of(engine, engine_key("fresh"))
    views = engine.peek(engine_key("fresh"))
    if own != 3:
        assert not views[own]["in_use"]  # owner hasn't seen it yet
    engine.reconcile(now=NOW + 5)
    views = engine.peek(engine_key("fresh"))
    assert [v["remaining"] for v in views] == [48] * engine.n_nodes


def test_second_window_applies_only_new_hits(engine):
    engine.process([req(key="win", hits=10)], node_idx=1, now=NOW)
    engine.reconcile(now=NOW + 10)
    assert engine.peek(engine_key("win"))[0]["remaining"] == 90
    # An empty window must not re-apply anything.
    engine.reconcile(now=NOW + 20)
    assert engine.peek(engine_key("win"))[0]["remaining"] == 90
    engine.process([req(key="win", hits=5)], node_idx=2, now=NOW + 25)
    engine.reconcile(now=NOW + 30)
    assert engine.peek(engine_key("win"))[0]["remaining"] == 85


def test_batched_mixed_nodes_one_tick(engine):
    # process_blocks lands every node's window in one SPMD launch.
    blocks = [
        [req(key=f"mix-{i}", hits=1, limit=9) for i in range(3)]
        for _ in range(engine.n_nodes)
    ]
    out = engine.process_blocks(blocks, now=NOW)
    assert all(r.remaining == 8 for blk in out for r in blk)
    engine.reconcile(now=NOW + 10)
    for i in range(3):
        views = engine.peek(engine_key(f"mix-{i}"))
        # Each key hit once per node; owner's hit direct + (n-1) via psum.
        want = 9 - engine.n_nodes
        assert [v["remaining"] for v in views] == [want] * engine.n_nodes


async def test_parity_with_grpc_reconciliation():
    """The collective path must land on the same authoritative state as the
    gRPC protocol (sendHits → owner apply → broadcast) for the same hits."""
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.config import BehaviorConfig
    from tests.helpers import warm_global_path

    name, key = "parity", "pk"
    hits_a, hits_b, limit = 10, 20, 100

    # gRPC path: two non-owners take hits; wait for reconciliation.
    behaviors = BehaviorConfig(global_sync_wait=0.05, batch_wait=0.002)
    c = await Cluster.start(3, behaviors=behaviors)
    try:
        owner = c.find_owning_daemon(name, key)
        non = c.list_non_owning_daemons(name, key)
        # Both non-owners' flush paths meet their first compile with no
        # deadline (helpers.warm_global_path) before the hits that count.
        await warm_global_path(c, name, owner, *non)
        ca, cb = non[0].client(), non[1].client()
        g = lambda h: RateLimitRequest(
            name=name, unique_key=key, hits=h, limit=limit,
            duration=60_000, behavior=Behavior.GLOBAL,
        )
        await ca.get_rate_limits([g(hits_a)])
        await cb.get_rate_limits([g(hits_b)])

        async def owner_settled():
            while True:
                oc = owner.client()
                resp = await oc.get_rate_limits([g(0)])
                await oc.close()
                if resp[0].remaining == limit - hits_a - hits_b:
                    return resp[0]
                await asyncio.sleep(0.02)

        grpc_final = await asyncio.wait_for(owner_settled(), timeout=60.0)
        await ca.close()
        await cb.close()
    finally:
        await c.stop()

    # Collective path: same hits, two mesh nodes, one reconcile.
    eng = MeshGlobalEngine(mesh=make_global_mesh(3), capacity=48, max_batch=16)
    r = lambda h: RateLimitRequest(
        name=name, unique_key=key, hits=h, limit=limit, duration=60_000,
        behavior=Behavior.GLOBAL, created_at=NOW,
    )
    eng.process([r(hits_a)], node_idx=1, now=NOW)
    eng.process([r(hits_b)], node_idx=2, now=NOW)
    eng.reconcile(now=NOW + 10)
    views = eng.peek(f"{name}_{key}")

    assert grpc_final.remaining == limit - hits_a - hits_b
    assert [v["remaining"] for v in views] == [grpc_final.remaining] * 3
    assert all(v["status"] == grpc_final.status for v in views)


async def test_cluster_global_mesh_service_path():
    """Full service stack with the collectives data plane: GLOBAL requests
    on any daemon ride the shared mesh engine and reconcile without any
    peer RPC (the gRPC hits/broadcast loops are bypassed)."""
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.config import BehaviorConfig

    behaviors = BehaviorConfig(global_sync_wait=0.03, batch_wait=0.002)
    c = await Cluster.start(3, behaviors=behaviors, global_mesh=True)
    try:
        g = lambda h: RateLimitRequest(
            name="meshsvc", unique_key="mk", hits=h, limit=100,
            duration=60_000, behavior=Behavior.GLOBAL,
        )
        c0, c1, c2 = (d.client() for d in c.daemons)
        out = await c0.get_rate_limits([g(5)])
        assert out[0].error == "" and out[0].remaining == 95
        out = await c1.get_rate_limits([g(7)])
        # 93 if c1's replica hasn't absorbed c0's hits yet, 88 if the
        # reconcile loop fired in between — both are correct non-owner
        # local answers; convergence is asserted below.
        assert out[0].error == "" and out[0].remaining in (93, 88)

        # The reconcile loops land the sum on every node's replica.
        async def synced():
            while True:
                resp = await c2.get_rate_limits([g(0)])
                if resp[0].remaining == 88:
                    return
                await asyncio.sleep(0.02)

        await asyncio.wait_for(synced(), timeout=5.0)
        # No peer RPC was issued for GLOBAL traffic: the engine reconciled
        # on-device (metric proves the loop ran).
        assert c.daemons[0].instance.global_mesh.metric_reconciles > 0
        for cl in (c0, c1, c2):
            await cl.close()
    finally:
        await c.stop()


# ----------------------------------------------------------------------
# Sparse reconcile (envelope-compacted collectives)
# ----------------------------------------------------------------------
def _drive(eng, rng, windows=4, keys=24):
    """Random GLOBAL traffic across nodes and windows, reconciling after
    each window; returns all responses."""
    out = []
    for w in range(windows):
        blocks = []
        for d in range(eng.n_nodes):
            n = int(rng.integers(1, 8))
            blocks.append([
                req(
                    key=f"sk{int(rng.integers(0, keys))}",
                    hits=int(rng.integers(1, 4)),
                    limit=50,
                    behavior=(
                        Behavior.GLOBAL | Behavior.RESET_REMAINING
                        if rng.random() < 0.1 else Behavior.GLOBAL
                    ),
                )
                for _ in range(n)
            ])
        out.append(eng.process_blocks(blocks, now=NOW + w * 1000))
        eng.reconcile(now=NOW + w * 1000 + 500)
    return out


def _full_state(eng):
    import numpy as np

    from gubernator_tpu.ops.buckets import np_logical, slice_field

    return {
        name: np_logical(
            slice_field(getattr(eng.state, name), (slice(None),)), name
        )
        for name in ("remaining", "remaining_f", "status", "in_use",
                     "limit", "expire_at")
    }


def test_sparse_reconcile_matches_dense():
    """Same traffic through a dense engine and a sparse one: identical
    responses and identical replicated state (hit/touched slots restored
    everywhere; untouched slots never moved)."""
    import numpy as np

    dense = MeshGlobalEngine(
        mesh=make_global_mesh(4), capacity=256, max_batch=32, sparse_k=0)
    sparse = MeshGlobalEngine(
        mesh=make_global_mesh(4), capacity=256, max_batch=32, sparse_k=32)
    r1 = _drive(dense, np.random.default_rng(7))
    r2 = _drive(sparse, np.random.default_rng(7))
    for w1, w2 in zip(r1, r2):
        for b1, b2 in zip(w1, w2):
            for a, b in zip(b1, b2):
                assert (a.status, a.remaining, a.reset_time) == (
                    b.status, b.remaining, b.reset_time)
    s1, s2 = _full_state(dense), _full_state(sparse)
    for name in s1:
        np.testing.assert_array_equal(s1[name], s2[name], err_msg=name)


def test_sparse_overflow_falls_back_dense():
    """Windows wider than the envelope take the in-program dense branch —
    results still match a dense engine exactly."""
    import numpy as np

    dense = MeshGlobalEngine(
        mesh=make_global_mesh(4), capacity=256, max_batch=64, sparse_k=0)
    tiny = MeshGlobalEngine(
        mesh=make_global_mesh(4), capacity=256, max_batch=64, sparse_k=4)
    for eng in (dense, tiny):
        rng = np.random.default_rng(11)
        blocks = [
            [req(key=f"ov{int(rng.integers(0, 40))}", hits=1, limit=30)
             for _ in range(20)]
            for _ in range(eng.n_nodes)
        ]
        eng.process_blocks(blocks, now=NOW)
        eng.reconcile(now=NOW + 10)
    s1, s2 = _full_state(dense), _full_state(tiny)
    for name in s1:
        np.testing.assert_array_equal(s1[name], s2[name], err_msg=name)


# ----------------------------------------------------------------------
# Fused probe+reconcile (one envelope gather per step)
# ----------------------------------------------------------------------
def _window(eng, rng, keys, width):
    """One random GLOBAL window: ``width`` requests per node over a
    ``keys``-key space (width > sparse_k forces envelope overflow)."""
    return [
        [
            req(
                key=f"fz{int(rng.integers(0, keys))}",
                hits=int(rng.integers(1, 4)),
                limit=10_000,
                behavior=(
                    Behavior.GLOBAL | Behavior.RESET_REMAINING
                    if rng.random() < 0.08 else Behavior.GLOBAL
                ),
            )
            for _ in range(width)
        ]
        for _ in range(eng.n_nodes)
    ]


def test_fused_sparse_step_parity_fuzz():
    """The fused program's (overflow bool, gathered envelope, post-step
    table) must match the unfused two-program path — probe, then sparse
    step or dense fallback — window for window, including overflowing
    windows that exercise the fallback."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gubernator_tpu.parallel.global_mesh import (
        ACC_COUNT,
        ACC_TOUCH,
        AUX_ROWS,
        make_global_overflow_fn,
        make_global_reconcile_fn,
        make_global_sparse_step_fn,
    )

    K = 8
    eng = MeshGlobalEngine(
        mesh=make_global_mesh(4), capacity=256, max_batch=64, sparse_k=K)
    n, cap = eng.n_nodes, eng.capacity
    # The unfused reference pair (non-donating jits: inputs stay live so
    # both paths run from identical buffers), plus the strict dense
    # program the engine itself uses for the fallback.
    probe = jax.jit(make_global_overflow_fn(eng.mesh, cap, n, K))
    old_sparse = jax.jit(
        make_global_reconcile_fn(eng.mesh, cap, n, sparse_k=K))
    old_dense = jax.jit(make_global_reconcile_fn(eng.mesh, cap, n, True))
    fused = jax.jit(
        make_global_sparse_step_fn(eng.mesh, cap, n, K, with_envelope=True))

    NW = 4 + len(AUX_ROWS)
    rng = np.random.default_rng(3)
    saw_overflow = saw_sparse = False
    for w in range(6):
        width = 20 if w in (2, 4) else 3   # wide windows overflow K=8
        t = NOW + w * 1000
        eng.process_blocks(_window(eng, rng, keys=40, width=width), now=t)

        # Unfused reference path.
        over_old = bool(np.asarray(probe(eng.accum)))
        st_old, acc_old = (old_dense if over_old else old_sparse)(
            eng.state, eng.aux, eng.accum, jnp.int64(t))

        # Fused path on the same inputs.
        st_new, acc_new, over_new, W = fused(
            eng.state, eng.aux, eng.accum, jnp.int64(t))
        assert bool(np.asarray(over_new)) == over_old
        W = np.asarray(W)

        # Envelope contents: the gathered per-node window/touch sets and
        # probe counts must equal a host-side recomputation from the
        # accumulators.
        acc_h = np.asarray(eng.accum)
        for d in range(n):
            for row, acc_row in ((0, ACC_COUNT), (NW, ACC_TOUCH)):
                mask = acc_h[d, acc_row] > 0
                slots = np.flatnonzero(mask)[:K]
                want = np.full(K, cap)
                want[: len(slots)] = slots
                np.testing.assert_array_equal(
                    W[d, row], want, err_msg=f"node {d} row {row}")
            assert W[d, NW + 1, 0] == int((acc_h[d, ACC_COUNT] > 0).sum())
            assert W[d, NW + 2, 0] == int((acc_h[d, ACC_TOUCH] > 0).sum())

        if over_old:
            saw_overflow = True
            # The fused step must hand back untouched buffers for the
            # host's dense fallback...
            for a, b in zip(jax.tree.leaves(st_new),
                            jax.tree.leaves(eng.state)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(acc_new), acc_h)
            # ...and fallback-on-returned-buffers equals the old path.
            st_new, acc_new = old_dense(
                st_new, eng.aux, acc_new, jnp.int64(t))
        else:
            saw_sparse = True
        for a, b in zip(jax.tree.leaves(st_new), jax.tree.leaves(st_old)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(acc_new),
                                      np.asarray(acc_old))

        # Advance the engine through its own (fused) reconcile and check
        # it landed on the same state.
        eng.reconcile(now=t)
        for a, b in zip(jax.tree.leaves(eng.state), jax.tree.leaves(st_old)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert saw_overflow and saw_sparse


def test_reconcile_dispatch_counter():
    """One mesh program per non-overflowing sparse step (the fused
    probe), two for an overflowing step (fused probe + dense fallback):
    the exact count the reconcile-dispatch Prometheus counter reads."""
    import numpy as np

    eng = MeshGlobalEngine(
        mesh=make_global_mesh(4), capacity=256, max_batch=64, sparse_k=8)
    rng = np.random.default_rng(5)

    eng.process_blocks(_window(eng, rng, keys=40, width=3), now=NOW)
    d0, f0 = eng.metric_reconcile_dispatches, eng.metric_dense_fallbacks
    eng.reconcile(now=NOW + 10)
    assert eng.metric_reconcile_dispatches == d0 + 1
    assert eng.metric_dense_fallbacks == f0

    eng.process_blocks(_window(eng, rng, keys=40, width=30), now=NOW + 20)
    eng.reconcile(now=NOW + 30)
    assert eng.metric_reconcile_dispatches == d0 + 3
    assert eng.metric_dense_fallbacks == f0 + 1

    # Dense-only engines: one program per step, by construction.
    dense = MeshGlobalEngine(
        mesh=make_global_mesh(4), capacity=256, max_batch=32, sparse_k=0)
    dense.process_blocks(_window(dense, rng, keys=20, width=3), now=NOW)
    dense.reconcile(now=NOW + 10)
    assert dense.metric_reconcile_dispatches == 1
    assert dense.metric_reconciles == 1
