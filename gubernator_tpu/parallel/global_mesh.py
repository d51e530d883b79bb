"""GLOBAL reconciliation as mesh collectives: the TPU-native data plane.

The reference reconciles GLOBAL rate limits with two O(peers) RPC fans
(``global.go``): **sendHits** — every non-owner aggregates observed hits per
key and unicasts them to each key's owner (``global.go:144-187``) — and
**broadcastPeers** — every owner pushes authoritative state to every other
peer (``global.go:234-283``).  When the "peers" are shards of one TPU mesh
(chips of a host, or hosts of a multi-host ICI/DCN mesh), both fans collapse
into collectives on the device:

* Each node keeps a **full replica** of the GLOBAL bucket table (the analog
  of the reference's non-owner local cache answering GLOBAL requests,
  ``gubernator.go:395-421``) plus a per-node **hit accumulator** (the analog
  of ``globalManager.hits``, ``global.go:99-112``).
* Slot ownership is by contiguous range: node ``d`` owns slots
  ``[d*capacity/n, (d+1)*capacity/n)`` — the mesh analog of consistent-hash
  key ownership.
* One **reconcile step** (the 100ms ``GlobalSyncWait`` cadence) runs as a
  single SPMD program:

  1. ``all_gather`` the hit accumulators over the mesh and fold each node's
     window into the authority in node order (or ``psum`` them into one
     application when strict sequencing is waived).  This *is* sendHits: a
     keyed reduction instead of O(peers) unicasts.
  2. ``all_gather`` the per-node authoritative slices into a fresh
     replicated base table.  This *is* broadcastPeers: one replication step
     instead of O(peers^2) pushes.
  3. Apply the summed hits to the base via the same branch-free
     ``bucket_transition`` every request takes, with DRAIN_OVER_LIMIT forced
     (the reference forces it on forwarded GLOBAL hits,
     ``gubernator.go:510-512``) and RESET_REMAINING OR-folded across nodes
     (``global.go:105-110``).  Every node computes the identical result, so
     replicas re-synchronize with zero additional traffic.

Between reconciles each node answers GLOBAL requests from its own replica
(and applies them locally — the reference's non-owner drains its local
cache copy too, ``getLocalRateLimit`` with IsOwner=false), while hits on
slots the node doesn't own are scatter-added into its accumulator.  Hits on
*owned* slots mutate the authoritative slice directly, matching the
reference's owner path (``gubernator.go:604-606`` applies then broadcasts).

Request parameters for the aggregated application (limit/duration/behavior/
created_at of the *latest* request per slot, matching the reference keeping
the queued request proto and summing hits into it) ride a per-node aux
table; the winner across nodes is picked with a ``pmax`` over write stamps.

gRPC remains the reconciliation transport only *across* meshes (separate
clusters / DCs) — within a mesh no RPC is issued at all.

**Scaling envelope (read before raising ``capacity``).**  The DENSE
reconcile all-gathers the (ACC_ROWS, capacity) accumulators plus the
per-node authoritative slices and applies ``bucket_transition`` to every
slot — O(capacity · n_nodes) device work and ICI traffic per step,
independent of how many slots were actually hit.  That form is the
default up to 2^16 slots: one fused pass, no sparsity bookkeeping, and
at the reference's GLOBAL keyspace (its defaults cap the whole cache at
50K items, config.go:139) a dense 64K-slot reconcile is ~25 MB of
collective traffic every 100 ms — microseconds of a v5e ICI's
~10 GB/s/link.

Past that, the SPARSE reconcile takes over (``sparse_k`` envelope,
auto-enabled above 2^16 slots): each node compacts its hit window and
touched-slot set device-side, the collectives move those envelopes —
O(hits · n_nodes) ICI bytes — owners apply the windows to their
authoritative rows with K-row gather/scatter, and only changed rows
re-broadcast.  Reconcile cost then scales with traffic, not table size,
lifting the envelope to multi-million-slot GLOBAL tables (hard cap
2^24).  The overflow probe is FUSED into the sparse program — the step
compacts and gathers its envelope once and emits the probe bool
alongside the update — and an overflowing step applies nothing and
falls back to the dense pass (host dispatch), so the envelope is a
performance knob, never a correctness one.  Each node still holds a
full replica (~100 B/slot) — HBM, not ICI, bounds capacity.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Sequence

import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from gubernator_tpu.parallel.partition import NodeLayout

# The canonical GLOBAL-mesh placement: one replica row per node,
# reconciled with psum collectives only (partition.py is the single
# source of every PartitionSpec both mesh engines place data with).
NODE_LAYOUT = NodeLayout()

from gubernator_tpu.ops.buckets import (
    BucketState,
    ReqBatch,
    bucket_transition,
    gather_state,
    logical_view,
    np_logical,
    scatter_state,
    slice_field,
    stored_view,
)
from gubernator_tpu.ops.engine import (
    REQ_ROWS,
    REQ_ROW_INDEX,
    pack_request_matrix,
    _slot_segments,
    make_slot_map,
    resolve_gregorian,
    unpack_reqs,
)
from gubernator_tpu.types import (
    Behavior,
    RateLimitRequest,
    RateLimitResponse,
)
from gubernator_tpu.utils import timeutil
from gubernator_tpu.utils import sanitize

I64 = jnp.int64
I32 = jnp.int32

# Aux rows: per-slot, per-node snapshot of the latest request's parameters —
# the mesh analog of the queued RateLimitReq the reference ships to owners
# (global.go:99-112 keeps the first request and sums hits into it; we keep
# the latest, which matches the reference's queue_update replacement
# semantics and lets limit changes propagate).
AUX_ROWS = (
    "limit", "duration", "algorithm", "behavior", "burst",
    "greg_exp", "greg_dur", "created_at", "stamp",
)
AUX = {name: i for i, name in enumerate(AUX_ROWS)}

# Accumulator rows (global.go:99-112's per-key aggregation, as dense arrays).
# ACC_TOUCH counts EVERY local application (owned, non-owned, queries):
# the sparse reconcile derives its restore/re-broadcast sets from it —
# which replica rows diverged provisionally, which owned rows were
# written directly.
ACC_HITS, ACC_RESET, ACC_COUNT, ACC_TOUCH = 0, 1, 2, 3
ACC_ROWS = 4


def make_global_mesh(n_nodes: Optional[int] = None,
                     devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D mesh over the 'node' axis (one device = one logical peer)."""
    if devices is None:
        devices = jax.devices()
        if n_nodes is not None:
            if len(devices) < n_nodes:
                raise ValueError(
                    f"global mesh needs {n_nodes} devices, "
                    f"have {len(devices)}"
                )
            devices = devices[:n_nodes]
    return Mesh(np.array(list(devices)), ("node",))


def make_global_process_fn(mesh: Mesh, capacity: int, n_nodes: int,
                           track_touch: bool = False):
    """Per-node GLOBAL request application + hit accumulation.

    ``state``/``aux``/``accum`` carry one replica row per node (sharded over
    'node'); ``reqs`` is ``(n_nodes, len(REQ_ROWS), B)`` — block *d* holds
    the requests that arrived at node *d* this window.

    ``track_touch`` maintains the ACC_TOUCH row the sparse reconcile
    needs; dense-only engines skip it (the int64 scatter-add is the
    most expensive op in this program, and the dense step never reads
    the row).
    """
    slice_sz = capacity // n_nodes

    def _local(state_blk, aux_blk, accum_blk, reqs_blk, now, stamp):
        st = jax.tree.map(lambda a: a[0], state_blk)
        aux = aux_blk[0]
        acc = accum_blk[0]
        r = unpack_reqs(reqs_blk[0])
        my = lax.axis_index("node")

        rank, group_size, _, _ = _slot_segments(r.slot, r.valid, capacity)
        n_rounds = jnp.max(jnp.where(r.valid, rank, 0)) + 1
        b = r.slot.shape[0]
        resp0 = (
            jnp.zeros(b, I32), jnp.zeros(b, I64), jnp.zeros(b, I64),
            jnp.zeros(b, I64), jnp.zeros(b, jnp.bool_),
        )

        def cond(carry):
            k, _, _ = carry
            return k < n_rounds

        def body(carry):
            k, st, resp = carry
            active = r.valid & (rank == k)
            gathered = gather_state(st, r.slot)
            new_g, r_out = bucket_transition(now, gathered, r)
            scat = jnp.where(active, r.slot, capacity)
            st = scatter_state(st, scat, new_g)
            new_resp = (r_out.status, r_out.limit, r_out.remaining,
                        r_out.reset_time, r_out.over_limit)
            resp = tuple(
                jnp.where(active, n, o) for n, o in zip(new_resp, resp)
            )
            return k + 1, st, resp

        _, st, resp = lax.while_loop(cond, body, (jnp.int32(0), st, resp0))

        # Aux params: one last-writer scatter per tick, not one per round —
        # every round would write the same per-slot "latest request" row the
        # final rank writes anyway, and the (9, B) int64 scatter is the
        # most expensive op in the program.
        aux_vals = jnp.stack([
            r.limit, r.duration, r.algorithm.astype(I64),
            r.behavior.astype(I64), r.burst, r.greg_exp, r.greg_dur,
            r.created_at, jnp.full_like(r.limit, stamp),
        ])
        tail = r.valid & (rank == group_size - 1)
        aux = aux.at[:, jnp.where(tail, r.slot, capacity)].set(
            aux_vals, mode="drop"
        )

        # Hit accumulation for non-owned slots (global.go:99-112): sum hits,
        # OR RESET_REMAINING, count contributions.  Zero-hit queries are not
        # queued (global.go:74-78).  Order-independent → one scatter-add.
        # int64 accumulators: narrowing to int32 would wrap (not saturate)
        # under accumulated hits across a window — a credit-instead-of-
        # drain bypass — so the slower 64-bit scatter-add stays.
        owned = (r.slot // slice_sz) == my.astype(I32)
        queue = r.valid & ~owned & (r.hits != 0)
        qslot = jnp.where(queue, r.slot, capacity)
        reset = queue & ((r.behavior & Behavior.RESET_REMAINING) != 0)
        touch = acc[ACC_TOUCH]
        if track_touch:
            tslot = jnp.where(r.valid, r.slot, capacity)
            touch = touch.at[tslot].add(r.valid.astype(I64), mode="drop")
        acc = jnp.stack([
            acc[ACC_HITS].at[qslot].add(jnp.where(queue, r.hits, 0), mode="drop"),
            acc[ACC_RESET].at[qslot].add(reset.astype(I64), mode="drop"),
            acc[ACC_COUNT].at[qslot].add(queue.astype(I64), mode="drop"),
            touch,
        ])

        packed = jnp.stack([
            resp[0].astype(I64), resp[1], resp[2], resp[3],
            resp[4].astype(I64),
        ])
        return (
            jax.tree.map(lambda a: a[None], st),
            aux[None],
            acc[None],
            packed[None],
        )

    state_spec = NODE_LAYOUT.replica_spec()
    return shard_map(
        _local,
        mesh=mesh,
        in_specs=(state_spec, P("node", None, None), P("node", None, None),
                  P("node", None, None), P(), P()),
        out_specs=(state_spec, P("node", None, None), P("node", None, None),
                   P("node", None, None)),
        check_vma=False,
    )


def _make_compact(capacity: int):
    """Compactor: first ``width`` set slots of a mask (slot order), padded
    with ``capacity``; overflow rows drop (the overflow probe rejects
    such steps before sparse results are used)."""
    def compact(mask, width):
        arange_c = jnp.arange(capacity, dtype=I32)
        rank = jnp.cumsum(mask.astype(I32)) - 1
        tgt = jnp.where(mask & (rank < width), rank, width)
        return jnp.full(width + 1, capacity, I32).at[tgt].set(
            arange_c, mode="drop")[:width]

    return compact


def _make_gather_rows(n_nodes: int, my):
    """all_gather-by-one-hot-psum over 'node' (the one collective this
    toolchain is guaranteed to lower; see make_global_reconcile_fn)."""
    def gather_rows(x):
        buf = jnp.zeros((n_nodes,) + x.shape, x.dtype).at[my].set(x)
        return lax.psum(buf, "node")

    return gather_rows


def _sparse_sets(acc_me, compact, K: int):
    """The sparse step's working sets, derived ONCE here for both the
    overflow probe and the sparse program — any drift between the two
    would let an overflowing step run the truncating sparse path, so
    they must share this function: (wmask, tmask, wslots, tslots)."""
    wmask = acc_me[ACC_COUNT] > 0      # my queued-hit window
    tmask = acc_me[ACC_TOUCH] > 0      # every slot I wrote locally
    return wmask, tmask, compact(wmask, K), compact(tmask, K)


def _mark_touched(capacity: int, n_nodes: int, slot_sets):
    """Union of every node's compacted slot sets as a capacity mask
    (``slot_sets``: (n, m, K) — padding rows carry ``capacity`` and
    drop)."""
    touched = jnp.zeros(capacity, jnp.bool_)
    m = slot_sets.shape[1]

    def mark(d, t):
        for j in range(m):
            t = t.at[slot_sets[d, j]].set(True, mode="drop")
        return t

    return lax.fori_loop(0, n_nodes, mark, touched)


def make_global_overflow_fn(mesh: Mesh, capacity: int, n_nodes: int,
                            sparse_k: int):
    """Envelope probe for the sparse reconcile: (accum) → replicated
    bool, True when this step's windows, touch sets, or any owner's
    re-broadcast share exceed the sparse envelopes — the caller then
    runs the dense program instead (host dispatch; see
    make_global_reconcile_fn).

    The serving engine no longer dispatches this probe: the fused step
    (:func:`make_global_sparse_step_fn`) computes the same bool inside
    the sparse program itself, from the same compacted sets, so the
    envelope is gathered ONCE per step instead of twice.  This program
    stays as the reference half of the unfused two-program pair the
    parity fuzz tests run against the fused step."""
    slice_sz = capacity // n_nodes
    K, K2 = int(sparse_k), 2 * int(sparse_k)

    def _probe(accum_blk):
        my = lax.axis_index("node")
        acc_me = accum_blk[0]
        owned = (jnp.arange(capacity, dtype=I32) // slice_sz) == my.astype(I32)
        gather_rows = _make_gather_rows(n_nodes, my)
        wmask, tmask, wslots, tslots = _sparse_sets(
            acc_me, _make_compact(capacity), K)
        counts = gather_rows(jnp.stack([
            jnp.count_nonzero(wmask), jnp.count_nonzero(tmask)]))
        sets = gather_rows(jnp.stack([wslots, tslots]))   # (n, 2, K)
        touched = _mark_touched(capacity, n_nodes, sets)
        bcounts = gather_rows(jnp.count_nonzero(touched & owned))
        return (jnp.max(counts) > K) | (jnp.max(bcounts) > K2)

    return shard_map(
        _probe,
        mesh=mesh,
        in_specs=(P("node", None, None),),
        out_specs=P(),
        check_vma=False,
    )


def make_global_sparse_step_fn(mesh: Mesh, capacity: int, n_nodes: int,
                               sparse_k: int, strict_sequencing: bool = True,
                               with_envelope: bool = False):
    """The FUSED sparse reconcile: overflow probe + sparse step as one
    mesh program — (state, aux, accum, now) → (state', accum', overflow).

    The unfused pair (make_global_overflow_fn + the sparse branch of
    make_global_reconcile_fn) compacts the per-node (window, touch) sets
    and all-gathers them TWICE per step: once for the probe's envelope
    counts, then again for the actual reconcile — paying the compaction
    (an O(capacity) cumsum per set) and the set-gather collective twice
    for the same bytes.  Here the step compacts once, rides the probe's
    counts on two extra rows of the ONE envelope gather, and derives the
    overflow bool in-program.  Per-owner re-broadcast shares need no
    collective at all: the gathered touch union is replicated, so every
    node counts every owner's K2 share from its own copy.

    Overflow steps must not apply truncated envelopes, and an in-program
    cond would re-impose the O(capacity) copy the sparse step removes
    (see make_global_reconcile_fn) — instead the bool gates every
    scatter (indices aim at the drop row) and the accumulator zeroing,
    so an overflowing step returns ``state``/``accum`` bit-unchanged and
    the host runs the rare dense fallback on them: one program per
    normal step, two per overflowing step, never a wasted gather.

    ``with_envelope`` additionally returns the gathered
    ``(n_nodes, 4 + len(AUX_ROWS) + 3, K)`` envelope (windows + touch
    sets + probe counts) — the parity tests' window into what crossed
    the mesh; the serving engine leaves it off.

    ``strict_sequencing`` is accepted for signature parity with
    make_global_reconcile_fn but the sparse step always sequences
    per-node windows (their per-window params require it).
    """
    del strict_sequencing  # sparse always sequences; see docstring
    slice_sz = capacity // n_nodes
    K, K2 = int(sparse_k), 2 * int(sparse_k)
    NW = 4 + len(AUX_ROWS)           # window payload rows (see `payload`)
    T_ROW, CW_ROW, CT_ROW = NW, NW + 1, NW + 2

    def _step(state_blk, aux_blk, accum_blk, now):
        my = lax.axis_index("node")
        rep = jax.tree.map(lambda a: a[0], state_blk)
        aux = aux_blk[0]
        acc_me = accum_blk[0]
        owned = (jnp.arange(capacity, dtype=I32) // slice_sz) == my.astype(I32)
        gather_rows = _make_gather_rows(n_nodes, my)

        wmask, tmask, wslots, tslots = _sparse_sets(
            acc_me, _make_compact(capacity), K)
        wsl = jnp.clip(wslots, 0, capacity - 1)
        # One envelope per node, one gather per step: the window payload
        # (slots + hits/reset/count + aux params), the touch set, and the
        # probe's two set-size counts broadcast across the K lanes.
        payload = jnp.concatenate([
            wslots.astype(I64)[None],
            acc_me[ACC_HITS][wsl][None],
            acc_me[ACC_RESET][wsl][None],
            acc_me[ACC_COUNT][wsl][None],
            aux[:, wsl],
            tslots.astype(I64)[None],
            jnp.broadcast_to(
                jnp.count_nonzero(wmask).astype(I64), (1, K)),
            jnp.broadcast_to(
                jnp.count_nonzero(tmask).astype(I64), (1, K)),
        ])                                      # (NW + 3, K)
        W = gather_rows(payload)                # (n, NW + 3, K)

        sets = jnp.stack([W[:, 0], W[:, T_ROW]], axis=1)  # (n, 2, K)
        touched = _mark_touched(capacity, n_nodes, sets)
        # Probe, from the one gather: any node's set wider than K, or —
        # counted locally on the replicated union, owner d's share being
        # rows [d*slice_sz, (d+1)*slice_sz) — any owner's re-broadcast
        # share wider than K2.
        counts = W[:, CW_ROW:CT_ROW + 1, 0]     # (n, 2)
        bcounts = jnp.sum(
            touched.reshape(n_nodes, slice_sz).astype(I32), axis=1)
        overflow = (jnp.max(counts) > K) | (jnp.max(bcounts) > K2)

        # sendHits at the authority (identical to the unfused sparse
        # step's fold, with ``overflow`` gating validity so a truncated
        # envelope never lands).
        def fold(d, st):
            slots_d = W[d, 0].astype(I32)
            sl = jnp.clip(slots_d, 0, capacity - 1)
            ok = ((slots_d < capacity) & owned[sl] & (W[d, 3] > 0)
                  & ~overflow)
            auxd = W[d, 4:NW]
            havep = auxd[AUX["stamp"]] > 0
            gathered = gather_state(st, sl)
            beh = jnp.where(havep, auxd[AUX["behavior"]], 0).astype(I32)
            beh = beh & ~jnp.int32(Behavior.RESET_REMAINING)
            beh = beh | jnp.int32(Behavior.DRAIN_OVER_LIMIT)
            req = ReqBatch(
                slot=sl,
                known=jnp.ones(K, jnp.bool_),
                hits=W[d, 1],
                limit=jnp.where(
                    havep, auxd[AUX["limit"]], gathered.limit),
                duration=jnp.where(
                    havep, auxd[AUX["duration"]], gathered.duration),
                algorithm=jnp.where(
                    havep, auxd[AUX["algorithm"]],
                    gathered.algorithm.astype(I64)).astype(I32),
                behavior=jnp.where(
                    W[d, 2] > 0,
                    beh | jnp.int32(Behavior.RESET_REMAINING), beh),
                created_at=jnp.where(
                    havep, auxd[AUX["created_at"]], now),
                burst=jnp.where(
                    havep, auxd[AUX["burst"]], gathered.burst),
                greg_exp=jnp.where(havep, auxd[AUX["greg_exp"]], 0),
                greg_dur=jnp.where(havep, auxd[AUX["greg_dur"]], 0),
                valid=ok,
            )
            new_g, _ = bucket_transition(now, gathered, req)
            return scatter_state(
                st, jnp.where(ok, sl, capacity), new_g)

        st = lax.fori_loop(0, n_nodes, fold, rep)

        # broadcastPeers, sparse (see make_global_reconcile_fn): the
        # union was already derived above for the probe — reused here,
        # masked off entirely when the step overflowed.
        bmask = touched & owned & ~overflow
        bslots = _make_compact(capacity)(bmask, K2)
        bsl = jnp.clip(bslots, 0, capacity - 1)
        rows = gather_state(st, bsl)
        BS = gather_rows(bslots)
        BR = jax.tree.map(gather_rows, rows)

        def install(d, st2):
            sl2 = BS[d]
            scat = jnp.where(sl2 < capacity, sl2, capacity)
            return scatter_state(
                st2, scat, jax.tree.map(lambda a: a[d], BR))

        st = lax.fori_loop(0, n_nodes, install, st)
        # Overflow keeps the accumulators: the host's dense fallback
        # still has the window to apply.
        acc_out = jnp.where(overflow, acc_me, jnp.zeros_like(acc_me))
        out = (
            jax.tree.map(lambda a: a[None], st),
            acc_out[None],
            overflow,
        )
        return out + (W,) if with_envelope else out

    state_spec = NODE_LAYOUT.replica_spec()
    out_specs = (state_spec, P("node", None, None), P())
    if with_envelope:
        out_specs = out_specs + (P(),)
    return shard_map(
        _step,
        mesh=mesh,
        in_specs=(state_spec, P("node", None, None), P("node", None, None),
                  P()),
        out_specs=out_specs,
        check_vma=False,
    )


def make_global_reconcile_fn(
    mesh: Mesh, capacity: int, n_nodes: int, strict_sequencing: bool = True,
    sparse_k: int = 0,
):
    """The collective reconcile step: aggregate hits + replicate authority.

    Collapses the reference's sendHits (global.go:144-187) and
    broadcastPeers (global.go:234-283) RPC fans into collectives.  With
    ``strict_sequencing`` (default) each node's aggregated window applies
    to the authority as its own batch, in node order — bit-exact with the
    reference, where every peer's window arrives as a separate
    GetPeerRateLimits RPC and is applied sequentially (edge branches like
    the new-item over-ask, algorithms.go:240-248, are sequencing-
    sensitive).  The non-strict path folds all nodes into one psum and a
    single application — one dense pass instead of ``n_nodes``, for
    deployments that accept aggregate-application semantics.

    ``sparse_k > 0`` returns the SPARSE step instead: every node
    compacts its hit window and its touched-slot set to a
    ``sparse_k``-row envelope, the collectives move those envelopes
    instead of full tables — O(hits · n) ICI bytes and gather/scatter
    work instead of O(capacity · n) — owners apply the gathered windows
    to their authoritative rows only, and re-broadcast just the
    changed/touched rows (2·sparse_k envelope).  This is what lifts the
    dense form's ~2^20-slot envelope (module docstring) to
    multi-million-slot GLOBAL tables.  The sparse program ASSUMES no
    envelope overflow; callers consult :func:`make_global_overflow_fn`
    first and run the dense program for the rare overflowing step (host
    dispatch, not an in-program cond: a cond would copy the whole
    untouched table through its output buffer and re-impose the
    O(capacity) cost the sparse step removes).  The reference ships only
    touched keys the same way (global.go:91-140).

    Sparse parameter semantics are per-window (each node's aggregated
    window applies with ITS OWN latest-request params, like each peer's
    GetPeerRateLimits RPC carrying its own request protos) — the dense
    path's cross-node stamp winner is a superset that can also resurrect
    params from nodes with no hits this window; the reference does not.
    """
    slice_sz = capacity // n_nodes

    def _recon(state_blk, aux_blk, accum_blk, now):
        # Every cross-node exchange below is a ``psum``: XLA:TPU's 64-bit
        # rewriter lowers only the sum all-reduce (a pmax over int64 is
        # UNIMPLEMENTED on jax 0.9.0 / libtpu 0.0.34, compiled for a
        # described v5e:2x2; int32 max is fine), and the stamps and
        # accumulators here are int64.  all_gather is expressed as a psum
        # of one-hot-row buffers; broadcast as an ownership-masked psum.
        my = lax.axis_index("node")
        rep = jax.tree.map(lambda a: a[0], state_blk)
        aux = aux_blk[0]
        acc_me = accum_blk[0]

        owned = (jnp.arange(capacity, dtype=I32) // slice_sz) == my.astype(I32)
        gather_rows = _make_gather_rows(n_nodes, my)

        def dense_recon(_):
            # broadcastPeers as a collective: every node contributes its
            # owned (authoritative) slice, masked psum reassembles the full
            # table in slot order on every node — replicas are now the
            # authoritative state, exactly what UpdatePeerGlobals installs
            # (gubernator.go:425-459).
            def bcast(a):
                if a.dtype == jnp.bool_:
                    return lax.psum(
                        jnp.where(owned, a, False).astype(I32), "node"
                    ) > 0
                return lax.psum(
                    jnp.where(owned, a, jnp.zeros((), a.dtype)), "node")

            # Stored-layout broadcast (the masked psum is exact on bitcast
            # i32 halves: exactly one node contributes per slot), then a
            # logical view for the dense transition below.
            base = logical_view(jax.tree.map(bcast, rep))

            # Latest request parameters across nodes: max over write
            # stamps (ties broken by node index), then a masked psum
            # selects the winner's aux row — the aggregated request proto
            # of global.go:99-112.
            stamp = aux[AUX["stamp"]]
            key = jnp.where(
                stamp > 0, stamp * n_nodes + my.astype(I64), jnp.int64(-1)
            )
            win = jnp.max(gather_rows(key), axis=0)
            mine = (key == win) & (win >= 0)
            params = lax.psum(jnp.where(mine[None, :], aux, 0), "node")
            havep = win >= 0

            # Forwarded GLOBAL hits get DRAIN_OVER_LIMIT forced
            # (gubernator.go:510-512); RESET_REMAINING applies iff queued
            # this window (stale RESET bits in aux must not re-fire).
            base_behavior = jnp.where(
                havep, params[AUX["behavior"]], 0).astype(I32)
            base_behavior = base_behavior & ~jnp.int32(
                Behavior.RESET_REMAINING)
            base_behavior = base_behavior | jnp.int32(
                Behavior.DRAIN_OVER_LIMIT)

            def make_req(hits, reset, valid):
                return ReqBatch(
                    slot=jnp.arange(capacity, dtype=I32),
                    known=jnp.ones(capacity, jnp.bool_),
                    hits=hits,
                    limit=jnp.where(havep, params[AUX["limit"]], base.limit),
                    duration=jnp.where(
                        havep, params[AUX["duration"]], base.duration
                    ),
                    algorithm=jnp.where(
                        havep, params[AUX["algorithm"]],
                        base.algorithm.astype(I64)
                    ).astype(I32),
                    behavior=jnp.where(
                        reset > 0,
                        base_behavior | jnp.int32(Behavior.RESET_REMAINING),
                        base_behavior,
                    ),
                    created_at=jnp.where(
                        havep, params[AUX["created_at"]], now),
                    burst=jnp.where(havep, params[AUX["burst"]], base.burst),
                    greg_exp=params[AUX["greg_exp"]],
                    greg_dur=params[AUX["greg_dur"]],
                    valid=valid,
                )

            def apply(st, hits, reset, valid):
                # Dense application: slot i ↔ request i — no gather/
                # scatter, no rank rounds; the whole table updates in one
                # elementwise pass.
                new_state, _ = bucket_transition(
                    now, st, make_req(hits, reset, valid)
                )
                return jax.tree.map(
                    lambda n, b: jnp.where(valid, n, b), new_state, st
                )

            # ACC_TOUCH is sparse-only bookkeeping; the dense exchange
            # moves the three rows it reads.
            acc3 = acc_me[:ACC_TOUCH]
            if strict_sequencing:
                # sendHits, exactly: every node's window is one batch at
                # the authority, applied in node order (all_gather +
                # on-device fold).
                acc_all = gather_rows(acc3)  # (n, 3, capacity)

                def fold(d, st):
                    return apply(
                        st,
                        acc_all[d, ACC_HITS],
                        acc_all[d, ACC_RESET],
                        acc_all[d, ACC_COUNT] > 0,
                    )

                merged = lax.fori_loop(0, n_nodes, fold, base)
            else:
                # sendHits as one reduction: cluster-total hits per slot.
                acc = lax.psum(acc3, "node")
                merged = apply(
                    base, acc[ACC_HITS], acc[ACC_RESET], acc[ACC_COUNT] > 0
                )
            return stored_view(merged)

        if not sparse_k:
            merged = dense_recon(None)
            return (
                jax.tree.map(lambda a: a[None], merged),
                jnp.zeros_like(accum_blk),
            )

        # ------------------------------------------------------------------
        # Sparse step: compact → gather envelopes → owner-apply → re-
        # broadcast changed rows.  Compaction is device-local O(capacity)
        # elementwise; everything crossing ICI is O(sparse_k · n).
        # ------------------------------------------------------------------
        K = int(sparse_k)
        K2 = 2 * K
        _, _, wslots, tslots = _sparse_sets(
            acc_me, _make_compact(capacity), K)

        wsl = jnp.clip(wslots, 0, capacity - 1)
        payload = jnp.concatenate([
            wslots.astype(I64)[None],
            acc_me[ACC_HITS][wsl][None],
            acc_me[ACC_RESET][wsl][None],
            acc_me[ACC_COUNT][wsl][None],
            aux[:, wsl],
        ])                                      # (4 + len(AUX_ROWS), K)

        def sparse_recon(_):
            W = gather_rows(payload)            # (n, 13, K)
            sets = gather_rows(jnp.stack([wslots, tslots]))  # (n, 2, K)

            # sendHits at the authority: fold each node's window into MY
            # owned rows, node order (strict semantics; the non-strict
            # psum variant would lose per-window params, so sparse always
            # sequences — window widths are small by construction).
            def fold(d, st):
                slots_d = W[d, 0].astype(I32)
                sl = jnp.clip(slots_d, 0, capacity - 1)
                ok = (slots_d < capacity) & owned[sl] & (W[d, 3] > 0)
                auxd = W[d, 4:]
                havep = auxd[AUX["stamp"]] > 0
                gathered = gather_state(st, sl)
                beh = jnp.where(havep, auxd[AUX["behavior"]], 0).astype(I32)
                beh = beh & ~jnp.int32(Behavior.RESET_REMAINING)
                beh = beh | jnp.int32(Behavior.DRAIN_OVER_LIMIT)
                req = ReqBatch(
                    slot=sl,
                    known=jnp.ones(K, jnp.bool_),
                    hits=W[d, 1],
                    limit=jnp.where(
                        havep, auxd[AUX["limit"]], gathered.limit),
                    duration=jnp.where(
                        havep, auxd[AUX["duration"]], gathered.duration),
                    algorithm=jnp.where(
                        havep, auxd[AUX["algorithm"]],
                        gathered.algorithm.astype(I64)).astype(I32),
                    behavior=jnp.where(
                        W[d, 2] > 0,
                        beh | jnp.int32(Behavior.RESET_REMAINING), beh),
                    created_at=jnp.where(
                        havep, auxd[AUX["created_at"]], now),
                    burst=jnp.where(
                        havep, auxd[AUX["burst"]], gathered.burst),
                    greg_exp=jnp.where(havep, auxd[AUX["greg_exp"]], 0),
                    greg_dur=jnp.where(havep, auxd[AUX["greg_dur"]], 0),
                    valid=ok,
                )
                new_g, _ = bucket_transition(now, gathered, req)
                return scatter_state(
                    st, jnp.where(ok, sl, capacity), new_g)

            st = lax.fori_loop(0, n_nodes, fold, rep)

            # broadcastPeers, sparse: my owned rows that changed (any
            # node's window) or that any node provisionally wrote (its
            # touch set) ship to every replica; receivers scatter them
            # in.  The union derivation is shared with the overflow
            # probe (_mark_touched) so the K2 bound it checked is
            # exactly the set compacted here.
            touched = _mark_touched(capacity, n_nodes, sets)
            bmask = touched & owned
            bslots = _make_compact(capacity)(bmask, K2)
            bsl = jnp.clip(bslots, 0, capacity - 1)
            rows = gather_state(st, bsl)
            BS = gather_rows(bslots)
            BR = jax.tree.map(gather_rows, rows)

            def install(d, st2):
                sl2 = BS[d]
                scat = jnp.where(sl2 < capacity, sl2, capacity)
                return scatter_state(
                    st2, scat, jax.tree.map(lambda a: a[d], BR))

            return lax.fori_loop(0, n_nodes, install, st)

        merged = sparse_recon(None)
        return (
            jax.tree.map(lambda a: a[None], merged),
            jnp.zeros_like(accum_blk),
        )

    state_spec = NODE_LAYOUT.replica_spec()
    return shard_map(
        _recon,
        mesh=mesh,
        in_specs=(state_spec, P("node", None, None), P("node", None, None), P()),
        out_specs=(state_spec, P("node", None, None)),
        check_vma=False,
    )


def make_global_evict_fn(mesh: Mesh):
    """Drop slots on every replica + clear their accumulators/stamps."""
    state_spec = NODE_LAYOUT.replica_spec()

    def _evict(state_blk, aux_blk, accum_blk, slots):
        st = jax.tree.map(lambda a: a[0], state_blk)
        # Zero the whole row, not just in_use: an evicted item is REMOVED
        # (lrucache.go:138-149), and stale don't-care fields would leak
        # into peek()/snapshots when the slot is reborn under the other
        # algorithm (same fix as the local engines' evict).
        from gubernator_tpu.ops.buckets import BucketState as _BS

        st = scatter_state(st, slots, _BS.zeros_logical(slots.shape[0]))
        aux = aux_blk[0].at[AUX["stamp"], slots].set(0, mode="drop")
        acc = accum_blk[0].at[:, slots].set(0, mode="drop")
        return (
            jax.tree.map(lambda a: a[None], st), aux[None], acc[None],
        )

    return shard_map(
        _evict,
        mesh=mesh,
        in_specs=(state_spec, P("node", None, None), P("node", None, None), P()),
        out_specs=(state_spec, P("node", None, None), P("node", None, None)),
        check_vma=False,
    )


class MeshGlobalEngine:
    """Host driver for the replicated GLOBAL table over a device mesh.

    One instance is shared by every service node resident on the mesh (the
    in-process cluster, or the per-host processes of a multi-host mesh);
    each node calls :meth:`process` with its node index, and one driver
    (any of them — calls are internally rate-limited) calls
    :meth:`maybe_reconcile` on the GlobalSyncWait cadence.
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        capacity: int = 1 << 16,
        max_batch: int = 1024,
        min_reconcile_ms: int = 0,
        strict_sequencing: bool = True,
        sparse_k: Optional[int] = None,
    ):
        from gubernator_tpu.config import validate_global_mesh_capacity

        validate_global_mesh_capacity(int(capacity))
        self.mesh = mesh if mesh is not None else make_global_mesh()
        self.n_nodes = self.mesh.devices.size
        # Capacity must split evenly into per-node authority slices.
        self.capacity = -(-int(capacity) // self.n_nodes) * self.n_nodes
        self.max_batch = int(max_batch)
        self.min_reconcile_ms = int(min_reconcile_ms)
        # Sparse reconcile envelope: auto-on past the dense envelope's
        # comfortable range (the dense step rewrites every slot on every
        # node; see make_global_reconcile_fn).  Small tables keep the
        # dense step — it is a single fused pass with no compaction
        # bookkeeping and its ICI cost is negligible there.
        if sparse_k is None:
            sparse_k = 4096 if self.capacity > (1 << 16) else 0
        self.sparse_k = min(int(sparse_k), self.capacity)

        row = NODE_LAYOUT.shardings(self.mesh, P("node", None))
        mat = NODE_LAYOUT.shardings(self.mesh, NODE_LAYOUT.mat3())
        self.state: BucketState = jax.tree.map(
            lambda a: jax.device_put(
                jnp.broadcast_to(a, (self.n_nodes,) + a.shape), row
            ),
            BucketState.zeros(self.capacity),
        )
        self.aux = jax.device_put(
            jnp.zeros((self.n_nodes, len(AUX_ROWS), self.capacity), I64), mat
        )
        self.accum = jax.device_put(
            jnp.zeros((self.n_nodes, ACC_ROWS, self.capacity), I64), mat
        )
        self._proc = jax.jit(
            make_global_process_fn(
                self.mesh, self.capacity, self.n_nodes,
                track_touch=bool(self.sparse_k),
            ),
            donate_argnums=(0, 1, 2),
        )
        # The sparse program always sequences per-node windows (its
        # per-window params force it), so when it is enabled the dense
        # overflow fallback must sequence too — otherwise the same
        # traffic would flip semantics on whichever steps happen to
        # overflow the envelope.
        self._recon_dense = jax.jit(
            make_global_reconcile_fn(
                self.mesh, self.capacity, self.n_nodes,
                strict_sequencing or bool(self.sparse_k),
            ),
            donate_argnums=(0, 2),
        )
        if self.sparse_k:
            # The fused step: ONE program computes the overflow probe and
            # the sparse reconcile from a single envelope compaction +
            # gather (the unfused probe/step pair gathered the same sets
            # twice per step; see make_global_sparse_step_fn).
            self._sparse_step = jax.jit(
                make_global_sparse_step_fn(
                    self.mesh, self.capacity, self.n_nodes, self.sparse_k,
                ),
                donate_argnums=(0, 2),
            )
        else:
            self._sparse_step = None
        self.metric_dense_fallbacks = 0
        # Mesh programs launched by reconcile steps: 1 per fused sparse
        # or dense step, 2 when an overflowing step runs the dense
        # fallback after the fused probe.  dispatches/reconciles of
        # exactly 1.0 is the fusion's observable
        # (tests/test_global_mesh.py::test_reconcile_dispatch_counter).
        self.metric_reconcile_dispatches = 0
        self._evict = jax.jit(
            make_global_evict_fn(self.mesh), donate_argnums=(0, 1, 2)
        )
        self.slots = make_slot_map(self.capacity)
        self._last_access = np.zeros(self.capacity, np.int64)
        self._pending: set = set()
        self._tick_count = 0
        self._last_reconcile_ms = 0
        self._reconcile_paused = 0
        self._lock = sanitize.rlock("MeshGlobalEngine._lock")
        self.metric_reconciles = 0
        self._req_sharding = mat
        self._warmup()

    def _warmup(self) -> None:
        m = np.zeros((self.n_nodes, len(REQ_ROWS), self.max_batch), np.int64)
        m[:, REQ_ROW_INDEX["slot"], :] = self.capacity
        self.state, self.aux, self.accum, resp = self._proc(
            self.state, self.aux, self.accum,
            jax.device_put(m, self._req_sharding), jnp.int64(0), jnp.int64(0),
        )
        np.asarray(resp)  # warm the response D2H path (see TickEngine._warmup)
        if self._sparse_step is not None:
            self.state, self.accum, over = self._sparse_step(
                self.state, self.aux, self.accum, jnp.int64(0)
            )
            np.asarray(over)  # warm the probe-bool D2H path
            if self.capacity <= (1 << 20):
                # Big tables leave the dense fallback to compile lazily on
                # the first (rare) overflowing step; warming it would run
                # a full O(capacity·n) pass at startup.
                self.state, self.accum = self._recon_dense(
                    self.state, self.aux, self.accum, jnp.int64(0)
                )
        else:
            self.state, self.accum = self._recon_dense(
                self.state, self.aux, self.accum, jnp.int64(0)
            )
        # Pre-compile the reclaim dead-scan (see TickEngine._warmup).
        from gubernator_tpu.ops.engine import device_dead_mask

        device_dead_mask(
            self.state.in_use[0], slice_field(self.state.expire_at, 0),
            0, self.capacity,
        )
        jax.block_until_ready(self.state)

    # ------------------------------------------------------------------
    # Request path (per node)
    # ------------------------------------------------------------------
    def process(
        self,
        requests: Sequence[RateLimitRequest],
        node_idx: int = 0,
        now: Optional[int] = None,
    ) -> List[RateLimitResponse]:
        """Apply GLOBAL requests that arrived at node ``node_idx``."""
        blocks: List[Sequence[RateLimitRequest]] = [
            [] for _ in range(self.n_nodes)
        ]
        blocks[node_idx] = requests
        return self.process_blocks(blocks, now)[node_idx]

    def process_blocks(
        self,
        blocks: Sequence[Sequence[RateLimitRequest]],
        now: Optional[int] = None,
    ) -> List[List[RateLimitResponse]]:
        """Apply one window of GLOBAL requests, grouped by receiving node.

        Every node's block lands in the same SPMD tick (one program launch
        for the whole mesh); responses mirror the block structure.
        """
        if len(blocks) != self.n_nodes:
            raise ValueError(f"expected {self.n_nodes} blocks, got {len(blocks)}")
        out: List[List[Optional[RateLimitResponse]]] = [
            [None] * len(blk) for blk in blocks
        ]
        with self._lock:
            now = now if now is not None else timeutil.now_ms()
            todo = [list(range(len(blk))) for blk in blocks]
            while any(todo):
                left = self._tick_once(blocks, todo, out, now)
                if left == todo:
                    for d, idxs in enumerate(left):
                        for j in idxs:
                            out[d][j] = RateLimitResponse(
                                error="global table full; eviction failed"
                            )
                    break
                todo = left
        return out  # type: ignore[return-value]

    def _tick_once(self, blocks, todo, out, now):
        """Column-vectorized like TickEngine.build_batch: one attribute
        pass per node block, then one fancy-indexed numpy write per
        request-matrix row (the scalar pack_request_col loop was the
        GLOBAL-mesh host bottleneck)."""
        b = self.max_batch
        m = np.zeros((self.n_nodes, len(REQ_ROWS), b), np.int64)
        R = REQ_ROW_INDEX
        m[:, R["slot"], :] = self.capacity
        self._tick_count += 1
        spill = [[] for _ in range(self.n_nodes)]
        packed: List[tuple] = []  # (d, col, j, request, slot, known, ge, gd)
        for d, idxs in enumerate(todo):
            col = 0
            for j in idxs:
                r = blocks[d][j]
                try:
                    ge, gd = resolve_gregorian(r, now)
                except timeutil.GregorianError as e:
                    out[d][j] = RateLimitResponse(error=str(e))
                    continue
                if col >= b:
                    spill[d].append(j)
                    continue
                slot, known = self._resolve(r.hash_key(), now)
                if slot is None:
                    spill[d].append(j)
                    continue
                packed.append((d, col, j, r, slot, known, ge, gd))
                col += 1
        if packed:
            dd_l, cc_l, jj, reqs_l, slot_l, known_l, ge_l, gd_l = zip(*packed)
            dd = np.asarray(dd_l, np.int64)
            cc = np.asarray(cc_l, np.int64)
            pack_request_matrix(
                m, cc, reqs_l, slot_l, known_l, now,
                nodes=dd, greg=(ge_l, gd_l),
            )
            self.state, self.aux, self.accum, resp = self._proc(
                self.state, self.aux, self.accum,
                jax.device_put(m, self._req_sharding),
                jnp.int64(now), jnp.int64(self._tick_count),
            )
            self._pending.clear()
            rm = np.asarray(resp)  # (n_nodes, 5, B)
            status, limit_o, remaining, reset = (
                rm[dd, r, cc].tolist() for r in range(4)
            )
            for t, (d, j) in enumerate(zip(dd_l, jj)):
                out[d][j] = RateLimitResponse(
                    status=status[t], limit=limit_o[t],
                    remaining=remaining[t], reset_time=reset[t],
                )
        return spill

    def _resolve(self, key: str, now: int):
        known = self.slots.get(key) is not None
        slot = self.slots.assign(key)
        if slot is None:
            self._reclaim(now)
            known = self.slots.get(key) is not None
            slot = self.slots.assign(key)
            if slot is None:
                return None, False
        if not known:
            self._pending.add(slot)
        self._last_access[slot] = self._tick_count
        return slot, known

    def _reclaim(self, now: int) -> None:
        """TTL-then-LRU slot reclamation (the shared policy,
        engine.select_reclaim_victims) over the replicated table.

        Authority for expiry is the owner's slice; rather than gather each
        slice, read node 0's replica — correct at reconcile boundaries and
        conservatively stale (never early) between them.
        """
        from gubernator_tpu.ops.engine import (
            device_dead_mask,
            select_reclaim_victims,
        )

        mapped = self.slots.mapped_mask()
        if self._pending:
            mapped[np.fromiter(self._pending, np.int64)] = False
        freed, victims = select_reclaim_victims(
            mapped,
            device_dead_mask(
                self.state.in_use[0], slice_field(self.state.expire_at, 0),
                now, self.capacity,
            ),
            self._last_access,
            self._tick_count,
            max(1, self.capacity // 16),
        )
        self.slots.release_batch(freed)
        if len(victims) == 0:
            return
        self.slots.release_batch(victims)
        from gubernator_tpu.ops.engine import evict_chunked

        def _evict3(bundle, padded):
            st, aux, acc = bundle
            return self._evict(st, aux, acc, padded)

        self.state, self.aux, self.accum = evict_chunked(
            _evict3, (self.state, self.aux, self.accum), victims, self.capacity
        )

    # ------------------------------------------------------------------
    # The collective reconcile (GlobalSyncWait cadence)
    # ------------------------------------------------------------------
    def reconcile(self, now: Optional[int] = None) -> None:
        """One psum + all_gather reconciliation step (see module doc).

        With a sparse envelope configured, the FUSED step computes the
        overflow probe inside the sparse program itself (one envelope
        compaction + gather per step) and returns the bool alongside the
        updated replicas.  An overflowing step applies nothing — its
        scatters are gated off on device, so the returned state/accum
        are the originals — and the host runs the rare dense fallback on
        them (still a host dispatch, not an in-program cond: a cond
        would copy the whole untouched table through the cond output and
        re-impose the O(capacity) cost the sparse step exists to
        remove).
        """
        with self._lock:
            now = now if now is not None else timeutil.now_ms()
            if self._sparse_step is not None:
                self.state, self.accum, over = self._sparse_step(
                    self.state, self.aux, self.accum, jnp.int64(now)
                )
                self.metric_reconcile_dispatches += 1
                if bool(np.asarray(over)):
                    self.metric_dense_fallbacks += 1
                    self.metric_reconcile_dispatches += 1
                    self.state, self.accum = self._recon_dense(
                        self.state, self.aux, self.accum, jnp.int64(now)
                    )
            else:
                self.metric_reconcile_dispatches += 1
                self.state, self.accum = self._recon_dense(
                    self.state, self.aux, self.accum, jnp.int64(now)
                )
            self._pending.clear()
            self._last_reconcile_ms = now
            self.metric_reconciles += 1

    def pause_reconcile(self) -> None:
        """Hold the reconcile cadence (nestable): the reshard coordinator
        quiets the collective plane for its bounded cutover window so
        reconcile programs don't contend with the relayout dispatch on
        the same devices (docs/resharding.md).  Hits keep accumulating —
        a paused cadence defers reconciliation, it never loses it."""
        with self._lock:
            self._reconcile_paused += 1

    def resume_reconcile(self) -> None:
        with self._lock:
            self._reconcile_paused = max(0, self._reconcile_paused - 1)

    def maybe_reconcile(self, now: Optional[int] = None) -> bool:
        """Reconcile unless one ran within ``min_reconcile_ms`` (lets every
        resident node drive the cadence without duplicate work) or the
        cadence is paused for a reshard cutover."""
        if self._reconcile_paused:
            return False
        now = now if now is not None else timeutil.now_ms()
        if now - self._last_reconcile_ms < self.min_reconcile_ms:
            return False
        self.reconcile(now)
        return True

    def cache_size(self) -> int:
        return len(self.slots)

    # Introspection used by the tests: per-node view of one key.
    def peek(self, key: str) -> Optional[List[dict]]:
        slot = self.slots.get(key)
        if slot is None:
            return None
        st = {
            name: np_logical(
                slice_field(getattr(self.state, name), (slice(None), slot)),
                name,
            )
            for name in ("remaining", "remaining_f", "status", "in_use", "limit")
        }
        return [
            {
                "remaining": int(st["remaining"][d]),
                "remaining_f": float(st["remaining_f"][d]),
                "status": int(st["status"][d]),
                "in_use": bool(st["in_use"][d]),
                "limit": int(st["limit"][d]),
            }
            for d in range(self.n_nodes)
        ]
