"""Canonical PartitionSpec layouts for the sharded serving path.

One frozen spec-helper per mesh axis family — the SNIPPETS [3] idiom
(a ``SpecLayout`` dataclass whose methods name every placement a
subsystem uses) applied to the bucket table instead of transformer
parameters.  Every ``PartitionSpec`` the sharded tick engine
(:mod:`gubernator_tpu.parallel.mesh_engine`) and the GLOBAL collectives
engine (:mod:`gubernator_tpu.parallel.global_mesh`) place data with is
minted HERE, so the two engines can never drift on what "sharded over
the table axis" or "one replica row per node" means, and a reviewer can
read the whole placement story in one file:

* :class:`ShardLayout` — the partitioned serving table.  The SoA bucket
  state is split over the 1-D ``('shard',)`` mesh by contiguous slot
  range (device *d* owns global slots ``[d*local_cap, (d+1)*local_cap)``);
  tick request/response traffic is *flat replicated* — one slab, the
  slot-sorted (19, B) matrix with ``now`` and the ragged ``offsets``
  in its tail (:meth:`RaggedExtents.split`), broadcast to every shard,
  each shard walking only its own extent on device (ops.raggedtick) —
  while maintenance blocks (evict/install/restore/
  readback) keep the leading shard axis.
* :class:`NodeLayout` — the replicated GLOBAL table.  One replica row
  per node (``P('node', None)``), accumulator/aux matrices alongside,
  scalars replicated.

The ragged extent spec lives here too (:class:`RaggedExtents`): the
flat batch is sorted by GLOBAL slot and ownership is ``slot //
local_capacity`` — nothing else — so each shard's rows form one
contiguous extent and the host-side per-shard counts compress to a
cumulative offsets vector.  Every producer of that vector (the serving
dispatch, reshard's post-cutover dispatches, the tests' extent audits)
derives it from this ONE dataclass, so the host packer and the
on-device extent walker can never drift on where a shard's rows live.
"""

from __future__ import annotations

from dataclasses import dataclass

import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu.ops.buckets import BucketState
from gubernator_tpu.ops.engine import REQ32_ROWS
from gubernator_tpu.ops.rowtable import RowState


@dataclass(frozen=True)
class ShardLayout:
    """Canonical PartitionSpecs for the slot-partitioned serving table
    (the ``('shard',)`` mesh of :func:`mesh_engine.make_mesh`)."""

    shard_axis: str = "shard"

    def table_spec(self, layout: str):
        """Spec tree for the bucket table in storage layout ``layout``:
        every column (or the row table's leading axis) splits over the
        shard axis by contiguous slot range."""
        if layout == "row":
            return RowState(table=P(self.shard_axis, None))
        return jax.tree.map(lambda _: P(self.shard_axis), BucketState.zeros(0))

    def blocked2(self) -> P:
        """(n_shards, W) host-blocked matrix: one row block per shard."""
        return P(self.shard_axis, None)

    def blocked3(self) -> P:
        """(n_shards, ROWS, W) host-blocked request/column matrix."""
        return P(self.shard_axis, None, None)

    def flat2(self) -> P:
        """(ROWS, B) flat matrix — the window's one upload
        (RaggedExtents.split) and its response — replicated to every
        shard; each device walks only its own ragged extent."""
        return P(None, None)

    def shardings(self, mesh: Mesh, spec_tree):
        """NamedShardings for a spec tree (or a bare spec) on ``mesh``.
        PartitionSpec is a tuple subclass, so tree traversal must treat
        it as a leaf."""
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda s: isinstance(s, P),
        )


@dataclass(frozen=True)
class NodeLayout:
    """Canonical PartitionSpecs for the replicated GLOBAL table (the
    ``('node',)`` mesh of :func:`global_mesh.make_global_mesh`): one
    replica row per node, reconciled with psum collectives only —
    nothing in this layout ever materializes densely on the host."""

    node_axis: str = "node"

    def replica_spec(self):
        """Spec tree for the per-node replica rows of the GLOBAL bucket
        table: (n_nodes, capacity) per column."""
        return jax.tree.map(
            lambda _: P(self.node_axis, None), BucketState.zeros(0)
        )

    def mat3(self) -> P:
        """(n_nodes, ROWS, capacity) per-node matrix (aux/accumulators/
        request blocks)."""
        return P(self.node_axis, None, None)

    def scalar(self) -> P:
        return P()

    def shardings(self, mesh: Mesh, spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda s: isinstance(s, P),
        )


# ----------------------------------------------------------------------
# Ragged extents (the on-device tick's wire spec).  The flat request
# matrix carries GLOBAL slots in its slot row and is sorted by them;
# ownership is derived from the slot value alone, so shard s's rows are
# the contiguous extent [offsets[s], offsets[s+1]).
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class RaggedExtents:
    """Host-side ragged extent spec for one (n_shards, local_capacity)
    layout: how a resolved batch's per-shard row counts become the
    ``(n_shards + 1,)`` cumulative offsets vector the extent walker
    (ops.raggedtick) consumes.

    The spec is layout-bearing state: ``MeshTickEngine`` swaps it
    atomically in ``_cutover`` alongside the mesh/ops/slotmaps, so a
    reshard recomputes every subsequent window's offsets against the
    NEW ``cap_to``-derived ownership — there is no width to
    re-derive."""

    n_shards: int
    local_capacity: int

    def counts(self, sh: np.ndarray, ok: np.ndarray) -> np.ndarray:
        """Per-shard live row counts of one resolved batch (``sh`` the
        per-request shard route, ``ok`` the live mask)."""
        if not ok.any():
            return np.zeros(self.n_shards, np.int64)
        return np.bincount(sh[ok], minlength=self.n_shards)

    def offsets(self, counts: np.ndarray, out=None) -> np.ndarray:
        """Cumulative extent offsets: shard s owns sorted lanes
        ``[offsets[s], offsets[s+1])``.  Valid because the packed batch
        sorts by GLOBAL slot (engine.sort_packed_by_slot) and global
        slots of shard s are exactly ``[s*cap, (s+1)*cap)`` — shards
        ascend with the sort, error/padding lanes (sentinel slot) sort
        past every extent.  Written into ``out`` where given (the
        window's upload: :meth:`split`)."""
        off = out if out is not None else np.empty(self.n_shards + 1, np.int32)
        off[0] = 0
        np.cumsum(counts, out=off[1:])
        return off

    def slab_rows(self, width: int) -> int:
        """Rows of a window's ONE upload at batch capacity ``width``:
        the REQ32 rows and, in whole rows after them, the tail — ``now``
        as the wide encoding's two int32 words (engine.stamp_now), then
        the ``n_shards + 1`` offsets.  One row more at any serving width
        (engine.SLAB_ROWS); more where ``width`` is under
        ``n_shards + 3``."""
        return REQ32_ROWS + -(-(self.n_shards + 3) // width)

    def split(self, slab):
        """A window's upload ``(slab_rows, B)`` cut up: the (19, B)
        request matrix, ``now``'s two words and the offsets.  THE layout
        of the tail, for both sides: the host packs into these (numpy
        views of the staging slab), the traced program reads them."""
        tail = slab[REQ32_ROWS:].reshape(-1)
        return slab[:REQ32_ROWS], tail[:2], tail[2:self.n_shards + 3]


# ----------------------------------------------------------------------
# Layout transitions (elastic resharding; docs/resharding.md).  THE one
# n→m transition spec: both the on-device all-to-all re-layout program
# and every host-side remap audit derive ownership from this dataclass,
# so the engine, the coordinator's audit, and the unit tests can never
# drift on where a live slot lands after a reshard.
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class LayoutTransition:
    """One n→m re-partitioning of the slot space.

    Global slot identity is preserved across the transition: slot ``g``
    of the old layout is slot ``g`` of the new one — only the ownership
    boundaries move.  Under the contiguous-range rule (``ShardLayout``:
    shard ``d`` owns ``[d*cap, (d+1)*cap)``) the new owner of ``g`` is
    ``g // cap_to`` and its new local offset ``g % cap_to`` — the same
    single derivation :class:`RaggedExtents` applies to request slots,
    now applied to the table itself.

    ``live_slots`` is the number of slots carrying state (the old
    layout's total capacity on a first transition); ``cap_to`` is sized
    ``ceil(live_slots / n_to)`` so every live slot fits, and threading
    ``live_slots`` through chained transitions (:meth:`then`) makes
    n→m→n a round trip: 8→3→8 at cap 128 passes through cap 342 and
    lands back at exactly cap 128."""

    n_from: int
    cap_from: int
    n_to: int
    cap_to: int
    live_slots: int

    # -- ownership derivation (host/np + traced/jnp alike) -------------
    def owner_of(self, g):
        """New owning shard of global slot ``g`` (vector or scalar)."""
        return g // self.cap_to

    def local_of(self, g):
        """New local offset of global slot ``g`` (vector or scalar)."""
        return g % self.cap_to

    def old_owner_of(self, g):
        """Old owning shard of global slot ``g``."""
        return g // self.cap_from

    @property
    def capacity_to(self) -> int:
        return self.n_to * self.cap_to

    @property
    def capacity_from(self) -> int:
        return self.n_from * self.cap_from

    def then(self, n_next: int) -> "LayoutTransition":
        """Chain a follow-up transition, threading ``live_slots`` so
        round trips are exact (8→3→8 == identity)."""
        return plan_transition(
            self.n_to, self.cap_to, n_next, live_slots=self.live_slots
        )

    def remap(self) -> np.ndarray:
        """(live_slots, 3) host audit table: ``[new_shard, new_local,
        new_flat]`` per live global slot — new_flat is provably the
        identity (``owner*cap_to + local == g``), which is what makes
        the device all-to-all a pure re-partitioning of the flat slot
        axis."""
        g = np.arange(self.live_slots, dtype=np.int64)
        own = self.owner_of(g)
        loc = self.local_of(g)
        return np.stack([own, loc, own * self.cap_to + loc], axis=1)


def plan_transition(
    n_from: int, cap_from: int, n_to: int, live_slots: int = None
) -> LayoutTransition:
    """Mint the :class:`LayoutTransition` for an n→m reshard.

    ``live_slots`` defaults to the old layout's full capacity
    (``n_from * cap_from``); pass a carried value when chaining (see
    :meth:`LayoutTransition.then`)."""
    if n_from < 1 or n_to < 1:
        raise ValueError(
            f"shard counts must be >= 1; got {n_from}→{n_to}")
    if cap_from < 1:
        raise ValueError(f"cap_from must be >= 1; got {cap_from}")
    live = n_from * cap_from if live_slots is None else int(live_slots)
    if not 0 < live <= n_from * cap_from:
        raise ValueError(
            f"live_slots {live} outside (0, {n_from * cap_from}]")
    cap_to = -(-live // n_to)  # ceil: every live slot keeps a home
    return LayoutTransition(
        n_from=int(n_from), cap_from=int(cap_from),
        n_to=int(n_to), cap_to=int(cap_to), live_slots=live,
    )


def relayout_block(x: jnp.ndarray, my: jnp.ndarray,
                   tr: LayoutTransition) -> jnp.ndarray:
    """Device-side half of the transition all-to-all (traced; runs per
    OLD shard inside a ``shard_map``).

    ``x`` is this shard's ``(cap_from, ...)`` slice of one table array
    (guard rows already stripped by the caller).  Each row's target
    placement in the NEW layout is derived from its global slot alone —
    ``slot // cap_to`` picks the new owner, ``slot % cap_to`` the new
    local offset — mirroring :class:`RaggedExtents`'s ownership rule.  The
    scatter lands rows in a zeroed ``(n_to * cap_to, ...)`` buffer;
    summing the per-shard buffers over the shard axis (one ``psum``,
    the caller's half) completes the exchange, because live slot ranges
    are disjoint across old shards."""
    g = my.astype(jnp.int64) * tr.cap_from + jnp.arange(
        tr.cap_from, dtype=jnp.int64
    )
    tgt = tr.owner_of(g) * tr.cap_to + tr.local_of(g)
    buf = jnp.zeros((tr.capacity_to,) + x.shape[1:], x.dtype)
    return buf.at[tgt].set(x, mode="drop")
