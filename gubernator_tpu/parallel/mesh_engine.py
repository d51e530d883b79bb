"""Multi-chip sharded tick engine: the bucket table over a TPU mesh.

The reference scales *within* a node by statically partitioning the key
space over N lock-free workers (``workers.go:19-37,125-147``) and *across*
nodes by consistent-hash ownership.  On TPU the intra-node story becomes a
table **sharded over the device mesh**: a 1-D ``Mesh(('shard',))`` where
device *d* owns the contiguous slot range ``[d*local_cap, (d+1)*local_cap)``.

The hot path is deliberately collective-free: the host resolves each key to
a global slot, routes it to the owning shard, and packs one request block
per shard — ``(n_shards, ROWS, B)`` — so a tick under ``shard_map`` is pure
data-parallel SPMD: every device gathers/updates only its own shard.  This
mirrors the reference's "no mutexes, keys statically routed to workers"
design, with devices in place of goroutines.  Collectives (``psum`` etc.)
enter only on the GLOBAL-behavior reconciliation path (the GLOBAL mesh
engine), matching how the reference keeps its hot loop local and
reconciles asynchronously (``global.go``).

Maintenance operations — evict, install, restore, readback — run as
per-shard blocked ``shard_map``s: the host builds one block per shard
(padding rows aim at the shard's local guard/sentinel) and each device
applies its block to its own slice.  Because the blocks reuse the
single-chip ops (`make_restore_fn` etc.) per shard, the mesh engine
supports BOTH table layouts: the int32-column SoA and the Pallas
row-DMA layout (rowtable.py) — the row layout's ~6-8x tick speedup is not
forfeited by going multi-chip.

**Both tick programs are the one-chip engine's 32-bit programs** (no
64-bit emulation on the device; a leaky bucket's steps are IEEE
binary64 on the bit pattern, ops/b64.py): duplicate-free windows take
``tick32.make_tick32_rows_fn`` (the fused Pallas ragged kernel on a
chip's row layout), duplicate-bearing windows
``tick32.make_sorted_tick32_rows_fn`` (``TickEngine._tick``: exact
per-slot order, closed-form folds), each walked over the shard's
extent.  The x64 ``make_tick_fn`` is the tests' reference only.

**The fill is columnar**: :meth:`MeshTickEngine.load_columns` takes the
Loader v2 snapshot (``TickEngine.load_columns``'s contract for the
fields the mesh restores), routes the keys with the same CRC-32 batch
the tick path uses, and lands the rows through the blocked restore;
``load_items`` is its dict-shaped edge.

**Ragged on-device dispatch (the only tick wire format).**  Keys are
strings, so hashing and the key→slot map stay host-side (SURVEY.md §7
"Host/device split") — but everything else is gone from the host: the
tick ships ONE buffer, the flat slot-sorted (19, B) compact matrix
carrying GLOBAL slots and, in the slab's tail after it, ``now`` as two
int32 words and the ``(n_shards + 1,)`` cumulative offsets
(:class:`partition.RaggedExtents` — the host already knows the
per-shard counts from the resolve; one ``device_put`` and one program
call a window, as the one-chip engine's one upload), and each device
walks only its own ``[offsets[my], offsets[my+1])`` extent of the flat matrix
(ops.raggedtick): no per-shard compaction into a padded
``local_width`` block, no skew fallback, one fixed-shape program per
batch capacity.  The flat batch sorts by GLOBAL slot and ownership is
``slot // local_capacity``, so each shard's rows are contiguous by
construction; responses merge into zeroed flat lanes per shard and
gather collectively with one exact ``psum``.  Adversarially skewed
windows (every key on one shard) run MORE ITERATIONS of the same
compiled extent walk — ``metric_routed_overflows`` stays wired as a
pinned-zero canary.  The upload reuses the single-chip engine's
staging-ring/async-H2D pipeline (ops.engine.StagingRing, single slab
shape) so window N+1's transfer rides under window N's tick.  All
PartitionSpecs come from :mod:`gubernator_tpu.parallel.partition`, the
canonical spec helper both mesh engines share.
"""

from __future__ import annotations

import threading
import time
import zlib
from typing import Dict, List, Optional, Sequence

import collections
import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from gubernator_tpu.native import NativeSlotMap, ShardedWindowPass
from gubernator_tpu.ops import i64pair as p64
from gubernator_tpu.ops import rowtable
from gubernator_tpu.ops.buckets import BucketState
from gubernator_tpu.ops.engine import (
    SNAP_FIELDS,
    ZOO_SNAP_FIELDS,
    EVICT_CHUNK,
    ITEM_INT_ROWS,
    READBACK_ROWS,
    REQ32_INDEX,
    RESTORE_CHUNK,
    StagingRing,
    describe_engine,
    device_dead_mask,
    items_from_columns,
    make_evict_fn,
    make_install_fn,
    make_layout_choice,
    make_readback_fn,
    make_restore_fn,
    masked_over_limit,
    pack_cols_req32,
    pack_wide_rows,
    pad_pow2,
    select_reclaim_victims,
    snapshot_from_items,
    sort_packed_by_slot,
    stamp_now,
    unpack_resp_compact,
)
from gubernator_tpu.ops.raggedtick import (
    choose_tile,
    make_fused_ragged_tick_fn,
    ragged_walk,
)
from gubernator_tpu.ops.reqcols import compact_blob
from gubernator_tpu.ops.tick32 import (
    _resolve_fused,
    make_sorted_tick32_rows_fn,
    make_tick32_rows_fn,
    stack6,
)
from gubernator_tpu.parallel.partition import (
    LayoutTransition,
    RaggedExtents,
    ShardLayout,
    plan_transition,
    relayout_block,
)
from gubernator_tpu.ops.rowtable import ROW_W, RowState
from gubernator_tpu.types import (
    Behavior, GlobalUpdate, RateLimitRequest, RateLimitResponse)
from gubernator_tpu.utils import flightrec, timeutil, tracing
from gubernator_tpu.utils.hotpath import hot_path
from gubernator_tpu.utils import sanitize


def make_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D device mesh over the 'shard' axis (the slot-partition axis)."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), ("shard",))


def make_window_pass(slots, local_capacity: int):
    """The native sharded window pass over a shard set's slot maps
    (``MeshTickEngine._pack_window``), or None where they are the
    pure-Python maps (``make_slot_map`` without the native library):
    those windows take the numpy chain."""
    if all(isinstance(sm, NativeSlotMap) for sm in slots):
        return ShardedWindowPass(slots, local_capacity)
    return None


class ShardedOps:
    """The per-shard device ops for one (mesh, local_capacity, layout):
    tick/evict/install/restore/readback, each a shard_map of the
    corresponding single-chip op, jitted with state donation.  Ticks use
    ONE wire format — the ragged flat dispatch, one slab of (19, B)
    rows, ``now`` and offsets (module docstring) — in two 32-bit
    programs of ``(state, slab)``: the sorted duplicate program
    (``tick32.make_sorted_tick32_rows_fn``, the one-chip ``_tick``) for
    duplicate-bearing windows and the duplicate-free parts program
    (the fused Pallas ragged kernel on the row layout).

    ``trace_counts`` increments once per TRACE of each program (the
    counter bump runs at trace time only): serving re-dispatch must hit
    the warmed executables, and tests pin the counts so a signature
    drift between warmup and serving (e.g. an uncommitted
    ``jnp.asarray`` where warmup used ``put_slab``) fails loudly instead of silently
    re-tracing per tick — with the ragged wire there is exactly one
    program per batch capacity, so ANY skew- or width-driven growth of
    these counters is a regression."""

    def __init__(self, mesh: Mesh, local_capacity: int, layout: str):
        self.mesh = mesh
        self.layout = layout
        self.local_capacity = local_capacity
        n = mesh.devices.size
        self.trace_counts = collections.Counter()
        lay = ShardLayout()
        self.spec_layout = lay

        if layout == "row":
            # Each shard's block is its own (local_cap+1, ROW_W) row table
            # — per-shard guard rows included, so local slot arithmetic
            # inside the block is identical to the single-chip engine's.
            def zeros_global():
                return RowState(
                    table=jnp.zeros((n * (local_capacity + 1), ROW_W), jnp.int32)
                )
        else:
            def zeros_global():
                return BucketState.zeros(n * local_capacity)

        state_spec = lay.table_spec(layout)
        self.state_spec = state_spec
        self.state_shardings = lay.shardings(mesh, state_spec)
        self.zeros_global = zeros_global
        self.block_sharding2 = lay.shardings(mesh, lay.blocked2())
        self.block_sharding3 = lay.shardings(mesh, lay.blocked3())

        evict = make_evict_fn(layout)
        install = make_install_fn(layout)
        restore = make_restore_fn(layout)
        readback = make_readback_fn(layout)

        def smap(fn, in_specs, out_specs):
            return jax.jit(
                shard_map(
                    fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                    check_vma=False,
                ),
                donate_argnums=(0,),
            )

        # ---- Ragged flat tick programs (module docstring): ONE
        # replicated upload in, the slot-sorted (19, B) batch with
        # ``now`` and the (n_shards + 1,) extent offsets in its tail
        # (RaggedExtents.split); each shard walks only its own
        # [offsets[my], offsets[my+1]) extent of the flat matrix
        # (ops.raggedtick) and the responses gather with one psum.
        # Compact int32 wire formats (engine.REQ32 / pack_resp_compact):
        # requests cross host->devices at 76 B each and responses return
        # at 24, the transfer win the single-chip engine gets.  ``now``
        # is read as its i32 pair (tick32.split_slab's reading): no
        # 64-bit value exists in either program.
        n_shards = n
        split = RaggedExtents(n, local_capacity).split

        def _window(slab):
            """(m, this shard's (start, count, lo), now) of one upload."""
            m, now, offsets = split(slab)
            my = lax.axis_index("shard")
            start = offsets[my]
            count = offsets[my + 1] - start
            lo = my.astype(jnp.int32) * local_capacity
            return m, (start, count, lo), p64.I64(now[0], now[1])

        def walk_rows(rows_fn, state_blk, slab):
            """One shard's extent of the flat batch through a
            single-chip 32-bit rows program, tile by tile; the six
            response rows merge into zeroed flat lanes."""
            m, extent, now = _window(slab)
            b = m.shape[1]

            def tile_tick(s_, blk):
                s2, rows = rows_fn(s_, blk, now)
                return s2, tuple(rows)

            return ragged_walk(
                tile_tick, state_blk, m, *extent,
                local_capacity, choose_tile(b, n_shards),
                tuple(jnp.zeros(b, jnp.int32) for _ in range(6)),
            )

        # Each program is ONE jit of (state, slab): the state donated,
        # the slab replicated (put_slab).  (The wrappers go to shard_map
        # by name: guberlint G006 finds its traced functions that way.)
        self.slab_sharding = lay.shardings(mesh, lay.flat2())
        flat = dict(
            mesh=mesh, in_specs=(state_spec, lay.flat2()),
            out_specs=(state_spec, lay.flat2()), check_vma=False,
        )

        # Duplicate-bearing windows: the one-chip engine's sorted
        # duplicate program (exact per-slot order, closed-form folds,
        # IEEE leaky steps; no 64-bit emulation).  A duplicate group
        # that straddles two tiles is two sequential ticks of its slot,
        # the state carried between them (raggedtick module doc).
        sorted_rows = make_sorted_tick32_rows_fn(local_capacity, layout)

        def _tick_ragged(state_blk, slab):
            self.trace_counts["tick_ragged"] += 1
            st, rows = walk_rows(sorted_rows, state_blk, slab)
            return st, lax.psum(stack6(rows), "shard")

        def named(fn, name):
            """The column layout's programs carry it in their names
            (``jit_mesh_tick_<program>_columns`` in a trace); the row
            layout's keep the module names of their cache entries.  (The
            functions still reach shard_map by their own names: G006.)"""
            if layout != "row":
                fn.__name__ = fn.__qualname__ = f"mesh_tick_{name}_columns"

        named(_tick_ragged, "sorted")
        self.tick_ragged = jax.jit(
            shard_map(_tick_ragged, **flat), donate_argnums=(0,))

        # The parts-native program for duplicate-free windows (the
        # production common case): host-dispatched as its OWN program,
        # so the row layout keeps the fused Mosaic kernel per shard,
        # which walks the extent inside the kernel (runtime chunk
        # count); elsewhere the unfused rows program is tiled by
        # ragged_walk like the duplicate program.
        self._fused32 = layout == "row" and _resolve_fused(None)
        if self._fused32:
            fused_ragged = make_fused_ragged_tick_fn(local_capacity)

            def _tick32_ragged(state_blk, slab):
                self.trace_counts["tick_unique_ragged"] += 1
                m, extent, now = _window(slab)
                st, resp = fused_ragged(state_blk, m, *extent, now)
                return st, lax.psum(resp, "shard")
        else:
            tick32_rows = make_tick32_rows_fn(local_capacity, layout)

            def _tick32_ragged(state_blk, slab):
                self.trace_counts["tick_unique_ragged"] += 1
                st, rows = walk_rows(tick32_rows, state_blk, slab)
                return st, lax.psum(stack6(rows), "shard")

        named(_tick32_ragged, "unique")
        self.tick_unique_ragged = jax.jit(
            shard_map(_tick32_ragged, **flat), donate_argnums=(0,))

        def _evict(state_blk, slots_blk):
            return evict(state_blk, slots_blk[0])

        self.evict = smap(
            _evict, (state_spec, P("shard", None)), state_spec
        )

        def _install(state_blk, cols_blk, now):
            return install(state_blk, cols_blk[0], now)

        self.install = smap(
            _install, (state_spec, P("shard", None, None), P()), state_spec
        )

        def _restore(state_blk, ints_blk, floats_blk):
            return restore(state_blk, ints_blk[0], floats_blk[0])

        self.restore = smap(
            _restore,
            (state_spec, P("shard", None, None), P("shard", None)),
            state_spec,
        )

        def _readback(state_blk, slots_blk):
            ints, floats = readback(state_blk, slots_blk[0])
            return ints[None], floats[None]

        # No donation: readback is a pure gather.
        self.readback = jax.jit(
            shard_map(
                _readback,
                mesh=mesh,
                in_specs=(state_spec, P("shard", None)),
                out_specs=(P("shard", None, None), P("shard", None)),
                check_vma=False,
            )
        )

    def init_state(self):
        """The zeroed table, made sharded: each chip zero-fills its own
        shard.  (Made whole on one device and then placed, it cost that
        device two whole tables: 12.8 GB at 12.5M rows.)"""
        return jax.jit(
            self.zeros_global, out_shardings=self.state_shardings)()

    def put_slab(self, slab: np.ndarray):
        """A window's one upload: ONE ``device_put`` of the host slab
        onto the replicated sharding the tick programs take it in, so
        the four copies leave in one call and the program call finds its
        argument where it wants it.  (An uncommitted ``jnp.asarray``
        lands on device 0 and the call replicates it from there, on
        jit's Python path: 2.25 ms a window against 1.74, PERF.md
        section 6, PR 37.)  Warm-up and serving both come through here:
        the committed sharding is part of the jit signature."""
        return jax.device_put(slab, self.slab_sharding)

    def put2(self, blk: np.ndarray):
        return jax.device_put(blk, self.block_sharding2)

    def put3(self, blk: np.ndarray):
        return jax.device_put(blk, self.block_sharding3)


class MeshRaggedTickHandle:
    """One dispatched ragged mesh tick: the flat (6, B) compact
    response is already in slot-sorted request-batch order (the shards'
    extent walks merged every lane in place; the psum gather summed the
    disjoint extents), so resolution is exactly the single-chip
    ``TickHandle`` contract — un-permute, rebuild the public (5, n)
    int64 matrix, run the deferred bookkeeping.  Duck-compatible with
    ``ops.engine.resolve_ticks`` (same-shape responses stack into one
    D2H)."""

    __slots__ = ("_engine", "_resp", "_n", "_inv", "errors", "_limit_req",
                 "_wt_args", "_done", "_flock", "_wid")

    def __init__(self, engine, resp, n, inv, errors, limit_req, wt_args):
        self._engine = engine
        self._resp = resp
        self._n = n
        self._inv = inv
        self.errors = errors
        # Copied: callers may reuse their ReqColumns buffers between
        # submit and resolve (the pipelining pattern).
        # guber: allow-G001(host column snapshot - limit_req is a host array; the copy is the pipelining contract, not a device sync)
        self._limit_req = np.array(limit_req[:n], np.int64, copy=True)
        self._wt_args = wt_args
        self._done: Optional[np.ndarray] = None
        self._flock = sanitize.lock("MeshRaggedTickHandle._flock")
        # as TickHandle: the recorder's window in dispatch, for _finish
        fr = flightrec.get()
        self._wid = fr.active() if fr is not None else None

    def _finish(self, raw: np.ndarray) -> None:
        with self._flock:
            if self._done is not None:
                return
            rm = unpack_resp_compact(
                raw[:, : self._n][:, self._inv], self._limit_req
            )
            eng = self._engine
            waited = flightrec.stage("finish_lock", into=self._wid).start()
            with eng._lock:
                waited.stop()
                # This window is resolved: it no longer holds its H2D
                # staging slab, and later windows' uploads stop counting
                # it as overlap (metric_h2d_overlapped).
                eng._inflight = max(0, eng._inflight - 1)
                eng.metric_over_limit += masked_over_limit(rm, self.errors)
                if eng.store is not None and self._wt_args is not None:
                    eng._write_through(*self._wt_args)
            self._resp = None  # release the device buffer reference
            self._done = rm

    def result(self):
        if self._done is None:
            self._finish(np.asarray(self._resp))
        return self._done, self.errors


class MeshTickEngine:
    """Host driver for the sharded table (multi-chip WorkerPool analog).

    Same contract as :class:`gubernator_tpu.ops.engine.TickEngine` — row or
    column layout, optional Store write/read-through — but the table lives
    sharded across ``mesh``; total capacity is ``n_shards * local_capacity``.
    Key→shard routing reuses the engine's slot allocator: global slot ``g``
    lives on shard ``g // local_capacity`` at local offset
    ``g % local_capacity`` — the ONE ownership rule, derived identically by
    the host resolve and the on-device extent walker
    (partition.RaggedExtents / ops.raggedtick).

    Every tick ships the ragged flat wire format (module docstring).
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        local_capacity: int = 1 << 14,
        max_batch: int = 1024,
        store=None,
        table_layout: str = "auto",
    ):
        from gubernator_tpu.config import env_knob
        from gubernator_tpu.ops.engine import make_slot_map

        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_shards = self.mesh.devices.size
        self.local_capacity = int(local_capacity)
        self.capacity = self.n_shards * self.local_capacity
        if self.capacity >= (1 << 31):
            # The flat wire format carries GLOBAL slots in an int32 row.
            raise ValueError(
                f"sharded table capacity {self.capacity} exceeds int32 "
                "global slots"
            )
        self.max_batch = int(max_batch)
        self.store = store
        # As-configured layout knob, kept verbatim so reshard() can
        # re-derive the auto choice (layout fit) for the new shard
        # count instead of freezing this build's resolution.
        self._table_layout_conf = table_layout
        self.layout = make_layout_choice(
            table_layout, self.local_capacity,
            self.mesh.devices.flat[0], self.max_batch,
        )
        self.ragged = RaggedExtents(self.n_shards, self.local_capacity)
        self.ops = ShardedOps(self.mesh, self.local_capacity, self.layout)
        self.state = self.ops.init_state()
        # One slot allocator per shard; keys are routed to shards by hash,
        # the mesh analog of the reference's hash-range→worker routing
        # (workers.go:180-184).
        self.slots = [make_slot_map(self.local_capacity) for _ in range(self.n_shards)]
        # The host side of a window in one native call (_pack_window):
        # there with the native slot maps, whose library carries the
        # pass; and the windows it answered, over metric_h2d_windows.
        self._window_pass = make_window_pass(self.slots, self.local_capacity)
        self.metric_native_pack_windows = 0
        self._last_access = np.zeros(self.capacity, np.int64)
        # Global slots assigned host-side but not yet written by a device
        # tick; device in_use/expire_at lag for these, so reclamation must
        # not treat them as dead (see TickEngine._pending).
        self._pending: set = set()
        self._tick_count = 0
        self._lock = sanitize.rlock("MeshTickEngine._lock")
        # Flat-upload staging ring + overlap telemetry (the PR 6
        # double-buffered H2D pipeline, shared via ops.engine.StagingRing;
        # sentinel is the GLOBAL capacity — flat padding lanes belong to
        # no shard).  The ragged wire has exactly ONE slab shape
        # (rows × max_batch), so the ring preallocates it up front.
        try:
            _depth = max(1, env_knob(
                "GUBER_TICK_PIPELINE_DEPTH", 4, parse=int))
        except ValueError:
            _depth = 4
        self._staging_slabs = 2 * _depth + 1
        self._staging = StagingRing(
            self.ragged.slab_rows(self.max_batch), self.capacity,
            self._staging_slabs, width=self.max_batch)
        self._inflight = 0
        self.metric_h2d_windows = 0
        self.metric_h2d_overlapped = 0
        # host->device uploads, over h2d_windows: uploads a window, 1.0
        # (the matrix, ``now`` and the offsets are one slab)
        self.metric_h2d_uploads = 0
        # windows by the program that answered them; they add up to
        # metric_h2d_windows
        self.metric_dup_windows = 0
        self.metric_unique_windows = 0
        self.metric_routed_windows = 0
        self.metric_routed_overflows = 0
        self.metric_hits = 0
        self.metric_misses = 0
        self.metric_over_limit = 0
        self.metric_unexpired_evictions = 0
        # the fills' seconds and the rows they landed (load_columns)
        self.load_seconds = 0.0
        self.load_rows = 0
        t0 = time.perf_counter()
        self._warmup()
        self.warmup_seconds = time.perf_counter() - t0

    def describe(self) -> dict:
        return describe_engine(
            self.mesh.devices.flat[0], self.n_shards, self.layout,
            self.ops._fused32, self.warmup_seconds,
            native_pack=self._window_pass is not None,
            load_seconds=self.load_seconds, load_rows=self.load_rows,
        )

    def _warmup(self) -> None:
        """Compile the serving-path programs at startup (see
        TickEngine._warmup): both ragged ticks — the sorted duplicate
        program and the duplicate-free parts program — with an
        all-sentinel batch and empty extents (a zeroed tail: the
        walkers' dynamic trip counts are runtime values, so the empty
        window compiles the same single program serving traffic uses).
        Warmup MUST dispatch with the exact serving signature: the
        whole slab through ``ShardedOps.put_slab`` (committed,
        replicated), never an uncommitted ``jnp.asarray`` — the
        sharding's commitment is part of the jit signature, and another
        one re-traces every warmed program (~0.6 s each; the
        ShardedOps.trace_counts pin in test_mesh_engine holds this)."""
        if jax.default_backend() == "tpu":
            # Eager tick compiles are a serving chip's live-deadline
            # concern (see TickEngine._warmup): on the CPU backend
            # (the tests) each shard_map trace costs seconds per engine
            # and most tests tick only one of the two programs — lazy
            # is the right trade.
            slab = np.zeros(
                (self.ragged.slab_rows(self.max_batch), self.max_batch),
                np.int32)
            slab[REQ32_INDEX["slot"]] = self.capacity
            self.state, resp = self.ops.tick_ragged(
                self.state, self.ops.put_slab(slab))
            # guber: allow-G001(init-time warmup D2H - deliberately materializes once at engine construction to pre-compile; never inside a serving tick)
            np.asarray(resp)  # warm the response D2H path
            self.state, resp = self.ops.tick_unique_ragged(
                self.state, self.ops.put_slab(slab))
            # guber: allow-G001(init-time warmup D2H - same as above)
            np.asarray(resp)
        cols = np.zeros((self.n_shards, 8, 1), np.int64)  # valid=0: no-op
        self.state = self.ops.install(
            self.state, self.ops.put3(cols), jnp.int64(0)
        )
        # Pre-compile the per-shard reclaim dead-scan (see TickEngine).
        self._shard_dead_mask(0, 0)
        # guber: allow-G001(init-time warmup barrier - construction completes only when the device programs are resident)
        jax.block_until_ready(self.state)

    def h2d_overlap_ratio(self) -> float:
        """Fraction of serving windows whose request upload overlapped an
        earlier window's still-running tick (see TickEngine)."""
        return self.metric_h2d_overlapped / max(1, self.metric_h2d_windows)

    # ------------------------------------------------------------------
    # Shard routing / reclamation
    # ------------------------------------------------------------------
    def _shard_of(self, key: str) -> int:
        return zlib.crc32(key.encode()) % self.n_shards

    def _shard_dead_mask(self, shard: int, now: int) -> np.ndarray:
        """Device-dead mask for one shard's slice of the table, scanned
        in the shard's own buffers on its own chip.  A slice of the
        sharded arrays would gather them onto one device first: the
        whole row table (12.8 GB at 12.5M rows, which a chip holding its
        1.6 GB shard refuses), or 1.1 GB of ``in_use`` and ``expire_at``
        columns at 125M slots."""

        def own(arr, start):
            return next(sh.data for sh in arr.addressable_shards
                        if (sh.index[0].start or 0) == start)

        if self.layout == "row":
            blk = own(self.state.table, shard * (self.local_capacity + 1))
            return rowtable.row_device_dead_mask(
                RowState(table=blk), now, self.local_capacity)
        lo = shard * self.local_capacity
        return device_dead_mask(
            own(self.state.in_use, lo),
            tuple(own(part, lo) for part in self.state.expire_at),
            now, self.local_capacity,
        )

    def _resolve(self, key: str, shard: int, now: int) -> tuple[Optional[int], bool]:
        """(global slot, known) for key within its shard, reclaiming if
        needed; slot is None when the shard is full of same-tick live slots
        (caller spills the request to the next tick)."""
        sm = self.slots[shard]
        known = sm.get(key) is not None
        local = sm.assign(key)
        if local is None:
            self._reclaim(shard, now)
            known = sm.get(key) is not None  # reclaim may release the key
            local = sm.assign(key)
            if local is None:
                return None, known
        g = shard * self.local_capacity + local
        if not known:
            self._pending.add(g)
        self._last_access[g] = self._tick_count
        return g, known

    def _reclaim(self, shard: int, now: int) -> None:
        """Free expired slots in one shard; fall back to LRU eviction —
        the shared TTL/LRU policy (engine.select_reclaim_victims) over this
        shard's slice of the table."""
        sm = self.slots[shard]
        lo = shard * self.local_capacity
        mapped = sm.mapped_mask()
        if self._pending:
            pend = [g - lo for g in self._pending if lo <= g < lo + self.local_capacity]
            if pend:
                # guber: allow-G001(host index build over a small python set - no device data; reclaim runs at most once per full shard, not per tick)
                mapped[np.asarray(pend, np.int64)] = False
        freed, victims = select_reclaim_victims(
            mapped,
            self._shard_dead_mask(shard, now),
            self._last_access[lo : lo + self.local_capacity],
            self._tick_count,
            max(1, self.local_capacity // 16),
        )
        sm.release_batch(freed)
        if len(victims) == 0:
            return
        self.metric_unexpired_evictions += len(victims)
        sm.release_batch(victims)
        self._evict_local(shard, victims)

    def _evict_local(self, shard: int, victims: np.ndarray) -> None:
        """Blocked device evict of one shard's local victim slots.

        One whole-mesh dispatch per reclaiming shard (other shards' rows
        pad to the guard).  Reclaims are per-shard events driven from the
        resolve loop, so the common case is exactly one shard per tick;
        if profiling ever shows multi-shard reclaim storms, batch the
        victim blocks across shards the way install_globals does."""
        for start in range(0, len(victims), EVICT_CHUNK):
            part = victims[start : start + EVICT_CHUNK]
            w = min(EVICT_CHUNK, pad_pow2(len(part)))
            blk = np.full((self.n_shards, w), self.local_capacity, np.int64)
            blk[shard, : len(part)] = part
            self.state = self.ops.evict(self.state, self.ops.put2(blk))

    # ------------------------------------------------------------------
    # The tick — columnar, pipelined (the round-3 TickEngine host path,
    # uniform across however many shards exist: workers.go:125-147)
    # ------------------------------------------------------------------
    @hot_path
    def _gregorian_cols(self, cols, now: int, errors: Dict[int, str]):
        """Host-side Gregorian resolution (flagged rows only)."""
        n = len(cols)
        GREG = int(Behavior.DURATION_IS_GREGORIAN)
        greg_e = np.zeros(n, np.int64)
        greg_d = np.zeros(n, np.int64)
        greg = cols.behavior & GREG
        if greg.any():
            for i in np.flatnonzero(greg):
                try:
                    d = int(cols.duration[i])
                    greg_e[i] = timeutil.gregorian_expiration(now, d)
                    greg_d[i] = timeutil.gregorian_duration(now, d)
                except timeutil.GregorianError as exc:
                    errors[int(i)] = str(exc)
        return greg_e, greg_d

    @hot_path
    def _resolve_columns(self, cols, now: int, errors: Dict[int, str],
                         resolved=None):
        """The sharded-slotmap resolve in numpy: one vectorized CRC-32
        batch routes keys to shards (bit-identical to the scalar
        ``_shard_of`` router — and to the ownership the device derives
        from the resulting global slot), the key blob regroups by shard
        with one byte-gather, and one native blob resolve per shard
        assigns local slots (``resolved``: the native window pass's
        ``(sh, slots, known)`` where it got that far, so no key is
        resolved twice).  A shard with a key that found no slot is
        reclaimed and its keys retried; keys whose shard stays full
        after reclaim become per-item errors (the reference's
        error-in-item convention).  Returns ``(sh, slots, known)`` with
        resolved rows stamped live (``_last_access``/``_pending``)."""
        n = len(cols)
        # Traced windows carry the resolve as a child span.
        with tracing.maybe_span("guber.mesh.resolve", {"batch": n}):
            return self._resolve_columns_locked(cols, now, errors, n, resolved)

    @hot_path
    def _group_by_shard(self, blob, offsets):
        """Route a packed key batch: ``sh`` (n,) the shard of every key
        (vectorized CRC-32 over the blob, bit-identical to the scalar
        ``_shard_of``), and the batch regrouped by shard with one
        byte-gather — ``order`` (the rows, shard by shard),
        ``grouped_blob`` / ``g_offsets`` (their keys, in that order) and
        ``starts`` (n_shards + 1,): shard ``s`` owns rows
        ``order[starts[s]:starts[s + 1]]``.  The tick path's resolve and
        the Loader's fill route through here alike."""
        from gubernator_tpu.native import crc32_batch

        shard_ids = crc32_batch(blob, offsets) % np.uint32(self.n_shards)
        sh = shard_ids.astype(np.int64)
        # A stable sort of 16-bit ids is a radix sort: at a 100M-key fill
        # the int64 one is most of the route's seconds.
        order = np.argsort(
            shard_ids.astype(np.uint16) if self.n_shards <= 1 << 16 else sh,
            kind="stable")
        # guber: allow-G001(key offsets are host numpy, never device)
        offs = np.asarray(offsets, np.int64)
        lens = np.diff(offs)
        lo = lens[order]
        cum = np.cumsum(lo)
        blob_arr = np.frombuffer(blob, np.uint8)
        width = int(lens[0]) if len(lens) else 0
        if width and (lens == width).all():
            # Keys of one length (a Loader's fill): a gather of rows,
            # not of bytes.
            grouped_blob = blob_arr[offs[0]:offs[-1]].reshape(
                -1, width)[order].tobytes()
        elif len(blob_arr):
            gather = (
                np.arange(int(cum[-1]), dtype=np.int64)
                - np.repeat(cum - lo, lo)
                + np.repeat(offs[:-1][order], lo)
            )
            grouped_blob = blob_arr[gather].tobytes()
        else:
            grouped_blob = b""
        g_offsets = np.concatenate(
            [np.zeros(1, np.int64), cum]
        )
        starts = np.searchsorted(sh[order], np.arange(self.n_shards + 1))
        return sh, order, grouped_blob, g_offsets, starts

    @hot_path
    def _resolve_columns_locked(self, cols, now, errors, n, resolved=None):
        if resolved is not None:
            sh, slots, known = resolved
        else:
            sh, order, grouped_blob, g_offsets, starts = self._group_by_shard(
                cols.key_blob, cols.key_offsets)
            slots = np.full(n, -1, np.int64)
            known = np.zeros(n, np.uint8)
            for s in range(self.n_shards):
                a, z = int(starts[s]), int(starts[s + 1])
                if a == z:
                    continue
                rows_s = order[a:z]
                off_s = g_offsets[a:z + 1] - g_offsets[a]
                blob_s = grouped_blob[g_offsets[a]:g_offsets[z]]
                slots[rows_s], known[rows_s] = self.slots[s].resolve_blob(
                    blob_s, off_s)

        # A shard with a key that found no slot: reclaim it, retry its
        # keys.  (Shards share nothing a reclaim reads or frees, so it
        # does not matter that the others were resolved before it.)
        for s in np.unique(sh[slots < 0]).tolist():
            rows_s = np.flatnonzero(sh == s)
            sl, kn = slots[rows_s], known[rows_s]
            # Stamp already-resolved rows live before reclaiming
            # (an unstamped reclaim could hand a just-resolved
            # slot to the retried keys).
            okm = sl >= 0
            g = s * self.local_capacity + sl[okm]
            self._last_access[g] = self._tick_count
            self._pending.update(g[kn[okm] == 0].tolist())
            self._reclaim(s, now)
            retry = np.flatnonzero(sl < 0)
            s2, k2 = self.slots[s].resolve_batch(
                [cols.key_bytes(int(rows_s[t])) for t in retry])
            sl[retry] = s2
            kn[retry] = k2
            for t in np.flatnonzero(sl < 0):
                errors[int(rows_s[t])] = (
                    "rate-limit shard full; eviction failed")
            slots[rows_s] = sl
            known[rows_s] = kn

        placed = slots >= 0
        g_res = sh[placed] * self.local_capacity + slots[placed]
        self._last_access[g_res] = self._tick_count
        self._pending.update(g_res[known[placed] == 0].tolist())
        return sh, slots, known

    @hot_path
    def _account_misses(self, cols, sh, slots, known, now: int) -> None:
        """Hit/miss accounting + Store read-through for one resolved
        batch (``known`` is updated in place for store-restored rows)."""
        n = len(cols)
        resolved = slots >= 0
        miss_like = resolved & (known == 0)
        if self.store is not None and self._pending:
            g_all = sh * self.local_capacity + np.maximum(slots, 0)
            pend = self._pending
            miss_like = miss_like | (resolved & np.fromiter(
                (int(g) in pend for g in g_all), np.bool_, n))
        n_res = int(resolved.sum())
        n_miss = int(miss_like.sum())
        self.metric_hits += n_res - n_miss
        self.metric_misses += n_miss
        if self.store is not None and n_miss:
            if cols.refs is None:
                raise ValueError(
                    "Store read-through needs request objects; build "
                    "the batch with ReqColumns.from_requests(..., "
                    "keep_refs=True)")
            self._read_through(
                cols.refs, list(range(n)), sh, slots, known,
                np.flatnonzero(miss_like), now)

    @hot_path
    def submit_columns(self, cols, now: Optional[int] = None):
        """Build + dispatch one mesh tick (≤ max_batch rows) and return
        a handle; device work is queued, not awaited, so host packing of
        the next tick overlaps device execution of this one
        (TickEngine.submit_columns's contract, sharded).

        Every window — skewed or not — takes the ragged flat dispatch:
        the extent walk's trip counts are runtime values, so there is
        no per-shard width to overflow and no fallback format
        (``metric_routed_overflows`` stays a pinned-zero canary)."""
        n = len(cols)
        if n > self.max_batch:
            raise ValueError(
                f"batch of {n} exceeds engine max {self.max_batch}")
        # Flight-recorder stages, the single-chip TickEngine.
        # submit_columns's under the same names: the native pass is one
        # call, so one "pack" range, the lease inside it; "route" (keys
        # -> shard -> slot and the hit/miss accounting, the layer the
        # sharded table adds on the host) is noted from the pass's own
        # clock and taken off "pack", which keeps the slab rows, the
        # slot sort and the slab's tail.
        waited = flightrec.stage("submit_lock").start()
        with self._lock:
            waited.stop()
            now = now if now is not None else timeutil.now_ms()
            self._tick_count += 1
            errors: Dict[int, str] = {}
            fr = flightrec.get()
            with flightrec.stage("pack"):
                with flightrec.stage("lease"):
                    # The native window pass cleans the rows it packs.
                    slab = self._staging.lease(
                        self.max_batch, clean=self._window_pass is None)
                sh, slots, ix, inv, has_dups, route_s = self._pack_window(
                    cols, now, slab, errors)
            if fr is not None:
                fr.note(fr.active(), "route", route_s)
                fr.note(fr.active(), "pack", -route_s)
            return self._dispatch_ragged(
                cols, now, slab, sh, slots, ix, inv, has_dups, errors)

    @hot_path
    def _pack_window(self, cols, now: int, slab: np.ndarray,
                     errors: Dict[int, str]):
        """One window's host side: keys to shards and slots, and the
        leased ``slab`` as the window's ONE upload
        (partition.RaggedExtents.split): the slot-sorted (19, B) compact
        matrix carrying GLOBAL slots and, in the tail, ``now`` and the
        per-shard extent offsets (the slot sort groups shards
        contiguously in ascending order), written in place.  Returns
        ``(sh, slots, ix, inv, has_dups, route_s)``: the route and the
        LOCAL slots in request order, ``ix`` the rows packed (None: all
        of them), ``inv`` the request -> sorted-lane permutation and
        ``route_s`` the seconds of the call that went to routing and
        accounting.

        The window the served path sees all day takes ONE native call
        (native/slotmap.cc guber_slotmap_pack_window_sharded, the
        sibling of the one-chip engine's window pass; for a wide window
        ctypes drops the GIL for all of it, where the numpy chain is
        some sixty to eighty short calls that each drop it and wait to
        take it back beside the event loop and the resolver).  What the
        batch shows decides, no knob: a Gregorian row (host calendar
        math), a key that finds no slot in its shard (reclaim and
        retry), or a new key with a Store to ask first takes
        :meth:`_pack_window_numpy` — from the slots the native pass
        already resolved, where it got that far.  So do the pure-Python
        slot maps (no native library)."""
        wp = self._window_pass
        resolved = None
        m, now_words, offs = self.ragged.split(slab)
        stamp_now(now_words, now)
        if wp is not None:
            n = len(cols)
            with tracing.maybe_span("guber.mesh.pack_window", {"batch": n}):
                status, sh, slots, known, inv, n_miss, counts, route_s = (
                    wp.pack_window(
                        cols, m, now, self.store is not None,
                        self._last_access, self._tick_count))
            if status >= 0:
                t0 = time.perf_counter()
                if n_miss:
                    g = sh * self.local_capacity + slots
                    self._pending.update(g[known == 0].tolist())
                self.metric_hits += n - n_miss
                self.metric_misses += n_miss
                self.metric_native_pack_windows += 1
                self.ragged.offsets(counts, out=offs)
                return (sh, slots, None, inv,
                        status != NativeSlotMap.PACK_UNIQUE,
                        route_s + time.perf_counter() - t0)
            if status == NativeSlotMap.PACK_RESOLVED_ONLY:
                resolved = sh, slots, known
            self._staging.clean(m)   # the pass left the rows as leased
        return self._pack_window_numpy(cols, now, m, offs, errors, resolved)

    @hot_path
    def _pack_window_numpy(self, cols, now: int, m: np.ndarray,
                           offs: np.ndarray, errors: Dict[int, str],
                           resolved=None):
        """:meth:`_pack_window` in numpy, for the windows the native
        pass leaves (and for the pure-Python slot maps), into the slab's
        clean (19, B) rows ``m`` and its tail's offsets ``offs``:
        Gregorian rows, the resolve (``resolved``: see
        :meth:`_resolve_columns`), hit/miss accounting and Store
        read-through, then the REQ32 rows of the rows that found a slot,
        one argsort and the extents' counts."""
        n = len(cols)
        t0 = time.perf_counter()
        greg_e, greg_d = self._gregorian_cols(cols, now, errors)
        sh, slots, known = self._resolve_columns(cols, now, errors, resolved)
        self._account_misses(cols, sh, slots, known, now)
        route_s = time.perf_counter() - t0
        ok = slots >= 0
        for i in errors:
            ok[i] = False
        ix = np.flatnonzero(ok)
        gslot = sh[ix] * self.local_capacity + slots[ix]
        pack_cols_req32(m, cols, gslot, known[ix], now, ix)
        pack_wide_rows(m, "greg_exp", greg_e[ix], ix)
        pack_wide_rows(m, "greg_dur", greg_d[ix], ix)
        inv, has_dups = sort_packed_by_slot(m, n, self.capacity)
        self.ragged.offsets(self.ragged.counts(sh, ok), out=offs)
        return sh, slots, ix, inv, has_dups, route_s

    @hot_path
    def _dispatch_ragged(
        self, cols, now, slab, sh, slots, ix, inv, has_dups, errors
    ) -> "MeshRaggedTickHandle":
        """The ragged flat dispatch of a packed window
        (:meth:`_pack_window`): the slab goes up with ONE async
        ``device_put`` (``ShardedOps.put_slab``: the transfer rides
        under the previous window's tick; the signature matches warmup,
        so re-dispatch reuses the compiled program) and one program call.
        Each shard walks only its own extent on device — no per-shard
        host loop, no padded per-shard block, responses gathered with
        one psum."""
        n = len(cols)
        with flightrec.stage("h2d"), \
                tracing.maybe_span("guber.mesh.dispatch_ragged",
                                   {"batch": n}):
            if has_dups:
                self.metric_dup_windows += 1
                tick = self.ops.tick_ragged
            else:
                self.metric_unique_windows += 1
                tick = self.ops.tick_unique_ragged
            self.state, resp = tick(self.state, self.ops.put_slab(slab))
        rest = flightrec.stage("handle").start()
        self._pending.clear()
        self.metric_routed_windows += 1
        self.metric_h2d_uploads += 1
        wt_args = None
        if self.store is not None:
            if ix is None:
                ix = np.arange(n)
            wt_args = (cols.refs, list(range(n)), ix, sh, slots, now)
        handle = MeshRaggedTickHandle(
            self, resp, n, inv, errors, cols.limit, wt_args
        )
        self.metric_h2d_windows += 1
        if self._inflight > 0:
            self.metric_h2d_overlapped += 1
        self._inflight += 1
        self._staging.retire(handle)
        rest.stop()
        if self.store is not None:
            handle.result()
        return handle

    @hot_path
    def submit_cols(self, cols, now: Optional[int] = None):
        """Dispatch a columnar batch of any width (chunked into
        max_batch ticks; chunk k+1 packs while chunk k executes)."""
        from gubernator_tpu.ops.engine import SubmittedBatch

        n = len(cols)
        now = now if now is not None else timeutil.now_ms()
        spans = [
            (s, min(s + self.max_batch, n))
            for s in range(0, n, self.max_batch)
        ]
        handles = [
            self.submit_columns(
                cols if len(spans) == 1 else cols.slice_chunk(s, e), now
            )
            for s, e in spans
        ]
        return SubmittedBatch(handles, spans, n)

    @hot_path
    def submit(
        self, requests: Sequence[RateLimitRequest], now: Optional[int] = None
    ):
        """Object-level dispatch without awaiting the device (the tick
        loop's pipelining hook)."""
        from gubernator_tpu.ops.reqcols import ReqColumns

        return self.submit_cols(
            ReqColumns.from_requests(
                requests, keep_refs=self.store is not None
            ),
            now,
        )

    def process_columns(self, cols, now: Optional[int] = None):
        if len(cols) == 0:
            return np.zeros((5, 0), np.int64), {}
        return self.submit_cols(cols, now).matrix()

    def process(
        self, requests: Sequence[RateLimitRequest], now: Optional[int] = None
    ) -> List[RateLimitResponse]:
        """Apply a batch of requests; responses in request order."""
        if not requests:
            return []
        return self.submit(requests, now).responses()

    @staticmethod
    def _blocked_chunks(per_shard):
        """Chunk schedule for blocked per-shard matrices: yields (start, w)
        strided by RESTORE_CHUNK with w = pad_pow2 of the widest shard's
        remaining rows (capped at RESTORE_CHUNK).  The stride/width
        interplay is subtle — when the remainder fits, w covers ALL of
        every shard's remaining rows, so stepping a full RESTORE_CHUNK
        skips nothing — and lives only here."""
        lens = [len(v) for v in (
            per_shard.values() if isinstance(per_shard, dict) else per_shard
        )]
        widest = max(lens, default=0)
        start = 0
        while start < widest:
            w = pad_pow2(min(
                RESTORE_CHUNK,
                max((n - start for n in lens if n > start), default=0),
            ))
            if w <= 0:
                return
            yield start, w
            start += RESTORE_CHUNK

    # ------------------------------------------------------------------
    # Store write/read-through (reference store.go:49-65) — blocked
    # ------------------------------------------------------------------
    def _read_through(
        self, requests, idx, shards, slots, known, miss_sel, now: int
    ) -> None:
        """Store.Get for cache misses (algorithms.go:45-51): install the
        persisted items, blocked per shard, before the tick runs."""
        rows_by_shard: Dict[int, List[tuple]] = {}
        restored: set = set()
        for j in miss_sel:
            g = int(shards[j]) * self.local_capacity + int(slots[j])
            if g in restored:
                known[j] = 1
                continue
            item = self.store.get(requests[idx[j]])
            if item is None:
                continue
            restored.add(g)
            known[j] = 1
            self._pending.discard(g)
            rows_by_shard.setdefault(int(shards[j]), []).append(
                (
                    (int(slots[j]), item["algorithm"], item["limit"],
                     item["remaining"], item["duration"], item["created_at"],
                     item["updated_at"], item["burst"], item["status"],
                     item["expire_at"], item.get("tat", 0),
                     item.get("prev_count", 0), 1),
                    item.get("remaining_f", 0.0),
                )
            )
        if not rows_by_shard:
            return
        w = pad_pow2(max(len(v) for v in rows_by_shard.values()))
        ints = np.zeros((self.n_shards, len(ITEM_INT_ROWS), w), np.int64)
        floats = np.zeros((self.n_shards, w), np.float64)
        for s, rows in rows_by_shard.items():
            for k, (row, rf) in enumerate(rows):
                ints[s, :, k] = row
                floats[s, k] = rf
        self.state = self.ops.restore(
            self.state, self.ops.put3(ints), self.ops.put2(floats)
        )

    def _write_through(
        self, requests, idx, sel, shards, slots, now: int
    ) -> None:
        """Store.OnChange with each touched slot's post-tick state,
        gathered with one blocked readback (write-through,
        algorithms.go:149-153); slots cleared by the tick map to
        Store.remove (remove-on-reset, algorithms.go:78-90)."""
        # Unique (shard, local slot) per touched bucket, final state only.
        seen: set = set()
        per_shard: Dict[int, List[tuple]] = {}
        for j in sel:
            g = int(shards[j]) * self.local_capacity + int(slots[j])
            if g in seen:
                continue
            seen.add(g)
            per_shard.setdefault(int(shards[j]), []).append(
                (int(slots[j]), requests[idx[j]])
            )
        w = pad_pow2(max(len(v) for v in per_shard.values()))
        blk = np.full((self.n_shards, w), self.local_capacity, np.int64)
        for s, rows in per_shard.items():
            blk[s, : len(rows)] = [sl for sl, _ in rows]
        ints, floats = self.ops.readback(self.state, self.ops.put2(blk))
        ints = np.asarray(ints)
        floats = np.asarray(floats)
        for s, rows in per_shard.items():
            for k, (sl, req) in enumerate(rows):
                f = dict(zip(READBACK_ROWS, ints[s, :, k]))
                key = self.slots[s].key_of(sl)
                if key is None:
                    continue
                if not f["in_use"]:
                    self.store.remove(key)
                    continue
                self.store.on_change(
                    req,
                    {
                        "key": key,
                        "algorithm": int(f["algorithm"]),
                        "limit": int(f["limit"]),
                        "remaining": int(f["remaining"]),
                        "remaining_f": float(floats[s, k]),
                        "duration": int(f["duration"]),
                        "created_at": int(f["created_at"]),
                        "updated_at": int(f["updated_at"]),
                        "burst": int(f["burst"]),
                        "status": int(f["status"]),
                        "expire_at": int(f["expire_at"]),
                        "tat": int(f["tat"]),
                        "prev_count": int(f["prev_count"]),
                    },
                )

    # ------------------------------------------------------------------
    # GLOBAL installs (UpdatePeerGlobals receive path) — blocked
    # ------------------------------------------------------------------
    def install_globals(
        self, updates: Sequence[GlobalUpdate], now: Optional[int] = None
    ) -> None:
        """Install owner-pushed GLOBAL state; see TickEngine.install_globals.
        One blocked install per RESTORE_CHUNK of the widest shard — each
        device writes only its own shard's rows."""
        if not updates:
            return
        with self._lock:
            now = now if now is not None else timeutil.now_ms()
            # New logical tick so the "touched this tick" reclaim guard
            # doesn't pin the previous tick's slots (see TickEngine).
            self._tick_count += 1
            by_slot: Dict[int, tuple] = {}
            for u in updates:
                shard = self._shard_of(u.key)
                g, _ = self._resolve(u.key, shard, now)
                if g is None:
                    continue  # shard full; drop (the next broadcast retries)
                self._pending.discard(g)
                # Dedup by slot, LAST update wins (install order) — one
                # scatter row per slot (see TickEngine.install_globals).
                by_slot[g] = (
                    g % self.local_capacity, u.algorithm, u.status.limit,
                    u.status.remaining, u.status.status, u.duration,
                    u.status.reset_time, 1,
                )
            if not by_slot:
                return
            per_shard: Dict[int, List[tuple]] = {}
            for g, row in by_slot.items():
                per_shard.setdefault(g // self.local_capacity, []).append(row)
            for start, w in self._blocked_chunks(per_shard):
                blk = np.zeros((self.n_shards, 8, w), np.int64)
                for s, rows in per_shard.items():
                    part = rows[start : start + w]
                    if part:
                        blk[s, :, : len(part)] = np.array(part, np.int64).T
                self.state = self.ops.install(
                    self.state, self.ops.put3(blk), jnp.int64(now)
                )

    # ------------------------------------------------------------------
    # Snapshot / restore (Loader.Load/Save analog; see TickEngine)
    # ------------------------------------------------------------------
    def _host_state(self):
        """Host-side stored-layout columns of the whole sharded table."""
        if self.layout == "row":
            table = np.asarray(self.state.table)
            cap1 = self.local_capacity + 1
            # Drop each shard's guard row, re-concatenate the data rows.
            data = table.reshape(self.n_shards, cap1, ROW_W)[:, :-1, :]
            flat = np.ascontiguousarray(
                data.reshape(self.capacity, ROW_W)
            )
            return rowtable.host_columns_from_rows(flat)
        return jax.tree.map(np.asarray, self.state)

    def export_items(self) -> List[dict]:
        """Drain live bucket state to host dicts — one D2H gather of the
        sharded table + one native key export per shard."""
        with self._lock:
            st = self._host_state()
            mapped = np.concatenate([sm.mapped_mask() for sm in self.slots])
            live = np.flatnonzero(mapped & st.in_use)
            if len(live) == 0:
                return []
            keys: List[bytes] = []
            owner = live // self.local_capacity
            for d in range(self.n_shards):
                sel = live[owner == d] - d * self.local_capacity
                if len(sel):
                    keys.extend(self.slots[d].keys_batch(sel))
            return items_from_columns(keys, st, live)

    def load_columns(self, snap: dict, now: Optional[int] = None) -> None:
        """Bulk restore from a columnar snapshot — the contract of
        ``TickEngine.load_columns`` for the fields the mesh restores
        (``SNAP_FIELDS``, the zoo columns as zeros when absent; no lease
        columns, no cold tier).  Expired rows are dropped with a
        vectorized blob compaction; keys are routed as the tick path
        routes them (:meth:`_group_by_shard`); one native blob-assign a
        shard maps them, reclaiming once on a full shard; duplicate keys
        dedup to their LAST occurrence (the row layout's
        one-DMA-per-slot contract); the data lands through the blocked
        restore in RESTORE_CHUNK-wide chunks.  No Python loop over
        keys.  The fill's seconds and the rows it landed add up in
        ``load_seconds`` / ``load_rows`` (``describe()``)."""
        t0 = time.perf_counter()
        try:
            self.load_rows += self._fill(snap, now)
        finally:
            self.load_seconds += time.perf_counter() - t0

    def _fill(self, snap: dict, now: Optional[int]) -> int:
        """:meth:`load_columns`'s work; the rows it landed."""
        with self._lock:
            now = now if now is not None else timeutil.now_ms()
            self._tick_count += 1  # unblock LRU reclaim (see install_globals)
            # guber: allow-G001(a snapshot is host numpy, never device; _cutover reaches this once a reshard, not per tick)
            offsets = np.asarray(snap["key_offsets"], np.int64)
            n = len(offsets) - 1
            if n == 0:
                return 0
            # guber: allow-G001(snapshot columns are host numpy, as above)
            cols = {f: np.asarray(snap[f]) for f in SNAP_FIELDS}
            # Pre-zoo snapshots lack the zoo columns: zeros, a fresh
            # window / TAT, the safe reading (engine.ZOO_SNAP_FIELDS).
            zeros = np.zeros(n, np.int64)
            # guber: allow-G001(host numpy, as above)
            cols.update(
                (f, np.asarray(snap.get(f, zeros))) for f in ZOO_SNAP_FIELDS)
            blob = snap["key_blob"]
            keep = cols["expire_at"] >= now
            if not keep.all():
                blob, offsets = compact_blob(blob, offsets, keep)
                cols = {f: c[keep] for f, c in cols.items()}
                n = int(keep.sum())
                if n == 0:
                    return 0
            sh, order, grouped_blob, g_offsets, starts = (
                self._group_by_shard(blob, offsets))
            lslots = np.full(n, -1, np.int64)
            pended = []
            for d in range(self.n_shards):
                a, z = int(starts[d]), int(starts[d + 1])
                if a == z:
                    continue
                off_d = g_offsets[a:z + 1] - g_offsets[a]
                blob_d = grouped_blob[g_offsets[a]:g_offsets[z]]
                ls = self.slots[d].assign_blob(blob_d, off_d)
                full = ls < 0
                if full.any():  # shard full: reclaim once, retry the rest
                    # Stamp the rows just assigned live first — device
                    # state is stale for them until the restore scatter
                    # runs, and an unstamped reclaim would hand their
                    # slots to the retried keys.
                    got = d * self.local_capacity + ls[~full]
                    self._last_access[got] = self._tick_count
                    self._pending.update(got.tolist())
                    pended.append(got)
                    self._reclaim(d, now)
                    ls[full] = self.slots[d].assign_blob(
                        *compact_blob(blob_d, off_d, full))
                lslots[order[a:z]] = ls
            # The rows shard by shard in the order they were assigned (a
            # shard still full: dropped).
            sel = order[lslots[order] >= 0]
            if len(sel) == 0:
                return 0
            g_uniq = sh[sel] * self.local_capacity + lslots[sel]
            if not (g_uniq[1:] > g_uniq[:-1]).all():
                # Last-wins dedup by global slot (same key -> same
                # slot): reverse + first-unique keeps each slot's final
                # occurrence, ascending by slot, so each shard's rows are
                # contiguous.  (A fresh table's slots come out ascending
                # and unique already: nothing to sort.)
                g_uniq, ridx = np.unique(g_uniq[::-1], return_index=True)
                sel = sel[len(sel) - 1 - ridx]
            self._last_access[g_uniq] = self._tick_count
            bounds = np.searchsorted(
                g_uniq, np.arange(self.n_shards + 1) * self.local_capacity)
            per_shard = [
                sel[bounds[d]:bounds[d + 1]] for d in range(self.n_shards)
            ]
            for start, w in self._blocked_chunks(per_shard):
                ints = np.zeros(
                    (self.n_shards, len(ITEM_INT_ROWS), w), np.int64)
                floats = np.zeros((self.n_shards, w), np.float64)
                for d, rows in enumerate(per_shard):
                    part = rows[start : start + w]
                    k = len(part)
                    if k == 0:
                        continue
                    ints[d, 0, :k] = lslots[part]
                    for r, name in enumerate(ITEM_INT_ROWS[1:-1], start=1):
                        ints[d, r, :k] = cols[name][part]
                    ints[d, -1, :k] = 1  # valid
                    floats[d, :k] = cols["remaining_f"][part]
                self.state = self.ops.restore(
                    self.state, self.ops.put3(ints), self.ops.put2(floats)
                )
            # The restore has written them: the device's view of these
            # slots is no longer stale, and a later reclaim may judge
            # them by it.
            for got in pended:
                self._pending.difference_update(got.tolist())
            return len(sel)

    def load_items(self, items: Sequence[dict], now: Optional[int] = None) -> None:
        """Install snapshot items into the sharded table (the
        dict-shaped Loader API edge: one pass builds the columnar
        snapshot, then :meth:`load_columns` does the real work)."""
        items = list(items)
        if not items:
            return
        self.load_columns(snapshot_from_items(items), now=now)

    # ------------------------------------------------------------------
    # Elastic live resharding (docs/resharding.md).  The n→m transition
    # is planned by partition.plan_transition — the ONE layout-transition
    # spec — moved on device by a collective all-to-all keyed by
    # ``slot // cap_to`` (partition.relayout_block), and committed by an
    # atomic host-side cutover that swaps every layout-bearing field at
    # once.  Nothing before the cutover mutates the serving layout, so
    # any failure up to (and inside) it rolls back to the old layout
    # with the table untouched.
    # ------------------------------------------------------------------
    @hot_path
    def _dispatch_relayout(self, tr: LayoutTransition):
        """Run the transition all-to-all on the OLD mesh: every shard
        scatters its live rows into a zeroed new-layout buffer at the
        spec-derived target (``slot // cap_to``, ``slot % cap_to``) and
        one ``psum`` completes the exchange — the re-layout itself is
        collective device work, not a per-shard host gather.  Returns
        the replicated new-flat-layout table (device arrays, one D2H
        away); traces once per transition shape and never touches the
        serving programs' signatures."""
        cap_from = self.local_capacity
        if self.layout == "row":
            def _relayout(state_blk):
                self.ops.trace_counts["relayout"] += 1
                my = lax.axis_index("shard")
                return lax.psum(
                    relayout_block(state_blk.table[:cap_from], my, tr),
                    "shard",
                )

            out_specs = P(None, None)
        else:
            def _relayout(state_blk):
                self.ops.trace_counts["relayout"] += 1
                my = lax.axis_index("shard")
                return jax.tree.map(
                    lambda a: lax.psum(relayout_block(a, my, tr), "shard"),
                    state_blk,
                )

            out_specs = jax.tree.map(lambda _: P(None), BucketState.zeros(0))
        prog = jax.jit(
            shard_map(
                _relayout, mesh=self.mesh,
                in_specs=(self.ops.state_spec,), out_specs=out_specs,
                check_vma=False,
            )
        )
        # No donation: the old state must survive for abort-and-rollback.
        return prog(self.state)

    def _transition_items(self, flat) -> tuple:
        """Materialize the re-laid-out table and pair each live slot with
        its key: the host half of the transition.  Because the spec's
        flat remap is the identity on live slots, old global slot ``g``
        addresses row ``g`` of the relayout output directly — the keys
        come from the old slotmaps, the state from the collective."""
        if self.layout == "row":
            rows = np.ascontiguousarray(np.asarray(flat))
            st = rowtable.host_columns_from_rows(rows)
        else:
            st = jax.tree.map(np.asarray, flat)
        mapped = np.concatenate([sm.mapped_mask() for sm in self.slots])
        live = np.flatnonzero(mapped & st.in_use[: self.capacity])
        if len(live) == 0:
            return [], 0
        keys: List[bytes] = []
        owner = live // self.local_capacity
        for d in range(self.n_shards):
            sel = live[owner == d] - d * self.local_capacity
            if len(sel):
                keys.extend(self.slots[d].keys_batch(sel))
        return items_from_columns(keys, st, live), len(live)

    def _build_shard_set(self, tr: LayoutTransition, devices):
        """Everything the new layout needs, built OFF to the side (the
        old layout keeps serving identity until the cutover swap): mesh,
        compiled ShardedOps, zeroed sharded state, per-shard slotmaps,
        staging ring."""
        from types import SimpleNamespace

        from gubernator_tpu.ops.engine import make_slot_map

        mesh = Mesh(np.array(list(devices)), ("shard",))
        layout = make_layout_choice(
            self._table_layout_conf, tr.cap_to, mesh.devices.flat[0],
            self.max_batch,
        )
        ops = ShardedOps(mesh, tr.cap_to, layout)
        slots = [make_slot_map(tr.cap_to) for _ in range(tr.n_to)]
        # The ragged extent spec IS the new layout's dispatch geometry:
        # post-cutover windows derive their offsets against cap_to's
        # ownership from this object — nothing width-shaped survives to
        # re-derive.
        ragged = RaggedExtents(tr.n_to, tr.cap_to)
        return SimpleNamespace(
            mesh=mesh, n_shards=tr.n_to, local_capacity=tr.cap_to,
            capacity=tr.capacity_to, layout=layout, ragged=ragged,
            ops=ops, state=ops.init_state(),
            slots=slots, window_pass=make_window_pass(slots, tr.cap_to),
            last_access=np.zeros(tr.capacity_to, np.int64),
            staging=StagingRing(
                ragged.slab_rows(self.max_batch), tr.capacity_to,
                self._staging_slabs, width=self.max_batch),
        )

    @hot_path
    def _cutover(self, new, items, now) -> None:
        """Atomically swap the serving layout to ``new`` and re-home the
        live items (keys re-route to ``crc32 % m`` so the ownership rule
        — route == ring == ``slot // local_capacity`` — holds in the new
        layout).  Every layout-bearing field swaps together under the
        engine lock; any failure restores the saved old layout verbatim
        (the old state was never donated), so the abort path is a plain
        tuple assignment — zero loss either way."""
        saved = (
            self.mesh, self.n_shards, self.local_capacity, self.capacity,
            self.ragged, self.layout, self.ops, self.state,
            self.slots, self._last_access, self._staging, self._pending,
            self._window_pass,
        )
        self.mesh = new.mesh
        self.n_shards = new.n_shards
        self.local_capacity = new.local_capacity
        self.capacity = new.capacity
        self.ragged = new.ragged
        self.layout = new.layout
        self.ops = new.ops
        self.state = new.state
        self.slots = new.slots
        self._window_pass = new.window_pass
        self._last_access = new.last_access
        self._staging = new.staging
        self._pending = set()
        self._inflight = 0
        try:
            if items:
                # guber: allow-G001(once a reshard, not per tick: the items become host numpy columns, no device sync)
                self.load_items(items, now)
            self._warmup()
        except Exception:
            (
                self.mesh, self.n_shards, self.local_capacity,
                self.capacity, self.ragged, self.layout, self.ops,
                self.state, self.slots, self._last_access, self._staging,
                self._pending, self._window_pass,
            ) = saved
            raise

    def reshard(self, new_shards: int, devices=None,
                now: Optional[int] = None) -> dict:
        """Re-layout the live table over ``new_shards`` devices, in
        place, under the engine lock (callers quiesce the tick pipeline
        first — the ReshardCoordinator's job; a straggler window merely
        serializes behind the lock and resolves against whichever layout
        it observes).  Returns a summary dict; raises — with the old
        layout intact — on any failure before or inside the cutover."""
        new_n = int(new_shards)
        if new_n < 1:
            raise ValueError(f"new_shards must be >= 1; got {new_n}")
        with self._lock:
            if new_n == self.n_shards:
                return {
                    "from_shards": self.n_shards, "to_shards": new_n,
                    "live_items": 0, "noop": True,
                }
            avail = list(devices) if devices is not None else jax.devices()
            if len(avail) < new_n:
                raise ValueError(
                    f"reshard to {new_n} shards needs {new_n} devices; "
                    f"{len(avail)} available"
                )
            tr = plan_transition(self.n_shards, self.local_capacity, new_n)
            if tr.capacity_to >= (1 << 31):
                raise ValueError(
                    f"resharded capacity {tr.capacity_to} exceeds int32 "
                    "global slots"
                )
            flat = self._dispatch_relayout(tr)
            items, n_live = self._transition_items(flat)
            new = self._build_shard_set(tr, avail[:new_n])
            self._cutover(new, items, now)
            return {
                "from_shards": tr.n_from, "to_shards": tr.n_to,
                "cap_from": tr.cap_from, "cap_to": tr.cap_to,
                "live_items": n_live, "noop": False,
            }

    def routing_parity_errors(self, keys: Sequence[str]) -> int:
        """Audit key→shard routing parity for ``keys`` (post-serving):
        the vectorized CRC-32 route, the scalar ``_shard_of`` host ring,
        and actual slotmap residency must all agree, each resident key
        must live on exactly ONE shard (a key mapped on two shards is a
        double-serve; on zero shards after serving, a drop), and its
        global slot must derive back to the owning shard — the exact
        invariant the device router applies (``slot // local_capacity``).
        Returns the number of keys violating any of these: the reshard
        coordinator's verify phase counts it, and
        tests/test_mesh_engine.py::test_routing_parity_fuzz_vs_host_ring
        holds it at exactly 0."""
        from gubernator_tpu.native import crc32_batch

        enc = [k.encode() for k in keys]
        blob = b"".join(enc)
        offsets = np.zeros(len(enc) + 1, np.int64)
        np.cumsum([len(e) for e in enc], out=offsets[1:])
        vec = (
            crc32_batch(blob, offsets) % np.uint32(self.n_shards)
        ).astype(np.int64)
        errs = 0
        with self._lock:
            for i, k in enumerate(keys):
                s = self._shard_of(k)
                owners = [
                    d for d in range(self.n_shards)
                    if self.slots[d].get(k) is not None
                ]
                if int(vec[i]) != s or owners != [s]:
                    errs += 1
                    continue
                local = self.slots[s].get(k)
                g = s * self.local_capacity + local
                if not (0 <= local < self.local_capacity) or \
                        g // self.local_capacity != s:
                    errs += 1
        return errs

    def cache_size(self) -> int:
        return sum(len(sm) for sm in self.slots)
