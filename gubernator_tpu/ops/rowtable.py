"""Row-major bucket table with Pallas per-row DMA gather/scatter.

The column layout (buckets.py) bounds a tick by ~40 random single-word
HBM accesses per decision (24 stored columns gathered + scattered), which
measures ~100-200M words/s on a v5e chip — a hard ~3M decisions/s/chip
ceiling regardless of batch size.  This module stores the whole bucket
row contiguously — one (capacity+1, 128) int32 array, 512 B per slot —
and moves it with one DMA per row from a Pallas kernel (a pipelined ring
of async copies, K in flight, 4 issued per loop step).  Measured on v5e:
~3-25 ns/row scatter and ~25-50 ns/row gather, capacity-independent —
about 6-8x the column layout's gather+scatter cost at 32k-request ticks.

Layout (int32 words within a row; 24 used, the rest spare):
  word 0        algorithm
  words 1-2     limit        (int64 as lo,hi — same bitcast as buckets.py)
  words 3-4     remaining
  words 5-7     remaining_f  (float64 as 3-way Dekker float32 split)
  words 8-9     duration
  words 10-11   created_at
  words 12-13   updated_at
  words 14-15   burst
  word 16       status
  words 17-18   expire_at
  word 19       in_use
  words 20-21   tat          (GCRA theoretical arrival time)
  words 22-23   prev_count   (sliding-window previous-window count)

Row ``capacity`` is a guard row: masked scatter lanes aim there (the row
equivalent of the column path's ``mode="drop"`` sentinel), and gathers of
padding slots read its garbage — callers mask those lanes out, exactly as
they do for the column path's zero-fill.

Why 128 words: Mosaic requires HBM<->VMEM DMA slices to be 128-element
aligned in the lane dimension, so 512 B is the minimum int32 row.  The
5x space cost vs the 24 used words is the price of one-DMA rows; engines
fall back to the column layout for tables too big to afford it (see
engine.make_layout_choice).

On non-TPU backends the kernels run in Pallas interpret mode (slow, but
semantically identical) so the row engine is testable on the CPU mesh.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gubernator_tpu.ops.buckets import (
    STATE_DTYPES,
    BucketState,
    to_logical,
    to_stored,
)

ROW_W = 128     # int32 words per row (Mosaic lane-alignment minimum)
# DMA pipeline shape: ring depth bounds outstanding copies — gathers
# are HBM-read-latency bound, so deeper rings hide more latency — and
# the unroll sets how many copies each scalar-loop step issues (the
# scalar loop is the issue-rate limiter).  Powers of two.
DMA_RING = 32
DMA_UNROLL = 4

# The kernels stage the whole (B, ROW_W) batch block in VMEM; Mosaic's
# default scoped-vmem budget rejects a 64k-row tick (gather out-block +
# scatter in-block, 32 MB each), so raise it — v5e has 128 MB of VMEM.
_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=100 * 1024 * 1024)


def _field_words(field: str) -> int:
    from gubernator_tpu.ops.buckets import _FLOAT, _WIDE

    if field in _WIDE:
        return 2
    if field in _FLOAT:
        return 3
    return 1


# word offset of each logical field within a row, in STATE_DTYPES order
FIELD_OFFSETS = {}
_o = 0
for _f in STATE_DTYPES:
    FIELD_OFFSETS[_f] = _o
    _o += _field_words(_f)
ROW_USED = _o  # 24
assert ROW_USED <= ROW_W


class RowState(NamedTuple):
    """Device bucket table in row layout (+1 guard row)."""

    table: jnp.ndarray  # (capacity + 1, ROW_W) int32

    @property
    def capacity(self) -> int:
        return self.table.shape[0] - 1

    @classmethod
    def zeros(cls, n: int) -> "RowState":
        return cls(table=jnp.zeros((n + 1, ROW_W), jnp.int32))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------------------
# Pallas kernels: one DMA per row, pipelined K-deep
# ----------------------------------------------------------------------
def _ring_loop(body_start, b: int):
    """Issue ``b`` DMAs through a ring of DMA_RING semaphores, DMA_UNROLL
    per scalar-loop step (the scalar loop, not the DMA engine, is the
    issue-rate limiter — unrolling measured ~10x on v5e)."""
    u = DMA_UNROLL if b % DMA_UNROLL == 0 and b >= 2 * DMA_RING else 1

    def body(g, _):
        for k in range(u):
            j = g * u + k

            @pl.when(j >= DMA_RING)
            def _(j=j):
                body_start(j - DMA_RING).wait()

            body_start(j).start()
        return 0

    lax.fori_loop(0, b // u, body, 0)

    def drain(j, _):
        body_start(j).wait()
        return 0

    lax.fori_loop(max(0, b - DMA_RING), b, drain, 0)


def _scatter_kernel(slots_ref, rows_ref, table_ref, out_ref, sems):
    b = rows_ref.shape[0]

    def start(j):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(j, 1), :],
            out_ref.at[pl.ds(slots_ref[j], 1), :],
            sems.at[lax.rem(j, DMA_RING)],
        )

    _ring_loop(start, b)


def _gather_kernel(slots_ref, table_ref, out_ref, sems):
    b = out_ref.shape[0]

    def start(j):
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(slots_ref[j], 1), :],
            out_ref.at[pl.ds(j, 1), :],
            sems.at[lax.rem(j, DMA_RING)],
        )

    _ring_loop(start, b)


def scatter_rows(table: jnp.ndarray, slots: jnp.ndarray,
                 rows: jnp.ndarray) -> jnp.ndarray:
    """Write ``rows[j]`` to ``table[slots[j]]`` for every j (row DMAs).

    ``slots`` must be int32 in [0, capacity]; duplicate *real* slots are
    a data race (callers scatter at most one row per slot — tick head
    rows, install/restore/evict dedup'd slots); duplicates of the guard
    row ``capacity`` are harmless (its content is never read as data).
    """
    b, w = rows.shape
    cap1 = table.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, w), lambda t, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((DMA_RING,))],
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            _scatter_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((cap1, w), jnp.int32),
            input_output_aliases={2: 0},
            compiler_params=_COMPILER_PARAMS,
            interpret=_interpret(),
            name="scatter_rows",
        )(slots, rows, table)


def gather_rows(table: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """Read ``table[slots[j]]`` into a (B, ROW_W) matrix (row DMAs)."""
    b = slots.shape[0]
    w = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((b, w), lambda t, *_: (0, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((DMA_RING,))],
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            _gather_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, w), jnp.int32),
            compiler_params=_COMPILER_PARAMS,
            interpret=_interpret(),
            name="gather_rows",
        )(slots, table)


# ----------------------------------------------------------------------
# Row matrix <-> logical columns
# ----------------------------------------------------------------------
def matrix_to_logical(m: jnp.ndarray) -> BucketState:
    """(B, ROW_W) int32 row matrix -> logical per-request columns."""
    def col(f):
        o = FIELD_OFFSETS[f]
        n = _field_words(f)
        if n == 1:
            raw = m[:, o]
            return to_logical(raw, f) if STATE_DTYPES[f] != jnp.bool_ \
                else raw != 0
        return to_logical(tuple(m[:, o + k] for k in range(n)), f)

    return BucketState(**{f: col(f) for f in STATE_DTYPES})


def logical_to_matrix(rows: BucketState) -> jnp.ndarray:
    """Logical per-request columns -> (B, ROW_W) int32 row matrix."""
    cols = []
    for f in STATE_DTYPES:
        stored = to_stored(getattr(rows, f), f)
        if isinstance(stored, tuple):
            cols.extend(p.astype(jnp.int32) for p in stored)
        else:
            cols.append(stored.astype(jnp.int32))
    b = cols[0].shape[0]
    mat = jnp.stack(cols, axis=1)  # (B, ROW_USED)
    return jnp.concatenate(
        [mat, jnp.zeros((b, ROW_W - ROW_USED), jnp.int32)], axis=1
    )


# ----------------------------------------------------------------------
# BucketState-helper equivalents over RowState
# ----------------------------------------------------------------------
def row_gather_state(state: RowState, idx: jnp.ndarray) -> BucketState:
    """Gather logical rows at ``idx``.  Out-of-range/padding indices clamp
    to the guard row and read garbage — callers mask those lanes (the
    column path's fill-with-zeros contract, weakened to "don't read").
    Unlike ``buckets.gather_state`` there is deliberately no ``fill``
    option: zero-filling would cost a second masked pass per lane, and
    every caller already ignores padding rows."""
    cap = state.capacity
    slots = jnp.clip(idx, 0, cap).astype(jnp.int32)
    return matrix_to_logical(gather_rows(state.table, slots))


def row_scatter_state(state: RowState, idx: jnp.ndarray,
                      rows: BucketState) -> RowState:
    """Scatter logical rows; indices ≥ capacity land in the guard row."""
    cap = state.capacity
    slots = jnp.clip(idx, 0, cap).astype(jnp.int32)
    return RowState(
        table=scatter_rows(state.table, slots, logical_to_matrix(rows))
    )


def row_evict(state: RowState, slots: jnp.ndarray) -> RowState:
    """Zero whole rows (in_use=0 plus all state) for evicted slots."""
    cap = state.capacity
    s32 = jnp.clip(slots, 0, cap).astype(jnp.int32)
    zeros = jnp.zeros((s32.shape[0], ROW_W), jnp.int32)
    return RowState(table=scatter_rows(state.table, s32, zeros))


@functools.lru_cache(maxsize=None)
def _jitted_row_dead_scan():
    """Row-layout TTL sweep: strided column reads + packbits (one pass
    over the table; the engine ships capacity/8 bytes D2H)."""

    def scan(table, now):
        o = FIELD_OFFSETS["expire_at"]
        in_use = table[:-1, FIELD_OFFSETS["in_use"]] != 0
        exp = to_logical((table[:-1, o], table[:-1, o + 1]), "expire_at")
        dead = (~in_use) | (exp < now)
        return jnp.packbits(dead, bitorder="little")

    return jax.jit(scan)


def row_device_dead_bits(state: RowState, now: int):
    """Dispatch the dead-slot scan; returns the device packed bitmask (see
    engine.device_dead_bits for the dispatch/materialize split)."""
    return _jitted_row_dead_scan()(state.table, jnp.int64(now))


def unpack_row_dead_bits(bits, capacity: int) -> np.ndarray:
    """The host mask of :func:`row_device_dead_bits`' packed bits."""
    # guber: allow-G001(the deliberate reclaim D2H, row-layout twin of unpack_dead_bits - at most once per reclaim round, never per tick)
    bits = np.asarray(bits)
    return np.unpackbits(bits, count=capacity, bitorder="little").astype(bool)


def row_device_dead_mask(state: RowState, now: int, capacity: int) -> np.ndarray:
    return unpack_row_dead_bits(row_device_dead_bits(state, now), capacity)


@functools.lru_cache(maxsize=None)
def _jitted_export_columns():
    """Slice the stored columns out of the row table on device, so a
    snapshot D2H moves ROW_USED words/slot, not ROW_W (5 GB -> 840 MB at
    10M slots)."""

    def export(table):
        return tuple(table[:-1, k] for k in range(ROW_USED))

    return jax.jit(export)


def row_host_columns(state: RowState) -> BucketState:
    """Fetch the table and rebuild a host-side stored-layout BucketState
    (np columns), for the export/items paths shared with the column
    engines."""
    cols = [np.asarray(c) for c in _jitted_export_columns()(state.table)]

    def stored(f):
        o = FIELD_OFFSETS[f]
        n = _field_words(f)
        if n == 1:
            c = cols[o]
            return c.astype(bool) if STATE_DTYPES[f] == jnp.bool_ else c
        return tuple(cols[o + k] for k in range(n))

    return BucketState(**{f: stored(f) for f in STATE_DTYPES})


def host_columns_from_rows(rows: np.ndarray) -> BucketState:
    """Host-side stored-layout BucketState from an (N, ROW_W) matrix of
    *data* rows (guard rows already dropped) — the mesh engine's export
    path, where the sharded table is fetched whole."""

    def stored(f):
        o = FIELD_OFFSETS[f]
        n = _field_words(f)
        if n == 1:
            c = np.ascontiguousarray(rows[:, o])
            return c.astype(bool) if STATE_DTYPES[f] == jnp.bool_ else c
        return tuple(np.ascontiguousarray(rows[:, o + k]) for k in range(n))

    return BucketState(**{f: stored(f) for f in STATE_DTYPES})
