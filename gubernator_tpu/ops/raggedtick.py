"""Ragged device-side window walking: each shard consumes only its own
``[offset, offset + count)`` extent of the flat slot-sorted batch.

The routed mesh path (PR 7) compacted the replicated flat (19, B)
request matrix into a padded (19, local_width) block per shard
(partition.route_block) and fell back to a host-blocked packer whenever
a window's per-shard skew exceeded ``local_width`` — but Zipf-skewed
traffic is the *normal* case at scale, so the fast path degraded exactly
when load concentrated.  Ragged Paged Attention (PAPERS.md, arXiv
2604.15464) shows the TPU-native shape: keep the flat matrix, add a
per-block row-count vector, and iterate ragged extents directly.

The flat matrix is already slot-sorted by GLOBAL slot
(engine.sort_packed_by_slot), and ownership is ``slot //
local_capacity`` — so each shard's rows form one CONTIGUOUS extent of
the batch, and the host (which computed the per-shard counts during
resolve) ships a cumulative ``offsets`` vector alongside the matrix.
No compaction, no padding lanes, no skew fallback: every per-shard
width is served by ONE fixed-shape program per batch capacity.

Three entry points, all sharing the extent/masking arithmetic:

* :func:`choose_tile` — the static tile width the XLA walker strides
  the extent with (~B/n, 64-lane quantized).
* :func:`ragged_walk` — the XLA extent walker wrapped around any
  single-chip tile tick (the merge-capable x64 program, or the unfused
  int32 parts program on CPU): a ``fori_loop`` over the extent's
  dynamic tile count, each tile clamped into the batch and masked so
  out-of-extent lanes become guard rows (slot = local_capacity,
  valid = 0), responses merged read-modify-write into a zeroed flat
  buffer so the cross-shard gather stays one exact ``psum``.
* :func:`make_fused_ragged_tick_fn` — the Pallas kernel (row layout):
  fusedtick's gather-DMA → in-register transition → scatter-DMA ring,
  with the chunk count now a *runtime* scalar (prefetched alongside the
  slots) so one compiled program serves every extent length.  Tail
  chunks clamp into the batch and aim their masked lanes' DMAs at the
  guard row; the response buffer zero-fills first, then each chunk
  merges its live lanes in place.

Masking guarantees (why clamped tiles are safe): a clamped tile
re-reads lanes the previous tile already served, but those lanes are
masked to guard rows — the tick scatters them at ``local_capacity``
(dropped / guard garbage by contract) and the response merge keeps the
previously-written value, so no lane is double-applied.  A duplicate
run split across two tiles is two *sequential* ticks of the same slot
(the state carry between tiles), which is exactly the merge program's
sequential-application semantics.

Reference semantics bar: algorithms.go:37-493 (via transition32).
"""

from __future__ import annotations

import functools
import math

import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gubernator_tpu.ops.engine import REQ32_INDEX, REQ32_ROWS
from gubernator_tpu.ops.fusedtick import (
    TW,
    _VMEM,
    _preq_from_rows,
    _pstate_from_T,
    _pstate_to_T,
    _transpose_bwd,
    _transpose_fwd,
)
from gubernator_tpu.ops.i64pair import I64
from gubernator_tpu.ops.rowtable import ROW_W, _interpret
from gubernator_tpu.ops.transition32 import transition32

I32 = jnp.int32


def choose_tile(b: int, n_shards: int) -> int:
    """Static tile width for :func:`ragged_walk`: ~B/n so the per-shard
    tile work matches the balanced load, 64-lane quantized (VPU lane
    width), floored at 64 and capped at the batch.  Skewed extents just
    run more iterations of the same tile — no retrace, no fallback."""
    tile = max(64, -(-int(b) // max(1, int(n_shards))))
    tile = -(-tile // 64) * 64
    return min(tile, int(b))


def ragged_walk(tick_tile, state, m, start, count, lo, local_capacity,
                tile, resp_zeros):
    """Walk one shard's ``[start, start + count)`` extent of the flat
    slot-sorted (19, B) matrix in ``tile``-wide steps (traced; runs per
    shard inside the mesh engine's ``shard_map`` programs).

    ``tick_tile(state, blk)`` is any single-chip tick closure over a
    (19, tile) LOCAL block; ``resp_zeros`` is the zeroed flat response
    pytree the tile responses merge into (a (6, B) matrix, or the
    unfused path's tuple of six (B,) rows).  Tiles near the batch edge
    clamp their base into ``[0, B - tile]`` and mask the re-read lanes:
    masked lanes become guard rows on the way in (slot =
    ``local_capacity``, valid = 0) and keep the already-merged value on
    the way out, so the returned buffer is exact on the extent and zero
    elsewhere — summing the per-shard buffers (one ``psum``) is the
    whole response gather."""
    R = REQ32_INDEX
    nrows, b = m.shape
    tile = min(int(tile), b)
    one_t = jnp.asarray(tile, count.dtype)
    n_tiles = (count + (one_t - 1)) // one_t
    lanes0 = jnp.arange(tile, dtype=jnp.int32)

    def body(t, carry):
        state, out = carry
        a = (start + t * tile).astype(jnp.int32)
        actual = jnp.clip(a, 0, b - tile)
        sl = lax.dynamic_slice(m, (jnp.int32(0), actual), (nrows, tile))
        lane = actual + lanes0
        live = (lane >= a) & (lane < (start + count).astype(jnp.int32))
        blk = sl.at[R["slot"]].set(
            jnp.where(
                live, sl[R["slot"]] - jnp.asarray(lo, sl.dtype),
                jnp.asarray(local_capacity, sl.dtype),
            )
        )
        blk = blk.at[R["valid"]].set(
            (live & (sl[R["valid"]] != 0)).astype(sl.dtype)
        )
        state, resp = tick_tile(state, blk)

        def merge(buf, r):
            r = r.astype(buf.dtype)
            if buf.ndim == 1:
                cur = lax.dynamic_slice(buf, (actual,), (tile,))
                return lax.dynamic_update_slice(
                    buf, jnp.where(live, r, cur), (actual,)
                )
            cur = lax.dynamic_slice(
                buf, (jnp.int32(0), actual), (buf.shape[0], tile)
            )
            return lax.dynamic_update_slice(
                buf, jnp.where(live[None, :], r, cur),
                (jnp.int32(0), actual),
            )

        out = jax.tree.map(merge, out, resp)
        return state, out

    return lax.fori_loop(0, n_tiles, body, (state, resp_zeros))


def make_fused_ragged_tick_fn(capacity: int, chunk: int | None = None):
    """(state: RowState, m32 (19, B) i32, start, count, lo, now)
    → (state, resp (6, B)).

    The ragged fused tick: fusedtick's double-buffered DMA ring, chunk
    count now ``ceil(count / C)`` at RUNTIME — ``(start, count, lo)``
    prefetch to SMEM beside the slot row, so ONE compiled program
    serves every extent length of a given batch capacity.  Unique-slot,
    slot-sorted extents on the row layout (duplicate-bearing windows
    take the merge-capable XLA walker); the response lanes outside the
    extent are exact zeros, ready for the cross-shard ``psum``.
    ``chunk`` as in make_fused_tick_fn."""

    def tick(state, m32, start, count, lo, now):
        b = m32.shape[1]
        c = min(chunk or 2048, b)
        slots = m32[REQ32_INDEX["slot"]]
        from gubernator_tpu.ops.tick32 import now_to_pair

        np_ = now_to_pair(now)
        now2 = jnp.stack([np_.lo, np_.hi])
        ext = jnp.stack([
            jnp.asarray(start, I32),
            jnp.asarray(count, I32),
            jnp.asarray(lo, I32),
        ])

        kernel = functools.partial(
            _ragged_kernel, capacity=capacity, C=c, B=b)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # slots, now2, ext
            grid=(1,),
            in_specs=[
                pl.BlockSpec((REQ32_ROWS, b), lambda t, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # table (HBM)
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # table out (aliased)
                pl.BlockSpec((6, b), lambda t, *_: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, c, ROW_W), I32),  # read buffers
                pltpu.VMEM((2, c, ROW_W), I32),  # write buffers
                pltpu.SemaphoreType.DMA((2,)),   # read sems (per buffer)
                pltpu.SemaphoreType.DMA((2,)),   # write sems (per buffer)
            ],
        )
        with jax.enable_x64(False):
            table, resp = pl.pallas_call(
                kernel,
                grid_spec=grid_spec,
                out_shape=[
                    jax.ShapeDtypeStruct((capacity + 1, ROW_W), I32),
                    jax.ShapeDtypeStruct((6, b), I32),
                ],
                input_output_aliases={4: 0},  # table input -> table output
                compiler_params=_VMEM,
                interpret=_interpret(),
            )(slots, now2, ext, m32, state.table)
        return state._replace(table=table), resp

    return tick


def _ragged_kernel(slots_ref, now_ref, ext_ref, m32_ref, table_ref,
                   tout_ref, resp_ref, rbuf, wbuf, rsem, wsem, *,
                   capacity, C, B):
    start = ext_ref[0]
    count = ext_ref[1]
    lo = ext_ref[2]
    end = start + count
    cap_i = jnp.int32(capacity)
    # Mosaic only loads/stores the (rows, B) request and response blocks
    # at lane offsets it can PROVE are multiples of the 128-lane tile,
    # and ``start`` is an arbitrary runtime lane.  So chunks are based
    # at ``start`` rounded DOWN to the alignment (the extra head lanes
    # are masked like any other off-extent lane), and the clamp bound
    # B - C is a multiple of it too.  The serving shapes (B, C = 2048
    # multiples) give 128; the small interpret-mode test chunks get
    # their own gcd so the same arithmetic is exercised there.
    A = math.gcd(128, C, B)
    base0 = (start // jnp.int32(A)) * jnp.int32(A)
    # Runtime chunk count, rounded UP to even so the double-buffered
    # pair loop keeps its static buffer parity (fusedtick's read/write
    # interleave); an odd extent pays one phantom chunk whose lanes are
    # all masked (guard-row DMAs, merged-out responses).  count == 0
    # (warmup / idle shard) skips the pipeline entirely.
    nc_live = jnp.where(
        count > 0, (end - base0 + jnp.int32(C - 1)) // jnp.int32(C), 0)
    nc = nc_live + lax.rem(nc_live, jnp.int32(2))
    U = 8 if C % 8 == 0 else 1

    def chunk_base(c):
        """(first live lane, clamped aligned base) of chunk ``c``: tail
        chunks slide back into the batch and mask the re-read lanes."""
        a = base0 + jnp.int32(c) * C
        actual = pl.multiple_of(jnp.clip(a, 0, jnp.int32(B - C)), A)
        return jnp.maximum(a, start), actual

    def lslot(c, j):
        # Rebasing is clipped defensively: a host extent bug must never
        # aim a DMA outside the (capacity + 1)-row table.
        a, actual = chunk_base(c)
        idx = actual + j
        live = (idx >= a) & (idx < end)
        return jnp.where(
            live, jnp.clip(slots_ref[idx] - lo, 0, cap_i), cap_i)

    def read_copy(c, buf, j):
        return pltpu.make_async_copy(
            tout_ref.at[pl.ds(lslot(c, j), 1), :],
            rbuf.at[buf, pl.ds(j, 1), :],
            rsem.at[buf],
        )

    def write_copy(c, buf, j):
        return pltpu.make_async_copy(
            wbuf.at[buf, pl.ds(j, 1), :],
            tout_ref.at[pl.ds(lslot(c, j), 1), :],
            wsem.at[buf],
        )

    def _loop(fn):
        def body(g, _):
            for k in range(U):
                fn(g * U + k)
            return 0

        lax.fori_loop(0, C // U, body, 0)

    def issue_reads(c, buf):
        _loop(lambda j: read_copy(c, buf, j).start())

    def wait_reads(c, buf):
        # One aggregate wait per chunk (see fusedtick._kernel).
        pltpu.make_async_copy(
            rbuf.at[buf], rbuf.at[buf], rsem.at[buf]).wait()

    def issue_writes(c, buf):
        _loop(lambda j: write_copy(c, buf, j).start())

    def wait_writes(c, buf):
        pltpu.make_async_copy(
            wbuf.at[buf], wbuf.at[buf], wsem.at[buf]).wait()

    def compute_store(c, buf):
        """Transition chunk ``c`` from rbuf[buf] into wbuf[buf], merging
        the live lanes' responses into resp_ref in place."""
        a, actual = chunk_base(c)
        T = _transpose_fwd(rbuf[buf, :, :TW])
        s = _pstate_from_T(T)
        lane = actual + lax.broadcasted_iota(I32, (1, C), 1)
        live = (lane >= a) & (lane < end)
        mr = m32_ref[:REQ32_ROWS, pl.ds(actual, C)]
        r = _preq_from_rows(mr)
        # Masked lanes ride the pipeline as guard rows: valid = 0 keeps
        # their transition inert and their scatter aims the guard.
        r = r._replace(valid=r.valid & live)
        now_pair = I64(
            jnp.full((1, C), now_ref[0], I32),
            jnp.full((1, C), now_ref[1], I32),
        )
        new_state, resp = transition32(now_pair, s, r)
        # Write-buffer store FIRST (see fusedtick.compute_store).
        out = _transpose_bwd(_pstate_to_T(new_state))  # (C, TW)
        wbuf[buf, :, :TW] = out
        rows = jnp.concatenate([
            resp.status,
            resp.over_limit.astype(I32),
            resp.remaining.lo,
            resp.remaining.hi,
            resp.reset_time.lo,
            resp.reset_time.hi,
        ], axis=0)
        cur = resp_ref[:, pl.ds(actual, C)]
        resp_ref[:, pl.ds(actual, C)] = jnp.where(live, rows, cur)

    # The flat response must be exact zeros off this shard's extent
    # (the cross-shard gather is a psum); chunks then merge their live
    # lanes read-modify-write.
    resp_ref[:, :] = jnp.zeros((6, B), I32)
    # Spare words of the write rows are zero for the whole kernel (rows
    # scatter whole-width; eviction/installs expect zeroed spares).
    wbuf[0, :, TW:] = jnp.zeros((C, ROW_W - TW), I32)
    wbuf[1, :, TW:] = jnp.zeros((C, ROW_W - TW), I32)

    # nc is even by construction: 0 (empty extent — whole pipeline
    # skipped) or >= 2, so the pair loop never needs an nc == 1 special
    # case the way the static-shape kernel does.
    @pl.when(nc > 0)
    def _():
        issue_reads(0, 0)
        issue_reads(1, 1)

        def pair_body(c2, _):
            for buf in (0, 1):
                c = 2 * c2 + buf
                wait_reads(c, buf)

                @pl.when(c >= 2)
                def _(c=c, buf=buf):
                    wait_writes(c - 2, buf)

                compute_store(c, buf)

                # Reads ahead of writes (see fusedtick.pair_body).
                @pl.when(c + 2 < nc)
                def _(c=c, buf=buf):
                    issue_reads(c + 2, buf)

                issue_writes(c, buf)

            return 0

        lax.fori_loop(0, nc // 2, pair_body, 0)
        wait_writes(nc - 2, 0)
        wait_writes(nc - 1, 1)
