"""The fused tick: gather-DMA → in-register transition → scatter-DMA in
ONE Pallas kernel.

The unfused tick is three serialized passes over HBM (row gather, an
XLA middle of extracts + emulated-64-bit transition, scatter).  This
kernel streams the batch through VMEM in double-buffered chunks so the
transition and the write stream hide under the read stream, which is the
hardware floor (random 512 B row reads are bound by the descriptor
rate, not by bandwidth; PERF.md has what has been measured):

  reads(chunk c+2) ──┐ issued while
  compute(chunk c)   ├─ writes(chunk c-1..c) drain
  responses(chunk c) ┘

Three parts-specific moves make the in-kernel transition possible/cheap:

* the transition itself is pure int32/f32 (ops/transition32.py) — Mosaic
  cannot compile 64-bit programs at all;
* row⇄column layout conversion rides the MXU: a (C, 32) int32 block is
  split into exact 16-bit halves, transposed by one-hot f32 matmuls
  (precision HIGHEST keeps them exact), and recombined — replacing the
  strided-slice extracts that cost ~390 us/tick in XLA;
* responses pack to the compact (6, B) int32 wire format in-kernel, so
  the program's outputs are exactly the bytes the host wants.

Contract (same as ops/tick32.make_tick32_fn): slot-sorted unique-slot
batches, padding rows at slot == capacity, row-layout tables only.
Duplicate-bearing batches take the merge-capable XLA program instead
(host dispatch in engine.submit_columns).

Reference semantics bar: algorithms.go:37-493 (via transition32).
"""

from __future__ import annotations

import functools

import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gubernator_tpu.ops.engine import REQ32_INDEX, REQ32_ROWS
from gubernator_tpu.ops.i64pair import I64
from gubernator_tpu.ops.rowtable import ROW_W, _interpret
from gubernator_tpu.ops.tfloat import T3
from gubernator_tpu.ops.transition32 import (
    PReq,
    PState,
    transition32,
)

I32 = jnp.int32
F32 = jnp.float32

# 24 table words ride the MXU transpose: ROW_USED (24 — 20 legacy words
# plus the zoo's tat/prev_count pairs), already a multiple of 8
# sublanes.  The transposed block is (TW, C).
TW = 24
_VMEM = pltpu.CompilerParams(
    vmem_limit_bytes=100 * 1024 * 1024)


def _eye(n):
    return (
        lax.broadcasted_iota(I32, (n, n), 0)
        == lax.broadcasted_iota(I32, (n, n), 1)
    ).astype(F32)


def _transpose_fwd(block):
    """(C, TW) int32 → (TW, C) via exact one-hot MXU matmuls."""
    lo = (block & jnp.int32(0xFFFF)).astype(F32)
    hi = ((block >> 16) & jnp.int32(0xFFFF)).astype(F32)
    dn = (((1,), (1,)), ((), ()))
    loT = lax.dot_general(_eye(TW), lo, dn, precision=lax.Precision.HIGHEST,
                          preferred_element_type=F32)
    hiT = lax.dot_general(_eye(TW), hi, dn, precision=lax.Precision.HIGHEST,
                          preferred_element_type=F32)
    return (hiT.astype(I32) << 16) | loT.astype(I32)


def _transpose_bwd(blockT):
    """(TW, C) int32 → (C, TW), same construction."""
    lo = (blockT & jnp.int32(0xFFFF)).astype(F32)
    hi = ((blockT >> 16) & jnp.int32(0xFFFF)).astype(F32)
    dn = (((0,), (0,)), ((), ()))
    loT = lax.dot_general(lo, _eye(TW), dn, precision=lax.Precision.HIGHEST,
                          preferred_element_type=F32)
    hiT = lax.dot_general(hi, _eye(TW), dn, precision=lax.Precision.HIGHEST,
                          preferred_element_type=F32)
    return (hiT.astype(I32) << 16) | loT.astype(I32)


def _bc_f32(x):
    return lax.bitcast_convert_type(x, F32)


def _bc_i32(x):
    return lax.bitcast_convert_type(x, I32)


def _pstate_from_T(T):
    """Rows of the transposed (TW, C) block → PState of (1, C) leaves.
    Word offsets are rowtable.FIELD_OFFSETS (the row layout)."""
    from gubernator_tpu.ops.rowtable import FIELD_OFFSETS as O

    def row(k):
        return T[k:k + 1, :]

    def pair(f):
        return I64(row(O[f]), row(O[f] + 1))

    fo = O["remaining_f"]
    return PState(
        algorithm=row(O["algorithm"]),
        limit=pair("limit"),
        remaining=pair("remaining"),
        remaining_f=T3(_bc_f32(row(fo)), _bc_f32(row(fo + 1)),
                       _bc_f32(row(fo + 2))),
        duration=pair("duration"),
        created_at=pair("created_at"),
        updated_at=pair("updated_at"),
        burst=pair("burst"),
        status=row(O["status"]),
        expire_at=pair("expire_at"),
        in_use=row(O["in_use"]) != 0,
        tat=pair("tat"),
        prev_count=pair("prev_count"),
    )


def _pstate_to_T(s: PState):
    """PState of (1, C) leaves → (TW, C) transposed block (spare rows 0)."""
    rows = [
        s.algorithm,
        s.limit.lo, s.limit.hi,
        s.remaining.lo, s.remaining.hi,
        _bc_i32(s.remaining_f.hi), _bc_i32(s.remaining_f.mid),
        _bc_i32(s.remaining_f.lo),
        s.duration.lo, s.duration.hi,
        s.created_at.lo, s.created_at.hi,
        s.updated_at.lo, s.updated_at.hi,
        s.burst.lo, s.burst.hi,
        s.status,
        s.expire_at.lo, s.expire_at.hi,
        s.in_use.astype(I32),
        s.tat.lo, s.tat.hi,
        s.prev_count.lo, s.prev_count.hi,
    ]
    c = rows[0].shape[1]
    if len(rows) < TW:
        pad = jnp.zeros((TW - len(rows), c), I32)
        rows = rows + [pad]
    return jnp.concatenate(rows, axis=0)


def _preq_from_rows(mr):
    """(19, C) request slice → PReq of (1, C) leaves."""

    def row(name):
        k = REQ32_INDEX[name]
        return mr[k:k + 1, :]

    def pair(name):
        k = REQ32_INDEX[name]
        return I64(mr[k:k + 1, :], mr[k + 1:k + 2, :])

    return PReq(
        slot=row("slot"),
        known=row("known") != 0,
        hits=pair("hits"),
        limit=pair("limit"),
        duration=pair("duration"),
        algorithm=row("algorithm"),
        behavior=row("behavior"),
        created_at=pair("created_at"),
        burst=pair("burst"),
        greg_exp=pair("greg_exp"),
        greg_dur=pair("greg_dur"),
        valid=row("valid") != 0,
    )


def make_fused_tick_fn(capacity: int, chunk: int | None = None):
    """(state: RowState, m32 (19, B) i32, now i64) → (state, resp (6, B)).

    Unique-slot, slot-sorted batches on the row layout; see module doc.
    ``chunk`` overrides the VMEM chunk rows (default 2048, the measured
    sweet spot on v5e; tests use small chunks to exercise the
    double-buffered path cheaply in interpret mode)."""

    def tick(state, m32, now):
        b = m32.shape[1]
        c = min(chunk or 2048, b)
        nc = b // c
        assert b % c == 0 and (nc == 1 or nc % 2 == 0), (b, c)
        slots = m32[REQ32_INDEX["slot"]]
        from gubernator_tpu.ops.tick32 import now_to_pair

        np_ = now_to_pair(now)
        now2 = jnp.stack([np_.lo, np_.hi])

        kernel = functools.partial(_kernel, capacity=capacity, C=c, nc=nc)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # slots, now2
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
                input_output_aliases={3: 0},  # table input -> table output
                compiler_params=_VMEM,
                interpret=_interpret(),
                name="fused_tick32",
            )(slots, now2, m32, state.table)
        return state._replace(table=table), resp

    return tick


def _kernel(slots_ref, now_ref, m32_ref, table_ref, tout_ref, resp_ref,
            rbuf, wbuf, rsem, wsem, *, capacity, C, nc, merged=False):
    cap_i = jnp.int32(capacity)

    # The scalar core's DMA work is the kernel's second wall (~23 ns per
    # read descriptor): slots are trusted in [0, capacity] (the host
    # packs them; engine._build_cols), waits are ONE bulk semaphore_wait
    # per chunk instead of C descriptor re-creations, and the issue
    # loops are manually 8-wide (Mosaic only supports unroll=1/full in
    # lax loops).
    del cap_i
    # 8-wide measured best on v5e (4: ~5% slower; 16: ~50% slower).
    U = 8 if C % 8 == 0 else 1

    def read_copy(c, buf, j):
        return pltpu.make_async_copy(
            tout_ref.at[pl.ds(slots_ref[c * C + j], 1), :],
            rbuf.at[buf, pl.ds(j, 1), :],
            rsem.at[buf],
        )

    def write_copy(c, buf, j):
        return pltpu.make_async_copy(
            wbuf.at[buf, pl.ds(j, 1), :],
            tout_ref.at[pl.ds(slots_ref[c * C + j], 1), :],
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
        # One aggregate wait for the whole chunk: DMA semaphores count
        # bytes, and the wait amount comes from the descriptor's dst
        # size — a (C, ROW_W) self-copy descriptor waits exactly the sum
        # of the C row copies without C descriptor re-creations.
        pltpu.make_async_copy(
            rbuf.at[buf], rbuf.at[buf], rsem.at[buf]).wait()

    def issue_writes(c, buf):
        _loop(lambda j: write_copy(c, buf, j).start())

    def wait_writes(c, buf):
        pltpu.make_async_copy(
            wbuf.at[buf], wbuf.at[buf], wsem.at[buf]).wait()

    def compute_store(c, buf):
        """Transition chunk ``c`` from rbuf[buf] into wbuf[buf] + resp."""
        base = c * C
        T = _transpose_fwd(rbuf[buf, :, :TW])
        s = _pstate_from_T(T)
        mr = m32_ref[:REQ32_ROWS, pl.ds(base, C)]
        r = _preq_from_rows(mr)
        now_pair = I64(
            jnp.full((1, C), now_ref[0], I32),
            jnp.full((1, C), now_ref[1], I32),
        )
        new_state, resp = transition32(now_pair, s, r)
        if merged:
            from gubernator_tpu.ops.transition32 import (
                MERGED24_ROWS,
                merged24_rows,
                merged_fold32,
            )

            cnt = m32_ref[REQ32_ROWS:REQ32_ROWS + 1, pl.ds(base, C)]
            new_state, head = merged_fold32(now_pair, new_state, r, cnt)
        # The write-buffer store comes FIRST: pair_body issues the row
        # scatters right after compute_store returns, and filling wbuf
        # before the response packing keeps the write DMAs from waiting
        # on VPU work they don't depend on.
        out = _transpose_bwd(_pstate_to_T(new_state))  # (C, TW)
        wbuf[buf, :, :TW] = out
        if merged:
            rows = list(merged24_rows(resp, head, r))
            rows += [jnp.zeros((1, C), I32)] * (MERGED24_ROWS - len(rows))
            # Row-major output via the same exact one-hot MXU transpose
            # the table rows use (TW == MERGED24_ROWS == 24).
            respT = _transpose_bwd(jnp.concatenate(rows, axis=0))
            resp_ref[pl.ds(base, C), :] = respT
        else:
            rows = [
                resp.status,
                resp.over_limit.astype(I32),
                resp.remaining.lo,
                resp.remaining.hi,
                resp.reset_time.lo,
                resp.reset_time.hi,
            ]
            resp_ref[:, pl.ds(base, C)] = jnp.concatenate(rows, axis=0)

    # Spare words of the write rows are zero for the whole kernel (rows
    # scatter whole-width; eviction/installs expect zeroed spares).
    wbuf[0, :, TW:] = jnp.zeros((C, ROW_W - TW), I32)
    wbuf[1, :, TW:] = jnp.zeros((C, ROW_W - TW), I32)

    issue_reads(0, 0)

    if nc == 1:
        wait_reads(0, 0)
        compute_store(0, 0)
        issue_writes(0, 0)
        wait_writes(0, 0)
        return

    issue_reads(1, 1)

    def pair_body(c2, _):
        for buf in (0, 1):
            c = 2 * c2 + buf
            wait_reads(c, buf)

            @pl.when(c2 > 0)
            def _(c=c, buf=buf):
                wait_writes(c - 2, buf)

            compute_store(c, buf)

            # Reads ahead of writes: the DMA queue serves descriptors in
            # order and the read stream is the critical path — feeding
            # chunk c's writes first would stall chunk c+2's reads
            # behind ~C write descriptors every chunk.
            @pl.when(c + 2 < nc)
            def _(c=c, buf=buf):
                issue_reads(c + 2, buf)

            issue_writes(c, buf)

        return 0

    lax.fori_loop(0, nc // 2, pair_body, 0)
    wait_writes(nc - 2, 0)
    wait_writes(nc - 1, 1)


def make_fused_merged_tick_fn(capacity: int, chunk: int | None = None):
    """Grouped variant of the fused tick: same DMA pipeline, with the
    closed-form duplicate fold (transition32.merged_fold32) applied
    in-register before the scatter.  ``count`` rides as a 20th
    request-matrix row so the kernel reads it from VMEM like any other
    request field.

    Output format is ROW-MAJOR ``(U, 24)`` (transition32.MERGED24 row
    order: compact resp + MergedHead extras + the request params the
    expansion needs) — the per-member expansion gathers whole 96 B rows
    by head index, which the TPU executes ~40x faster than 15 separate
    lane-dimension gathers (chained-differential probe: 95 µs vs 3.6 ms
    for 32K members).  The transpose into row-major rides the same
    one-hot MXU blocks as the table rows."""
    from gubernator_tpu.ops.transition32 import MERGED24_ROWS

    def tick(state, mhead, count, now):
        b = mhead.shape[1]
        c = min(chunk or 2048, b)
        nc = b // c
        assert b % c == 0 and (nc == 1 or nc % 2 == 0), (b, c)
        slots = mhead[REQ32_INDEX["slot"]]
        from gubernator_tpu.ops.tick32 import now_to_pair

        np_ = now_to_pair(now)
        now2 = jnp.stack([np_.lo, np_.hi])
        m20 = jnp.concatenate([mhead, count[None].astype(I32)], axis=0)

        kernel = functools.partial(
            _kernel, capacity=capacity, C=c, nc=nc, merged=True)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # slots, now2
            grid=(1,),
            in_specs=[
                pl.BlockSpec((REQ32_ROWS + 1, b), lambda t, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # table (HBM)
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # table out (aliased)
                pl.BlockSpec((b, MERGED24_ROWS), lambda t, *_: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, c, ROW_W), I32),
                pltpu.VMEM((2, c, ROW_W), I32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        with jax.enable_x64(False):
            table, resp = pl.pallas_call(
                kernel,
                grid_spec=grid_spec,
                out_shape=[
                    jax.ShapeDtypeStruct((capacity + 1, ROW_W), I32),
                    jax.ShapeDtypeStruct((b, MERGED24_ROWS), I32),
                ],
                input_output_aliases={3: 0},
                compiler_params=_VMEM,
                interpret=_interpret(),
                name="fused_merged_tick32",
            )(slots, now2, m20, state.table)
        return state._replace(table=table), resp

    return tick
