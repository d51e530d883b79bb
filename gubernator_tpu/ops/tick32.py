"""The parts-native tick program: compact i32 requests in, compact i32
responses out, no 64-bit ops anywhere in the hot path.

This is the unique-slot fast program: the host sorts every batch by slot
(engine._build_cols) and knows whether duplicates exist; batches with at
most one request per slot — the overwhelming production shape and the
memory-traffic worst case — dispatch here, duplicate-bearing batches take the
merge-capable program (engine.make_tick_fn).  Keeping the two as
separate host-dispatched programs (instead of a traced lax.cond) lets
this one stay pure int32/float32, which is what allows it to run inside
a Mosaic kernel at all (Mosaic refuses jax_enable_x64 programs) and
removes XLA's emulated-64-bit overhead from the XLA fallback.

Layouts:
* ``row`` — Pallas per-row DMA gather/scatter around a parts transition
  (fused kernel lands behind this same factory).
* ``columns`` — direct i32 part-column gathers/scatters (the 100M-slot
  regime, where the row table doesn't fit).

Reference semantics: algorithms.go:37-493 via ops/transition32.py.
"""

from __future__ import annotations

import functools

import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax
import jax.numpy as jnp

from gubernator_tpu.ops import i64pair as p64
from gubernator_tpu.types import Algorithm, Behavior
from gubernator_tpu.ops.transition32 import (
    preq_from_compact,
    pstate_from_matrix,
    pstate_gather_columns,
    pstate_scatter_columns,
    pstate_to_matrix,
    transition32,
)

I32 = jnp.int32


def now_to_pair(now) -> p64.I64:
    """``now`` as the (lo, hi) i32 pair.  A scalar int64 is split (scalar
    arithmetic only — this toolchain's X64 rewriter has no 64-bit
    bitcasts); a pair passes through: the engine's entries read the two
    words out of the window's one upload (:func:`split_slab`), and no
    64-bit value then exists on the device at all."""
    if isinstance(now, p64.I64):
        return now
    hi = (now >> 32).astype(I32)
    lo_u = now & jnp.int64(0xFFFFFFFF)
    lo = jnp.where(
        lo_u >= jnp.int64(1 << 31), lo_u - jnp.int64(1 << 32), lo_u
    ).astype(I32)
    return p64.I64(lo, hi)


def _resolve_fused(fused: bool | None) -> bool:
    """Default: fused Pallas on real TPU, unfused XLA elsewhere.  On CPU
    the fused kernel only exists in interpret mode (a Python-stepped DMA
    loop — seconds per tick), so the 8-device test mesh would crawl;
    GUBER_TPU_FUSED_TICK=0/1 still forces either path on any backend
    (tests/test_fusedtick.py covers fused-vs-unfused parity in interpret
    mode explicitly).  Read through the config registry at engine
    construction (not per tick — the resolved choice is baked into the
    jitted program cache key)."""
    from gubernator_tpu.config import env_knob

    if fused is not None:
        return fused
    env = env_knob("GUBER_TPU_FUSED_TICK")
    if env is not None:
        return env != "0"
    return jax.default_backend() == "tpu"


def split_slab(slab):
    """A unique or sequential window's upload (engine.SLAB_ROWS, B) →
    (the (19, B) REQ32 matrix, ``now`` as its i32 pair): the tick's clock
    rides in the first two words of the slab's last row
    (engine.stamp_now), so a window is one host→device copy."""
    from gubernator_tpu.ops.engine import REQ32_ROWS

    return slab[:REQ32_ROWS], p64.I64(slab[REQ32_ROWS, 0], slab[REQ32_ROWS, 1])


def stack6(rows) -> jnp.ndarray:
    """Six (B,) response rows → the (6, B) compact matrix, written row by
    row into a zeroed block, NOT ``jnp.stack``: XLA:CPU's emitters walk
    a concatenate-rooted fusion's whole operand graph once per output
    element (make_tick32_rows_fn), while a row written by
    dynamic-update-slice is one memoized loop.  That is what lets tick
    and stack be ONE program on every backend."""
    out = jnp.zeros((len(rows),) + rows[0].shape, I32)
    for k, row in enumerate(rows):
        out = jax.lax.dynamic_update_slice(out, row[None], (k, 0))
    return out


def _resp_rows(resp) -> tuple:
    """PResp → the six compact response rows, unstacked (same order as
    presp_to_compact: status, over, rem lo/hi, reset lo/hi)."""
    return (
        resp.status,
        resp.over_limit.astype(I32),
        resp.remaining.lo,
        resp.remaining.hi,
        resp.reset_time.lo,
        resp.reset_time.hi,
    )


def make_tick32_rows_fn(capacity: int, layout: str = "columns"):
    """The XLA (non-Pallas) tick program, response as SIX SEPARATE row
    vectors rather than one stacked (6, B) matrix.

    The split exists because stacking is poison on the CPU backend:
    XLA:CPU emits a concatenate-rooted fusion over this very deep
    elementwise graph by recursively re-evaluating each operand's
    expression tree per output element (no memoization across the
    diamond-shaped reuse in the i64-pair/triple-f32 arithmetic), which
    turns a ~10 µs tick into ~0.2 s *per batch element* — a 64-wide tick
    took 12 s on the 8-device test mesh.  Returning the rows as separate
    program outputs keeps every fusion root single-output, which XLA
    emits as one memoized loop.  TPU's emitter doesn't have the
    pathology.  Callers that want the (6, B) matrix from the same
    program assemble it with :func:`stack6`, which no backend minds.
    """

    if layout == "row":
        from gubernator_tpu.ops.rowtable import gather_rows, scatter_rows

        def tick(state, m32, now):
            r = preq_from_compact(m32)
            slots = jnp.clip(r.slot, 0, capacity)
            mat = gather_rows(state.table, slots)
            s = pstate_from_matrix(mat)
            new_g, resp = transition32(now_to_pair(now), s, r)
            scat = jnp.where(r.valid, slots, jnp.int32(capacity))
            table = scatter_rows(state.table, scat, pstate_to_matrix(new_g))
            return state._replace(table=table), _resp_rows(resp)

    else:

        def tick(state, m32, now):
            r = preq_from_compact(m32)
            slots = jnp.clip(r.slot, 0, capacity - 1)
            s = pstate_gather_columns(state, slots)
            new_g, resp = transition32(now_to_pair(now), s, r)
            # unclipped slot: padding rows (slot == capacity) drop
            scat = jnp.where(r.valid, r.slot, jnp.int32(capacity))
            state = pstate_scatter_columns(state, scat, new_g)
            return state, _resp_rows(resp)

    return tick


def make_tick32_fn(capacity: int, layout: str = "columns",
                   fused: bool | None = None):
    """Build (state, m32, now) → (state, resp6) for unique-slot batches.

    Contract (matches make_tick_fn's compact in/out so TickHandle code is
    shared): ``m32`` is the (19, B) compact request matrix, slot-sorted,
    padding/error rows carrying slot == capacity; at most one valid
    request per real slot.  ``resp6`` is the (6, B) compact response
    matrix; rows past the live count are unspecified.

    One traceable function (tests/test_fusedtick.py jits it whole);
    :func:`jitted_tick32` is the engine's entry, the same program behind
    the window's one upload.
    """

    if layout == "row" and _resolve_fused(fused):
        from gubernator_tpu.ops.fusedtick import make_fused_tick_fn

        return make_fused_tick_fn(capacity)

    rows_fn = make_tick32_rows_fn(capacity, layout)

    def tick(state, m32, now):
        state, rows = rows_fn(state, m32, now)
        return state, stack6(rows)

    return tick


def _jit_on_slab(tick, name: str):
    """(state, m32, now) tick → the engine's ONE jitted program of
    ``(state, slab)``, state donated: the REQ32 rows and ``now`` both
    come out of the window's one upload (:func:`split_slab`).  ``name``
    is what the program is called in a trace (module ``jit_<name>``):
    the unique and the sequential program share this wrapper and must
    not share a name."""

    def run(state, slab):
        return tick(state, *split_slab(slab))

    run.__name__ = run.__qualname__ = name
    return jax.jit(run, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def jitted_tick32(capacity: int, layout: str = "columns",
                  fused: bool | None = None):
    """Engine entry for unique-slot windows: (state, slab (SLAB_ROWS, B))
    → (state, (6, B) compact responses), one program call on one upload
    — the fused Pallas row kernel, or the XLA rows with their stack."""
    return _jit_on_slab(make_tick32_fn(capacity, layout, fused),
                        "tick32_unique")


# ----------------------------------------------------------------------
# Grouped ("scatter-add") tick: unique heads + closed-form fold
# ----------------------------------------------------------------------
def make_merged_tick32_rows_fn(capacity: int, layout: str = "columns"):
    """(state, mhead (19, U) i32, count (U,) i32, now) → (state, 15-row
    tuple): the unique-head tick with the duplicate-group fold applied to
    the table row (transition32.merged_fold32) and the head extras the
    expansion program needs.  Same unstacked-rows discipline as
    make_tick32_rows_fn (XLA:CPU concat-fusion pathology)."""
    from gubernator_tpu.ops.transition32 import merged_fold32

    def rows_of(now, s, r, count, new_g, resp):
        folded, head = merged_fold32(now, new_g, r, count)
        return folded, (
            resp.status,
            resp.over_limit.astype(I32),
            resp.remaining.lo, resp.remaining.hi,
            resp.reset_time.lo, resp.reset_time.hi,
            head.base.lo, head.base.hi,
            head.q.lo, head.q.hi,
            head.rate_i.lo, head.rate_i.hi,
            head.s0,
            head.expire.lo, head.expire.hi,
        )

    if layout == "row":
        from gubernator_tpu.ops.rowtable import gather_rows, scatter_rows

        def tick(state, mhead, count, now):
            r = preq_from_compact(mhead)
            slots = jnp.clip(r.slot, 0, capacity)
            mat = gather_rows(state.table, slots)
            s = pstate_from_matrix(mat)
            np_ = now_to_pair(now)
            new_g, resp = transition32(np_, s, r)
            folded, rows = rows_of(np_, s, r, count, new_g, resp)
            scat = jnp.where(r.valid, slots, jnp.int32(capacity))
            table = scatter_rows(
                state.table, scat, pstate_to_matrix(folded))
            return state._replace(table=table), rows

    else:

        def tick(state, mhead, count, now):
            r = preq_from_compact(mhead)
            slots = jnp.clip(r.slot, 0, capacity - 1)
            s = pstate_gather_columns(state, slots)
            np_ = now_to_pair(now)
            new_g, resp = transition32(np_, s, r)
            folded, rows = rows_of(np_, s, r, count, new_g, resp)
            scat = jnp.where(r.valid, r.slot, jnp.int32(capacity))
            state = pstate_scatter_columns(state, scat, folded)
            return state, rows

    return tick


# ----------------------------------------------------------------------
# Layered tick: host-planned unit layers through the narrow merged core
# ----------------------------------------------------------------------
def _expand_sorted(flat15, m32, uidx, rank):
    """Member responses from a flattened unit-layer journal: head values
    gathered per member from ``flat15[:, uidx]``; request params come
    from each member's OWN compact columns (within a unit all members
    are identical to the head by construction, so no head-param gather
    is needed).  Returns the six compact rows, unstacked."""
    from gubernator_tpu.ops.engine import REQ32_INDEX
    from gubernator_tpu.ops.transition32 import _expand_members

    g = [row[uidx] for row in flat15]

    def rpair(name):
        k = REQ32_INDEX[name]
        return p64.I64(m32[k], m32[k + 1])

    return _expand_members(
        g[:6],
        base=p64.I64(g[6], g[7]), q=p64.I64(g[8], g[9]),
        rate_i=p64.I64(g[10], g[11]), s0=g[12],
        expire=p64.I64(g[13], g[14]),
        h=rpair("hits"), limit=rpair("limit"),
        created=rpair("created_at"),
        algorithm=m32[REQ32_INDEX["algorithm"]],
        behavior=m32[REQ32_INDEX["behavior"]],
        rank=rank,
    )


@functools.lru_cache(maxsize=None)
def jitted_layered_pipeline(capacity: int, layout: str, w0: int,
                            k_layers: int, layer_width: int = 512,
                            fused: bool | None = None):
    """Engine entry for mixed-duplicate batches with a host layer plan
    (engine.build_layer_plan): (state, mh0, cnt0, mhk, cntk, slab, uidx,
    rank) → (state, (6, B) compact responses); ``slab`` is the window's
    staging slab as the unique program takes it, ``now`` in its last row
    (:func:`split_slab`).

    Layer 0 (every segment's first unit, up to ``w0`` heads) and then
    ``k_layers - 1`` narrow layers each run the merged tick — gather,
    transition, closed-form count-fold, scatter — CHAINED THROUGH THE
    TABLE (layer k+1's gather reads layer k's scatter), so a segment's
    units apply in exact batch order at one narrow tick per layer
    instead of one full-width gather/scatter round per unit.  One
    elementwise expansion then derives every member's response from its
    unit's journal row.  The fused Pallas kernel serves the layers on
    the row layout (real TPU); the XLA merged core serves columns/CPU.
    """
    if layout == "row" and _resolve_fused(fused):
        from gubernator_tpu.ops.fusedtick import make_fused_merged_tick_fn
        from gubernator_tpu.ops.transition32 import expand32_rowmajor

        tick0 = make_fused_merged_tick_fn(capacity, chunk=min(2048, w0))
        tickk = make_fused_merged_tick_fn(
            capacity, chunk=min(2048, layer_width))

        def tick32_layered(state, mh0, cnt0, mhk, cntk, slab, uidx, rank):
            _, now = split_slab(slab)
            state, r24_0 = tick0(state, mh0, cnt0, now)   # (W0, 24)

            def layer(k, carry):
                st, J = carry
                st, r24 = tickk(st, mhk[k], cntk[k], now)
                return st, jax.lax.dynamic_update_slice(
                    J, r24[None], (k, 0, 0))

            J0 = jnp.zeros((max(k_layers - 1, 1), layer_width, 24), I32)
            state, J = jax.lax.fori_loop(
                0, k_layers - 1, layer, (state, J0))
            flat24 = jnp.concatenate(
                [r24_0, J.reshape(-1, 24)], axis=0)
            return state, jnp.stack(
                expand32_rowmajor(flat24, uidx, rank))

        return jax.jit(tick32_layered, donate_argnums=(0,))

    core = make_merged_tick32_rows_fn(capacity, layout)

    def tick32_layered(state, mh0, cnt0, mhk, cntk, slab, uidx, rank):
        m32, now = split_slab(slab)
        state, rows0 = core(state, mh0, cnt0, now)

        def layer(k, carry):
            state, J = carry
            state, rows = core(state, mhk[k], cntk[k], now)
            # Journal as FIFTEEN separate carries: stacking the deep
            # parts graphs inside the loop would hand XLA:CPU a
            # concatenate-rooted mega-fusion (make_tick32_rows_fn).
            J = tuple(
                jax.lax.dynamic_update_slice(a, r[None], (k, 0))
                for a, r in zip(J, rows)
            )
            return state, J

        J0 = tuple(
            jnp.zeros((max(k_layers - 1, 1), layer_width), I32)
            for _ in range(15)
        )
        state, J = jax.lax.fori_loop(0, k_layers - 1, layer, (state, J0))
        flat15 = [
            jnp.concatenate([r0, a.reshape(-1)])
            for r0, a in zip(rows0, J)
        ]
        return state, jnp.stack(_expand_sorted(flat15, m32, uidx, rank))

    return jax.jit(tick32_layered, donate_argnums=(0,))


# ----------------------------------------------------------------------
# Sorted mixed-duplicate tick: chained unit rounds, parts-native
# ----------------------------------------------------------------------
def make_sorted_tick32_rows_fn(capacity: int, layout: str = "columns",
                               unit_unroll: int = 8):
    """The mixed-duplicate program, parts-native: (state, m32 (19, B)
    slot-sorted compact requests, now) → (state, 6-row compact response
    tuple), preserving exact per-slot request order.

    Structure (the engine.make_tick_fn tick_sorted contract, restated in
    int32/f32 parts so no XLA 64-bit emulation rides the mixed-herd
    path):

    * a *unit* is a maximal run of identical fold-eligible duplicates
      (engine._sorted_merge_plan); uniform groups are one unit, groups
      broken by RESET/Gregorian/query/parameter-change rows are several;
    * each round gathers once, then applies up to ``unit_unroll`` units
      per slot IN REGISTERS — head transition (transition32), follower
      fold (merged_fold32 + _expand_members, the grouped program's own
      closed forms), then forward-propagates the folded row state so the
      next unit's head chains without a scatter/gather round trip — and
      scatters once, from each slot's last applied head;
    * cost: ceil(units / unit_unroll) gather+scatter rounds, with
      sequential unit transitions amortized onto cheap elementwise work
      (the Go reference serializes the same traffic per key,
      workers.go:190-258; here the chain rides the VPU).
    """
    from gubernator_tpu.ops.transition32 import (
        _expand_members, merged_fold32)

    if layout == "row":
        from gubernator_tpu.ops.rowtable import gather_rows, scatter_rows

        def gather_mat(state, slots):
            return gather_rows(state.table, slots)

        def scatter_mat(state, scat, mat):
            return state._replace(
                table=scatter_rows(state.table, scat, mat))
    else:

        def gather_mat(state, slots):
            return pstate_to_matrix(pstate_gather_columns(state, slots))

        def scatter_mat(state, scat, mat):
            return pstate_scatter_columns(
                state, scat, pstate_from_matrix(mat))

    def tick(state, m32, now):
        from gubernator_tpu.ops.engine import (
            REQ32_INDEX as R,
            _seg_max_all,
            _seg_min_all,
        )

        b = m32.shape[1]
        idx = jnp.arange(b, dtype=I32)
        rq = preq_from_compact(m32)
        np_ = now_to_pair(now)
        slot = rq.slot
        slots_clip = jnp.clip(slot, 0, capacity - 1)
        key = jnp.where(rq.valid, slot, jnp.int32(capacity))
        is_start = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), key[1:] != key[:-1]])

        # Unit plan (engine._sorted_merge_plan on the compact matrix):
        # "equals its predecessor" chains to "equals its head" within a
        # contiguous run.
        PARAM_ROWS = (
            R["algorithm"], R["behavior"],
            R["hits"], R["hits"] + 1,
            R["limit"], R["limit"] + 1,
            R["duration"], R["duration"] + 1,
            R["created_at"], R["created_at"] + 1,
            R["burst"], R["burst"] + 1,
            R["greg_exp"], R["greg_exp"] + 1,
            R["greg_dur"], R["greg_dur"] + 1,
        )
        eqp = jnp.ones(b - 1, jnp.bool_)
        for row in PARAM_ROWS:
            eqp = eqp & (m32[row, 1:] == m32[row, :-1])
        same_as_prev = is_start | jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), eqp])
        NO_MERGE = jnp.int32(
            int(Behavior.RESET_REMAINING)
            | int(Behavior.DURATION_IS_GREGORIAN))
        hits_pos = p64.gt(rq.hits, p64.const(0, slot))
        # Closed-form duplicate folds exist only for token/leaky; zoo
        # lanes (algorithm >= 2) stay size-1 units and transition
        # sequentially within the same dispatch.
        legacy_alg = rq.algorithm <= jnp.int32(Algorithm.LEAKY_BUCKET)
        ok = (
            rq.valid & same_as_prev & hits_pos
            & ((rq.behavior & NO_MERGE) == 0)
            & (rq.known | is_start)
            & legacy_alg
        )
        unit_start = is_start | ~ok
        nxt = jnp.where(unit_start, idx, jnp.int32(b))
        sfx = jax.lax.associative_scan(jnp.minimum, nxt[::-1])[::-1]
        unit_end = jnp.concatenate(
            [sfx[1:], jnp.full((1,), b, jnp.int32)])

        resp0 = tuple(jnp.zeros(b, I32) for _ in range(6))
        bmax = jnp.int32(b - 1)

        def sub_step(applied, g_mat, resp, cur_head, last_head):
            """One unit per slot, no scans: ``cur_head[i]`` points at the
            head row the row's segment processes this sub-step (every
            row of a segment shares the value), so all head→member data
            flow is B-indexed gathers.  Rows whose pointer has walked
            into a following segment are harmless: a head is always the
            lowest-indexed live row of its unit, so the ``i > h`` fold
            guard never matches across segments."""
            cand = ~applied
            head = cand & (idx == cur_head)
            s = pstate_from_matrix(g_mat)
            new_s, r_out = transition32(np_, s, rq)
            cnt = jnp.where(head, unit_end - idx, jnp.int32(1))
            folded, mh = merged_fold32(np_, new_s, rq, cnt)
            head6 = _resp_rows(r_out)
            folded_mat = pstate_to_matrix(folded)

            h = cur_head  # (B,) row index of my segment's current head
            def hv(a):
                return a[h]

            hpos = h
            uend = hv(unit_end)
            base = p64.I64(hv(mh.base.lo), hv(mh.base.hi))
            q = p64.I64(hv(mh.q.lo), hv(mh.q.hi))
            rate_i = p64.I64(hv(mh.rate_i.lo), hv(mh.rate_i.hi))
            s0 = hv(mh.s0)
            expire = p64.I64(hv(mh.expire.lo), hv(mh.expire.hi))
            head6_p = tuple(hv(r6) for r6 in head6)
            head_live = hv(head)  # my segment fired a head this sub-step

            rank = idx - hpos
            alive = p64.le(np_, expire)
            fold = (cand & ok & head_live & alive
                    & (rank > 0) & (idx < uend))
            member6 = _expand_members(
                head6_p, base=base, q=q, rate_i=rate_i, s0=s0,
                expire=expire, h=rq.hits, limit=rq.limit,
                created=rq.created_at, algorithm=rq.algorithm,
                behavior=rq.behavior, rank=rank,
            )
            upd = head | fold
            resp = tuple(
                jnp.where(upd, mv, rv) for rv, mv in zip(resp, member6)
            )
            # Chain: every row's working state becomes its segment
            # head's unit-final state (only rows that head the NEXT
            # sub-step consume it, so over-sharing is free and simple).
            g_mat = jnp.where(
                head_live[:, None], folded_mat[h], g_mat)
            applied = applied | head | fold
            last_head = jnp.where(head, idx, last_head)
            # Advance the pointer: a live fold consumed the whole unit
            # (next head = unit end); a dead head consumed only itself.
            nxt_h = jnp.where(
                head_live,
                jnp.minimum(
                    jnp.where(alive, uend, hpos + 1), bmax),
                cur_head,
            )
            return applied, g_mat, resp, nxt_h, last_head

        def round_body(carry):
            applied, state, resp = carry
            g_mat = gather_mat(state, slots_clip)
            cand0 = ~applied
            # One segmented min per ROUND seeds the head pointers; the
            # sub-steps advance them with gathers only.
            first_cand = _seg_min_all(
                is_start, jnp.where(cand0, idx, jnp.int32(b)))
            cur_head = jnp.minimum(first_cand, bmax)
            sc = (applied, g_mat, resp, cur_head, jnp.full(b, -1, I32))
            sc = jax.lax.fori_loop(
                0, max(1, unit_unroll),
                lambda _k, c: jax.lax.cond(
                    jnp.all(c[0]), lambda cc: cc,
                    lambda cc: sub_step(*cc), c,
                ),
                sc,
            )
            applied, g_mat, resp, cur_head, last_head = sc
            seg_last = _seg_max_all(is_start, last_head)
            scat_src = (last_head >= 0) & (last_head == seg_last)
            scat = jnp.where(scat_src, slot, jnp.int32(capacity))
            state = scatter_mat(state, scat, g_mat)
            return applied, state, resp

        applied0 = ~rq.valid
        _, state, resp = jax.lax.while_loop(
            lambda c: ~jnp.all(c[0]), round_body,
            (applied0, state, resp0),
        )
        return state, resp

    return tick


@functools.lru_cache(maxsize=None)
def jitted_sorted_tick32(capacity: int, layout: str = "columns",
                         unit_unroll: int = 8):
    """Engine entry for mixed-duplicate windows: (state, slab) → (state,
    (6, B) compact responses), one program like jitted_tick32."""
    rows_fn = make_sorted_tick32_rows_fn(capacity, layout, unit_unroll)

    def tick(state, m32, now):
        state, rows = rows_fn(state, m32, now)
        return state, stack6(rows)

    return _jit_on_slab(tick, "tick32_sequential")


@functools.lru_cache(maxsize=None)
def jitted_merged_pipeline(capacity: int, layout: str = "columns",
                           fused: bool | None = None):
    """Engine entry for grouped windows: ONE jitted function of (state,
    buf, b) → (state, (6, B) compact responses), state donated, ``b``
    (the batch width) static.  ``buf`` is the grouped plan as the host
    pack laid it out, uploaded once (engine.plan_views: ``uidx[b]
    rank[b] count[upad] mhead[19][upad]`` and the two words of
    ``now``); the program cuts it by static offsets, so its compiled
    shapes are the (b, upad) pairs.  The merged tick and the member
    expansion run inside the same program, hiding the format split: the
    fused Pallas kernel emits the row-major (U, 24) block (one whole-row
    gather per member — the TPU-fast layout), the XLA fallback emits
    unstacked rows (the CPU-safe layout)."""
    if layout == "row" and _resolve_fused(fused):
        from gubernator_tpu.ops.fusedtick import make_fused_merged_tick_fn
        from gubernator_tpu.ops.transition32 import expand32_rowmajor

        # Not a jit of its own inside the one program, though its trace
        # (seconds of Python) would then be shared by the batch widths
        # that share a head width: a program with a nested jit missed
        # the persistent compile cache at every start on the chip (warm
        # set-up 71 -> 157 s, PERF.md section 6, PR 33).
        tick = make_fused_merged_tick_fn(capacity)

        def expand(r24, mhead, uidx, rank):
            return jnp.stack(expand32_rowmajor(r24, uidx, rank))

    else:
        from gubernator_tpu.ops.transition32 import expand32_rows

        tick = make_merged_tick32_rows_fn(capacity, layout)

        def expand(rows, mhead, uidx, rank):
            return stack6(expand32_rows(tuple(rows), mhead, uidx, rank))

    def tick32_grouped(state, buf, b):
        from gubernator_tpu.ops.engine import plan_views

        mhead, count, uidx, rank, now = plan_views(buf, b)
        state, heads = tick(state, mhead, count, p64.I64(now[0], now[1]))
        return state, expand(heads, mhead, uidx, rank)

    return jax.jit(
        tick32_grouped, donate_argnums=(0,), static_argnums=(2,))
