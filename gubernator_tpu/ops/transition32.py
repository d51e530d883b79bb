"""Parts-native bucket transition: the full token/leaky decision tree in
pure int32/float32 ops.

Semantically this is :func:`gubernator_tpu.ops.buckets.bucket_transition`
(itself the vectorized form of the reference's ``tokenBucket()`` /
``leakyBucket()``, algorithms.go:37-493, every branch and quirk in the
same precedence) restated over the *storage* representation — i64 fields
as (lo, hi) int32 pairs (:mod:`gubernator_tpu.ops.i64pair`), the leaky
``remaining`` float64 stored as a float32 triple
(:mod:`gubernator_tpu.ops.tfloat`) and computed as IEEE binary64 on its
bit pattern (:mod:`gubernator_tpu.ops.b64`): every floating step of
upstream's ``leakyBucket`` rounds here as float64 does, so the answers
are its ``int64()`` truncations, never one off.  Running on the parts
directly:

* removes ``jax_enable_x64`` from the tick entirely (XLA's generic
  64-bit emulation and the bitcast-heavy row<->logical conversion were
  ~30% of a 32K tick), and
* makes the transition compilable *inside* a Mosaic/Pallas kernel,
  where it can overlap the per-row DMA streams (the fused tick).

Every function here is shape-polymorphic and elementwise, so the same
code serves (B,) XLA columns and (1, C) Pallas blocks.
"""

from __future__ import annotations

from typing import NamedTuple

import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax.numpy as jnp
from jax import lax

from gubernator_tpu.algos import ZOO_MIN
from gubernator_tpu.algos import table as zoo_table
from gubernator_tpu.ops import b64
from gubernator_tpu.ops import i64pair as p64
from gubernator_tpu.ops import tfloat as tf
from gubernator_tpu.ops.i64pair import I64
from gubernator_tpu.ops.tfloat import T3
from gubernator_tpu.types import Algorithm, Behavior, Status

I32 = jnp.int32
F32 = jnp.float32


class PState(NamedTuple):
    """Per-request gathered bucket state, storage parts (cf. BucketState)."""

    algorithm: jnp.ndarray   # i32
    limit: I64
    remaining: I64
    remaining_f: T3
    duration: I64
    created_at: I64
    updated_at: I64
    burst: I64
    status: jnp.ndarray      # i32
    expire_at: I64
    in_use: jnp.ndarray      # bool
    tat: I64                 # GCRA theoretical arrival time
    prev_count: I64          # sliding-window previous-window count


class PReq(NamedTuple):
    """Request batch, storage parts (cf. ReqBatch)."""

    slot: jnp.ndarray        # i32
    known: jnp.ndarray       # bool
    hits: I64
    limit: I64
    duration: I64
    algorithm: jnp.ndarray   # i32
    behavior: jnp.ndarray    # i32
    created_at: I64
    burst: I64
    greg_exp: I64
    greg_dur: I64
    valid: jnp.ndarray       # bool


class PResp(NamedTuple):
    """Responses, storage parts (compact wire: limit echoed host-side)."""

    status: jnp.ndarray      # i32
    remaining: I64
    reset_time: I64
    over_limit: jnp.ndarray  # bool


def transition32(now: I64, s: PState, r: PReq) -> tuple[PState, PResp]:
    """Mirror of ``bucket_transition`` on parts — same branch structure,
    same precedence, same quirks; see buckets.py for the line-by-line
    reference mapping.  Comments here mark only parts-specific moves."""
    UNDER = jnp.int32(Status.UNDER_LIMIT)
    OVER = jnp.int32(Status.OVER_LIMIT)

    shape = jnp.shape(r.slot)
    zero = p64.const(0, r.slot)
    one = p64.const(1, r.slot)
    zero_t = tf.zeros_like(r.slot)
    zero_b = b64.zeros_like(r.slot)

    reset_b = (r.behavior & jnp.int32(Behavior.RESET_REMAINING)) != 0
    drain_b = (r.behavior & jnp.int32(Behavior.DRAIN_OVER_LIMIT)) != 0
    greg_b = (r.behavior & jnp.int32(Behavior.DURATION_IS_GREGORIAN)) != 0

    exists = r.known & s.in_use & p64.le(now, s.expire_at)
    is_token = r.algorithm == jnp.int32(Algorithm.TOKEN_BUCKET)
    algo_match = s.algorithm == r.algorithm

    h = r.hits
    h_query = p64.is_zero(h)
    h_pos = p64.gt(h, zero)
    safe_limit = p64.select(p64.is_zero(r.limit), one, r.limit)

    # ------------------------------------------------------------------
    # TOKEN BUCKET
    # ------------------------------------------------------------------
    tok_reset = exists & reset_b
    tok_exist = exists & ~reset_b & algo_match

    t_rem0 = p64.select(
        p64.ne(s.limit, r.limit),
        p64.max_(p64.add(s.remaining, p64.sub(r.limit, s.limit)), zero),
        s.remaining,
    )
    rl_status = s.status
    rl_rem_base = t_rem0
    dur_changed = p64.ne(s.duration, r.duration)
    expire_cand = p64.select(
        greg_b, r.greg_exp, p64.add(s.created_at, r.duration))
    renew = p64.le(expire_cand, r.created_at)
    expire_new = p64.select(
        renew, p64.add(r.created_at, r.duration), expire_cand)
    t_created = p64.select(dur_changed & renew, r.created_at, s.created_at)
    t_rem1 = p64.select(dur_changed & renew, r.limit, t_rem0)
    t_expire = p64.select(dur_changed, expire_new, s.expire_at)
    rl_reset = p64.select(dur_changed, expire_new, s.expire_at)

    t_query = h_query
    t_at_zero = ~t_query & p64.is_zero(rl_rem_base) & h_pos
    t_exact = ~t_query & ~t_at_zero & p64.eq(t_rem1, h)
    t_over = ~t_query & ~t_at_zero & ~t_exact & p64.gt(h, t_rem1)
    t_dec = ~t_query & ~t_at_zero & ~t_exact & ~t_over

    te_rem = p64.select(
        t_exact,
        zero,
        p64.select(
            t_over,
            p64.select(drain_b, zero, t_rem1),
            p64.select(t_dec, p64.sub(t_rem1, h), t_rem1),
        ),
    )
    te_status = jnp.where(t_at_zero, OVER, s.status)
    te_resp_status = jnp.where(t_at_zero | t_over, OVER, rl_status)
    te_resp_rem = p64.select(
        t_exact,
        zero,
        p64.select(
            t_over,
            p64.select(drain_b, zero, rl_rem_base),
            p64.select(t_dec, p64.sub(t_rem1, h), rl_rem_base),
        ),
    )

    tn_expire = p64.select(
        greg_b, r.greg_exp, p64.add(r.created_at, r.duration))
    tn_over = p64.gt(h, r.limit)
    tn_rem = p64.select(tn_over, r.limit, p64.sub(r.limit, h))
    tn_resp_status = jnp.where(tn_over, OVER, UNDER)

    # ------------------------------------------------------------------
    # LEAKY BUCKET
    # ------------------------------------------------------------------
    burst = p64.select(p64.is_zero(r.burst), r.limit, r.burst)
    leak_exist = exists & algo_match
    burst_b = b64.from_pair(burst)

    b_rem0 = b64.select(reset_b, burst_b, b64.from_triple(s.remaining_f))
    burst_changed = p64.ne(s.burst, burst)
    b_rem1 = b64.select(
        burst_changed & p64.gt(burst, b64.trunc_to_pair(b_rem0)),
        burst_b,
        b_rem0,
    )
    # One division serves both branches: an existing bucket's rate takes
    # the Gregorian interval, a new bucket's the raw duration (quirk).
    rate = b64.div(
        b64.from_pair(
            p64.select(leak_exist & greg_b, r.greg_dur, r.duration)),
        b64.from_pair(safe_limit),
    )
    duration_eff = p64.select(greg_b, p64.sub(r.greg_exp, now), r.duration)
    elapsed = p64.sub(r.created_at, s.updated_at)
    leak = b64.div(
        b64.from_pair(elapsed),
        b64.select(b64.is_zero(rate), b64.const(1.0, r.slot), rate))
    leaked = p64.gt(b64.trunc_to_pair(leak), zero)
    b_rem2 = b64.select(leaked, b64.add(b_rem1, leak), b_rem1)
    b_upd = p64.select(leaked, r.created_at, s.updated_at)
    b_rem3 = b64.select(
        p64.gt(b64.trunc_to_pair(b_rem2), burst), burst_b, b_rem2)

    rem_i = b64.trunc_to_pair(b_rem3)
    # Go converts the float rate with int64(rate): toward zero, also for
    # the negative rate of a negative duration (algorithms.go:336,377).
    rate_i = b64.trunc_to_pair(rate)
    l_at_zero = p64.is_zero(rem_i) & h_pos
    l_exact = ~l_at_zero & p64.eq(rem_i, h)
    l_over = ~l_at_zero & ~l_exact & p64.gt(h, rem_i)
    l_query = ~l_at_zero & ~l_exact & ~l_over & h_query
    l_dec = ~l_at_zero & ~l_exact & ~l_over & ~l_query

    b_left = b64.sub(b_rem3, b64.from_pair(h))
    le_remf = b64.select(
        l_exact,
        zero_b,
        b64.select(
            l_over,
            b64.select(drain_b, zero_b, b_rem3),
            b64.select(l_dec, b_left, b_rem3),
        ),
    )
    le_resp_status = jnp.where(l_at_zero | l_over, OVER, UNDER)
    le_resp_rem = p64.select(
        l_exact,
        zero,
        p64.select(
            l_over,
            p64.select(drain_b, zero, rem_i),
            p64.select(l_dec, b64.trunc_to_pair(b_left), rem_i),
        ),
    )
    le_reset_rem = p64.select(l_over, rem_i, le_resp_rem)
    le_resp_reset = p64.add(
        r.created_at, p64.mul(p64.sub(r.limit, le_reset_rem), rate_i))
    le_expire = p64.select(
        ~h_query, p64.add(r.created_at, duration_eff), s.expire_at)

    ln_duration = p64.select(greg_b, p64.sub(r.greg_exp, now), r.duration)
    ln_over = p64.gt(h, burst)
    ln_remf = b64.select(
        ln_over, zero_b, b64.from_pair(p64.sub(burst, h)))
    ln_resp_rem = p64.select(ln_over, zero, p64.sub(burst, h))
    ln_resp_reset = p64.add(
        r.created_at, p64.mul(p64.sub(r.limit, ln_resp_rem), rate_i))
    ln_resp_status = jnp.where(ln_over, OVER, UNDER)
    ln_expire = p64.add(r.created_at, ln_duration)
    leaky_remf = b64.to_triple(b64.select(leak_exist, le_remf, ln_remf))

    # ------------------------------------------------------------------
    # ALGORITHM ZOO (gubernator_tpu/algos): the same policy table the
    # x64 oracle folds in, instantiated on the parts backend.
    # ------------------------------------------------------------------
    is_zoo = r.algorithm >= jnp.int32(ZOO_MIN)
    zs, zr = zoo_table.zoo_transitions(
        zoo_table.PartsOps, s, r, exists, reset_b, drain_b)

    def z64(zoo_v, legacy_v):
        return p64.select(is_zoo, zoo_v, legacy_v)

    def z32(zoo_v, legacy_v):
        return jnp.where(is_zoo, zoo_v, legacy_v)

    # ------------------------------------------------------------------
    # Select per-request outcome (token-reset / token-exist / token-new /
    # leaky-exist / leaky-new)
    # ------------------------------------------------------------------
    def sel32(tr, te, tn, le, ln):
        tok = jnp.where(tok_reset, tr, jnp.where(tok_exist, te, tn))
        lk = jnp.where(leak_exist, le, ln)
        return jnp.where(is_token, tok, lk)

    def sel64(tr, te, tn, le, ln):
        tok = p64.select(tok_reset, tr, p64.select(tok_exist, te, tn))
        lk = p64.select(leak_exist, le, ln)
        return p64.select(is_token, tok, lk)

    # 0/1 int32 lanes, not bool: Mosaic cannot lower selects between
    # bool vectors (i8->i1 truncation); the != 0 at the end emits a
    # plain compare instead.
    true_ = jnp.ones(shape, I32)
    false_ = jnp.zeros(shape, I32)

    new_state = PState(
        algorithm=z32(
            r.algorithm,
            jnp.where(
                is_token,
                jnp.int32(Algorithm.TOKEN_BUCKET),
                jnp.int32(Algorithm.LEAKY_BUCKET),
            )),
        limit=r.limit,
        remaining=z64(
            zs.remaining,
            sel64(zero, te_rem, tn_rem, s.remaining, s.remaining)),
        remaining_f=tf.select(
            is_zoo | (is_token & tok_reset), zero_t,
            tf.select(is_token, s.remaining_f, leaky_remf)),
        duration=z64(
            r.duration,
            sel64(zero, r.duration, r.duration, r.duration, ln_duration)),
        created_at=z64(
            zs.created_at,
            sel64(zero, t_created, r.created_at, s.created_at,
                  s.created_at)),
        updated_at=z64(
            r.created_at,
            sel64(zero, s.updated_at, s.updated_at, b_upd, r.created_at)),
        burst=z64(r.burst, sel64(zero, s.burst, s.burst, burst, burst)),
        status=z32(
            zs.status,
            sel32(jnp.zeros(shape, I32), te_status, UNDER, s.status,
                  UNDER)),
        expire_at=z64(
            zs.expire_at,
            sel64(zero, t_expire, tn_expire, le_expire, ln_expire)),
        in_use=z32(true_, sel32(false_, true_, true_, true_, true_)) != 0,
        tat=z64(zs.tat, zero),
        prev_count=z64(zs.prev_count, zero),
    )

    resp = PResp(
        status=z32(
            zr.status,
            sel32(jnp.full(shape, UNDER), te_resp_status, tn_resp_status,
                  le_resp_status, ln_resp_status)),
        remaining=z64(
            zr.remaining,
            sel64(r.limit, te_resp_rem, tn_rem, le_resp_rem,
                  ln_resp_rem)),
        reset_time=z64(
            zr.reset_time,
            sel64(zero, rl_reset, tn_expire, le_resp_reset,
                  ln_resp_reset)),
        over_limit=z32(
            zr.over_limit,
            sel32(
                false_,
                (t_at_zero | t_over).astype(I32),
                tn_over.astype(I32),
                (l_at_zero | l_over).astype(I32),
                ln_over.astype(I32),
            )) != 0,
    )
    return new_state, resp


# ----------------------------------------------------------------------
# Wire / table adapters
# ----------------------------------------------------------------------
def preq_from_compact(m32: jnp.ndarray) -> PReq:
    """(19, B) compact int32 request matrix → PReq (no 64-bit ops;
    device-side inverse of pack_request_matrix32)."""
    from gubernator_tpu.ops.engine import REQ32_INDEX

    def wide(name):
        i = REQ32_INDEX[name]
        return I64(m32[i], m32[i + 1])

    return PReq(
        slot=m32[REQ32_INDEX["slot"]],
        known=m32[REQ32_INDEX["known"]] != 0,
        hits=wide("hits"),
        limit=wide("limit"),
        duration=wide("duration"),
        algorithm=m32[REQ32_INDEX["algorithm"]],
        behavior=m32[REQ32_INDEX["behavior"]],
        created_at=wide("created_at"),
        burst=wide("burst"),
        greg_exp=wide("greg_exp"),
        greg_dur=wide("greg_dur"),
        valid=m32[REQ32_INDEX["valid"]] != 0,
    )


def presp_to_compact(resp: PResp) -> jnp.ndarray:
    """PResp → (6, B) compact int32 response matrix (same row order as
    pack_resp_compact: status, over, rem lo/hi, reset lo/hi)."""
    return jnp.stack([
        resp.status,
        resp.over_limit.astype(I32),
        resp.remaining.lo,
        resp.remaining.hi,
        resp.reset_time.lo,
        resp.reset_time.hi,
    ])


def _f32(x):
    return lax.bitcast_convert_type(x, F32)


def _i32(x):
    return lax.bitcast_convert_type(x, I32)


def pstate_from_matrix(m: jnp.ndarray) -> PState:
    """(B, ROW_W) gathered row matrix → PState (int32 slices + f32
    bitcasts only — replaces matrix_to_logical's x64 conversion)."""
    from gubernator_tpu.ops.rowtable import FIELD_OFFSETS as O

    def pair(f):
        return I64(m[..., O[f]], m[..., O[f] + 1])

    fo = O["remaining_f"]
    return PState(
        algorithm=m[..., O["algorithm"]],
        limit=pair("limit"),
        remaining=pair("remaining"),
        remaining_f=T3(
            _f32(m[..., fo]), _f32(m[..., fo + 1]), _f32(m[..., fo + 2])),
        duration=pair("duration"),
        created_at=pair("created_at"),
        updated_at=pair("updated_at"),
        burst=pair("burst"),
        status=m[..., O["status"]],
        expire_at=pair("expire_at"),
        in_use=m[..., O["in_use"]] != 0,
        tat=pair("tat"),
        prev_count=pair("prev_count"),
    )


def pstate_to_matrix(s: PState) -> jnp.ndarray:
    """PState → (B, ROW_W) row matrix (inverse of pstate_from_matrix;
    spare words zero, like logical_to_matrix)."""
    from gubernator_tpu.ops.rowtable import ROW_W

    cols = [
        s.algorithm,
        s.limit.lo, s.limit.hi,
        s.remaining.lo, s.remaining.hi,
        _i32(s.remaining_f.hi), _i32(s.remaining_f.mid),
        _i32(s.remaining_f.lo),
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
    mat = jnp.stack(cols, axis=-1)
    b = mat.shape[:-1]
    return jnp.concatenate(
        [mat, jnp.zeros(b + (ROW_W - len(cols),), I32)], axis=-1)


def pstate_gather_columns(state, idx: jnp.ndarray) -> PState:
    """Gather a PState from a stored-layout column-table BucketState
    (tuples of i32 part columns) without any 64-bit conversion."""

    def pair(f):
        lo, hi = getattr(state, f)
        return I64(lo[idx], hi[idx])

    fh, fm, fl = state.remaining_f
    return PState(
        algorithm=state.algorithm[idx],
        limit=pair("limit"),
        remaining=pair("remaining"),
        remaining_f=T3(_f32(fh[idx]), _f32(fm[idx]), _f32(fl[idx])),
        duration=pair("duration"),
        created_at=pair("created_at"),
        updated_at=pair("updated_at"),
        burst=pair("burst"),
        status=state.status[idx],
        expire_at=pair("expire_at"),
        in_use=state.in_use[idx],
        tat=pair("tat"),
        prev_count=pair("prev_count"),
    )


def pstate_scatter_columns(state, idx: jnp.ndarray, rows: PState):
    """Scatter a PState back into a stored-layout column BucketState
    (drop mode, like scatter_state)."""

    def put(col, vals):
        return col.at[idx].set(vals, mode="drop")

    return state._replace(
        algorithm=put(state.algorithm, rows.algorithm),
        limit=(put(state.limit[0], rows.limit.lo),
               put(state.limit[1], rows.limit.hi)),
        remaining=(put(state.remaining[0], rows.remaining.lo),
                   put(state.remaining[1], rows.remaining.hi)),
        remaining_f=(
            put(state.remaining_f[0], _i32(rows.remaining_f.hi)),
            put(state.remaining_f[1], _i32(rows.remaining_f.mid)),
            put(state.remaining_f[2], _i32(rows.remaining_f.lo)),
        ),
        duration=(put(state.duration[0], rows.duration.lo),
                  put(state.duration[1], rows.duration.hi)),
        created_at=(put(state.created_at[0], rows.created_at.lo),
                    put(state.created_at[1], rows.created_at.hi)),
        updated_at=(put(state.updated_at[0], rows.updated_at.lo),
                    put(state.updated_at[1], rows.updated_at.hi)),
        burst=(put(state.burst[0], rows.burst.lo),
               put(state.burst[1], rows.burst.hi)),
        status=put(state.status, rows.status),
        expire_at=(put(state.expire_at[0], rows.expire_at.lo),
                   put(state.expire_at[1], rows.expire_at.hi)),
        in_use=put(state.in_use, rows.in_use),
        tat=(put(state.tat[0], rows.tat.lo),
             put(state.tat[1], rows.tat.hi)),
        prev_count=(put(state.prev_count[0], rows.prev_count.lo),
                    put(state.prev_count[1], rows.prev_count.hi)),
    )


# ----------------------------------------------------------------------
# Grouped ("scatter-add") tick: closed-form duplicate fold on parts
# ----------------------------------------------------------------------
# The BASELINE north star names hot-key scatter-add: Zipf traffic puts
# many identical requests on one key per window, and the device should
# tick each hot slot ONCE, not once per duplicate.  The host dedups the
# slot-sorted batch (engine._build_group_plan), the kernel transitions
# each unique head and folds the group's followers closed-form into the
# table row (merged_fold32 — the parts mirror of engine._merged_formulas,
# same math, same quirks), and a second elementwise program reconstructs
# every member's response from the head outputs (expand32).  The fold is
# rank-arithmetic only, so a k-deep hot key costs the same HBM traffic
# as a unique key.

class MergedHead(NamedTuple):
    """Per-head extras the expansion needs, alongside the head's own
    compact response."""

    base: I64        # post-head integer remaining (token R0 / trunc F0)
    q: I64           # base // hits (the last under-limit rank)
    rate_i: I64      # floor(duration / limit) — leaky reset slope
    s0: jnp.ndarray  # post-head stored status (pre-fold), i32
    expire: I64      # post-head expire_at


def merged_fold32(now: I64, new_s: PState, r: PReq, count: jnp.ndarray
                  ) -> tuple[PState, MergedHead]:
    """Fold ``count - 1`` identical followers into the head's
    post-transition row (engine._merged_formulas semantics: the i <= q
    steps decrement, the rest are over-limit; stored token status flips
    on an at-zero step; leaky remaining_f zeroes exactly on an
    exact-remainder or drain step).  ``count == 1`` is the identity, so
    unique slots ride the same program.

    Host contract (engine._build_group_plan): every member of a
    count > 1 group is identical to its head, hits > 0, known, and free
    of RESET_REMAINING / Gregorian behaviors.
    """
    OVER = jnp.int32(Status.OVER_LIMIT)
    zero = p64.const(0, r.slot)
    one = p64.const(1, r.slot)

    is_tok = r.algorithm == jnp.int32(Algorithm.TOKEN_BUCKET)
    h = p64.select(p64.gt(r.hits, zero), r.hits, one)  # div-safe
    f0 = b64.from_triple(new_s.remaining_f)
    base = p64.select(is_tok, new_s.remaining, b64.trunc_to_pair(f0))
    base_pos = p64.select(p64.is_neg(base), zero, base)  # div domain
    q = p64.div_floor_pos(base_pos, h)
    li = p64.from_i32(count - 1)
    alive = p64.le(now, new_s.expire_at)
    # Closed-form fold is only valid for the token/leaky pair; the host
    # group planner never groups zoo lanes (engine gates eligibility on
    # algorithm <= LEAKY_BUCKET), this mask is defense in depth.
    legacy = r.algorithm <= jnp.int32(Algorithm.LEAKY_BUCKET)
    fold = (count > 1) & alive & r.valid & legacy

    qh = p64.mul(q, h)
    residue = p64.sub(base, qh)          # base - q*h, >= 0
    divisible = p64.is_zero(residue)
    drain = (r.behavior & jnp.int32(Behavior.DRAIN_OVER_LIMIT)) != 0
    l_under = p64.le(li, q)
    rem_over = p64.select(drain, zero, residue)
    rem_last = p64.select(l_under, p64.sub(base, p64.mul(li, h)), rem_over)
    # i32 lanes through the select: Mosaic cannot lower selects between
    # bool vectors (see transition32's sel32 note).
    at_zero_last = jnp.where(
        divisible,
        p64.gt(li, q).astype(I32),
        (drain & p64.gt(li, p64.add(q, one))).astype(I32),
    ) != 0
    status_last = jnp.where(at_zero_last, OVER, new_s.status)

    zero_t = tf.zeros_like(r.slot)
    zero_f = (
        (p64.ge(q, one) & divisible & p64.ge(li, q))
        | (p64.gt(base, zero) & drain & p64.gt(li, q))
    )
    li_capped = p64.min_(li, q)
    # ``li`` float64 subtractions of ``h``, one after another, are each
    # exact while remaining_f < 2^53 (h is whole and the difference has
    # no more bits than remaining_f), so they equal this one.  Beyond
    # 2^53 upstream's own count is no longer exact.
    remf_last = tf.select(
        zero_f,
        zero_t,
        b64.to_triple(
            b64.sub(f0, b64.from_pair(p64.mul(li_capped, h)))),
    )

    safe_limit = p64.select(p64.is_zero(r.limit), one, r.limit)
    rate_i = p64.div_floor_pos(
        p64.select(p64.is_neg(r.duration), zero, r.duration), safe_limit)

    folded = new_s._replace(
        remaining=p64.select(fold & is_tok, rem_last, new_s.remaining),
        status=jnp.where(fold & is_tok, status_last, new_s.status),
        remaining_f=tf.select(
            fold & ~is_tok, remf_last, new_s.remaining_f),
    )
    head = MergedHead(
        base=base, q=q, rate_i=rate_i, s0=new_s.status,
        expire=new_s.expire_at,
    )
    return folded, head


def _expand_members(head6, base, q, rate_i, s0, expire, h, limit,
                    created, algorithm, behavior, rank) -> tuple:
    """The follower-response derivation shared by both expansion layouts
    (engine._merged_formulas response rules): ``head6`` is the head's own
    compact response (taken verbatim at rank 0), the rest are the head
    fold outputs / uniform request params broadcast per member."""
    OVER = jnp.int32(Status.OVER_LIMIT)
    UNDER = jnp.int32(Status.UNDER_LIMIT)
    zero = p64.const(0, rank)
    one = p64.const(1, rank)
    is_tok = algorithm == jnp.int32(Algorithm.TOKEN_BUCKET)
    drain = (behavior & jnp.int32(Behavior.DRAIN_OVER_LIMIT)) != 0
    h = p64.select(p64.gt(h, zero), h, one)

    i = p64.from_i32(rank)
    under = p64.le(i, q)
    residue = p64.sub(base, p64.mul(q, h))
    rem_over = p64.select(drain, zero, residue)
    rem_resp = p64.select(under, p64.sub(base, p64.mul(i, h)), rem_over)
    status = jnp.where(under, jnp.where(is_tok, s0, UNDER), OVER)
    over = ~under
    reset_rem = p64.select(
        under,
        rem_resp,
        p64.select(drain & p64.gt(i, p64.add(q, one)), zero, residue),
    )
    leaky_reset = p64.add(
        created, p64.mul(p64.sub(limit, reset_rem), rate_i))
    reset = p64.select(is_tok, expire, leaky_reset)

    is_head = rank == 0
    return (
        jnp.where(is_head, head6[0], status),
        jnp.where(is_head, head6[1], over.astype(I32)),
        jnp.where(is_head, head6[2], rem_resp.lo),
        jnp.where(is_head, head6[3], rem_resp.hi),
        jnp.where(is_head, head6[4], reset.lo),
        jnp.where(is_head, head6[5], reset.hi),
    )


def expand32_rows(
    mh_rows: tuple,        # 15 (U,) rows of the merged-program output
    mhead: jnp.ndarray,    # (19, U) head request matrix (uniform params)
    uidx: jnp.ndarray,     # (B,) i32 → head column of each member
    rank: jnp.ndarray,     # (B,) i32 rank within the duplicate group
) -> tuple:
    """Per-member responses for a grouped tick → the six compact rows,
    unstacked (see _expand_members).  rank-0 members take the head's own
    response verbatim; padding members (uidx pointing at a padded head
    column) produce unspecified values, exactly like the plain tick's
    padding lanes.  Rows stay unstacked so chained callers on the CPU
    backend avoid the concatenate-fusion pathology
    (tick32.make_tick32_rows_fn)."""
    from gubernator_tpu.ops.engine import REQ32_INDEX

    g = [row[uidx] for row in mh_rows]   # 15 (B,) head rows per member
    req = mhead[:, uidx]                 # (19, B)

    def rpair(name):
        k = REQ32_INDEX[name]
        return I64(req[k], req[k + 1])

    return _expand_members(
        g[:6],
        base=I64(g[6], g[7]), q=I64(g[8], g[9]),
        rate_i=I64(g[10], g[11]), s0=g[12], expire=I64(g[13], g[14]),
        h=rpair("hits"), limit=rpair("limit"),
        created=rpair("created_at"),
        algorithm=req[REQ32_INDEX["algorithm"]],
        behavior=req[REQ32_INDEX["behavior"]],
        rank=rank,
    )


# Row order of the row-major merged output (fused kernel): compact resp,
# MergedHead extras, then the (uniform) request params the expansion
# needs — one 96 B row gather per member instead of 15+ lane gathers.
MERGED24_ROWS = 24  # 23 used + 1 spare (matches the kernel's TW transpose)


def merged24_rows(resp: PResp, head: MergedHead, r: PReq) -> tuple:
    """The 23 used rows of the row-major merged output, in order."""
    return (
        resp.status,
        resp.over_limit.astype(I32),
        resp.remaining.lo, resp.remaining.hi,
        resp.reset_time.lo, resp.reset_time.hi,
        head.base.lo, head.base.hi,
        head.q.lo, head.q.hi,
        head.rate_i.lo, head.rate_i.hi,
        head.s0,
        head.expire.lo, head.expire.hi,
        r.hits.lo, r.hits.hi,
        r.limit.lo, r.limit.hi,
        r.created_at.lo, r.created_at.hi,
        r.algorithm,
        r.behavior,
    )


def expand32_rowmajor(resp24: jnp.ndarray, uidx: jnp.ndarray,
                      rank: jnp.ndarray) -> tuple:
    """Per-member responses from the row-major (U, 24) merged output →
    six compact rows, unstacked (see _expand_members).  One whole-row
    gather per member — the TPU-fast layout (chained-differential probe:
    95 µs vs 3.6 ms for 32K members against lane-dimension gathers)."""
    g = resp24[uidx]                     # (B, 24)

    def cpair(k):
        return I64(g[:, k], g[:, k + 1])

    return _expand_members(
        tuple(g[:, k] for k in range(6)),
        base=cpair(6), q=cpair(8), rate_i=cpair(10), s0=g[:, 12],
        expire=cpair(13), h=cpair(15), limit=cpair(17),
        created=cpair(19), algorithm=g[:, 21], behavior=g[:, 22],
        rank=rank,
    )
