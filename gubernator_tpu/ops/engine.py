"""Tick-batched rate-limit engine: the TPU replacement for the worker pool.

The reference shards its key space over N single-goroutine workers with
private cache shards and routes each request through channels
(``workers.go:19-37,125-147``).  Here the whole table is one device-resident
struct-of-arrays (:class:`gubernator_tpu.ops.buckets.BucketState`) and a
*tick* applies an entire batch of requests in one fused XLA program:

    gather slots → branch-free transition → scatter back

**Sequential semantics for duplicate keys.**  Go serializes same-key requests
via worker ownership; a batch may contain several hits on one key and each
must observe the state left by the previous one.  We reproduce this exactly:
requests are ranked by arrival order *within* their slot (a stable sort by
slot + a segmented iota), and a ``lax.while_loop`` applies one "rank round"
at a time — round *k* touches at most one request per slot, so gathers and
scatters never conflict.  Batches with all-unique keys run exactly one round.

**Host/device split.**  The host owns the key→slot mapping (strings never
reach the device), stamps wall-clock time, resolves Gregorian calendar math,
and reclaims slots (TTL first, then LRU by last-touched tick — mirroring the
expired-on-read eviction + evict-oldest of ``lrucache.go:88-149``).  The
device owns all bucket arithmetic.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional, Sequence

import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from gubernator_tpu.ops.buckets import (
    BucketState,
    ReqBatch,
    RespBatch,
    bucket_transition,
    gather_state,
    np_logical,
    scatter_state,
)
from gubernator_tpu.ops import rowtable
from gubernator_tpu.ops.reqcols import CREATED_UNSET, ReqColumns, compact_blob
from gubernator_tpu.ops.rowtable import RowState
from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    GlobalUpdate,
    RateLimitRequest,
    RateLimitResponse,
    Status,
    has_behavior,
)
from gubernator_tpu.utils import flightrec, timeutil
from gubernator_tpu.utils.hotpath import hot_path
from gubernator_tpu.utils import sanitize


# Table storage layouts (see rowtable.py for the row design rationale):
#   "columns" — tuple-of-int32-columns SoA; XLA gathers/scatters.  The
#               CPU/mesh default, and the fallback for huge tables.
#   "row"     — (capacity+1, 128)-word rows moved by Pallas per-row DMA.
#               ~6-8x faster ticks on TPU, 512 B/slot.
ROW_LAYOUT_MAX_BYTES = 6 << 30  # beyond this, fall back to columns


def make_layout_choice(layout: str, capacity: int, device,
                       max_batch: int = 0) -> str:
    """Resolve an engine ``table_layout`` setting ("auto"/"row"/"columns").

    ``max_batch`` participates because the row kernels stage the whole
    request block in VMEM (512 B/row): widths past 64k rows don't fit
    alongside the double-buffered pipeline, so auto falls back."""
    if layout == "auto":
        row_bytes = (capacity + 1) * rowtable.ROW_W * 4
        return (
            "row"
            if device.platform == "tpu"
            and row_bytes <= ROW_LAYOUT_MAX_BYTES
            and pad_pow2(max_batch or 1) <= EVICT_CHUNK
            else "columns"
        )
    if layout not in ("row", "columns"):
        raise ValueError(f"unknown table layout {layout!r}")
    return layout


def _layout_ops(layout: str):
    """(zeros, gather, scatter) for a storage layout."""
    if layout == "row":
        return (
            RowState.zeros,
            rowtable.row_gather_state,
            rowtable.row_scatter_state,
        )
    return (
        BucketState.zeros,
        gather_state,
        scatter_state,
    )


def _slot_segments(slot: jnp.ndarray, valid: jnp.ndarray, capacity: int):
    """Per-request segment info for requests sharing a slot.

    Stable-sorts by slot (invalid rows pushed past ``capacity``), computes a
    segmented iota over equal-slot runs, and scatters everything back to
    request order.  O(B log B), no table-sized buffers.  Returns
    ``(rank, group_size, head_idx, seg_id)``: arrival rank within the slot
    group, the group's member count, the original index of the group's
    first request, and a dense segment id usable as a B-bounded scatter
    target for segmented reductions.
    """
    # int32 sort key: capacity < 2^31 always (slots are i32); a 64-bit
    # key doubles the on-device sort cost for nothing.
    sort_key = jnp.where(valid, slot, capacity).astype(jnp.int32)
    order = jnp.argsort(sort_key, stable=True)
    return _segments_from_sorted(sort_key[order], order)


def _segments_from_sorted(sorted_key: jnp.ndarray, order: jnp.ndarray):
    """Segment info from an already-sorted key column (see
    :func:`_slot_segments`; the tick sorts once for duplicate detection
    and reuses the result here)."""
    b = sorted_key.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_key[1:] != sorted_key[:-1]]
    )
    seg_start = lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    rank_sorted = idx - seg_start
    seg_id_sorted = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    sizes = jnp.zeros(b, jnp.int32).at[seg_id_sorted].add(1)
    inv = jnp.zeros(b, jnp.int32).at[order].set(idx)  # request → sorted pos
    rank = rank_sorted[inv]
    seg_id = seg_id_sorted[inv]
    group_size = sizes[seg_id]
    head_idx = order[seg_start][inv]
    return rank, group_size, head_idx, seg_id


def _rank_within_slot(slot: jnp.ndarray, valid: jnp.ndarray, capacity: int):
    """Arrival rank of each request among requests sharing its slot."""
    return _slot_segments(slot, valid, capacity)[0]


def pad_pow2(n: int) -> int:
    """Next power of two ≥ n: variable-width scatter batches (install/evict)
    quantize to a few shapes so jit doesn't recompile per width."""
    return 1 << max(0, (int(n) - 1)).bit_length()


# Row layout of the packed request matrix (one H2D transfer per tick instead
# of 12 — per-transfer latency dominates small ticks).
REQ_ROWS = (
    "slot", "known", "hits", "limit", "duration", "algorithm", "behavior",
    "created_at", "burst", "greg_exp", "greg_dur", "valid",
)
REQ_ROW_INDEX = {name: i for i, name in enumerate(REQ_ROWS)}


def pack_request_matrix(
    m: np.ndarray,
    sel,
    requests,
    slots,
    known,
    now: int,
    *,
    nodes=None,
    behav=None,
    greg=None,
) -> None:
    """Vectorized fill of the packed LEGACY int64 request matrix: one
    attribute pass over ``requests`` plus one fancy-indexed numpy write
    per row.  Remaining user: the GLOBAL mesh engine (global_mesh.py) —
    the single-chip and sharded tick engines moved to the compact int32
    wire format (:func:`pack_request_matrix32` / REQ32 layout).

    ``m`` is (len(REQ_ROWS), B), or (N, len(REQ_ROWS), B) with ``nodes``
    giving the leading-axis index per request.  ``behav`` optionally
    passes precomputed int behaviors (IntFlag conversion is a measured
    host hotspot).  ``greg`` is (greg_exp, greg_dur) per request, or None
    when the caller already wrote those rows."""
    if len(requests) == 0:
        return
    R = REQ_ROW_INDEX

    def put(row, vals):
        if nodes is None:
            m[R[row], sel] = vals
        else:
            m[nodes, R[row], sel] = vals

    if behav is None:
        behav = [int(r.behavior) for r in requests]
    hits, limit, duration, algo, created, burst = zip(*(
        (r.hits, r.limit, r.duration, int(r.algorithm),
         r.created_at if r.created_at is not None else now, r.burst)
        for r in requests
    ))
    put("slot", slots)
    put("known", known)
    put("hits", hits)
    put("limit", limit)
    put("duration", duration)
    put("algorithm", algo)
    put("behavior", behav)
    put("created_at", created)
    put("burst", burst)
    if greg is not None:
        put("greg_exp", greg[0])
        put("greg_dur", greg[1])
    put("valid", 1)


def resolve_gregorian(r: "RateLimitRequest", now: int) -> tuple[int, int]:
    """Host-side Gregorian resolution for one request: (greg_exp, greg_dur).

    Returns (0, 0) when DURATION_IS_GREGORIAN is unset; raises
    :class:`gubernator_tpu.utils.timeutil.GregorianError` on a bad selector
    (callers surface it in the per-item ``error`` field, the reference's
    error-in-item convention, gubernator.go:208-216).
    """
    if not has_behavior(r.behavior, Behavior.DURATION_IS_GREGORIAN):
        return 0, 0
    return (
        timeutil.gregorian_expiration(now, r.duration),
        timeutil.gregorian_duration(now, r.duration),
    )


def unpack_reqs(packed: jnp.ndarray) -> ReqBatch:
    """(12, B) int64 matrix → ReqBatch (device-side, inside jit)."""
    f = dict(zip(REQ_ROWS, packed))
    return ReqBatch(
        slot=f["slot"].astype(jnp.int32),
        known=f["known"].astype(jnp.bool_),
        hits=f["hits"],
        limit=f["limit"],
        duration=f["duration"],
        algorithm=f["algorithm"].astype(jnp.int32),
        behavior=f["behavior"].astype(jnp.int32),
        created_at=f["created_at"],
        burst=f["burst"],
        greg_exp=f["greg_exp"],
        greg_dur=f["greg_dur"],
        valid=f["valid"].astype(jnp.bool_),
    )


# Compact int32 request wire format: narrow fields ride one i32 row each,
# 8-byte fields ride (lo, hi) i32 pairs — 76 B/request over the link
# instead of the legacy int64 matrix's 96 (the engine's H2D is a top cost
# both over remote links and on PCIe hosts at high tick rates).
REQ32_NARROW = ("slot", "known", "algorithm", "behavior", "valid")
REQ32_WIDE = (
    "hits", "limit", "duration", "created_at", "burst",
    "greg_exp", "greg_dur",
)
REQ32_INDEX = {name: i for i, name in enumerate(REQ32_NARROW)}
for _j, _name in enumerate(REQ32_WIDE):
    REQ32_INDEX[_name] = len(REQ32_NARROW) + 2 * _j  # the lo row; hi = +1
REQ32_ROWS = len(REQ32_NARROW) + 2 * len(REQ32_WIDE)  # 19
# One window, one upload (TickEngine.submit_columns): the tick's ``now``
# crosses to the device inside the buffer that is uploaded anyway, as
# the wide encoding's (lo, hi) pair (:func:`stamp_now`).  A unique or
# sequential window uploads its staging slab, the REQ32 rows and one row
# more whose first two words are ``now``; a grouped window uploads its
# plan, with the two words at the end (:func:`plan_views`).
SLAB_ROWS = REQ32_ROWS + 1


def split_i64(v: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64 → (lo, hi) int32 pair — THE host-side definition of the
    compact wire format's wide encoding (device inverse:
    unpack_reqs_compact; host inverse: join_i32_pair)."""
    return (
        (v & 0xFFFFFFFF).astype(np.uint32).view(np.int32),
        (v >> 32).astype(np.int32),
    )


@hot_path
def stamp_now(words: np.ndarray, now: int) -> None:
    """Write the tick's ``now`` into ``words[0:2]`` as :func:`split_i64`
    would (lo, hi), in plain int arithmetic: two stores a window."""
    lo = now & 0xFFFFFFFF
    words[0] = lo - ((lo & 0x80000000) << 1)
    words[1] = now >> 32


@hot_path
def pack_wide_rows(m32: np.ndarray, name: str, values, ix) -> None:
    """Host-side write of an int64 column as its (lo, hi) i32 pair.
    Runs per tick on the dispatch thread (every @hot_path packer funnels
    through it) — marked so G001 visits it directly."""
    # guber: allow-G001(host-side wire packing - values is a host list or np column, asarray is the cheap staging copy, never a device sync)
    lo, hi = split_i64(np.asarray(values, np.int64))
    r = REQ32_INDEX[name]
    m32[r, ix] = lo
    m32[r + 1, ix] = hi


def pack_request_matrix32(
    m32: np.ndarray,
    sel,
    requests,
    slots,
    known,
    now: int,
    *,
    nodes=None,
    greg=None,
) -> None:
    """Compact-format counterpart of :func:`pack_request_matrix`: fill a
    (REQ32_ROWS, B) — or (N, REQ32_ROWS, B) with ``nodes`` — int32 matrix
    from request objects.  One attribute pass + one vectorized write per
    row (wide fields as lo/hi pairs)."""
    if len(requests) == 0:
        return
    R = REQ32_INDEX

    def put(row, vals):
        if nodes is None:
            m32[R[row], sel] = vals
        else:
            m32[nodes, R[row], sel] = vals

    def put_wide(name, vals):
        if nodes is None:
            pack_wide_rows(m32, name, vals, sel)
            return
        v = np.asarray(vals, np.int64)
        lo, hi = split_i64(v)
        r = REQ32_INDEX[name]
        m32[nodes, r, sel] = lo
        m32[nodes, r + 1, sel] = hi

    behav, hits, limit, duration, algo, created, burst = zip(*(
        (int(r.behavior), r.hits, r.limit, r.duration, int(r.algorithm),
         r.created_at if r.created_at is not None else now, r.burst)
        for r in requests
    ))
    put("slot", slots)
    put("known", known)
    put("algorithm", algo)
    put("behavior", behav)
    put("valid", 1)
    put_wide("hits", hits)
    put_wide("limit", limit)
    put_wide("duration", duration)
    put_wide("created_at", created)
    put_wide("burst", burst)
    if greg is not None:
        put_wide("greg_exp", greg[0])
        put_wide("greg_dur", greg[1])


@hot_path
def pack_cols_req32(m32: np.ndarray, cols, slots, known, now: int, ix) -> None:
    """Shard-aware columnar REQ32 fill: write one resolved batch's
    request columns into a staging slab — the ONE definition of how a
    ``ReqColumns`` batch becomes compact wire rows, shared by the
    single-chip engine (``TickEngine._build_cols``) and the sharded
    mesh engine's flat routed packer.

    ``ix`` selects the packed lanes (a slice for the contiguous
    no-error batch, a fancy index when shed/error rows are skipped).
    ``slots`` may be LOCAL (single-chip) or GLOBAL (mesh-routed) — the
    packer doesn't care, which is what makes it shard-aware: ownership
    is a property of the slot value, not of the wire format."""
    R = REQ32_INDEX
    m32[R["slot"], ix] = slots
    m32[R["known"], ix] = known
    m32[R["algorithm"], ix] = cols.algorithm[ix]
    m32[R["behavior"], ix] = cols.behavior[ix]
    m32[R["valid"], ix] = 1
    pack_wide_rows(m32, "hits", cols.hits[ix], ix)
    pack_wide_rows(m32, "limit", cols.limit[ix], ix)
    pack_wide_rows(m32, "duration", cols.duration[ix], ix)
    ca = cols.created_at[ix]
    pack_wide_rows(
        m32, "created_at", np.where(ca != CREATED_UNSET, ca, now), ix
    )
    pack_wide_rows(m32, "burst", cols.burst[ix], ix)


def sort_packed_by_slot(m32: np.ndarray, n: int, capacity: int):
    """Stable in-place sort of a packed REQ32 batch's live lanes by the
    slot row (same-slot requests keep arrival order — the duplicate-
    sequencing contract) and duplicate detection against ``capacity``'s
    padding sentinel.  Returns ``(inv, has_dups)``: the request→sorted-
    lane permutation (responses un-permute through it) and whether any
    live slot repeats (routes the batch to the merge-capable program)."""
    R = REQ32_INDEX
    order = np.argsort(m32[R["slot"], :n], kind="stable")
    m32[:, :n] = m32[:, :n][:, order]
    inv = np.empty(n, np.int64)
    inv[order] = np.arange(n)
    sl = m32[R["slot"], :n]
    has_dups = bool(  # guber: allow-G001(m32 is host numpy, never device)
        ((sl[1:] == sl[:-1]) & (sl[1:] < capacity)).any()
    )
    return inv, has_dups


class StagingRing:
    """Reusable host staging slabs for async H2D request uploads — the
    double-buffered pipeline contract (docs/architecture.md)
    factored out of ``TickEngine`` so the sharded mesh engine shares one
    implementation: a slab recycles only once the tick handle that
    consumed it has resolved (until then jax may still read the host
    buffer for the in-flight copy), and when every slab is in flight
    the lease falls back to a fresh allocation rather than corrupting
    one.  Callers hold their engine lock around lease()/retire() (ring
    state is unsynchronized).

    ``width`` picks between the two slab regimes:

    * ``width=None`` (single-chip ``TickEngine``): slabs materialize
      lazily per leased width — the engine quantizes batch sizes to a
      small width ladder, so the dict stays a handful of entries.
    * ``width=B`` (sharded mesh engine): ONE ring of ``(rows, B)``
      slabs preallocated up front.  The ragged dispatch always leases
      the full batch capacity — extent offsets, not slab shape, carry
      the per-window size — so there is exactly one slab shape, one
      H2D signature, one traced program."""

    __slots__ = ("rows", "sentinel", "depth", "_stage", "_next", "_leased",
                 "metric_leases", "metric_fallback_allocs")

    def __init__(self, rows: int, sentinel: int, depth: int,
                 width: Optional[int] = None):
        self.rows = int(rows)
        self.sentinel = int(sentinel)
        self.depth = int(depth)
        self._stage: Dict[int, list] = {}   # width -> [[matrix, handle]]
        self._next: Dict[int, int] = {}
        if width is not None:
            w = int(width)
            self._stage[w] = [
                [np.empty((self.rows, w), np.int32), None]
                for _ in range(self.depth)
            ]
            self._next[w] = 0
        self._leased: Optional[list] = None
        # Plain-int telemetry (caller holds the engine lock): total
        # leases and how many missed the ring entirely (every slab
        # in flight → fresh allocation) — surfaced by /debug/state.
        self.metric_leases = 0
        self.metric_fallback_allocs = 0

    def lease(self, b: int, clean: bool = True) -> np.ndarray:
        """A zeroed (rows, b) slab with the slot row pre-set to the
        padding sentinel (padding lanes scatter out of bounds).
        ``clean=False`` hands the slab out as its last window left it:
        the caller's packer cleans it (:meth:`clean`, or the native
        window pass, which does it with the GIL released)."""
        ring = self._stage.get(b)
        if ring is None:
            ring = self._stage[b] = [
                [np.empty((self.rows, b), np.int32), None]
                for _ in range(self.depth)
            ]
            self._next[b] = 0
        slot = None
        start = self._next[b]
        for k in range(len(ring)):
            cand = ring[(start + k) % len(ring)]
            h = cand[1]
            if h is None or h._done is not None:
                slot = cand
                self._next[b] = (start + k + 1) % len(ring)
                break
        self.metric_leases += 1
        if slot is None:
            # Every slab still feeds an unresolved window (caller is
            # pipelining deeper than the ring): plain allocation.
            m = np.empty((self.rows, b), np.int32)
            self._leased = None
            self.metric_fallback_allocs += 1
        else:
            slot[1] = None
            m = slot[0]
            self._leased = slot
        if clean:
            self.clean(m)
        return m

    def clean(self, m: np.ndarray) -> None:
        """Zero a slab and aim every lane at the padding sentinel."""
        m.fill(0)
        m[REQ32_INDEX["slot"]] = self.sentinel

    def telemetry(self) -> dict:
        """Snapshot for /debug/state: ring shape, per-width slab counts
        and how many slabs are currently bound to unresolved handles."""
        widths = {}
        for w, ring in self._stage.items():
            in_flight = sum(
                1 for _, h in ring if h is not None and h._done is None
            )
            widths[int(w)] = {"slabs": len(ring), "in_flight": in_flight}
        return {
            "depth": self.depth,
            "leases": self.metric_leases,
            "fallback_allocs": self.metric_fallback_allocs,
            "widths": widths,
        }

    def retire(self, handle) -> None:
        """Bind the most recent lease to the tick handle consuming it
        (the slab recycles when that handle resolves); ``None`` frees
        the slab immediately — the dispatch never uploaded it."""
        if self._leased is not None:
            self._leased[1] = handle
            self._leased = None


@hot_path
def join_i32_pair(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Host-side (lo, hi) int32 pair → int64 (the compact wire format's
    inverse; two's complement preserved for negatives).  Per-tick on the
    dispatch thread (group/layer plan builds) — G001 visits it directly."""
    return (
        # guber: allow-G001(host-side wire unpacking - inputs are host i32 rows, asarray is a view, never a device sync)
        (np.asarray(hi).astype(np.int64) << 32)
        # guber: allow-G001(host-side wire unpacking - same as the hi row above)
        | np.asarray(lo).astype(np.uint32).astype(np.int64)
    )


def unpack_reqs_compact(m32: jnp.ndarray) -> ReqBatch:
    """(19, B) int32 matrix → ReqBatch (device-side, inside jit)."""

    def wide(name):
        r = REQ32_INDEX[name]
        lo = m32[r].astype(jnp.uint32).astype(jnp.int64)
        return (m32[r + 1].astype(jnp.int64) << 32) | lo

    return ReqBatch(
        slot=m32[REQ32_INDEX["slot"]],
        known=m32[REQ32_INDEX["known"]].astype(jnp.bool_),
        hits=wide("hits"),
        limit=wide("limit"),
        duration=wide("duration"),
        algorithm=m32[REQ32_INDEX["algorithm"]],
        behavior=m32[REQ32_INDEX["behavior"]],
        created_at=wide("created_at"),
        burst=wide("burst"),
        greg_exp=wide("greg_exp"),
        greg_dur=wide("greg_dur"),
        valid=m32[REQ32_INDEX["valid"]].astype(jnp.bool_),
    )


def pack_resp(resp: RespBatch) -> jnp.ndarray:
    """RespBatch → (5, B) int64 matrix (one D2H transfer)."""
    return jnp.stack(
        [
            resp.status.astype(jnp.int64),
            resp.limit,
            resp.remaining,
            resp.reset_time,
            resp.over_limit.astype(jnp.int64),
        ]
    )


def pack_resp_compact(resp: RespBatch) -> jnp.ndarray:
    """RespBatch → (6, B) **int32** matrix: status, over_limit, and the
    lo/hi halves of remaining and reset_time.

    The response ``limit`` is always an echo of the request's limit
    (reference algorithms.go returns rl.Limit after the limit-delta rules
    update stored state to it), so the host reconstructs it from the
    request columns instead of shipping 8 more bytes per decision — 24
    B/decision instead of 40 over the link (TickHandle._finish rebuilds
    the public (5, B) int64 contract)."""

    def split(v):
        return (
            (v & jnp.int64(0xFFFFFFFF)).astype(jnp.int32),
            (v >> 32).astype(jnp.int32),
        )

    rl, rh = split(resp.remaining)
    tl, th = split(resp.reset_time)
    return jnp.stack(
        [resp.status, resp.over_limit.astype(jnp.int32), rl, rh, tl, th]
    )


def unpack_resp_compact(raw: np.ndarray, limit_req: np.ndarray) -> np.ndarray:
    """Host inverse of :func:`pack_resp_compact`: (6, n) int32 in request
    order + the request-order limit column → the (5, n) int64 response
    matrix.  Values at per-item-error indices are unspecified (callers
    overwrite those with error responses)."""
    n = raw.shape[1]
    out = np.empty((5, n), np.int64)
    out[0] = raw[0]
    out[1] = limit_req[:n]
    out[2] = join_i32_pair(raw[2], raw[3])
    out[3] = join_i32_pair(raw[4], raw[5])
    out[4] = raw[1]
    return out


def group_upad(b: int, u: int = 0) -> int:
    """The grouped plan's quantized head width for a batch width ``b``:
    hard floor at max(256, b/4) so serving traffic compiles a handful of
    (Upad, B) merged/expansion programs, not one per traffic shape."""
    return pad_pow2(max(u, 256, b // 4))


def grouped_warm_shapes(widths: tuple, deep: bool) -> list:
    """The (batch width, head width) shapes of the grouped program that
    ``TickEngine._warmup`` compiles: every batch width at its floor, and
    with ``deep`` (a serving chip) at every deeper head width
    ``group_upad`` can give it too.  A shape first met under traffic is
    traced and lowered on the dispatch thread, compile cache or not:
    seconds in which nothing is answered, at no fixed time (a window of
    one call that is mostly one hot key plans to another head width
    than its neighbours', and can first come minutes in)."""
    shapes = []
    for w in widths:
        upad = group_upad(w)
        shapes.append((w, upad))
        while deep and upad < w:
            upad *= 2
            shapes.append((w, upad))
    return shapes


def plan_views(buf, b: int):
    """A grouped window's one upload, cut into its parts: ``(mhead (19,
    upad), count (upad,), uidx (b,), rank (b,), now (2,))`` as views of
    the flat int32 ``buf`` laid out ``uidx[b] rank[b] count[upad]
    mhead[19][upad] now[2]`` — the native window pass's scratch
    (native/slotmap.cc guber_slotmap_pack_window) with ``now`` behind
    it.  THE definition of the layout for the numpy plan
    (:func:`build_group_plan`) and, on a traced ``buf``, for the device
    program (tick32.jitted_merged_pipeline); ``upad`` follows from the
    length, which :func:`plan_words` gives."""
    upad = (buf.shape[0] - 2 - 2 * b) // (REQ32_ROWS + 1)
    at = 2 * b + upad
    end = at + REQ32_ROWS * upad
    return (
        buf[at:end].reshape(REQ32_ROWS, upad), buf[2 * b:at],
        buf[:b], buf[b:2 * b], buf[end:],
    )


def plan_words(b: int, upad: int) -> int:
    """int32 words of a grouped window's upload (:func:`plan_views`)."""
    return 2 * b + (REQ32_ROWS + 1) * upad + 2


def _param_rows_equal_prev(m: np.ndarray, nl: int) -> np.ndarray:
    """(nl,) bool: row i carries identical request parameters to row
    i-1 (the 17 REQ32 parameter rows both duplicate planners fold on —
    ONE definition so the grouped and layered plans can never disagree
    on unit boundaries)."""
    R = REQ32_INDEX
    rows = (
        R["algorithm"], R["behavior"],
        R["hits"], R["hits"] + 1,
        R["limit"], R["limit"] + 1,
        R["duration"], R["duration"] + 1,
        R["created_at"], R["created_at"] + 1,
        R["burst"], R["burst"] + 1,
        R["greg_exp"], R["greg_exp"] + 1,
        R["greg_dur"], R["greg_dur"] + 1,
    )
    eq = np.ones(nl, bool)
    for r in rows:
        eq[1:] &= m[r, 1:nl] == m[r, : nl - 1]
    return eq


def build_group_plan(m: np.ndarray, n: int, capacity: int, now: int,
                     min_dup_frac: float = 1 / 8):
    """Host-side grouped-tick plan for a slot-sorted compact batch (the
    BASELINE north star's hot-key scatter-add): duplicate groups collapse
    to one device row each when every follower is identical to its head,
    known, hits > 0, free of RESET_REMAINING / Gregorian behaviors, and
    under a head that provably comes out alive — the same eligibility the
    device-side fold uses (:func:`_apply_merged_followers` ``ok``).
    Returns ``(mhead (19, Upad), count (Upad,), uidx (B,), rank (B,),
    u, buf)`` — ``u`` the live head count, ``buf`` the one flat buffer
    the four arrays are views of and the window uploads, ``now`` in its
    last two words (:func:`plan_views`) — or None when any group is
    ineligible (those batches keep the sequential rank-round program,
    whose per-unit rounds handle mixed groups) or when fewer than
    ``min_dup_frac`` of the live rows are followers: a near-unique batch
    saves almost no device rows while the grouped path's (U, 24) head
    block costs ~4x the compact response's D2H bytes, so shallow
    duplication stays on the sequential program.  The savings check runs
    before the O(n·rows) eligibility sweep and the plan allocations.

    ``uidx``/``rank`` address the expansion program
    (transition32.expand32_rows): member i's response derives from head column
    ``uidx[i]`` at rank ``rank[i]``.  Error lanes keep their real group
    head (they share its slot run) and lanes past ``n`` point at column
    ``upad - 1`` — which aliases the last real head when ``u == upad``.
    Both are harmless: their response values are unspecified and are
    sliced/masked downstream exactly like the plain tick's padding
    lanes."""
    R = REQ32_INDEX
    b = m.shape[1]
    s = m[R["slot"], :n]
    live = s < capacity
    if n == 0 or not live.any():
        return None
    is_start = np.empty(n, bool)
    is_start[0] = True
    np.not_equal(s[1:], s[:-1], out=is_start[1:])
    # Row savings count LIVE followers only: error/padding lanes share
    # slot == capacity and would otherwise masquerade as one huge
    # "duplicate group".
    dup_rows = int(np.count_nonzero(~is_start & live))
    if dup_rows < max(1, int(min_dup_frac * int(np.count_nonzero(live)))):
        return None
    starts = np.flatnonzero(is_start)
    gid = np.cumsum(is_start) - 1
    rank = np.arange(n, dtype=np.int32) - starts[gid].astype(np.int32)

    eq_prev = _param_rows_equal_prev(m, n)
    hits_pos = join_i32_pair(m[R["hits"], :n], m[R["hits"] + 1, :n]) > 0
    known = m[R["known"], :n] != 0
    no_merge = int(Behavior.RESET_REMAINING | Behavior.DURATION_IS_GREGORIAN)
    beh_ok = (m[R["behavior"], :n] & no_merge) == 0
    # The fold requires the head row to come out ALIVE (post-transition
    # expire_at >= now) — a dead head sends the x64 path's followers to
    # fresh-install rank rounds, which the closed form cannot express.
    # duration > 0 plus created_at >= now guarantees it for every
    # reachable head branch (new: expire = created+duration > now;
    # exists: expire_cand > created >= now); groups that fail (negative
    # durations, client-backdated duplicates) keep the sequential
    # program.
    dur = join_i32_pair(m[R["duration"], :n], m[R["duration"] + 1, :n])
    created = join_i32_pair(
        m[R["created_at"], :n], m[R["created_at"] + 1, :n])
    alive_ok = (dur > 0) & (created >= now)
    # Closed-form folds exist only for token/leaky; zoo duplicates
    # (algorithm >= 2) keep the sequential program's size-1 units.
    alg_ok = m[R["algorithm"], :n] <= int(Algorithm.LEAKY_BUCKET)
    follower = ~is_start & live
    if np.any(follower
              & ~(eq_prev & known & hits_pos & beh_ok & alive_ok & alg_ok)):
        return None

    u = len(starts)
    upad = group_upad(b, u)
    # Fresh every window: it is uploaded asynchronously, and jax may
    # read it until the copy is done.
    buf = np.empty(plan_words(b, upad), np.int32)
    mhead, count, uidx, rank_b, now_words = plan_views(buf, b)
    mhead[:, :u] = m[:REQ32_ROWS, starts]
    mhead[:, u:] = 0
    mhead[R["slot"], u:] = capacity  # padding heads aim at the guard row
    count[:u] = np.diff(np.append(starts, n))
    count[u:] = 1
    uidx[:n] = gid
    uidx[n:] = upad - 1
    rank_b[:n] = rank
    rank_b[n:] = 0
    stamp_now(now_words, now)
    return mhead, count, uidx, rank_b, u, buf


def build_layer_plan(m: np.ndarray, n: int, capacity: int, now: int,
                     layer_width: int = 512, max_layers: int = 32,
                     min_dup_frac: float = 1 / 8):
    """Host-side UNIT-LAYER plan for mixed/ineligible duplicate batches —
    the general case :func:`build_group_plan` declines (groups broken by
    RESET/parameter-change/query rows).

    A *unit* is a maximal run of identical fold-eligible duplicates
    (the same definition the sequential program uses,
    :func:`_sorted_merge_plan`); layer ``k`` collects the k-th unit of
    every slot segment.  Each layer then ticks through the NARROW merged
    program (one head row + count per unit, closed-form fold), chained
    through the table — layer k+1's gather sees layer k's scatter — and
    a single elementwise expansion maps every member's response from its
    unit's journal row.  Cost: K narrow ticks instead of one full
    gather/scatter round per unit, where K = max units per segment.

    Returns ``(mh0 (19, W0), cnt0 (W0,), mhk (K-1, 19, LW), cntk
    (K-1, LW), uidx (B,), rank (B,), k_pad)`` or None when the batch is
    ineligible: a count>1 unit whose head is not provably alive
    (build_group_plan's alive_ok argument), more than ``max_layers``
    units on one segment, a non-first layer wider than ``layer_width``
    (adversarial shapes keep the sequential program, which is always
    correct), or fewer than ``min_dup_frac`` of the live rows being
    duplicates — a near-unique batch gains nothing here, and sending it
    through would compile wide (w0 ≈ B) layered shapes that warmup
    never prepared (the sequential program those batches keep IS
    warmed).  ``uidx`` addresses the flattened journal (layer-0 block
    first, then the K-1 narrow blocks); padding/error lanes are left at
    position 0 — a real unit's journal row — and their response values
    are unspecified, masked/sliced downstream exactly like the plain
    tick's padding lanes."""
    R = REQ32_INDEX
    b = m.shape[1]
    s = m[R["slot"], :n]
    live = s < capacity
    nl = int(np.count_nonzero(live))
    if nl == 0:
        return None
    # Error rows carry slot == capacity and sort to the tail: the live
    # prefix is contiguous.
    s = s[:nl]
    is_start = np.empty(nl, bool)
    is_start[0] = True
    np.not_equal(s[1:], s[:-1], out=is_start[1:])
    dup_rows = int(np.count_nonzero(~is_start))
    if dup_rows < max(1, int(min_dup_frac * nl)):
        return None

    eq_prev = _param_rows_equal_prev(m, nl)
    NO_MERGE = int(Behavior.RESET_REMAINING | Behavior.DURATION_IS_GREGORIAN)
    hits_pos = join_i32_pair(m[R["hits"], :nl], m[R["hits"] + 1, :nl]) > 0
    ok = (
        (is_start | eq_prev)
        & hits_pos
        & ((m[R["behavior"], :nl] & NO_MERGE) == 0)
        & ((m[R["known"], :nl] != 0) | is_start)
        # zoo lanes have no closed-form fold: size-1 units only
        & (m[R["algorithm"], :nl] <= int(Algorithm.LEAKY_BUCKET))
    )
    unit_start = is_start | ~ok
    heads = np.flatnonzero(unit_start)
    u = len(heads)
    sizes = np.diff(np.append(heads, nl)).astype(np.int32)

    # Unit ordinal within its segment.
    seg_of_unit = (np.cumsum(is_start) - 1)[heads]
    first_unit_of_seg = np.full(seg_of_unit[-1] + 1, u, np.int64)
    unit_idx = np.arange(u)
    np.minimum.at(first_unit_of_seg, seg_of_unit, unit_idx)
    ord_ = (unit_idx - first_unit_of_seg[seg_of_unit]).astype(np.int64)
    k_layers = int(ord_.max()) + 1
    if k_layers > max_layers:
        return None
    if k_layers > 1:
        wide = np.bincount(ord_[ord_ >= 1])
        if len(wide) and wide.max() > layer_width:
            return None
    # Fold-eligible heads (count>1) must come out alive: duration > 0
    # plus created_at >= now guarantees it on every reachable branch
    # (see build_group_plan's alive_ok derivation).
    multi = sizes > 1
    if multi.any():
        hr = heads[multi]
        dur = join_i32_pair(m[R["duration"], :nl][hr],
                            m[R["duration"] + 1, :nl][hr])
        created = join_i32_pair(m[R["created_at"], :nl][hr],
                                m[R["created_at"] + 1, :nl][hr])
        if not ((dur > 0) & (created >= now)).all():
            return None

    w0_n = int(np.count_nonzero(ord_ == 0))
    w0 = group_upad(b, w0_n)
    # Quantize the layer count so serving traffic compiles a handful of
    # shapes, padding with all-padding layers (slot=capacity heads).
    # Multiples of 4 past 4 (not pow2): each padding layer costs a real
    # narrow tick, and pow2 rounding at k=17 would run 15 dead layers.
    if k_layers <= 2:
        k_pad = 2
    elif k_layers <= 4:
        k_pad = 4
    else:
        k_pad = -(-k_layers // 4) * 4
    k_pad = min(k_pad, max_layers)

    def head_block(unit_sel, width):
        mh = np.zeros((REQ32_ROWS, width), np.int32)
        mh[R["slot"]] = capacity
        cnt = np.ones(width, np.int32)
        k = len(unit_sel)
        mh[:, :k] = m[:, :nl][:, heads[unit_sel]]
        cnt[:k] = sizes[unit_sel]
        return mh, cnt

    # Per-unit flat journal position, layer-0 block first.
    pos_of_unit = np.empty(u, np.int64)
    lay0 = np.flatnonzero(ord_ == 0)
    pos_of_unit[lay0] = np.arange(len(lay0))
    mh0, cnt0 = head_block(lay0, w0)
    mhk = np.zeros((k_pad - 1, REQ32_ROWS, layer_width), np.int32)
    mhk[:, R["slot"], :] = capacity
    cntk = np.ones((k_pad - 1, layer_width), np.int32)
    for k in range(1, k_layers):
        sel = np.flatnonzero(ord_ == k)
        pos_of_unit[sel] = w0 + (k - 1) * layer_width + np.arange(len(sel))
        mhk[k - 1], cntk[k - 1] = head_block(sel, layer_width)

    gid_unit = np.cumsum(unit_start) - 1        # row → unit
    uidx = np.zeros(b, np.int64)
    uidx[:nl] = pos_of_unit[gid_unit]
    rank = np.zeros(b, np.int32)
    rank[:nl] = np.arange(nl, dtype=np.int32) - heads[gid_unit].astype(np.int32)
    return (mh0, cnt0, mhk, cntk, uidx.astype(np.int32), rank,
            k_pad)


def masked_over_limit(resp_mat: np.ndarray, errors) -> int:
    """Over-limit count from a public (5, n) response matrix with the
    per-item-error lanes zeroed first — their values are unspecified in
    the device response (on the row layout they gather guard-row
    garbage; see unpack_resp_compact)."""
    over = resp_mat[4]
    if errors:
        over = over.copy()
        over[list(errors)] = 0
    return int(over.sum())


def _apply_merged_followers(
    new_g: BucketState,
    resp: RespBatch,
    reqs: ReqBatch,
    now: jnp.ndarray,
    rank: jnp.ndarray,
    group_size: jnp.ndarray,
    head_idx: jnp.ndarray,
    seg_id: jnp.ndarray,
):
    """Closed-form application of duplicate-key followers (token + leaky).

    Runs against ``new_g`` — the per-request rows of the heads' round-0
    transition output (``new_g[head_idx]`` is each request's post-head slot
    state), so the whole merge needs no table gather and no second scatter:
    the head's scatter row carries the group-final values.  For a slot
    group whose members are *identical* requests (hits>0, no
    RESET_REMAINING/Gregorian), the sequential fold the rank rounds would
    perform has a closed form in the member's rank ``i`` against the
    post-head state.  Let ``base`` be the post-head integer remaining —
    ``remaining`` for token buckets, ``trunc(remaining_f)`` for leaky
    (algorithms.go:383-387 works on the truncated value) — and
    ``q = base // h``:

        i <= q  → UNDER, remaining base - i·h
                  (token echoes stored status S0, leaky reports UNDER)
        i >  q  → OVER_LIMIT, remaining = drain ? 0 : base - q·h
                  (divisible base makes base - q·h == 0, unifying the
                  exact-remainder → at-zero and over-ask cases)

    matching algorithms.go:157-198 (token) and :389-430 (leaky) exactly:
    the ``i <= q`` steps are the dec/exact branches, ``i > q`` is over-ask
    until remaining hits zero and the already-at-zero branch afterwards.
    Leaky followers never drip: the head either advanced ``updated_at`` to
    ``created_at`` (follower elapsed = 0) or left it where a same-instant
    drip already truncated to zero tokens (algorithms.go:361-367), so the
    follower's drip is zero too.

    Stored token status only flips to OVER on an at-zero step
    (algorithms.go:162-169), first at rank ``q+1`` when h divides base, at
    ``q+2`` under DRAIN_OVER_LIMIT, never otherwise; leaky has no persisted
    status.  Leaky ``remaining_f`` keeps its fractional part through
    integer decrements but is *exactly zeroed* by an exact-remainder step
    (:392-397) or a drain step (:414-417).  The group-final state is
    evaluated at the last member's rank (``group_size - 1``) and written
    into the HEAD's scatter row; expire/created/duration are untouched
    (token hits never renew; leaky followers re-bump the same expiration
    the head wrote; a uniform group can't change limit or duration after
    its head).

    Returns ``(rows, resp, merged)``: the head rows of ``new_g`` with the
    group-final remaining/status/remaining_f folded in, per-request
    responses, and the follower rows handled here (excluded from the rank
    rounds).
    """
    b = reqs.slot.shape[0]
    NO_MERGE = jnp.int32(
        Behavior.RESET_REMAINING | Behavior.DURATION_IS_GREGORIAN
    )

    def hd(a):
        return a[head_idx]

    same_as_head = (
        (reqs.hits == hd(reqs.hits))
        & (reqs.limit == hd(reqs.limit))
        & (reqs.duration == hd(reqs.duration))
        & (reqs.behavior == hd(reqs.behavior))
        & (reqs.created_at == hd(reqs.created_at))
        & (reqs.burst == hd(reqs.burst))
        & (reqs.algorithm == hd(reqs.algorithm))
    )
    # Followers must take the exists path (known & in_use & now<=expire);
    # heads are exempt from the known check (their round-0 transition
    # handles the new-item case and leaves in_use set).
    ok = (
        reqs.valid
        & same_as_head
        & (reqs.hits > 0)
        & ((reqs.behavior & NO_MERGE) == 0)
        & (reqs.known | (rank == 0))
        # zoo lanes (algorithm >= 2) have no closed-form fold
        & (reqs.algorithm <= jnp.int32(Algorithm.LEAKY_BUCKET))
    )
    # A group merges only if every valid member is mergeable: one bad row
    # (different hits/limit/..., RESET, query) sends the whole group to the
    # rank rounds so cross-member interactions stay sequential.
    bad_per_seg = jnp.zeros(b, jnp.int32).at[seg_id].add(
        (reqs.valid & ~ok).astype(jnp.int32)
    )
    group_ok = bad_per_seg[seg_id] == 0

    # Post-head state of the group's slot, read straight from the heads'
    # transition output (identical to a table gather after the head
    # scatter, minus the gather).
    return _merged_formulas(
        new_g, resp, reqs, now, rank, group_size - 1,
        fold_mask=group_ok & ok & (rank > 0),
        head_mask=group_ok & ok & (rank == 0) & (group_size > 1),
        R0=hd(new_g.remaining), F0=hd(new_g.remaining_f),
        S0=hd(new_g.status), E=hd(new_g.expire_at),
    )


def _merged_formulas(new_g, resp, reqs, now, rank, last_rank, fold_mask,
                     head_mask, R0, F0, S0, E):
    """The closed-form follower fold shared by the gather-based (unsorted)
    group merge and the scan-based (sorted-input) unit merge; see
    :func:`_apply_merged_followers` for the math.  ``R0/F0/S0/E`` are the
    fold head's post-transition remaining/remaining_f/status/expire_at
    broadcast to every member; ``rank`` is the member's distance from
    that head, ``last_rank`` the distance of the fold window's last
    member.  ``fold_mask``/``head_mask`` select the members folding /
    the heads absorbing a window (both are further gated on the head
    state being alive here)."""
    TOKEN = jnp.int32(Algorithm.TOKEN_BUCKET)
    UNDER = jnp.int32(Status.UNDER_LIMIT)
    OVER = jnp.int32(Status.OVER_LIMIT)
    is_tok = reqs.algorithm == TOKEN
    N0 = F0.astype(jnp.int64)  # Go float64→int64 truncation
    alive = now <= E

    merged = fold_mask & alive

    h = jnp.where(reqs.hits > 0, reqs.hits, jnp.int64(1))  # div-safe
    i = rank.astype(jnp.int64)
    base = jnp.where(is_tok, R0, N0)
    q = base // h
    drain = (reqs.behavior & Behavior.DRAIN_OVER_LIMIT) != 0
    under = i <= q
    rem_over = jnp.where(drain, jnp.int64(0), base - q * h)
    rem_resp = jnp.where(under, base - i * h, rem_over)
    # Leaky reset_time tracks the would-be post-step remaining: the over-ask
    # branch reports it from the *pre*-step value, the at-zero rows that
    # follow a drain report zero (algorithms.go:400-430).
    safe_limit = jnp.where(reqs.limit == 0, jnp.int64(1), reqs.limit)
    rate_i = (reqs.duration.astype(jnp.float64) / safe_limit.astype(jnp.float64)).astype(jnp.int64)
    reset_rem = jnp.where(
        under, rem_resp, jnp.where(drain & (i > q + 1), jnp.int64(0), base - q * h)
    )
    leaky_reset = reqs.created_at + (reqs.limit - reset_rem) * rate_i
    resp = RespBatch(
        status=jnp.where(
            merged,
            jnp.where(under, jnp.where(is_tok, S0, UNDER), OVER),
            resp.status,
        ),
        limit=jnp.where(merged, reqs.limit, resp.limit),
        remaining=jnp.where(merged, rem_resp, resp.remaining),
        reset_time=jnp.where(
            merged, jnp.where(is_tok, E, leaky_reset), resp.reset_time
        ),
        over_limit=jnp.where(merged, ~under, resp.over_limit),
    )

    # Window-final state, evaluated at the LAST member's rank and folded
    # into the head's scatter row (one scatter for head + whole window).
    li = last_rank.astype(jnp.int64)
    l_under = li <= q
    rem_last = jnp.where(l_under, base - li * h, rem_over)
    divisible = base - q * h == 0
    # Token: stored status flips OVER once an at-zero step occurred.
    at_zero_last = jnp.where(divisible, li > q, drain & (li > q + 1))
    status_last = jnp.where(at_zero_last, OVER, S0)
    # Leaky: the float remaining keeps its fraction through decrements but
    # collapses to exactly 0.0 after an exact-remainder step (q ≥ 1,
    # divisible, reached) or a drain step (base > 0, passed rank q).
    zero_f = ((q >= 1) & divisible & (li >= q)) | ((base > 0) & drain & (li > q))
    remf_last = jnp.where(
        zero_f,
        jnp.float64(0.0),
        F0 - (jnp.minimum(li, q) * h).astype(jnp.float64),
    )
    head_ovr = head_mask & alive
    rows = new_g._replace(
        remaining=jnp.where(head_ovr & is_tok, rem_last, new_g.remaining),
        status=jnp.where(head_ovr & is_tok, status_last, new_g.status),
        remaining_f=jnp.where(
            head_ovr & ~is_tok, remf_last, new_g.remaining_f
        ),
    )
    return rows, resp, merged


def _seg_propagate(is_start, vals):
    """Broadcast each segment head's values to every member (segmented
    inclusive scan; the classic (flag, value) combine — associative)."""
    def combine(a, b):
        fa, va = a[0], a[1:]
        fb, vb = b[0], b[1:]
        return (fa | fb,) + tuple(
            jnp.where(fb, y, x) for x, y in zip(va, vb)
        )

    out = lax.associative_scan(combine, (is_start,) + tuple(vals))
    return out[1:]


def _seg_min_all(is_start, val):
    """Per-row minimum of ``val`` over the row's whole segment, without
    scatters: a forward segmented min covers [start..i], a backward one
    covers [i..end]."""
    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, jnp.minimum(va, vb))

    fwd = lax.associative_scan(combine, (is_start, val))[1]
    last = jnp.concatenate([is_start[1:], jnp.ones((1,), jnp.bool_)])
    bwd = lax.associative_scan(
        combine, (last[::-1], val[::-1])
    )[1][::-1]
    return jnp.minimum(fwd, bwd)


def _seg_max_all(is_start, val):
    """Per-row maximum of ``val`` over the row's whole segment (mirror of
    :func:`_seg_min_all`)."""
    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, jnp.maximum(va, vb))

    fwd = lax.associative_scan(combine, (is_start, val))[1]
    last = jnp.concatenate([is_start[1:], jnp.ones((1,), jnp.bool_)])
    bwd = lax.associative_scan(
        combine, (last[::-1], val[::-1])
    )[1][::-1]
    return jnp.maximum(fwd, bwd)


def _sorted_merge_plan(reqs: ReqBatch, is_start: jnp.ndarray):
    """Static fold structure for a slot-sorted batch: the ``ok``
    fold-eligibility predicate and the end index of each row's *unit*
    (maximal contiguous run of identical fold-eligible requests).

    Units are the granularity of the sorted tick's rounds: a uniform
    duplicate group is one unit (one round — the thundering-herd fast
    path), and a group broken by RESET/Gregorian/query/parameter-change
    rows costs one round per unit, NOT one per duplicate (round-3's 6.5 s
    adversarial corner: a ~700-deep hot key interleaved with RESET rows
    degenerated to ~700 gather+scatter rounds)."""
    NO_MERGE = jnp.int32(
        Behavior.RESET_REMAINING | Behavior.DURATION_IS_GREGORIAN
    )
    b = reqs.slot.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)

    def eq_prev(a):
        return jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), a[1:] == a[:-1]]
        )

    # "Equals its predecessor" chains to "equals its head" within a
    # contiguous run, so run membership is a neighbor compare.
    same_as_prev = is_start | (
        eq_prev(reqs.hits)
        & eq_prev(reqs.limit)
        & eq_prev(reqs.duration)
        & eq_prev(reqs.behavior)
        & eq_prev(reqs.created_at)
        & eq_prev(reqs.burst)
        & eq_prev(reqs.algorithm)
    )
    ok = (
        reqs.valid
        & same_as_prev
        & (reqs.hits > 0)
        & ((reqs.behavior & NO_MERGE) == 0)
        # group heads are exempt from the known check (their transition
        # handles the new-item case); group-rank==0 IS is_start
        & (reqs.known | is_start)
        # zoo lanes (algorithm >= 2) have no closed-form fold
        & (reqs.algorithm <= jnp.int32(Algorithm.LEAKY_BUCKET))
    )
    unit_start = is_start | ~ok
    nxt = jnp.where(unit_start, idx, jnp.int32(b))
    sfx = lax.associative_scan(jnp.minimum, nxt[::-1])[::-1]
    unit_end = jnp.concatenate([sfx[1:], jnp.full((1,), b, jnp.int32)])
    return ok, unit_end


def make_tick_fn(capacity: int, merge_uniform: bool = True,
                 layout: str = "columns", sorted_input: bool = False,
                 compact_resp: bool = False, compact_req: bool = False,
                 unit_unroll: int = 8):
    """Build the jittable tick: (state, reqs, now) → (state, responses).

    Pure function of its inputs (no clocks, no host state) so the driver can
    compile-check it and shard it.

    **Thundering-herd fast path** (``merge_uniform``): a batch full of
    duplicates of one hot key is the reference's headline scenario
    (docs/architecture.md, benchmark_test.go:122-147).  Naive rank rounds
    cost one full gather+scatter per duplicate.  When every request in a
    slot group is *identical* (same hits/limit/duration/algorithm/behavior/
    created_at/burst, hits>0, token or leaky bucket, no RESET/Gregorian)
    the sequential fold over the group has a closed form in the member's
    rank: the group head runs the normal transition (handling new-item/
    renewal/limit-delta/drip), every follower's response is prefix
    arithmetic on the head's post-state, and only the last member scatters
    the final state.  Duplicate cost collapses from O(dups) rounds to O(1);
    mixed groups fall back to rank rounds bounded by the *non-merged* ranks
    only.
    """

    _, _gather, _scatter = _layout_ops(layout)

    def tick_sorted(state, reqs: ReqBatch, now: jnp.ndarray, resp0):
        """Sorted-input tick: unit rounds.

        Contract: the host packed the batch sorted by slot with
        invalid/padding rows (slot=capacity) at the end, so every slot
        group is a contiguous run and all segment math is neighbor
        compares + scans — no device sort, no B-sized gathers/scatters
        anywhere in the merge path.

        Each round applies, per slot, the FIRST not-yet-applied request
        as that slot's head (full transition) and closed-form-folds the
        rest of the head's *unit* — its maximal run of identical
        fold-eligible duplicates (:func:`_sorted_merge_plan`) — so a
        uniform duplicate group costs one round (the thundering-herd
        fast path) and a group interleaved with RESET/query/Gregorian or
        parameter-change rows costs one round per unit, never one per
        duplicate.  Heads whose post-state is already expired fold
        nothing; their followers simply head later rounds, preserving
        exact per-slot sequencing (reference workers.go:19-37 serializes
        per key; algorithms.go is the per-request bar)."""
        b = reqs.slot.shape[0]
        sorted_key = jnp.where(
            reqs.valid, reqs.slot, capacity
        ).astype(jnp.int32)
        is_start = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), sorted_key[1:] != sorted_key[:-1]]
        )
        has_dups = jnp.any((~is_start[1:]) & reqs.valid[1:])

        def unique_branch(_):
            gathered = _gather(state, reqs.slot)
            new_g, r_out = bucket_transition(now, gathered, reqs)
            resp = jax.tree.map(
                lambda old, new: jnp.where(reqs.valid, new, old),
                resp0, r_out,
            )
            scat = jnp.where(reqs.valid, reqs.slot, capacity)
            return _scatter(state, scat, new_g), resp

        def dup_branch(_):
            idx = jnp.arange(b, dtype=jnp.int32)
            ok, unit_end = _sorted_merge_plan(reqs, is_start)

            def cond(carry):
                return ~jnp.all(carry[0])

            def sub_step(applied, g, resp, last_head):
                """Apply, per slot, the first unapplied unit (head
                transition + closed-form fold) entirely in registers:
                ``g`` holds each row's view of its slot's CURRENT state,
                updated by forward propagation — no gather or scatter per
                unit (those happen once per round, in ``body``)."""
                cand = ~applied
                headpos = _seg_min_all(
                    is_start, jnp.where(cand, idx, jnp.int32(b))
                )
                head = cand & (idx == headpos)
                new_g, r_out = bucket_transition(now, g, reqs)
                resp = jax.tree.map(
                    lambda old, new: jnp.where(head, new, old), resp, r_out
                )
                # Broadcast the head's post-transition values (and its
                # position / unit end) forward over its group; rows
                # before the head are already applied and masked out.
                R0, F0, S0, E, hpos, uend = _seg_propagate(
                    is_start | head,
                    (new_g.remaining, new_g.remaining_f, new_g.status,
                     new_g.expire_at, idx, unit_end),
                )
                fold_rank = idx - hpos
                fold = cand & ok & (fold_rank > 0) & (idx < uend)
                rows, resp, merged = _merged_formulas(
                    new_g, resp, reqs, now, fold_rank, uend - 1 - hpos,
                    fold_mask=fold,
                    head_mask=head & (uend - hpos > 1),
                    R0=R0, F0=F0, S0=S0, E=E,
                )
                # Chain units in-register: broadcast the head's
                # unit-final row state forward over its segment so the
                # next sub-step's head (the following unit of the same
                # slot) transitions from post-unit state.  The
                # propagated ``head`` flag distinguishes spans whose
                # nearest boundary is a live head from spans headed by a
                # stale segment start (those keep their state).
                prop = _seg_propagate(is_start | head, (head,) + tuple(rows))
                from_head = prop[0]
                g = jax.tree.map(
                    lambda cur, pv: jnp.where(from_head, pv, cur),
                    g, type(rows)(*prop[1:]),
                )
                applied = applied | head | merged
                last_head = jnp.where(head, idx, last_head)
                return applied, g, resp, last_head

            def body(carry):
                applied, st, resp = carry
                g = _gather(st, reqs.slot)
                sc = (applied, g, resp, jnp.full(b, -1, jnp.int32))
                # unit_unroll units per slot per ROUND: one gather and
                # one scatter amortize over up to that many sequential
                # units (parameter-change/RESET-broken groups cost
                # ceil(units / unit_unroll) rounds, not one round per
                # unit).  A fori_loop (not a Python unroll) keeps the
                # compiled graph one sub_step big, and its cond skips
                # finished sub-steps so a batch whose units are
                # exhausted early (the uniform-herd one-unit case) pays
                # for one.
                sc = lax.fori_loop(
                    0, max(1, unit_unroll),
                    lambda _k, c: lax.cond(
                        jnp.all(c[0]), lambda cc: cc,
                        lambda cc: sub_step(*cc), c,
                    ),
                    sc,
                )
                applied, g, resp, last_head = sc
                # One scatter per slot, from its LAST applied head this
                # round — that row's ``g`` carries the slot's final
                # chained state (heads are boundary rows of the final
                # propagation, so their own values survive in ``g``).
                seg_last = _seg_max_all(is_start, last_head)
                scat_src = (last_head >= 0) & (last_head == seg_last)
                scat = jnp.where(scat_src, reqs.slot, capacity)
                st = _scatter(st, scat, g)
                return applied, st, resp

            _, st, resp = lax.while_loop(
                cond, body, (~reqs.valid, state, resp0)
            )
            return st, resp

        return lax.cond(has_dups, dup_branch, unique_branch, None)

    def tick(state, reqs: ReqBatch, now: jnp.ndarray):
        b = reqs.slot.shape[0]

        resp0 = RespBatch(
            status=jnp.zeros(b, jnp.int32),
            limit=jnp.zeros(b, jnp.int64),
            remaining=jnp.zeros(b, jnp.int64),
            reset_time=jnp.zeros(b, jnp.int64),
            over_limit=jnp.zeros(b, jnp.bool_),
        )

        if merge_uniform and sorted_input:
            return tick_sorted(state, reqs, now, resp0)

        def round_step(st, resp, active):
            gathered = _gather(st, reqs.slot)
            new_g, r_out = bucket_transition(now, gathered, reqs)
            # Scatter only this round's rows; inactive rows aim out of
            # bounds and are dropped (guard row for the row layout).
            scat = jnp.where(active, reqs.slot, capacity)
            st = _scatter(st, scat, new_g)
            resp = jax.tree.map(
                lambda old, new: jnp.where(active, new, old), resp, r_out
            )
            return st, resp

        # Round 0: every group head takes the full transition (new item,
        # renewal, limit delta, RESET — all head-only concerns).  With the
        # merge fast path the heads' scatter rows already carry the whole
        # group's final state, so head + followers cost ONE scatter.
        gathered = _gather(state, reqs.slot)
        new_g, r_out = bucket_transition(now, gathered, reqs)

        if merge_uniform:
            # The duplicate-group machinery costs ~2x the rest of a tick
            # — and an all-unique batch needs none of it.  Detect
            # duplicates once, then lax.cond so unique batches skip
            # straight to "every row is its own head".
            def unique_branch(_):
                resp = jax.tree.map(
                    lambda old, new: jnp.where(reqs.valid, new, old),
                    resp0, r_out,
                )
                return new_g, resp, reqs.valid, jnp.zeros(b, jnp.int32)

            sort_key = jnp.where(
                reqs.valid, reqs.slot, capacity
            ).astype(jnp.int32)
            order = jnp.argsort(sort_key, stable=True)
            sorted_key = sort_key[order]
            has_dups = jnp.any(
                (sorted_key[1:] == sorted_key[:-1])
                & (sorted_key[1:] < jnp.int32(capacity))
            )

            def dup_branch(_):
                rank, group_size, head_idx, seg_id = (
                    _segments_from_sorted(sorted_key, order)
                )
                heads = reqs.valid & (rank == 0)
                resp = jax.tree.map(
                    lambda old, new: jnp.where(heads, new, old),
                    resp0, r_out,
                )
                rows, resp, merged = _apply_merged_followers(
                    new_g, resp, reqs, now,
                    rank, group_size, head_idx, seg_id,
                )
                return rows, resp, merged, rank

            rows, resp, merged, rank = lax.cond(
                has_dups, dup_branch, unique_branch, None
            )
        else:
            rank = _rank_within_slot(reqs.slot, reqs.valid, capacity)
            heads0 = reqs.valid & (rank == 0)
            resp = jax.tree.map(
                lambda old, new: jnp.where(heads0, new, old), resp0, r_out
            )
            rows = new_g
            merged = jnp.zeros(b, jnp.bool_)

        heads = reqs.valid & (rank == 0)
        scat = jnp.where(heads, reqs.slot, capacity)
        state = _scatter(state, scat, rows)

        # Rank rounds for whatever didn't merge (mixed-parameter groups,
        # RESET/Gregorian flows, queries): round k applies at most one
        # request per slot.
        pending = reqs.valid & ~merged
        n_rounds = jnp.max(jnp.where(pending, rank, 0)) + 1

        def cond(carry):
            k, _, _ = carry
            return k < n_rounds

        def body(carry):
            k, st, resp = carry
            st, resp = round_step(st, resp, pending & (rank == k))
            return k + 1, st, resp

        _, state, resp = lax.while_loop(cond, body, (jnp.int32(1), state, resp))
        return state, resp

    def tick_packed(state, packed: jnp.ndarray, now: jnp.ndarray):
        reqs = (
            unpack_reqs_compact(packed)
            if compact_req
            else unpack_reqs(packed)
        )
        state, resp = tick(state, reqs, now)
        return state, (
            pack_resp_compact(resp) if compact_resp else pack_resp(resp)
        )

    tick_packed.unpacked = tick
    return tick_packed


def make_install_fn(layout: str = "columns"):
    """Jitted scatter installing owner-pushed GLOBAL state into the table.

    Mirrors the reference's ``UpdatePeerGlobals`` install
    (gubernator.go:425-459): ExpireAt comes from the pushed ``reset_time``;
    token buckets install {status, limit, duration, remaining,
    created_at=now}; leaky buckets install {remaining_f, limit, duration,
    burst=limit, updated_at=now}.  ``cols`` rows: slot, algorithm, limit,
    remaining, status, duration, reset_time, valid.
    """

    _, _gather, _scatter = _layout_ops(layout)

    def install(state, cols: jnp.ndarray, now: jnp.ndarray):
        slot, algo, limit, remaining, status, duration, reset_time, valid = cols
        # Every integer-count algorithm (token bucket and the whole zoo)
        # installs remaining into the int column; only leaky buckets route
        # it through remaining_f.  A pushed zoo bucket restarts its
        # window/TAT locally (tat/prev_count zero) — the counter value is
        # the authoritative part of an owner push, the phase is not.
        is_leaky = algo == jnp.int64(int(Algorithm.LEAKY_BUCKET))
        # Invalid rows aim one past the table and drop.  The sentinel must
        # stay < 2^31: GSPMD partitions the scatter with int32 index math,
        # and a 2^40 sentinel truncates to slot 0 on a sharded table.
        scat = jnp.where(valid != 0, slot, jnp.int64(state.capacity))

        zero = jnp.zeros_like(limit)
        rows = BucketState(
            algorithm=algo.astype(jnp.int32),
            limit=limit,
            remaining=jnp.where(is_leaky, jnp.int64(0), remaining),
            remaining_f=jnp.where(
                is_leaky, remaining.astype(jnp.float64), jnp.float64(0.0)
            ),
            duration=duration,
            created_at=jnp.where(is_leaky, jnp.int64(0), now),
            updated_at=jnp.where(is_leaky, now, jnp.int64(0)),
            burst=jnp.where(is_leaky, limit, jnp.int64(0)),
            status=status.astype(jnp.int32),
            expire_at=reset_time,
            in_use=valid != 0,
            tat=zero,
            prev_count=zero,
        )
        return _scatter(state, scat, rows)

    return install


# Field order for full-state restore/readback matrices (Store hooks).
ITEM_INT_ROWS = (
    "slot", "algorithm", "limit", "remaining", "duration", "created_at",
    "updated_at", "burst", "status", "expire_at", "tat", "prev_count",
    "valid",
)


def make_restore_fn(layout: str = "columns"):
    """Jitted scatter installing *full* item state — the read-through path
    (Store.Get on cache miss, reference algorithms.go:45-51) and the
    Loader.Load restore.  ``ints`` is (13, B) int64 per ITEM_INT_ROWS;
    ``floats`` is (B,) float64 (leaky ``remaining_f``)."""

    _, _gather, _scatter = _layout_ops(layout)

    def restore(state, ints: jnp.ndarray, floats: jnp.ndarray):
        f = dict(zip(ITEM_INT_ROWS, ints))
        # Sentinel must stay < 2^31 (see make_install_fn).
        scat = jnp.where(f["valid"] != 0, f["slot"], jnp.int64(state.capacity))

        rows = BucketState(
            algorithm=f["algorithm"].astype(jnp.int32),
            limit=f["limit"],
            remaining=f["remaining"],
            remaining_f=floats,
            duration=f["duration"],
            created_at=f["created_at"],
            updated_at=f["updated_at"],
            burst=f["burst"],
            status=f["status"].astype(jnp.int32),
            expire_at=f["expire_at"],
            in_use=f["valid"] != 0,
            tat=f["tat"],
            prev_count=f["prev_count"],
        )
        return _scatter(state, scat, rows)

    return restore


def make_readback_fn(layout: str = "columns"):
    """Jitted gather of full item state at given slots — the write-through
    path (Store.OnChange after every mutation, algorithms.go:149-153).
    Returns ((12, B) int64, (B,) float64).  Out-of-range (padding) slots
    read zeros on the column layout and guard-row garbage on the row
    layout — callers must not read rows past their real batch."""

    _, _gather, _scatter = _layout_ops(layout)

    def readback(state, slots: jnp.ndarray):
        # Column layout zero-fills out-of-range slots; the row layout has
        # no fill option (guard-row garbage instead) — callers never read
        # past their real batch, so both contracts are safe here.
        rows = (
            _gather(state, slots)
            if layout == "row"
            else _gather(state, slots, fill=True)
        )
        ints = jnp.stack(
            [
                rows.algorithm.astype(jnp.int64),
                rows.limit,
                rows.remaining,
                rows.duration,
                rows.created_at,
                rows.updated_at,
                rows.burst,
                rows.status.astype(jnp.int64),
                rows.expire_at,
                rows.tat,
                rows.prev_count,
                rows.in_use.astype(jnp.int64),
            ]
        )
        return ints, rows.remaining_f

    return readback


READBACK_ROWS = (
    "algorithm", "limit", "remaining", "duration", "created_at",
    "updated_at", "burst", "status", "expire_at", "tat", "prev_count",
    "in_use",
)


# Columnar snapshot schema: every stored bucket field as a (live,) array
# plus the key blob/offsets pair.  The Loader v2 wire format.
SNAP_FIELDS = (
    "algorithm", "limit", "remaining", "remaining_f", "duration",
    "created_at", "updated_at", "burst", "status", "expire_at",
)


# Cooperative quota-lease columns (docs/leases.md), parallel to the SoA
# table and exported as EXTRA snapshot keys (np.savez carries them
# transparently) so outstanding delegations survive a restore.  Kept out
# of SNAP_FIELDS proper: the slim-transfer probe/select schema, the item
# dict shape, and the cold tier's column contract all iterate
# SNAP_FIELDS, and a pre-lease snapshot must keep loading (absent keys
# restore as all-zeros = no outstanding delegation, which is the safe
# reading: clients re-grant).
LEASE_SNAP_FIELDS = ("lease_budget", "lease_expire", "lease_gen")


# Algorithm-zoo state columns (docs/algorithms.md): GCRA's theoretical
# arrival time and the sliding window's previous-window count.  Like the
# lease columns these are EXTRA snapshot keys so pre-zoo snapshots keep
# loading (absent keys restore as zeros — a fresh window/TAT, which is
# the safe reading).  Unlike the lease columns they live IN the device
# table, so they ride the slim-transfer probe/select path via SNAP_WIDE.
ZOO_SNAP_FIELDS = ("tat", "prev_count")


# Wide (int64) snapshot fields, in SNAP_FIELDS order, minus the narrow
# algorithm/status columns — the unit of the slim-transfer schema below.
# The zoo columns append after the legacy seven (word offsets 20-23).
SNAP_WIDE = (
    "limit", "remaining", "duration", "created_at", "updated_at",
    "burst", "expire_at", "tat", "prev_count",
)
SNAP_CHUNK = 1 << 21  # live rows per export D2H chunk (~44-64 MB each)


@functools.lru_cache(maxsize=None)
def _jitted_snap_wide(layout: str):
    """(state, slots (w,) i32) → (ROW_USED, w) i32 stored-word matrix of
    the gathered slots — the device-side staging buffer the probe/select
    programs slice.  Padding slots must point at a REAL row (the caller
    pads with the chunk's first slot) so the probe's range statistics
    aren't polluted by guard-row zeros."""
    from gubernator_tpu.ops.buckets import STATE_DTYPES

    if layout == "row":

        def f(state, slots):
            return state.table[slots, : rowtable.ROW_USED].T

    else:

        def f(state, slots):
            rows = []
            for name in STATE_DTYPES:
                col = getattr(state, name)
                for p in col if isinstance(col, tuple) else (col,):
                    c = p[slots]
                    rows.append(
                        c if c.dtype == jnp.int32 else c.astype(jnp.int32)
                    )
            return jnp.stack(rows)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _jitted_snap_probe():
    """(ROW_USED, w) words → (len(SNAP_WIDE), 3) i32 per-field stats:
    [all hi words are the lo word's sign extension, min hi, max hi].
    The export uses them to pick, per chunk, which hi columns need to
    cross the link at all (verdict r3 #7: the int64 columns were the
    bytes inflating a ~0.9 GB / 110 s 10M export)."""
    O = rowtable.FIELD_OFFSETS

    def f(m):
        out = []
        for name in SNAP_WIDE:
            lo, hi = m[O[name]], m[O[name] + 1]
            out.append(
                jnp.stack([
                    jnp.all(hi == (lo >> 31)).astype(jnp.int32),
                    jnp.min(hi),
                    jnp.max(hi),
                ])
            )
        return jnp.stack(out)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _jitted_snap_select(hi_mask: tuple):
    """(ROW_USED, w) words → (W, w) transfer matrix: the SNAP_WIDE lo
    words, the hi words the chunk's probe proved necessary, the 3
    remaining_f parts, and one packed algorithm|status|in_use word."""
    O = rowtable.FIELD_OFFSETS

    def f(m):
        rows = [m[O[name]] for name in SNAP_WIDE]
        rows += [
            m[O[name] + 1]
            for name, keep in zip(SNAP_WIDE, hi_mask) if keep
        ]
        fo = O["remaining_f"]
        rows += [m[fo], m[fo + 1], m[fo + 2]]
        rows.append(
            (m[O["algorithm"]] & 0xFF)
            | ((m[O["status"]] & 0xFF) << 8)
            | ((m[O["in_use"]] & 1) << 16)
        )
        return jnp.stack(rows)

    return jax.jit(f)


def _snap_decode(part, k, probe, hi_mask, sel_np):
    """One transfer chunk → (kept_slots, {snap_field: column}) with dead
    (in_use=0) rows dropped.  Inverse of _jitted_snap_select + probe."""
    mat = sel_np[:, :k]
    r = len(SNAP_WIDE)
    his = {}
    for name, keep in zip(SNAP_WIDE, hi_mask):
        if keep:
            his[name] = mat[r]
            r += 1
    f32 = mat[r : r + 3]
    packed = mat[r + 3]
    alive = ((packed >> 16) & 1).astype(bool)
    cols: dict = {}
    for i, name in enumerate(SNAP_WIDE):
        lo = mat[i]
        if name in his:
            hi = his[name].astype(np.int64)
        else:
            all_se, hmin, _ = probe[i]
            if all_se:
                cols[name] = lo.astype(np.int64)[alive]
                continue
            hi = np.int64(hmin)  # probe proved the hi word constant
        cols[name] = (
            (hi << 32) | lo.view(np.uint32).astype(np.int64)
        )[alive]
    cols["remaining_f"] = sum(
        w.view(np.float32).astype(np.float64) for w in f32
    )[alive]
    cols["algorithm"] = (packed & 0xFF).astype(np.int64)[alive]
    cols["status"] = ((packed >> 8) & 0xFF).astype(np.int64)[alive]
    return part[alive], cols


def snapshot_from_items(items: Sequence[dict]) -> dict:
    """Loader-contract item dicts → columnar snapshot (the inverse of
    :func:`items_from_snapshot`; the one place the dict→columns
    conversion lives)."""
    from gubernator_tpu.ops.reqcols import pack_blob

    blob, offsets = pack_blob([it["key"].encode() for it in items])
    snap: dict = {"key_blob": blob, "key_offsets": offsets}
    for f in SNAP_FIELDS:
        dt = np.float64 if f == "remaining_f" else np.int64
        snap[f] = np.asarray([it[f] for it in items], dt)
    # Zoo columns default to zero for legacy items (pre-zoo Loader
    # sources never mention them).
    for f in ZOO_SNAP_FIELDS:
        snap[f] = np.asarray([it.get(f, 0) for it in items], np.int64)
    return snap


def items_from_snapshot(snap: dict) -> List[dict]:
    """Columnar snapshot → Loader-contract item dicts (the dict API edge;
    per-item Python lives only here)."""
    offsets = snap["key_offsets"]
    blob = snap["key_blob"]
    n = len(offsets) - 1
    fields = SNAP_FIELDS + ZOO_SNAP_FIELDS
    cols = {
        f: snap[f].tolist() if f in snap else [0] * n for f in fields
    }
    keys = [
        bytes(blob[offsets[j] : offsets[j + 1]]).decode() for j in range(n)
    ]
    return [
        {"key": keys[j], **{f: cols[f][j] for f in fields}}
        for j in range(n)
    ]


def items_from_columns(keys: List[bytes], st, live: np.ndarray) -> List[dict]:
    """Build Loader-contract item dicts for the live slots of a (host) state.

    Shared by both engines' ``export_items``: one vectorized slice per
    column, then the (unavoidable, dict-shaped) per-item build.
    """
    from gubernator_tpu.ops.buckets import slice_field

    cols = {
        name: np_logical(slice_field(getattr(st, name), live), name)
        for name in (
            "algorithm", "limit", "remaining", "remaining_f", "duration",
            "created_at", "updated_at", "burst", "status", "expire_at",
            "tat", "prev_count",
        )
    }
    return [
        {
            "key": keys[j].decode(),
            "algorithm": int(cols["algorithm"][j]),
            "limit": int(cols["limit"][j]),
            "remaining": int(cols["remaining"][j]),
            "remaining_f": float(cols["remaining_f"][j]),
            "duration": int(cols["duration"][j]),
            "created_at": int(cols["created_at"][j]),
            "updated_at": int(cols["updated_at"][j]),
            "burst": int(cols["burst"][j]),
            "status": int(cols["status"][j]),
            "expire_at": int(cols["expire_at"][j]),
            "tat": int(cols["tat"][j]),
            "prev_count": int(cols["prev_count"][j]),
        }
        for j in range(len(live))
    ]


def make_evict_fn(layout: str = "columns"):
    """Jitted slot eviction: zero a batch of slots (LRU reclamation).

    Both layouts zero the WHOLE row, not just ``in_use``: an evicted item
    is removed in the reference (lrucache.go:138-149), and stale
    don't-care fields would otherwise leak into the next tenant's
    snapshot when the slot is reborn under the other algorithm."""

    if layout == "row":
        return rowtable.row_evict

    def evict(state: BucketState, slots: jnp.ndarray) -> BucketState:
        # Zero the whole row, not just in_use: an evicted item is REMOVED
        # in the reference (lrucache.go:138-149), and leaving stale
        # don't-care fields behind leaks them into the next tenant's
        # snapshot when the slot is reborn under the other algorithm
        # (found by the row/column fuzz parity suite).
        zeros = BucketState.zeros_logical(slots.shape[0])
        return scatter_state(state, slots, zeros)

    return evict


@functools.lru_cache(maxsize=None)
def _jitted_tick(capacity: int, layout: str = "columns",
                 sorted_input: bool = False, compact_resp: bool = False,
                 compact_req: bool = False):
    """Shared jitted tick per capacity: engines pass state explicitly, so an
    in-process multi-daemon cluster (the reference's test topology,
    cluster/cluster.go) compiles the kernel once, not once per daemon."""
    return jax.jit(
        make_tick_fn(capacity, layout=layout, sorted_input=sorted_input,
                     compact_resp=compact_resp, compact_req=compact_req),
        donate_argnums=(0,),
    )


@functools.lru_cache(maxsize=None)
def _jitted_evict(layout: str = "columns"):
    return jax.jit(make_evict_fn(layout), donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jitted_install(layout: str = "columns"):
    return jax.jit(make_install_fn(layout), donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jitted_restore(layout: str = "columns"):
    return jax.jit(make_restore_fn(layout), donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jitted_readback(layout: str = "columns"):
    return jax.jit(make_readback_fn(layout))


@functools.lru_cache(maxsize=None)
def _jitted_lease_apply(is_set: bool):
    """One lease-column window as a single scatter over the three lease
    columns (docs/leases.md).  ``is_set`` picks grant semantics (install
    the authoritative outstanding/expiry/generation triple) vs reconcile
    deltas (budget += delta clamped at zero; expiry/generation only move
    forward).  Padding lanes carry slot == capacity, which ``mode="drop"``
    discards on device — no host-side masking pass."""

    def f(budget_col, expire_col, gen_col, slots, budgets, expires, gens):
        if is_set:
            budget_col = budget_col.at[slots].set(budgets, mode="drop")
            expire_col = expire_col.at[slots].set(expires, mode="drop")
            gen_col = gen_col.at[slots].set(gens, mode="drop")
        else:
            budget_col = jnp.maximum(
                budget_col.at[slots].add(budgets, mode="drop"), 0
            )
            expire_col = expire_col.at[slots].max(expires, mode="drop")
            gen_col = gen_col.at[slots].max(gens, mode="drop")
        return budget_col, expire_col, gen_col

    return jax.jit(f, donate_argnums=(0, 1, 2))


class SlotMap:
    """Host-side key→slot table (the stand-in for ``lrucache.go``'s map).

    Python-dict based; the C++ native version (gubernator_tpu/native) slots in
    behind the same interface for the 10M+ key regime.
    """

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._map: Dict[str, int] = {}
        self._keys: List[Optional[str]] = [None] * capacity
        self._free: List[int] = list(range(capacity - 1, -1, -1))

    def __len__(self) -> int:
        return len(self._map)

    def get(self, key: str) -> Optional[int]:
        return self._map.get(key)

    def assign(self, key: str) -> Optional[int]:
        """Return the slot for key, allocating if new; None if table full."""
        s = self._map.get(key)
        if s is not None:
            return s
        if not self._free:
            return None
        s = self._free.pop()
        self._map[key] = s
        self._keys[s] = key
        return s

    def release(self, slot: int) -> None:
        key = self._keys[slot]
        if key is not None:
            del self._map[key]
            self._keys[slot] = None
            self._free.append(slot)

    def key_of(self, slot: int) -> Optional[str]:
        return self._keys[slot]

    def mapped_mask(self) -> np.ndarray:
        """Boolean array over slots: True where a key is assigned."""
        return np.fromiter(
            (k is not None for k in self._keys), np.bool_, count=self.capacity
        )

    def resolve_batch(self, keys: List[bytes]):
        """(slots, known) for a batch of keys; slot -1 = table full.
        Interface-compatible with NativeSlotMap.resolve_batch."""
        n = len(keys)
        slots = np.empty(n, np.int64)
        known = np.empty(n, np.uint8)
        get = self._map.get
        for j in range(n):
            k = keys[j].decode()
            s = get(k)
            if s is not None:
                slots[j] = s
                known[j] = 1
            else:
                s = self.assign(k)
                slots[j] = -1 if s is None else s
                known[j] = 0
        return slots, known

    def resolve_blob(self, blob, offsets: np.ndarray):
        """(slots, known) for keys packed as one blob + offsets (the
        columnar hot-path format; NativeSlotMap resolves this with zero
        per-key Python).  ``blob`` may be any bytes-like buffer — slices
        are coerced to bytes for the per-key decode."""
        mv = memoryview(blob)
        return self.resolve_batch(
            [bytes(mv[offsets[j] : offsets[j + 1]]) for j in range(len(offsets) - 1)]
        )

    def release_batch(self, slots: np.ndarray) -> None:
        for s in slots:
            self.release(int(s))

    def keys_batch(self, slots: np.ndarray) -> List[bytes]:
        return [
            (k.encode() if (k := self._keys[int(s)]) is not None else b"")
            for s in slots
        ]

    def keys_blob(self, slots: np.ndarray) -> tuple[bytes, np.ndarray]:
        """Keys of a batch of slots as one (blob, offsets) pair (the
        columnar snapshot format; NativeSlotMap does this natively)."""
        from gubernator_tpu.ops.reqcols import pack_blob

        return pack_blob(self.keys_batch(slots))

    def assign_blob(self, blob: bytes, offsets: np.ndarray) -> np.ndarray:
        return self.assign_batch(
            [blob[offsets[j] : offsets[j + 1]] for j in range(len(offsets) - 1)]
        )

    def assign_batch(self, keys: List[bytes]) -> np.ndarray:
        out = np.empty(len(keys), np.int64)
        for j, k in enumerate(keys):
            s = self.assign(k.decode())
            out[j] = -1 if s is None else s
        return out


@functools.lru_cache(maxsize=None)
def _jitted_dead_scan():
    """Device-side TTL sweep of the column layout: ``~in_use | expired``
    packed to a bitmask so the per-reclaim D2H is capacity/8 bytes, not
    the 9 bytes/slot the old host sweep copied (90 MB per sweep at 10M
    slots).  Packed by stride, not by neighbour: bit j of byte i is slot
    ``j * m + i`` (``m = ceil(capacity / 8)``; :func:`unpack_dead_bits`).
    XLA:TPU compiles ``packbits``' reduction over a minor axis of eight
    into code that grows with the table (311 s and 165 MB of code for a
    31.25M-slot shard, on a described v5e); over the major axis it is
    one loop.  ``expire_at`` is compared as its int32 pair, with no
    64-bit column."""

    def scan(in_use, exp_lo, exp_hi, now):
        now_hi = (now >> 32).astype(jnp.int32)
        now_lo = (now & 0xFFFFFFFF).astype(jnp.uint32)
        lo = lax.bitcast_convert_type(exp_lo, jnp.uint32)
        dead = (~in_use) | (exp_hi < now_hi) | ((exp_hi == now_hi) & (lo < now_lo))
        m = -(-dead.shape[0] // 8)
        dead = jnp.pad(dead, (0, 8 * m - dead.shape[0])).reshape(8, m)
        shift = jnp.arange(8, dtype=jnp.uint8)[:, None]
        return jnp.sum(dead.astype(jnp.uint8) << shift, axis=0, dtype=jnp.uint8)

    return jax.jit(scan)


def device_dead_bits(in_use, expire_field, now: int):
    """Dispatch the dead-slot scan; returns the *device* packed bitmask
    (callers materialize with :func:`unpack_dead_bits`).  Split from
    :func:`device_dead_mask` so the background reclaimer can dispatch
    under the engine lock (the state buffers are donated by the next tick)
    but pay the D2H wait outside it."""
    lo, hi = expire_field
    return _jitted_dead_scan()(in_use, lo, hi, jnp.int64(now))


def unpack_dead_bits(bits, capacity: int) -> np.ndarray:
    """The host mask of :func:`device_dead_bits`' stride-packed bits."""
    return np.unpackbits(
        # guber: allow-G001(the deliberate reclaim D2H - materializing the packed dead bitmask is this helper's whole job; callers pay it off-lock, at most once per reclaim round, never per tick)
        np.asarray(bits)[None, :], axis=0, count=8, bitorder="little"
    ).reshape(-1)[:capacity].astype(bool)


def device_dead_mask(in_use, expire_field, now: int, capacity: int) -> np.ndarray:
    """Host bool mask of device-dead slots (unused or TTL-expired), computed
    on device and shipped as a packed bitmask."""
    return unpack_dead_bits(device_dead_bits(in_use, expire_field, now), capacity)


def select_reclaim_victims(
    mapped: np.ndarray,
    dead_dev: np.ndarray,
    last_access: np.ndarray,
    tick_count: int,
    want: int,
) -> tuple[np.ndarray, np.ndarray]:
    """TTL-then-LRU victim selection over a table (or a shard slice of one).

    The one reclaim policy shared by all engines (expired-on-read eviction +
    evict-oldest of lrucache.go:88-149): returns ``(expired, lru_victims)``
    as local slot indices.  ``dead_dev`` is the device's view of dead slots
    (:func:`device_dead_mask`).  Expired slots release host-side with no
    device work; LRU victims must *also* be device-evicted (their ``in_use``
    is still set, and stale state must not resurrect if the slot is reused).

    ``mapped`` must already exclude host-pending slots (assigned but not
    yet written by a tick); slots touched this tick are excluded here —
    both look dead on device but are live.
    """
    mapped = mapped & (last_access != tick_count)
    dead = mapped & dead_dev
    freed = np.flatnonzero(dead)
    none = np.empty(0, np.int64)
    if len(freed) >= want:
        return freed, none
    live = np.flatnonzero(mapped & ~dead)
    n = min(want - len(freed), len(live))
    if n <= 0:
        return freed, none
    if n >= len(live):
        return freed, live
    # argpartition, not argsort: O(live) — a full sort of a 10M-slot table
    # costs seconds per reclaim for ordering we don't need.
    return freed, live[np.argpartition(last_access[live], n - 1)[:n]]


EVICT_CHUNK = 1 << 16
RESTORE_CHUNK = 1 << 15  # bounds the per-call VMEM row staging (16 MB)


def evict_chunked(evict_fn, state, victims: np.ndarray, capacity: int):
    """Apply a device evict scatter in width-capped chunks.

    Padding the whole batch to ``pad_pow2(len(victims))`` would compile an
    unbounded program width — including a ~1M-wide one on the first
    big-table reclaim (tens of seconds of jit on a slow toolchain).
    Capping at EVICT_CHUNK bounds compiles to the log2(EVICT_CHUNK) small
    widths, each cheap to build and shared via jit's shape cache."""
    for start in range(0, len(victims), EVICT_CHUNK):
        part = victims[start : start + EVICT_CHUNK]
        w = min(EVICT_CHUNK, pad_pow2(len(part)))
        padded = np.full(w, capacity, np.int32)
        padded[: len(part)] = part
        state = evict_fn(state, jnp.asarray(padded))
    return state


def make_slot_map(capacity: int):
    """Native C++ slotmap when the shared library is available (built by
    gubernator_tpu/native/Makefile), pure-Python fallback otherwise."""
    from gubernator_tpu.native import NativeSlotMap, load_library

    if load_library() is None:  # native.library_path logged the WARNING
        return SlotMap(capacity)
    return NativeSlotMap(capacity)


def describe_engine(device, devices: int, layout: str, fused: bool,
                    warmup_seconds: float, native_pack: bool = False,
                    load_seconds: float = 0.0, load_rows: int = 0) -> dict:
    """What an engine resolved to at construction — the daemon logs it
    once at start, so the backend that answers is never a guess (layout,
    fused and warm-up follow jax's default backend; see
    make_layout_choice, tick32._resolve_fused, _warmup; native_pack:
    the host pack is the native window pass, TickEngine._build_cols) —
    and the seconds its fills took and the rows they landed
    (``load_columns``; the start-up Loader's fill runs before the daemon
    serves)."""
    return {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "devices": devices,
        "layout": layout,
        "fused": fused,
        "native_pack": native_pack,
        "warmup_seconds": round(warmup_seconds, 3),
        "load_seconds": round(load_seconds, 3),
        "load_rows": load_rows,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }


class TickHandle:
    """One dispatched tick: device work is queued, host readback deferred.

    ``result()`` materializes the (5, n) response matrix in request order
    (rows: status, limit, remaining, reset_time, over_limit) and runs the
    deferred per-tick bookkeeping (over-limit metric, Store write-through).
    Idempotent; safe to call from a different thread than the dispatcher.
    """

    __slots__ = ("_engine", "_resp", "_n", "_inv", "errors", "_refs",
                 "_slots_req", "_limit_req", "_done", "_flock", "_wid")

    def __init__(self, engine, resp, n, inv, errors, refs, slots_req,
                 limit_req=None):
        self._engine = engine
        self._resp = resp
        self._n = n
        self._inv = inv
        self.errors = errors
        self._refs = refs
        self._slots_req = slots_req
        # Request-order limit column: the compact device response omits
        # the limit echo (pack_resp_compact); reconstruction needs it.
        # COPIED — the caller may reuse/rewrite its ReqColumns buffers
        # between submit and resolve (the pipelining pattern), and this
        # column is read at resolve time.
        self._limit_req = (
            None if limit_req is None
            # guber: allow-G001(host column snapshot - limit_req is a host array; the copy is the pipelining contract, not a device sync)
            else np.array(limit_req[:n], np.int64, copy=True)
        )
        self._done: Optional[np.ndarray] = None
        self._flock = sanitize.lock("TickHandle._flock")
        # The flight recorder's window in dispatch (None: no recorder,
        # or no tick loop): where _finish notes its wait for the lock.
        fr = flightrec.get()
        self._wid = fr.active() if fr is not None else None

    def _finish(self, raw: np.ndarray) -> None:
        """Complete from an already-materialized device response matrix:
        (6, W) int32 compact (TickEngine's format — it compiles its tick
        with compact_resp=True and always passes limit_req) or the
        (5, W) int64 legacy layout used by engines that don't."""
        with self._flock:
            if self._done is not None:
                return
            # The [:, inv] un-permutes the slot-sorted batch.
            rm = raw[:, : self._n][:, self._inv]
            if self._limit_req is not None:  # compact → public (5, n) int64
                rm = unpack_resp_compact(rm, self._limit_req)
            eng = self._engine
            # submit_columns holds the lock across the next window's
            # pack and dispatch: the wait for it is the recorder's
            # "finish_lock", an overlay inside the drain's "tick".
            waited = flightrec.stage("finish_lock", into=self._wid).start()
            with eng._lock:
                waited.stop()
                # This window is resolved: it no longer holds its H2D
                # staging slab, and later windows' uploads stop counting
                # it as overlap (see TickEngine.metric_h2d_overlapped).
                eng._inflight = max(0, eng._inflight - 1)
                eng.metric_over_limit += masked_over_limit(rm, self.errors)
                if eng.store is not None:
                    eng._write_through(
                        self._refs, self._slots_req, self._n, self.errors
                    )
            self._resp = None  # release the device buffer reference
            self._done = rm

    def result(self) -> tuple[np.ndarray, Dict[int, str]]:
        if self._done is None:
            self._finish(np.asarray(self._resp))
        return self._done, self.errors


def resolve_ticks(handles: Sequence[TickHandle]) -> None:
    """Materialize many dispatched ticks' responses in as few D2H
    transfers as possible: same-shape response buffers are stacked on
    device (a cheap async op) and fetched in ONE host transfer.

    Each D2H transfer has a fixed cost, so resolving K ticks together
    pays it once instead of K times (how much that is worth on a local
    chip: not measured)."""
    todo = [h for h in handles if h._done is None]
    if len(todo) <= 1:
        for h in todo:
            h.result()
        return
    groups: Dict[tuple, List[TickHandle]] = {}
    for h in todo:
        groups.setdefault(tuple(h._resp.shape), []).append(h)
    for hs in groups.values():
        if len(hs) == 1:
            hs[0].result()
            continue
        stacked = np.asarray(jnp.stack([h._resp for h in hs]))
        for k, h in enumerate(hs):
            h._finish(stacked[k])


class SubmittedBatch:
    """A dispatched object-level batch (one or more chunked ticks); the
    tick loop resolves it off the dispatch thread."""

    __slots__ = ("_handles", "_spans", "_n")

    def __init__(self, handles, spans, n):
        self._handles = handles
        self._spans = spans
        self._n = n

    def handles(self) -> List[TickHandle]:
        return self._handles

    def matrix(self) -> tuple[np.ndarray, Dict[int, str]]:
        """(5, n) response matrix in request order + per-item errors
        (the columnar result shape; responses() wraps it in dataclasses)."""
        resolve_ticks(self._handles)  # one D2H for all chunks
        out = np.empty((5, self._n), np.int64)
        errors: Dict[int, str] = {}
        for h, (s, e) in zip(self._handles, self._spans):
            rm, errs = h.result()
            out[:, s:e] = rm
            for i, msg in errs.items():
                errors[s + i] = msg
        return out, errors

    def responses(self) -> List[RateLimitResponse]:
        resolve_ticks(self._handles)  # one D2H for all chunks
        out: List[Optional[RateLimitResponse]] = [None] * self._n
        for h, (s, e) in zip(self._handles, self._spans):
            rm, errors = h.result()
            status, limit, remaining, reset = (rm[r].tolist() for r in range(4))
            for i in range(e - s):
                out[s + i] = (
                    RateLimitResponse(error=errors[i])
                    if i in errors
                    else RateLimitResponse(
                        status=status[i],
                        limit=limit[i],
                        remaining=remaining[i],
                        reset_time=reset[i],
                    )
                )
        return out  # type: ignore[return-value]


class TickEngine:
    """Owns the device state table and applies request batches tick by tick.

    Thread-safe: the service layer calls :meth:`process` from its tick loop;
    loaders/metrics may snapshot concurrently.
    """

    def __init__(
        self,
        capacity: int = 1 << 16,
        max_batch: int = 4096,
        device: Optional[jax.Device] = None,
        store=None,
        table_layout: str = "auto",
        bg_reclaim: Optional[bool] = None,
        cold_capacity: int = 0,
        ssd=None,
    ):
        self.capacity = int(capacity)
        self.max_batch = int(max_batch)
        # Optional write/read-through Store (reference store.go:49-65).
        # Write-through costs one extra D2H readback of touched slots per
        # tick; read-through one extra scatter when misses hit the store.
        self.store = store
        # Tiered bucket state (docs/tiering.md): a host-side cold store
        # LRU victims demote into (readback-then-evict) and misses
        # promote out of, so bucket continuity survives hot↔cold cycling
        # — without it, eviction zeroes the row and a key cycling back
        # in restarts with a full budget.  0 = disabled (strict
        # evict-destroys semantics, the reference's lrucache.go:138-149).
        self.cold = None
        if cold_capacity > 0:
            from gubernator_tpu.tiering import ColdStore

            self.cold = ColdStore(int(cold_capacity), store=store)
        # Third tier (docs/tiering.md): an SsdStore absorbing the cold
        # tier's overflow.  It interposes as the cold tier's write-behind
        # sink — the engine-level Store keeps its write/read-through
        # roles — and the miss path gains one batched hop (hot miss →
        # cold miss → SSD take_batch) whose hits merge into the SAME
        # one-scatter-per-tick restore as cold hits.
        self.ssd = ssd
        if ssd is not None:
            if self.cold is None:
                raise ValueError(
                    "SSD tier requires a cold tier (cold_capacity > 0): "
                    "the SSD store only ever holds cold-tier overflow"
                )
            self.cold.store = ssd
        self.device = device or jax.devices()[0]
        self.layout = make_layout_choice(
            table_layout, self.capacity, self.device, self.max_batch
        )
        zeros, _, _ = _layout_ops(self.layout)
        with jax.default_device(self.device):
            self.state = jax.tree.map(jnp.asarray, zeros(self.capacity))
        # Mixed/ineligible duplicate batches run the parts-native chained
        # unit-round program (tick32.make_sorted_tick32_rows_fn): exact
        # per-slot order, ceil(units/8) gather+scatter rounds, no XLA
        # 64-bit emulation.  The x64 program (engine.make_tick_fn) stays
        # the parity reference in tests and the mesh engine's walker.
        from gubernator_tpu.config import env_knob

        from gubernator_tpu.ops.tick32 import (
            jitted_merged_pipeline,
            jitted_sorted_tick32,
            jitted_tick32,
        )

        self._tick = jitted_sorted_tick32(self.capacity, self.layout)

        # Note on request-buffer donation: the request slab
        # has no same-shape program output, and XLA's input-output
        # aliasing is exact-shape, so donating it buys nothing (jax
        # warns "donated buffers were not usable").  The double-buffered
        # H2D contract is therefore: donated STATE buffers + the host
        # staging ring + async upload — each window's upload rides
        # under the previous window's tick, and the request buffer is
        # simply dropped when its tick completes.
        self._tick32 = jitted_tick32(self.capacity, self.layout)
        # Grouped batches (uniform duplicate groups — Zipf/hot-key
        # traffic) tick each unique head once with a closed-form follower
        # fold, then expand per-member responses elementwise: the
        # scatter-add architecture from BASELINE.json.  Serving-scale
        # engines warm it per width (see _warmup); small test-cluster
        # engines compile lazily on the first grouped batch.
        self._tick32m = jitted_merged_pipeline(self.capacity, self.layout)
        # Tick widths: one narrow program for typical service batches
        # (≤ the reference's 1000-item batch limit) plus the full width.
        # Singleton for small engines so test clusters don't pay an extra
        # compile per daemon.
        mb = pad_pow2(self.max_batch)
        self._widths = (
            (mb,) if mb < 2048 else tuple(sorted({max(1024, mb // 4), mb}))
        )
        self._evict = _jitted_evict(self.layout)
        self._install = _jitted_install(self.layout)
        self._restore = _jitted_restore(self.layout)
        self._readback = _jitted_readback(self.layout)
        # Double-buffered H2D staging (docs/architecture.md): the
        # packed request matrix for each window is built in a reusable
        # host slab and uploaded with an *async* host→device copy, so window
        # N+1's transfer rides the link while window N's tick still
        # runs on device.  The ring holds 2x the tick pipeline depth of
        # slabs per program width: a slab recycles only once the tick
        # that consumed it has resolved (its H2D is then provably
        # complete — jax may read the host buffer until the transfer
        # finishes), and when every slab is still in flight the lease
        # falls back to a fresh allocation rather than corrupting one.
        try:
            _depth = max(1, env_knob(
                "GUBER_TICK_PIPELINE_DEPTH", 4, parse=int))
        except ValueError:
            _depth = 4
        self._stage_depth = 2 * _depth + 1
        self._staging = StagingRing(
            SLAB_ROWS, self.capacity, self._stage_depth
        )
        # H2D overlap telemetry: a window counts as overlapped when its
        # upload was dispatched while at least one earlier window was
        # still unresolved — the pipelined steady state.  /debug/state
        # and the gubernator_tpu_h2d_overlap_ratio gauge read
        # overlapped/windows (h2d_overlap_ratio below).
        self._inflight = 0
        self.metric_h2d_windows = 0
        self.metric_h2d_overlapped = 0
        # Host→device uploads submit_columns issued: over
        # metric_h2d_windows it reads 1.0 where every window is one
        # buffer (grouped, unique, sequential; a layered window's plan
        # is still seven).
        self.metric_h2d_uploads = 0
        self.slots = make_slot_map(self.capacity)
        # The host pack in one native call (_build_cols): there with the
        # native slot map, and then taken by every window it can answer.
        # Windows it packed, to read against metric_h2d_windows.
        self._native_pack = hasattr(self.slots, "pack_window")
        self.metric_native_pack_windows = 0
        self._last_access = np.zeros(self.capacity, np.int64)
        # Slots mutated since the last export — the incremental snapshot's
        # working set (export_columns(dirty_only=True)).  Marked at the
        # three mutation sites (tick, GLOBAL install, snapshot restore);
        # cleared by any export.  The reference's Store OnChange trickles
        # per-request updates continuously (store.go:49-65); here the
        # delta accumulates host-side and drains on the export cadence.
        self._dirty = np.zeros(self.capacity, bool)
        # Slots assigned host-side but not yet written by a device tick; the
        # device's in_use lags for these, so reclamation must not treat them
        # as dead (or two live keys could share a slot within one tick).
        self._pending: set = set()
        self._tick_count = 0
        self._lock = sanitize.rlock("TickEngine._lock")
        # Background reclaim (SURVEY §7 "reclaim off the serving path"):
        # when free slots dip under the low watermark AND the batch had
        # misses, a reclaimer thread runs TTL-then-LRU victim selection on
        # snapshots outside the lock, so a full 10M-slot table doesn't put
        # an argpartition + dead-scan D2H on the p99 of a serving tick.
        # Auto-enabled for big tables only: small tables keep the strict
        # evict-at-capacity semantics (reference lrucache.go:138-149) that
        # the behavior suite pins, and the sync fallback still guarantees
        # progress when the reclaimer is behind.
        self._bg_reclaim = (
            bg_reclaim if bg_reclaim is not None else self.capacity >= (1 << 18)
        )
        self._reclaim_low = min(
            self.capacity // 8, max(2 * self.max_batch, self.capacity // 64)
        )
        self._reclaim_evt = threading.Event()
        self._reclaim_closed = False
        self._reclaim_thread: Optional[threading.Thread] = None
        # Request-time clock: the max `now` any tick has seen.  Background
        # reclaim judges TTL expiry against THIS, not the wall clock —
        # callers may drive synthetic time (tests, replay harnesses).
        self._last_now = 0
        # Metrics mirrors (lrucache.go:48-59, gubernator.go:60-111 families).
        self.metric_hits = 0
        self.metric_misses = 0
        self.metric_over_limit = 0
        # Rows dispatched with algorithm LEAKY: the share of decisions
        # that take the float64 leaky path (ops/b64.py).
        self.metric_leaky_rows = 0
        self.metric_unexpired_evictions = 0
        # Windows by the dispatch branch that answered them
        # (submit_columns): the four add up to metric_h2d_windows.  In a
        # trace the programs are jit_tick32_unique / _grouped /
        # _sequential / _layered (ops/tick32.py).
        self.metric_unique_ticks = 0
        self.metric_grouped_ticks = 0
        self.metric_sequential_ticks = 0
        self.metric_layered_ticks = 0
        # Tiering telemetry: cold lookups that hit on the miss path,
        # batched restore scatters the promote path dispatched (and the
        # ticks that needed one — their ratio must stay 1.0: promotion
        # is one scatter per tick, never per key), readback dispatches
        # the demote path ran, and reclaim rounds that had LRU victims
        # (readbacks happen ONLY inside those).  Shed counts requests
        # answered with a per-item table-full error instead of a raise.
        self.metric_cold_hits = 0
        self.metric_promotions = 0
        self.metric_promote_dispatches = 0
        self.metric_promote_ticks = 0
        self.metric_demote_readbacks = 0
        self.metric_evict_reclaims = 0
        self.metric_shed_requests = 0
        # SSD-tier exact-work telemetry: lookups counts take_batch
        # calls (exactly 1 per tick that still had misses after the
        # cold hop), and tick_path_reads is the structural proof that no
        # SSD read ever lands inside the tick-dispatch block (must stay
        # 0).  tests/test_ssd.py::test_three_tier_churn_keeps_consumed_budget
        # holds both.
        self.metric_ssd_hits = 0
        self.metric_ssd_lookups = 0
        self.metric_ssd_miss_ticks = 0
        self.metric_ssd_tick_path_reads = 0
        # Cooperative quota-lease columns (docs/leases.md): per-slot
        # outstanding delegated budget, lease expiry (epoch ms), and
        # generation — device-resident so grant/renew/reconcile land as
        # ONE batched scatter per window (lease_window; the exact-work
        # dispatch counter below proves one dispatch per window) and so
        # delegations survive a snapshot round-trip (LEASE_SNAP_FIELDS).
        # Nomenclature: StagingRing "leases" are H2D slab reservations;
        # everything lease_* on the engine is quota leases.
        self._lease_budget = jnp.zeros(self.capacity, jnp.int64)
        self._lease_expire = jnp.zeros(self.capacity, jnp.int64)
        self._lease_gen = jnp.zeros(self.capacity, jnp.int32)
        self.metric_lease_dispatches = 0
        self.metric_lease_windows = 0
        self.metric_lease_ops = 0
        # the fills' seconds and the rows they landed (load_columns)
        self.load_seconds = 0.0
        self.load_rows = 0
        t0 = time.perf_counter()
        self._warmup()
        self.warmup_seconds = time.perf_counter() - t0

    def describe(self) -> dict:
        from gubernator_tpu.ops.tick32 import _resolve_fused

        d = describe_engine(
            self.device, 1, self.layout,
            self.layout == "row" and _resolve_fused(None),
            self.warmup_seconds,
            native_pack=self._native_pack,
            load_seconds=self.load_seconds, load_rows=self.load_rows,
        )
        d["leaky_rows"] = self.metric_leaky_rows
        return d

    def _warmup(self) -> None:
        """Compile the tick/install programs now (first compile is seconds;
        it must land at startup, not on the first live request's deadline).
        An all-padding batch leaves the zeroed state untouched.

        The response matrix is materialized host-side too, so the first
        D2H of each buffer shape is paid here and not on the first live
        request, where a slow one would blow the 500ms peer batch_timeout
        and trigger forward retries that double-count hits."""
        on_chip = jax.default_backend() == "tpu"
        for w in self._widths:
            # A window's upload as submit_columns makes it: the slab, all
            # padding, ``now`` (0) in its last row.
            m = np.zeros((SLAB_ROWS, w), np.int32)
            m[REQ32_INDEX["slot"]] = self.capacity
            if on_chip:
                # The sequential chained-unit program only serves
                # adversarial duplicate shapes; like the layered warmup
                # below, eager-compiling it is a serving chip's live-
                # deadline concern — on the CPU backend (the tests) most
                # engines never tick it and lazy is the right trade.
                self.state, resp = self._tick(self.state, jnp.asarray(m))
                np.asarray(resp)
            self.state, resp = self._tick32(self.state, jnp.asarray(m))
            np.asarray(resp)
        # Warm the grouped (scatter-add) pipeline at each width's floor
        # head shape (group_upad — the shape every sub-quantum hot-key
        # window hits) so the first grouped batch doesn't pay the
        # compile on a live deadline; on a serving chip at the deeper
        # head widths too (grouped_warm_shapes).
        # Gated to serving-scale engines: test-cluster engines (small
        # capacity, usually no duplicate traffic) skip the extra
        # compiles.
        if self.capacity >= (1 << 14):
            for w, upad in grouped_warm_shapes(self._widths, on_chip):
                # The plan of an all-padding window, in the one buffer
                # a grouped window uploads (plan_views).
                buf = np.zeros(plan_words(w, upad), np.int32)
                mh, count, uidx, _, _ = plan_views(buf, w)
                mh[REQ32_INDEX["slot"]] = self.capacity
                count[:] = 1
                uidx[:] = upad - 1
                self.state, resp = self._tick32m(
                    self.state, jnp.asarray(buf), w)
                np.asarray(resp)
        if self.capacity >= (1 << 16) and on_chip:
            # Warm the layered pipeline's most common shape (w0 at the
            # narrow width's floor, 2 layers — what a typical mixed-herd
            # serving batch plans to) so the first live one doesn't pay
            # the compile; deeper/wider shapes stay lazy, as do
            # mid-sized engines (in-process test clusters default to
            # 50k-slot tables and rarely see mixed-duplicate traffic —
            # their first such batch compiles then).  TPU-only: the
            # live-deadline concern is a serving chip's; on the CPU
            # backend (the tests) the same compile costs minutes per
            # engine and lazy is the right trade.
            from gubernator_tpu.ops.tick32 import jitted_layered_pipeline

            w = self._widths[0]
            w0 = group_upad(w)
            mh0 = np.zeros((REQ32_ROWS, w0), np.int32)
            mh0[REQ32_INDEX["slot"]] = self.capacity
            mhk = np.zeros((1, REQ32_ROWS, 512), np.int32)
            mhk[:, REQ32_INDEX["slot"], :] = self.capacity
            m32 = np.zeros((SLAB_ROWS, w), np.int32)
            m32[REQ32_INDEX["slot"]] = self.capacity
            fn = jitted_layered_pipeline(self.capacity, self.layout, w0, 2)
            self.state, resp = fn(
                self.state, jnp.asarray(mh0), jnp.ones(w0, np.int32),
                jnp.asarray(mhk), jnp.ones((1, 512), np.int32),
                jnp.asarray(m32), jnp.zeros(w, np.int32),
                jnp.zeros(w, np.int32),
            )
            np.asarray(resp)
        cols = np.zeros((8, 1), np.int64)  # valid=0 row: install is a no-op
        self.state = self._install(self.state, jnp.asarray(cols), jnp.int64(0))
        # Compile the reclaim dead-scan now too: its first invocation
        # otherwise jits a capacity-wide program on the serving path, right
        # when the table first fills (tens of seconds on slow toolchains).
        self._dead_mask(0)
        jax.block_until_ready(self.state)

    def _dead_bits(self, now: int):
        """Dispatch the device dead-slot scan (packed bitmask, on device)."""
        if self.layout == "row":
            return rowtable.row_device_dead_bits(self.state, now)
        return device_dead_bits(self.state.in_use, self.state.expire_at, now)

    def _dead_mask(self, now: int) -> np.ndarray:
        return self._unpack_dead(self._dead_bits(now))

    def _unpack_dead(self, bits) -> np.ndarray:
        """The host mask of :meth:`_dead_bits` (each layout's scan packs
        its own way)."""
        if self.layout == "row":
            return rowtable.unpack_row_dead_bits(bits, self.capacity)
        return unpack_dead_bits(bits, self.capacity)

    # ------------------------------------------------------------------
    # Host-side request preparation
    # ------------------------------------------------------------------
    def _resolve_slot(self, key: str, now: int) -> tuple[int, bool]:
        known = self.slots.get(key) is not None
        slot = self.slots.assign(key)
        if slot is None:
            self._reclaim(now)
            slot = self.slots.assign(key)
            if slot is None:
                raise RuntimeError("rate-limit table full; eviction failed")
        if not known:
            self._pending.add(slot)
        if known:
            self.metric_hits += 1
        else:
            self.metric_misses += 1
        return slot, known

    def _reclaim(self, now: int, want: Optional[int] = None) -> None:
        """Free expired slots; fall back to LRU eviction (lrucache.go:115-149).

        LRU victims take the readback-then-evict path: their rows are
        pulled D2H *before* the evict scatter and demoted into the cold
        tier (when one is configured), so unexpired bucket state survives
        eviction instead of evaporating (docs/tiering.md)."""
        mapped = self.slots.mapped_mask()
        if self._pending:
            mapped[np.fromiter(self._pending, np.int64)] = False
        freed, victims = select_reclaim_victims(
            mapped,
            self._dead_mask(now),
            self._last_access,
            self._tick_count,
            want or max(1, self.capacity // 16),
        )
        self.slots.release_batch(freed)
        if len(victims) == 0:
            if self.cold is not None:
                self.cold.expire(now)
            return
        self.metric_unexpired_evictions += len(victims)
        finish = self._demote_dispatch(victims, now)
        self.slots.release_batch(victims)
        self.state = evict_chunked(self._evict, self.state, victims, self.capacity)
        finish()
        if self.cold is not None:
            self.cold.expire(now)

    def _demote_dispatch(self, victims: np.ndarray, now: int):
        """Readback-then-evict, dispatch half: queue the D2H readback of
        the victim rows *before* the caller's evict scatter (same device
        stream — program order guarantees the readback observes pre-evict
        state) and capture the victims' keys before the slot map releases
        them.  Returns a finish closure that materializes the readback
        (the D2H wait), lands live rows in the cold tier, and fires
        ``Store.remove`` for rows leaving the tiered cache entirely — the
        documented remove-on-eviction contract (store.py) the old blind
        zeroing never honored.  The background reclaimer runs the closure
        outside the engine lock; the sync path runs it inline.

        Called only from reclaim rounds that selected LRU victims — a
        reclaim-free tick never pays a readback."""
        self.metric_evict_reclaims += 1
        if self.cold is None and self.store is None:
            return lambda: None
        keys = self.slots.keys_batch(victims)
        if self.cold is None:
            # No cold tier: eviction is terminal — honor Store.remove
            # (store.py: "remove on eviction") without any device work.
            def finish_remove():
                for k in keys:
                    if k:
                        # guber: allow-g009(Store.remove is the pluggable Store contract's thread-safe entry point; the engine calls it but never rebinds self.store after __init__)
                        self.store.remove(k.decode())

            return finish_remove
        pending = []
        for start in range(0, len(victims), RESTORE_CHUNK):
            part = victims[start : start + RESTORE_CHUNK]
            padded = np.full(pad_pow2(len(part)), self.capacity, np.int64)
            padded[: len(part)] = part
            self.metric_demote_readbacks += 1
            pending.append(
                (len(part), self._readback(self.state, jnp.asarray(padded)))
            )

        def finish():
            off = 0
            for k_n, (ints, floats) in pending:
                im = np.asarray(ints)[:, :k_n]
                fl = np.asarray(floats)[:k_n]
                part_keys = keys[off : off + k_n]
                off += k_n
                f = dict(zip(READBACK_ROWS, im))
                # Rows dead on device (never ticked, or TTL-expired) are
                # not demoted — resurrecting them would hand the next
                # tenant stale state; they leave the cache entirely.
                live = (f["in_use"] != 0) & (f["expire_at"] >= now)
                sel = np.flatnonzero(live)
                if len(sel):
                    cols = {
                        name: f[name][sel]
                        for name in READBACK_ROWS
                        if name != "in_use"
                    }
                    cols["remaining_f"] = fl[sel]
                    self.cold.put_columns(
                        [part_keys[int(j)] for j in sel], cols, now
                    )
                if self.store is not None:
                    for j in np.flatnonzero(~live):
                        k = part_keys[int(j)]
                        if k:
                            self.store.remove(k.decode())

        return finish

    # ------------------------------------------------------------------
    # Background reclaim
    # ------------------------------------------------------------------
    def _maybe_trigger_reclaim(self) -> None:
        """Wake the reclaimer when free slots dip under the watermark.
        Called under the lock from the build path, only when the batch had
        misses — a full table under pure-hit traffic must NOT evict (the
        reference evicts on insert pressure only, lrucache.go:88-103)."""
        if not self._bg_reclaim or self._reclaim_closed:
            return
        if self.capacity - len(self.slots) >= self._reclaim_low:
            return
        if self._reclaim_thread is None:  # lazy: most engines never need it
            self._reclaim_thread = threading.Thread(
                target=self._reclaim_loop, daemon=True, name="guber-reclaim"
            )
            self._reclaim_thread.start()
        self._reclaim_evt.set()

    def _reclaim_loop(self) -> None:
        import logging

        while True:
            self._reclaim_evt.wait()
            self._reclaim_evt.clear()
            if self._reclaim_closed:
                return
            try:
                self._reclaim_background()
            except Exception:
                logging.getLogger("gubernator.engine").exception(
                    "background reclaim failed"
                )

    def _reclaim_background(self) -> None:
        """One reclaim round with the expensive work off the lock.

        Phase 1 (lock): *dispatch* the device dead-scan — must happen under
        the lock because the next tick donates the state buffers.  Expiry
        is judged against the engine's request-time clock (``_last_now``),
        NOT the host wall clock: callers may drive synthetic time (tests,
        replay), and the reference's expiry is always relative to request
        ``CreatedAt`` (algorithms.go:46-57).
        Phase 2 (no lock): materialize the dead bitmask (D2H wait).
        Phase 3 (lock): snapshot mapped/pending/last_access.
        Phase 4 (no lock): TTL-then-LRU victim selection (argpartition over
        the table — the cost that used to spike serving p99).
        Phase 5 (lock): revalidate — drop any candidate touched since the
        snapshot (later builds stamp tick_count > snap under the lock) —
        then release slots and dispatch the evict scatter (async).
        """
        with self._lock:
            # Size the round to the watermark deficit (target: 2x the low
            # watermark free, capped at the sync quantum) — the trigger
            # may have been satisfied already by an earlier round.
            free = self.capacity - len(self.slots)
            want = min(self.capacity // 16, 2 * self._reclaim_low - free)
            if want <= 0 or self._last_now == 0:
                return
            # snap is taken HERE, before the scan is dispatched: the dead
            # bitmask is stale for anything that ticks during the D2H
            # wait, and the phase-5 `la <= snap` filter must therefore
            # drop every slot touched at tick > snap — a bucket revived
            # mid-wait must not be freed on the strength of the old scan.
            snap = self._tick_count
            bits = self._dead_bits(self._last_now)
        dead = self._unpack_dead(bits)
        with self._lock:
            mapped = self.slots.mapped_mask()
            if self._pending:
                mapped[np.fromiter(self._pending, np.int64)] = False
            la = self._last_access.copy()
        freed, victims = select_reclaim_victims(mapped, dead, la, snap, want)
        finish = None
        with self._lock:
            freed = freed[self._last_access[freed] <= snap]
            victims = victims[self._last_access[victims] <= snap]
            self.slots.release_batch(freed)
            if len(victims):
                self.metric_unexpired_evictions += len(victims)
                # Dispatch the demote readback BEFORE the evict scatter
                # (device program order = pre-evict state) but run the
                # D2H wait + cold-tier insert outside the lock.
                finish = self._demote_dispatch(victims, self._last_now)
                self.slots.release_batch(victims)
                # guber: allow-g009(every post-start touch holds _lock; the unguarded peers are _warmup, which runs in __init__ before the reclaim thread exists)
                self.state = evict_chunked(
                    self._evict, self.state, victims, self.capacity
                )
        if finish is not None:
            finish()
        if self.cold is not None:
            self.cold.expire(self._last_now)

    def close(self) -> None:
        """Stop the background reclaimer.  Engines are otherwise GC-safe
        (the thread is a daemon and lazily started); services close via
        V1Instance.close."""
        self._reclaim_closed = True
        self._reclaim_evt.set()
        t = self._reclaim_thread
        if t is not None:
            t.join(timeout=5)
        # The engine owns its SSD tier's writer thread: drain + stop it
        # so staged demote batches reach disk before the process exits.
        if self.ssd is not None:
            self.ssd.close()

    @hot_path
    def _lease_matrix(self, b: int) -> np.ndarray:
        """A (SLAB_ROWS, b) staging slab from the per-width ring — see
        :class:`StagingRing` for the recycle contract.  Zeroed, its slot
        row at the padding sentinel, unless the native window pass is
        there to do that (it cleans the REQ32 rows it packs; the numpy
        pack cleans those the pass hands back).  Called under the engine
        lock (ring state is unsynchronized)."""
        with flightrec.stage("lease"):
            return self._staging.lease(b, clean=not self._native_pack)

    @hot_path
    def _build_cols(self, cols: ReqColumns, now: int):
        """One window's host pack: keys to slots, the padded (19, B)
        request matrix in slot order, the dirty marks, and the grouped
        plan where duplicates qualify.  Returns ``(slab, n, errors, inv,
        has_dups, plan)``: ``slab`` is the (SLAB_ROWS, B) staging slab,
        the request matrix in its REQ32 rows and ``now`` stamped in its
        last one — what a window without a grouped plan uploads.

        The window the served path sees all day takes ONE native call
        (native/slotmap.cc guber_slotmap_pack_window; for a wide window
        ctypes drops the GIL for all of it, so the gRPC thread and the
        resolver run beside it, where some hundred short numpy calls
        each dropped the GIL and waited to take it back; a narrow
        window's call keeps it, NativeSlotMap.pack_window).  What the
        batch shows decides, no knob: a Gregorian row
        (host calendar math), a key that finds no slot (reclaim and
        retry), or a new key with a Store or cold tier to ask first
        takes :meth:`_build_cols_numpy` — from the slots the native pass
        already resolved, where it got that far.

        A single int32 matrix means one H2D transfer per tick; per-transfer
        latency dominates small ticks.
        """
        n = len(cols)
        if n > self.max_batch:
            raise ValueError(f"batch of {n} exceeds engine max {self.max_batch}")
        # Width quantization: a tick's device cost scales with the padded
        # width (scatter lanes), so small batches use the narrow program
        # instead of paying for max_batch lanes of padding.  Both widths
        # are compiled at warmup.
        b = next(w for w in self._widths if w >= n)
        slab = self._lease_matrix(b)
        stamp_now(slab[REQ32_ROWS], now)
        m = slab[:REQ32_ROWS]
        resolved = None
        if self._native_pack:
            sm = self.slots
            status, slots, known, inv, n_miss, plan, n_leaky = sm.pack_window(
                cols, m, now,
                self.store is not None or self.cold is not None,
                self._last_access, self._tick_count, self._dirty,
                group_upad(b, n),
            )
            if status >= 0:
                if n_miss:
                    self._pending.update(slots[known == 0].tolist())
                    # Insert pressure near a full table: reclaim in the
                    # background (see _build_cols_numpy).
                    self._maybe_trigger_reclaim()
                self.metric_hits += n - n_miss
                self.metric_misses += n_miss
                self.metric_leaky_rows += n_leaky
                self.metric_native_pack_windows += 1
                return slab, n, {}, inv, status != sm.PACK_UNIQUE, plan
            if status == sm.PACK_RESOLVED_ONLY:
                resolved = slots, known
            self._staging.clean(m)  # the pass left the rows as leased
        return self._build_cols_numpy(cols, now, slab, resolved)

    @hot_path
    def _build_cols_numpy(self, cols: ReqColumns, now: int, slab: np.ndarray,
                          resolved=None):
        """:meth:`_build_cols` in numpy, for the windows the native pass
        leaves (and for the pure-Python slot map): one blob resolve
        (``resolved``: the native pass's ``(slots, known)`` where it has
        them already, so no key is resolved twice) + a dozen vectorized
        numpy writes + one argsort + the plan, into the REQ32 rows of
        ``slab``."""
        n = len(cols)
        R = REQ32_INDEX
        m = slab[:REQ32_ROWS]
        errors: Dict[int, str] = {}

        # Gregorian resolution (host-side calendar math) — only requests
        # carrying the flag pay for it; failures become per-item errors.
        GREG = int(Behavior.DURATION_IS_GREGORIAN)
        greg = cols.behavior & GREG
        if greg.any():
            for i in np.flatnonzero(greg):
                try:
                    d = int(cols.duration[i])
                    pack_wide_rows(
                        m, "greg_exp", timeutil.gregorian_expiration(now, d), i
                    )
                    pack_wide_rows(
                        m, "greg_dur", timeutil.gregorian_duration(now, d), i
                    )
                except timeutil.GregorianError as exc:
                    errors[int(i)] = str(exc)

        # One native call resolves every key to a slot (the reference does a
        # per-key map lookup inside each worker goroutine; here it's a batch
        # against the C++ open-addressing table, fed the key blob directly).
        if errors:
            # guber: allow-G001(builds a host index list, never device)
            sel = np.array([i for i in range(n) if i not in errors], np.int64)
            if len(sel) == 0:
                return slab, n, errors, np.arange(n, dtype=np.int64), False, None
            slots, known = self.slots.resolve_batch(
                [cols.key_bytes(int(i)) for i in sel]
            )
        else:
            sel = None  # the whole batch, contiguous
            slots, known = resolved or self.slots.resolve_blob(
                cols.key_blob, cols.key_offsets
            )
        if (slots < 0).any():
            # Stamp the already-resolved rows live *before* reclaiming:
            # fresh misses look unused on device and known slots carry a
            # stale _last_access, so an unstamped reclaim could release
            # slots resolved microseconds ago and hand them to the retried
            # keys — two keys sharing one bucket within the same tick.
            ok = slots >= 0
            self._last_access[slots[ok]] = self._tick_count
            self._pending.update(slots[ok & (known == 0)].tolist())
            # Free at least as many slots as this batch still needs — the
            # capacity//16 default can be smaller than one batch's misses,
            # which would fail the retry with room still reclaimable.
            needed = int((~ok).sum())
            self._reclaim(now, want=max(needed, self.capacity // 16))
            retry = np.flatnonzero(slots < 0)
            retry_src = retry if sel is None else sel[retry]
            s2, k2 = self.slots.resolve_batch(
                [cols.key_bytes(int(j)) for j in retry_src]
            )
            slots[retry] = s2
            known[retry] = k2
            if (slots < 0).any():
                # Graceful degradation: a truly full table (reclaim freed
                # nothing — e.g. every slot is pending in this very batch)
                # sheds the unplaceable items with per-item errors instead
                # of failing the whole batch (the reference's
                # error-in-item convention, gubernator.go:208-216); the
                # rest of the batch is still served.
                shed = np.flatnonzero(slots < 0)
                shed_src = shed if sel is None else sel[shed]
                for j in shed_src:
                    errors[int(j)] = "rate-limit table full; eviction failed"
                self.metric_shed_requests += len(shed)
                keep = slots >= 0
                sel = (
                    np.flatnonzero(keep)
                    if sel is None
                    # guber: allow-G001(sel is host numpy, never device)
                    else np.asarray(sel)[keep]
                ).astype(np.int64)
                slots = slots[keep]
                known = known[keep]
                if len(slots) == 0:
                    return slab, n, errors, np.arange(n, dtype=np.int64), False, None
        self._last_access[slots] = self._tick_count
        miss = known == 0
        self._pending.update(slots[miss].tolist())
        n_miss = int(miss.sum())
        self.metric_hits += len(miss) - n_miss
        self.metric_misses += n_miss
        if n_miss:
            # Insert pressure near a full table: reclaim in the background
            # so the dead-scan/argpartition never lands on a serving tick.
            self._maybe_trigger_reclaim()

        if self.cold is not None and miss.any():
            miss = self._promote_misses(cols, sel, slots, known, miss, now)

        if self.store is not None and miss.any():
            if cols.refs is None:
                raise ValueError(
                    "Store read-through needs request objects; build the "
                    "batch with ReqColumns.from_requests(..., keep_refs=True)"
                )
            rt_sel = np.arange(n, dtype=np.int64) if sel is None else sel
            self._read_through(cols.refs, rt_sel, slots, known, miss)

        # Vectorized pack: plain slices on the (typical) no-error batch,
        # fancy-indexed writes when error rows must be skipped.  Narrow
        # fields write one i32 row; 8-byte fields write (lo, hi) pairs
        # (pack_wide_rows) — the compact wire format unpack_reqs_compact
        # reads on device.
        ix = slice(0, n) if sel is None else sel
        pack_cols_req32(m, cols, slots, known, now, ix)
        self.metric_leaky_rows += int(np.count_nonzero(
            m[R["algorithm"], ix] == int(Algorithm.LEAKY_BUCKET)))
        # Sort the batch by slot (stable: same-slot requests keep arrival
        # order, the duplicate-sequencing contract).  The tick's
        # sorted-input path then does all segment math with neighbor
        # compares + scans — a host argsort here is ~100x cheaper than
        # the device-side gathers/scatters it replaces.  Error rows
        # (slot=capacity) sort to the end with the padding; sorted
        # neighbors then reveal duplicate slots for free (unique batches
        # dispatch to the parts-native program, duplicate-bearing ones
        # to the merge-capable program).
        inv, has_dups = sort_packed_by_slot(m, n, self.capacity)
        # Dirty marking feeds export_columns(dirty_only=True); pure
        # queries — hits == 0 on a known slot, no RESET_REMAINING —
        # read bucket state without moving it, so marking them would
        # inflate deltas under read-heavy traffic (advisor finding).
        # Unknown slots always mark (the tick creates the row), as
        # does RESET (removal/refill).  A leaky-bucket query can
        # refill tokens on device, but the refill is derived from
        # (updated_at, now) and recomputes identically after a
        # baseline+delta restore, so skipping it loses nothing.
        tick_slots = m[R["slot"], :n]
        hr = R["hits"]
        mutating = (
            (m[hr, :n] != 0)
            | (m[hr + 1, :n] != 0)
            | (m[R["known"], :n] == 0)
            | ((m[R["behavior"], :n] & int(Behavior.RESET_REMAINING)) != 0)
        )
        mut_slots = tick_slots[mutating & (tick_slots < self.capacity)]
        if len(mut_slots):
            self._dirty[mut_slots] = True
        plan = (
            build_group_plan(m, n, self.capacity, now) if has_dups else None
        )
        return slab, n, errors, inv, has_dups, plan

    @hot_path
    def _promote_misses(
        self, cols: ReqColumns, sel, slots, known, miss, now: int
    ) -> np.ndarray:
        """Consult the cold tier for this batch's misses and batch-reinstall
        the hits via ONE restore scatter before the tick runs — the
        promote half of the tiering flow (docs/tiering.md).  Promotion is
        a move: the cold tier drops its copy, the device row becomes the
        owner, and the request proceeds as a *known* slot so the bucket
        keeps its consumed budget (no fresh-bucket bypass).  Returns the
        updated miss mask (read-through only sees what stayed cold-miss).

        Duplicate keys in one batch resolve to one miss row (the slot
        map marks later occurrences known), so hit rows map to unique
        slots and the single scatter has no write conflicts.

        With an SSD tier attached, keys that also miss cold take one
        more hop — ONE batched ``take_batch`` against the slab store per
        tick (never per key; tests/test_ssd.py holds the ratio) — and its
        hits merge into the same scatter, so the promote dispatch count
        is unchanged by the third tier.  The SSD read seconds are recorded
        as the flight recorder's "ssd" stage and subtracted from "pack"
        (which brackets all of _build_cols), keeping the tick/pack
        stages clean of SSD I/O by construction."""
        midx = np.flatnonzero(miss)
        # guber: allow-G001(sel is host numpy, never device)
        src = midx if sel is None else np.asarray(sel)[midx]
        keys = [cols.key_bytes(int(j)) for j in src]
        pos, ccols = self.cold.take(keys, now)
        self.metric_cold_hits += len(pos)
        if self.ssd is not None and len(pos) < len(midx):
            cold_hit = np.zeros(len(midx), bool)
            if len(pos):
                cold_hit[pos] = True
            rem = np.flatnonzero(~cold_hit)
            fr = flightrec.get()
            with flightrec.stage("ssd") as read:
                spos, scols = self.ssd.take_batch(
                    [keys[int(j)] for j in rem], now
                )
            if fr is not None:
                read.out_of("pack")
            self.metric_ssd_lookups += 1
            self.metric_ssd_miss_ticks += 1
            if len(spos):
                self.metric_ssd_hits += len(spos)
                srows = rem[spos]
                if len(pos):
                    pos = np.concatenate([pos, srows])
                    ccols = {
                        f: np.concatenate([ccols[f], scols[f]])
                        for f in scols
                    }
                else:
                    pos, ccols = srows, scols
        if len(pos) == 0:
            return miss
        hit_rows = midx[pos]
        known[hit_rows] = 1
        hit_slots = slots[hit_rows]
        # The restore lands the device rows right here, so these slots
        # are live (in_use set) before the tick — no longer pending.
        self._pending.difference_update(int(s) for s in hit_slots)
        self._dirty[hit_slots] = True
        # One batched scatter for the whole tick's promotions (chunked
        # only past RESTORE_CHUNK, which a ≤max_batch tick never is).
        for start in range(0, len(hit_rows), RESTORE_CHUNK):
            part = slice(start, start + RESTORE_CHUNK)
            k = len(hit_slots[part])
            w = pad_pow2(k)
            ints = np.zeros((len(ITEM_INT_ROWS), w), np.int64)
            floats = np.zeros(w, np.float64)
            ints[0, :k] = hit_slots[part]
            for r, name in enumerate(ITEM_INT_ROWS[1:-1], start=1):
                ints[r, :k] = ccols[name][part]
            ints[-1, :k] = 1  # valid
            floats[:k] = ccols["remaining_f"][part]
            self.state = self._restore(
                self.state, jnp.asarray(ints), jnp.asarray(floats)
            )
            self.metric_promote_dispatches += 1
        self.metric_promote_ticks += 1
        self.metric_promotions += len(hit_rows)
        return known == 0

    def _read_through(self, requests, sel, slots, known, miss) -> None:
        """Store.Get for cache misses (algorithms.go:45-51): install the
        persisted items so the kernel sees existing buckets."""
        restore_rows: List[tuple] = []
        restored: set = set()
        for j in np.flatnonzero(miss):
            slot = int(slots[j])
            if slot in restored:
                known[j] = 1
                continue
            item = self.store.get(requests[sel[j]])
            if item is None:
                continue
            restored.add(slot)
            known[j] = 1
            self._pending.discard(slot)
            restore_rows.append(
                (
                    (slot, item["algorithm"], item["limit"], item["remaining"],
                     item["duration"], item["created_at"], item["updated_at"],
                     item["burst"], item["status"], item["expire_at"],
                     item.get("tat", 0), item.get("prev_count", 0), 1),
                    item.get("remaining_f", 0.0),
                )
            )
        if restore_rows:
            w = pad_pow2(len(restore_rows))
            ints = np.zeros((len(ITEM_INT_ROWS), w), np.int64)
            floats = np.zeros(w, np.float64)
            for j, (row, rf) in enumerate(restore_rows):
                ints[:, j] = row
                floats[j] = rf
            self.state = self._restore(
                self.state, jnp.asarray(ints), jnp.asarray(floats)
            )

    # ------------------------------------------------------------------
    # The tick
    # ------------------------------------------------------------------
    @hot_path
    def submit_columns(
        self, cols: ReqColumns, now: Optional[int] = None
    ) -> "TickHandle":
        """Build + dispatch one tick (≤ max_batch rows) and return a handle.

        Device work (H2D, tick, response buffer) is *queued*, not awaited —
        the caller materializes via :meth:`TickHandle.result`, so host
        packing of the next tick overlaps device execution of this one
        (the double-buffering SURVEY §7 calls for; the round-2 engine
        serialized pack → dispatch → blocking D2H and paid the sum).

        With a Store attached the handle is resolved before return (the
        write-through readback must observe exactly this tick's state, so
        no later tick may be dispatched first).
        """
        # Flight-recorder stages (utils/flightrec.py), consecutive on
        # this thread: "submit_lock" the wait for the lock the resolver
        # takes in TickHandle._finish; "pack" slot resolve + matrix fill
        # + sort + dirty marks + the grouped plan, native or numpy (the
        # lease is broken out inside it, _lease_matrix); "h2d" the
        # queued device dispatch; "handle" the rest, to the return.
        waited = flightrec.stage("submit_lock").start()
        with self._lock:
            waited.stop()
            now = now if now is not None else timeutil.now_ms()
            self._last_now = max(self._last_now, now)
            self._tick_count += 1
            with flightrec.stage("pack"):
                packed, n, errors, inv, has_dups, plan = self._build_cols(
                    cols, now)
            dev_m = None
            uploads = 1
            # Structural tick-path evidence: any SSD lookup issued while
            # the tick-dispatch block below runs would land in this
            # delta.  _build_cols (the only legitimate lookup site) has
            # already returned, so the counter stays 0 by construction —
            # and tests/test_ssd.py keeps it that way.
            ssd_reads0 = (
                self.ssd.metric_lookup_calls if self.ssd is not None else 0
            )
            # One upload and one program call a window.  Each crossing
            # into the runtime drops the GIL and waits to take it back
            # from the event loop or the resolver, so what the pack
            # returned reaches the device as ONE host buffer, ``now``
            # inside it (stamp_now), and one jitted program cuts it up:
            # the plan where the pack made one, else the slab.  The
            # upload is an ASYNC host→device copy (jnp.asarray of a
            # numpy buffer queues the transfer and returns; jax may
            # read the buffer until it completes: a plan's is fresh
            # every window, and the staging ring keeps a slab stable
            # until its tick resolves), so this window's H2D overlaps
            # the previous window's still-running tick.  Deliberately
            # asarray, not a committed device_put: a committed sharding
            # is a new jit signature and re-traces every warmed program
            # once per width (measured ~0.6 s each on the CPU suite).
            #
            with flightrec.stage("h2d"):
                if plan is not None:
                    # Grouped tick: unique heads through the parts
                    # program (fold on device), member responses from
                    # the elementwise expansion — a k-deep hot key costs
                    # one row of HBM traffic, not k.  The slab is not
                    # uploaded: the plan holds its head columns.
                    self.metric_grouped_ticks += 1
                    self.state, resp = self._tick32m(
                        self.state, jnp.asarray(plan[5]), packed.shape[1]
                    )
                elif has_dups:
                    # Layered dispatch is gated to serving-scale engines
                    # (same threshold as the grouped warmup): each
                    # (w0, k_pad) shape is a real XLA compile, and small
                    # test-cluster engines churning capacities would pay
                    # a compile storm for batches the sequential program
                    # already handles in a round or two.
                    lplan = (
                        build_layer_plan(
                            packed[:REQ32_ROWS], n, self.capacity, now)
                        if self.capacity >= (1 << 14) else None
                    )
                    dev_m = jnp.asarray(packed)
                    if lplan is not None:
                        # Mixed groups with a host layer plan: one
                        # narrow merged tick per unit layer, chained
                        # through the table (tick32.
                        # jitted_layered_pipeline) — K narrow ticks
                        # instead of one full round per unit.  No cell
                        # meets it: its plan still crosses array by
                        # array, beside the slab.
                        from gubernator_tpu.ops.tick32 import (
                            jitted_layered_pipeline,
                        )

                        mh0, cnt0, mhk, cntk, uidx, rank, kpad = lplan
                        self.metric_layered_ticks += 1
                        fn = jitted_layered_pipeline(
                            self.capacity, self.layout, mh0.shape[1], kpad
                        )
                        uploads = 7   # the slab and the plan's six arrays
                        self.state, resp = fn(
                            self.state, jnp.asarray(mh0),
                            jnp.asarray(cnt0), jnp.asarray(mhk),
                            jnp.asarray(cntk), dev_m,
                            jnp.asarray(uidx), jnp.asarray(rank),
                        )
                    else:
                        # No plan: too few followers to pay for one (a
                        # large uniform population at BatchLimit repeats
                        # a few dozen keys a window: base2-leaky-1m's
                        # closed cell lands here in every window), or an
                        # adversarial shape (over-deep/over-wide unit
                        # structure, unprovable head liveness).  The
                        # sequential chained-unit program is always
                        # correct.
                        self.metric_sequential_ticks += 1
                        self.state, resp = self._tick(self.state, dev_m)
                else:
                    # The common serving shape.
                    self.metric_unique_ticks += 1
                    dev_m = jnp.asarray(packed)
                    self.state, resp = self._tick32(self.state, dev_m)
            rest = flightrec.stage("handle").start()
            if self.ssd is not None:
                self.metric_ssd_tick_path_reads += (
                    self.ssd.metric_lookup_calls - ssd_reads0
                )
            self._pending.clear()
            slots_req = (
                packed[REQ32_INDEX["slot"], :n][inv].astype(np.int64)
                if self.store is not None
                else None
            )
            handle = TickHandle(
                self, resp, n, inv, errors, cols.refs, slots_req,
                limit_req=cols.limit,
            )
            # Overlap telemetry + slab retirement: this window's upload
            # was dispatched while `_inflight` earlier windows were
            # still unresolved (their ticks run while our bytes move).
            self.metric_h2d_windows += 1
            self.metric_h2d_uploads += uploads
            if self._inflight > 0:
                self.metric_h2d_overlapped += 1
            self._inflight += 1
            # The slab recycles once this tick resolves; grouped ticks
            # never uploaded it (dev_m is None) and free it for the very
            # next lease.
            self._staging.retire(handle if dev_m is not None else None)
            rest.stop()
            if self.store is not None:
                handle.result()
            return handle

    @hot_path
    def submit_cols(
        self, cols: ReqColumns, now: Optional[int] = None
    ) -> SubmittedBatch:
        """Dispatch a columnar batch of any width without awaiting the
        device (chunked into max_batch ticks; chunk k+1 packs while chunk
        k executes).  Resolve via ``.matrix()`` / ``.responses()``."""
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

    def process_columns(
        self, cols: ReqColumns, now: Optional[int] = None
    ) -> tuple[np.ndarray, Dict[int, str]]:
        """Apply a columnar batch; returns the (5, n) response matrix in
        request order (rows: status, limit, remaining, reset_time,
        over_limit) plus per-item errors."""
        if len(cols) == 0:
            return np.zeros((5, 0), np.int64), {}
        return self.submit_cols(cols, now).matrix()

    @hot_path
    def submit(
        self, requests: Sequence[RateLimitRequest], now: Optional[int] = None
    ) -> SubmittedBatch:
        """Dispatch an object-level batch without awaiting the device: the
        tick loop's pipelining hook (resolve via ``.responses()`` on a
        reader thread while this thread packs the next window)."""
        return self.submit_cols(
            ReqColumns.from_requests(
                requests, keep_refs=self.store is not None
            ),
            now,
        )

    def process(
        self, requests: Sequence[RateLimitRequest], now: Optional[int] = None
    ) -> List[RateLimitResponse]:
        """Apply a batch of requests; returns responses in request order
        (the dataclass API edge over the columnar path)."""
        if not requests:
            return []
        return self.submit(requests, now).responses()

    def _write_through(
        self, requests: Sequence[RateLimitRequest], slots: np.ndarray,
        n: int, errors: Dict[int, str],
    ) -> None:
        """Store.OnChange with each touched slot's post-tick state
        (write-through, algorithms.go:149-153).  A slot cleared by the tick
        (RESET_REMAINING removal) maps to Store.remove instead, matching the
        reference's remove-on-reset (algorithms.go:78-90).  ``slots`` is in
        request order (process() un-permutes the sorted batch)."""
        # Pad to a power of two so this per-tick hot path compiles a handful
        # of widths, not one per batch size; padding slots aim out of range
        # (zero-fill on columns, guard-row garbage on rows) and rows past n
        # are never read host-side.
        padded = np.full(pad_pow2(max(1, n)), self.capacity, np.int64)
        padded[:n] = slots
        ints, floats = self._readback(self.state, jnp.asarray(padded))
        ints = np.asarray(ints)
        floats = np.asarray(floats)
        seen: set = set()
        for i in range(n):
            if i in errors:
                continue
            slot = int(slots[i])
            if slot in seen:
                continue  # duplicate key in batch: one OnChange, final state
            seen.add(slot)
            key = self.slots.key_of(slot)
            if key is None:
                continue
            f = dict(zip(READBACK_ROWS, ints[:, i]))
            if not f["in_use"]:
                self.store.remove(key)
                continue
            self.store.on_change(
                requests[i],
                {
                    "key": key,
                    "algorithm": int(f["algorithm"]),
                    "limit": int(f["limit"]),
                    "remaining": int(f["remaining"]),
                    "remaining_f": float(floats[i]),
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

    def install_globals(
        self, updates: Sequence[GlobalUpdate], now: Optional[int] = None
    ) -> None:
        """Install owner-pushed GLOBAL state (UpdatePeerGlobals receive path,
        gubernator.go:425-459).  Writes land on device immediately (no tick),
        so installed slots are live the moment this returns."""
        if not updates:
            return
        with self._lock:
            now = now if now is not None else timeutil.now_ms()
            # New logical tick: without this, slots touched by the *previous*
            # tick still satisfy the "touched this tick" reclaim guard and
            # LRU eviction can't free anything.
            self._tick_count += 1
            # Dict keyed by slot: duplicate keys in one push dedup to the
            # LAST update (install order), which the row layout requires —
            # two concurrent row DMAs to one slot are a data race
            # (rowtable.scatter_rows) — and the column path's sequential
            # scatter resolved the same way.
            by_slot: Dict[int, tuple] = {}
            for u in updates:
                try:
                    slot, _ = self._resolve_slot(u.key, now)
                except RuntimeError:
                    continue  # table full; drop (the next broadcast retries)
                self._last_access[slot] = self._tick_count
                self._pending.discard(slot)  # device write happens right here
                by_slot[slot] = (
                    slot, u.algorithm, u.status.limit, u.status.remaining,
                    u.status.status, u.duration, u.status.reset_time, 1,
                )
            if not by_slot:
                return
            self._dirty[list(by_slot)] = True
            rows = list(by_slot.values())
            # Width-chunked like load_items: the row layout stages the
            # batch in VMEM, so one huge push must not compile one huge
            # program.
            for start in range(0, len(rows), RESTORE_CHUNK):
                part = rows[start : start + RESTORE_CHUNK]
                cols = np.zeros((8, pad_pow2(len(part))), np.int64)
                cols[:, : len(part)] = np.array(part, np.int64).T
                self.state = self._install(
                    self.state, jnp.asarray(cols), jnp.int64(now)
                )

    # ------------------------------------------------------------------
    # Snapshot / restore (Loader.Load/Save analog, workers.go:329-534)
    # ------------------------------------------------------------------
    @hot_path
    def lease_window(
        self,
        keys: Sequence[bytes],
        budgets: Sequence[int],
        expires: Sequence[int],
        gens: Sequence[int],
        is_set: bool = True,
    ) -> int:
        """Apply one window of quota-lease column mutations as ONE
        batched device scatter (docs/leases.md).

        ``is_set=True`` installs authoritative (outstanding, expiry,
        generation) triples — the grant/sync commit path; ``False``
        applies reconcile deltas (budget += delta clamped ≥ 0,
        expiry/generation monotone).  Keys not resident in the hot table
        are skipped — the LeaseManager's host records stay authoritative
        and re-mirror on the next window that finds the slot.  Returns
        the number of column updates applied; exactly one device
        dispatch regardless (metric_lease_dispatches/windows is the
        exact-work invariant the lease tests pin at 1.0)."""
        n = len(keys)
        if n == 0:
            return 0
        with self._lock:
            get = self.slots.get
            slots = np.full(n, self.capacity, np.int64)
            for j in range(n):
                s = get(keys[j].decode())
                if s is not None:
                    slots[j] = s
            live = slots < self.capacity
            w = pad_pow2(n)
            slot_pad = np.full(w, self.capacity, np.int64)
            slot_pad[:n] = slots
            bud = np.zeros(w, np.int64)
            bud[:n] = budgets
            exp = np.zeros(w, np.int64)
            exp[:n] = expires
            gen = np.zeros(w, np.int32)
            gen[:n] = gens
            fn = _jitted_lease_apply(is_set)
            self._lease_budget, self._lease_expire, self._lease_gen = fn(
                self._lease_budget, self._lease_expire, self._lease_gen,
                jnp.asarray(slot_pad), jnp.asarray(bud), jnp.asarray(exp),
                jnp.asarray(gen),
            )
            self.metric_lease_dispatches += 1
            self.metric_lease_windows += 1
            applied = int(live.sum())
            self.metric_lease_ops += applied
            self._dirty[slots[live]] = True
            return applied

    def lease_columns(self, keys: Sequence[bytes]):
        """Host readback of the lease columns for a batch of keys:
        (budget, expire_ms, generation) int64/int64/int32 arrays, zeros
        for non-resident keys.  Diagnostics/tests only — the serving
        path never reads these back."""
        n = len(keys)
        with self._lock:
            get = self.slots.get
            slots = np.full(n, -1, np.int64)
            for j in range(n):
                s = get(keys[j].decode())
                if s is not None:
                    slots[j] = s
            live = slots >= 0
            bud = np.zeros(n, np.int64)
            exp = np.zeros(n, np.int64)
            gen = np.zeros(n, np.int32)
            if live.any():
                idx = jnp.asarray(slots[live])
                bud[live] = np.asarray(self._lease_budget[idx])
                exp[live] = np.asarray(self._lease_expire[idx])
                gen[live] = np.asarray(self._lease_gen[idx])
            return bud, exp, gen

    def export_columns(self, dirty_only: bool = False) -> dict:
        """Bulk snapshot: numpy columns + one key blob (the Loader v2
        format; see SNAP_FIELDS).  The reference streams items through a
        channel (store.go:69-78); the columnar analog of that stream is
        arrays.

        Transfer discipline (verdict r3 #7): only LIVE slots cross the
        link, as int32 words, and only the words a per-chunk device probe
        proves necessary — hi words that are sign extensions of their lo
        (values < 2^31: limits, remainings, sub-25-day durations) are
        dropped, constant hi words (epoch-ms columns inside one ~50-day
        window) become one host scalar, and algorithm/status/in_use pack
        into a single word.  Typical cost: 44 B/item instead of the full
        table's 80 B/slot.  Chunks pipeline: while chunk i drains over
        the link, chunk i+1's gather/probe runs on device.
        ``last_export_stats`` records what actually crossed.

        ``dirty_only=True`` exports only the slots mutated since the
        previous export (any kind): the incremental path — a delta moves
        bytes proportional to the touched working set, not the table
        (the reference's Store OnChange design trickles the same way,
        store.go:49-65).  Deltas are ordinary (smaller) snapshots:
        ``load_columns`` applies them as upserts, so delta files append
        to a full baseline.  Removals are only partially reproduced:
        TTL-expired rows fall out at load time via the expire_at filter
        (like the reference's persisted-but-expired items), but an
        unexpired LRU *eviction* is not represented — a baseline+delta
        restore can resurrect keys the source engine evicted to make
        room.  That matches upsert-trickle semantics (the reference's
        OnChange stream carries no deletions either, store.go:49-65);
        restores needing eviction fidelity should take a full export.
        Every export (full or delta) resets the dirty set."""
        with self._lock:
            mask = self.slots.mapped_mask()
            if dirty_only:
                mask &= self._dirty
            mapped = np.flatnonzero(mask)
            self._dirty[:] = False
            n = len(mapped)
            empty = {
                "key_blob": b"",
                "key_offsets": np.zeros(1, np.int64),
                **{
                    f: np.zeros(
                        0, np.float64 if f == "remaining_f" else np.int64
                    )
                    for f in SNAP_FIELDS
                },
                **{f: np.zeros(0, np.int64) for f in ZOO_SNAP_FIELDS},
                **{f: np.zeros(0, np.int64) for f in LEASE_SNAP_FIELDS},
            }
            if n == 0:
                self.last_export_stats = {
                    "d2h_bytes": 0, "items": 0, "partial": dirty_only}
                return self._export_with_cold(empty, dirty_only)
            w = SNAP_CHUNK if n > SNAP_CHUNK else pad_pow2(n)
            wide_fn = _jitted_snap_wide(self.layout)
            probe_fn = _jitted_snap_probe()
            d2h = 0
            parts: List[np.ndarray] = []
            chunks: List[dict] = []
            prev = None
            for start in range(0, n, w):
                part = mapped[start : start + w]
                k = len(part)
                slots_pad = np.full(w, part[0], np.int32)
                slots_pad[:k] = part
                wide = wide_fn(self.state, jnp.asarray(slots_pad))
                probe = np.asarray(probe_fn(wide))
                hi_mask = tuple(
                    not (bool(probe[i, 0]) or probe[i, 1] == probe[i, 2])
                    for i in range(len(SNAP_WIDE))
                )
                sel = _jitted_snap_select(hi_mask)(wide)
                del wide
                d2h += probe.nbytes + int(np.prod(sel.shape)) * 4
                if prev is not None:
                    p, cols = _snap_decode(
                        prev[0], prev[1], prev[2], prev[3],
                        np.asarray(prev[4]),
                    )
                    parts.append(p)
                    chunks.append(cols)
                prev = (part, k, probe, hi_mask, sel)
            p, cols = _snap_decode(
                prev[0], prev[1], prev[2], prev[3], np.asarray(prev[4])
            )
            parts.append(p)
            chunks.append(cols)
            live = np.concatenate(parts)
            if len(live) == 0:
                self.last_export_stats = {
                    "d2h_bytes": d2h, "items": 0, "partial": dirty_only}
                return self._export_with_cold(empty, dirty_only)
            blob, offsets = self.slots.keys_blob(live)
            snap: dict = {"key_blob": blob, "key_offsets": offsets}
            # The zoo columns decode from the same chunks (they sit in
            # SNAP_WIDE) and export as extra keys beside SNAP_FIELDS.
            for name in SNAP_FIELDS + ZOO_SNAP_FIELDS:
                snap[name] = np.concatenate([c[name] for c in chunks])
            # Lease columns ride as extra snapshot keys gathered at the
            # same live slots (order-aligned with the key blob).  One
            # device gather per column per export, not per chunk: the
            # lease columns are narrow (24 B/slot total), so the slim
            # probe/select machinery isn't worth threading them through.
            lidx = jnp.asarray(live)
            snap["lease_budget"] = np.array(self._lease_budget[lidx])
            snap["lease_expire"] = np.array(self._lease_expire[lidx])
            snap["lease_gen"] = np.array(
                self._lease_gen[lidx], dtype=np.int64)
            self.last_export_stats = {
                "d2h_bytes": d2h,
                "items": len(live),
                "bytes_per_item": round(d2h / max(len(live), 1), 1),
                "partial": dirty_only,
            }
            return self._export_with_cold(snap, dirty_only)

    def _export_with_cold(self, snap: dict, dirty_only: bool) -> dict:
        """Append the cold tier's (dirty) entries to a columnar snapshot:
        demoted state is still cached state and must survive a Loader
        save/restore cycle (docs/tiering.md).  Hot and cold are disjoint
        by construction (promotion is a move), so the merge is a plain
        concatenation — no dedup pass."""
        if self.cold is None:
            return snap
        ckeys, ccols = self.cold.export_columns(dirty_only)
        if not ckeys:
            return snap
        from gubernator_tpu.ops.reqcols import pack_blob

        blob2, offs2 = pack_blob(ckeys)
        off1 = np.asarray(snap["key_offsets"], np.int64)
        base = int(off1[-1]) if len(off1) else 0
        snap["key_blob"] = bytes(snap["key_blob"]) + blob2
        snap["key_offsets"] = np.concatenate([off1, offs2[1:] + base])
        for f in SNAP_FIELDS + ZOO_SNAP_FIELDS:
            # The cold tier stores the zoo columns too (COLD_FIELDS),
            # so demoted zoo state survives the round trip.
            snap[f] = np.concatenate([np.asarray(snap[f]), ccols[f]])
        for f in LEASE_SNAP_FIELDS:
            # Cold rows hold no delegation (demotion targets idle slots;
            # leases live on hot, recently-granted keys): zero-pad so the
            # lease columns stay aligned with the merged key blob.
            if f in snap:
                snap[f] = np.concatenate([
                    np.asarray(snap[f]),
                    np.zeros(len(ckeys), np.int64),
                ])
        self.last_export_stats["items"] = (
            self.last_export_stats.get("items", 0) + len(ckeys)
        )
        self.last_export_stats["cold_items"] = len(ckeys)
        return snap

    def export_items(self) -> List[dict]:
        """Drain live bucket state to host dicts (the dict-shaped Loader
        API edge over :meth:`export_columns`)."""
        return items_from_snapshot(self.export_columns())

    def load_columns(self, snap: dict, now: Optional[int] = None) -> None:
        """Bulk restore from a columnar snapshot (see export_columns).

        Expired rows are dropped with a vectorized blob compaction; one
        native blob-assign maps every key; duplicate keys dedup to their
        LAST occurrence (install order — the row layout's one-DMA-per-slot
        contract); the data lands in RESTORE_CHUNK-wide jitted scatters.
        The fill's seconds and the rows it landed add up in
        ``load_seconds`` / ``load_rows`` (``describe()``).
        """
        t0 = time.perf_counter()
        try:
            self.load_rows += self._fill(snap, now)
        finally:
            self.load_seconds += time.perf_counter() - t0

    def _fill(self, snap: dict, now: Optional[int]) -> int:
        """:meth:`load_columns`'s work; the rows it landed."""
        with self._lock:
            now = now if now is not None else timeutil.now_ms()
            self._last_now = max(self._last_now, now)
            self._tick_count += 1  # see install_globals: unblock LRU reclaim
            offsets = np.asarray(snap["key_offsets"], np.int64)
            n = len(offsets) - 1
            if n == 0:
                return 0
            cols = {f: np.asarray(snap[f]) for f in SNAP_FIELDS}
            # Pre-zoo snapshots lack the zoo state columns: restore them
            # as zeros — a fresh window/TAT, the safe reading (see
            # ZOO_SNAP_FIELDS).
            for f in ZOO_SNAP_FIELDS:
                cols[f] = (
                    np.asarray(snap[f]) if f in snap
                    else np.zeros(n, np.int64)
                )
            # Pre-lease snapshots simply lack the lease keys: restore
            # them as no-delegation (zeros) rather than failing.
            has_lease = all(f in snap for f in LEASE_SNAP_FIELDS)
            if has_lease:
                for f in LEASE_SNAP_FIELDS:
                    cols[f] = np.asarray(snap[f])
            blob = snap["key_blob"]
            keep = cols["expire_at"] >= now
            if not keep.all():
                blob, offsets = compact_blob(blob, offsets, keep)
                cols = {f: c[keep] for f, c in cols.items()}
                n = int(keep.sum())
                if n == 0:
                    return 0
            shortfall = len(self.slots) + n - self.capacity
            if shortfall > 0:
                self._reclaim(now, want=shortfall)
            slots = self.slots.assign_blob(blob, offsets)
            if self.cold is not None and (slots < 0).any():
                # Full table: the overflow tail lands in the cold tier
                # instead of being dropped — a restore bigger than the
                # device table keeps the whole working set (the miss
                # path promotes rows back as traffic touches them).
                over = np.flatnonzero(slots < 0)
                offsets = np.asarray(offsets, np.int64)
                self.cold.put_columns(
                    [bytes(blob[offsets[j] : offsets[j + 1]]) for j in over],
                    {f: cols[f][over] for f in SNAP_FIELDS + ZOO_SNAP_FIELDS},
                    now,
                )
            sel = np.flatnonzero(slots >= 0)  # full table: drop the tail
            if len(sel) == 0:
                return 0
            # Last-wins dedup by slot (same key → same slot): reverse +
            # first-unique keeps each slot's final occurrence.
            s = slots[sel]
            _, ridx = np.unique(s[::-1], return_index=True)
            sel = sel[len(s) - 1 - ridx]
            self._last_access[slots[sel]] = self._tick_count
            self._dirty[slots[sel]] = True
            # Chunked like evict_chunked: one restore per RESTORE_CHUNK
            # keeps the compiled width bounded — the row layout stages
            # the batch in VMEM (512 B/row), so a multi-million-item
            # snapshot in one call would not even compile.
            for start in range(0, len(sel), RESTORE_CHUNK):
                part = sel[start : start + RESTORE_CHUNK]
                k = len(part)
                w = pad_pow2(k)
                ints = np.zeros((len(ITEM_INT_ROWS), w), np.int64)
                floats = np.zeros(w, np.float64)
                ints[0, :k] = slots[part]
                for r, name in enumerate(ITEM_INT_ROWS[1:-1], start=1):
                    ints[r, :k] = cols[name][part]
                ints[-1, :k] = 1  # valid
                floats[:k] = cols["remaining_f"][part]
                self.state = self._restore(
                    self.state, jnp.asarray(ints), jnp.asarray(floats)
                )
            if has_lease:
                # Restore the lease columns with one host read-modify-
                # write + push per column: restores are rare (startup,
                # failover) and the columns are narrow, so clarity beats
                # a fourth jitted scatter here.
                tgt = slots[sel]
                lb = np.array(self._lease_budget)
                le = np.array(self._lease_expire)
                lg = np.array(self._lease_gen)
                lb[tgt] = cols["lease_budget"][sel]
                le[tgt] = cols["lease_expire"][sel]
                lg[tgt] = cols["lease_gen"][sel]
                self._lease_budget = jnp.asarray(lb)
                self._lease_expire = jnp.asarray(le)
                self._lease_gen = jnp.asarray(lg)
            return len(sel)

    def load_items(self, items: Sequence[dict], now: Optional[int] = None) -> None:
        """Install snapshot items into the table (the dict-shaped Loader
        API edge: one pass builds the columnar snapshot, then
        :meth:`load_columns` does the real work)."""
        items = list(items)
        if not items:
            return
        self.load_columns(snapshot_from_items(items), now=now)

    def cache_size(self) -> int:
        return len(self.slots)

    def cold_size(self) -> int:
        """Entries currently held by the cold tier (0 when tiering is
        disabled) — the occupancy gauge's second axis."""
        return 0 if self.cold is None else len(self.cold)

    def hot_occupancy(self) -> float:
        """Fraction of device slots holding a mapped key (0.0–1.0)."""
        return len(self.slots) / self.capacity if self.capacity else 0.0

    def h2d_overlap_ratio(self) -> float:
        """Fraction of windows whose request upload was dispatched while
        an earlier window's tick was still unresolved — 0.0 for fully
        serial submission, →1.0 when the pipeline keeps the H2D of
        window N+1 riding under window N's device tick (the
        double-buffered steady state)."""
        return self.metric_h2d_overlapped / max(1, self.metric_h2d_windows)
