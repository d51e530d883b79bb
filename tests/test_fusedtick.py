"""Differential tests for the fused Pallas tick (interpret mode on CPU):
fused kernel vs the unfused parts program vs the merge-capable x64
program, on randomized unique-slot batches over a populated row table.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gubernator_tpu.ops.engine import (
    REQ32_INDEX, REQ32_ROWS, _jitted_tick, pack_request_matrix32)
from gubernator_tpu.ops.fusedtick import (
    make_fused_merged_tick_fn, make_fused_tick_fn)
from gubernator_tpu.ops.raggedtick import make_fused_ragged_tick_fn
from gubernator_tpu.ops.rowtable import RowState
from gubernator_tpu.ops.tick32 import (
    make_merged_tick32_rows_fn, make_tick32_fn, make_tick32_rows_fn)
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest

NOW = 1_700_000_000_000

# Small chunks force the double-buffered pipelined path (nc >= 2)
# without production-width batches.  Mosaic (real TPU) requires the
# chunk to be lane-aligned (128); interpret mode takes the least that
# keeps the 8-wide issue loops and the ragged extents below apart.
SMALL_CHUNK = 128 if jax.default_backend() == "tpu" else 16
W = 4 * SMALL_CHUNK     # every case's batch width but the nc = 8 one
# Room for the widest batch's unique slots (8 chunks), small enough
# that most rows of a batch land on a slot ``state0`` populated.
CAP = 16 * SMALL_CHUNK


def make_plain(cap):
    """Unfused oracle via the two-program split: stacking the response
    inside the jit hands XLA:CPU a concatenate-rooted fusion it executes
    as a per-element tree walk (minutes per test — see
    ops/tick32.make_tick32_rows_fn); the eager stack is its own tiny
    program."""
    inner = jax.jit(make_tick32_rows_fn(cap, "row"))

    def f(state, m, now):
        s, rows = inner(state, m, now)
        return s, jnp.stack(rows)

    return f


def build_batch(rng, b, n, with_behaviors=True):
    """Sorted unique-slot compact request matrix with n live rows."""
    m = np.zeros((REQ32_ROWS, b), np.int32)
    m[REQ32_INDEX["slot"]] = CAP
    slots = np.sort(rng.choice(CAP, n, replace=False))
    reqs = []
    for i in range(n):
        behavior = Behavior(0)
        if with_behaviors:
            p = rng.random()
            if p < 0.15:
                behavior = Behavior.RESET_REMAINING
            elif p < 0.3:
                behavior = Behavior.DRAIN_OVER_LIMIT
        reqs.append(RateLimitRequest(
            name="f", unique_key=f"k{slots[i]}",
            hits=int(rng.choice([0, 1, 2, 5, -3, 10**11])),
            limit=int(rng.choice([3, 10, 1000, 1 << 34])),
            duration=int(rng.choice([1_000, 30_000, 3_600_000])),
            algorithm=Algorithm(int(rng.integers(0, 2))),
            behavior=behavior,
            burst=int(rng.choice([0, 5, 2000])),
            created_at=NOW - int(rng.choice([0, 500, 3_000, 61_000])),
        ))
    pack_request_matrix32(
        m, np.arange(n), reqs, slots,
        rng.random(n) < 0.8, NOW)
    return m


# One jit object per program for the whole module: a new ``jax.jit(...)``
# is a new trace and compile whatever the shape, and every case used to
# build its own.  None of these donates, so the cases share ``state0``.
PLAIN = make_plain(CAP)
FUSED = jax.jit(make_fused_tick_fn(CAP, chunk=SMALL_CHUNK))
RAGGED = jax.jit(make_fused_ragged_tick_fn(CAP, chunk=SMALL_CHUNK))


@pytest.fixture(scope="module")
def state0():
    """A row table a few prior ticks have written, so gathered states
    are non-trivial: three full-width batches over CAP = 4 W slots."""
    rng = np.random.default_rng(0)
    state = jax.tree.map(jnp.asarray, RowState.zeros(CAP))
    for k in range(3):
        m = build_batch(rng, W, W, with_behaviors=False)
        state, _ = PLAIN(state, jnp.asarray(m), jnp.int64(NOW - 10_000 + k))
    return state


@pytest.mark.parametrize("seed,mult", [(1, 4), (2, 8)])
def test_fused_matches_unfused(seed, mult, state0):
    """nc = 4/8 chunks exercises the double-buffered pipelined path."""
    b = SMALL_CHUNK * mult
    rng = np.random.default_rng(seed)

    # live rows reach into the last chunk, padding lanes close it
    m = build_batch(rng, b, int(rng.integers(b - SMALL_CHUNK + 1, b)))
    now = jnp.int64(NOW)

    s_f, r_f = FUSED(state0, jnp.asarray(m), now)
    s_p, r_p = PLAIN(state0, jnp.asarray(m), now)

    n = int((np.asarray(m[REQ32_INDEX["slot"]]) < CAP).sum())
    np.testing.assert_array_equal(
        np.asarray(r_f)[:, :n], np.asarray(r_p)[:, :n])
    np.testing.assert_array_equal(
        np.asarray(s_f.table), np.asarray(s_p.table))


def test_fused_matches_merge_program_on_unique(state0):
    """The x64 merge-capable program and the fused kernel agree on a
    unique-slot batch (the dispatch boundary in engine.submit_columns)."""
    rng = np.random.default_rng(7)
    legacy = _jitted_tick(CAP, "row", sorted_input=True,
                          compact_resp=True, compact_req=True)

    n = W - 3   # the last chunk holds live rows and padding lanes
    m = build_batch(rng, W, n)
    now = jnp.int64(NOW)
    s_f, r_f = FUSED(state0, jnp.asarray(m), now)
    # the engine's program donates its state: hand it a copy
    s_l, r_l = legacy(jax.tree.map(jnp.copy, state0), jnp.asarray(m), now)

    np.testing.assert_array_equal(
        np.asarray(r_f)[:, :n], np.asarray(r_l)[:, :n])
    mat_f = np.asarray(s_f.table)
    mat_l = np.asarray(s_l.table)
    # the merge program's padding lanes scatter to the guard row too;
    # compare only real slots
    np.testing.assert_array_equal(mat_f[:CAP], mat_l[:CAP])


def test_fused_single_chunk_width(state0):
    """b < chunk size exercises the nc == 1 path."""
    rng = np.random.default_rng(9)
    fused = jax.jit(make_tick32_fn(CAP, "row", fused=True))
    n = W - 3   # the last chunk holds live rows and padding lanes
    m = build_batch(rng, W, n)
    now = jnp.int64(NOW)
    s_f, r_f = fused(state0, jnp.asarray(m), now)
    s_p, r_p = PLAIN(state0, jnp.asarray(m), now)
    np.testing.assert_array_equal(
        np.asarray(r_f)[:, :n], np.asarray(r_p)[:, :n])
    np.testing.assert_array_equal(
        np.asarray(s_f.table), np.asarray(s_p.table))


@pytest.mark.parametrize("case", ["full", "odd", "tiny", "empty"])
def test_ragged_fused_matches_plain_on_extent(case, state0):
    """The ragged Pallas kernel, walking only ``[start, start + count)``
    of a flat global-slot batch, matches the plain program run on the
    localized extent alone — and leaves every off-extent response lane
    exactly zero (the cross-shard gather is a psum).

    ``odd`` picks an unaligned start and an odd chunk count (the
    phantom-chunk even-rounding path); ``tiny`` is a sub-chunk extent
    (nc_live == 1 rounds to 2); ``empty`` skips the pipeline entirely.
    """
    b = W
    start, count = {
        "full": (0, b),
        "odd": (SMALL_CHUNK + 5, 3 * SMALL_CHUNK - 5),
        "tiny": (5, 7),
        "empty": (50, 0),
    }[case]
    lo = CAP  # this shard's slot base in a 3-shard global slot space

    rng = np.random.default_rng(31)

    # Local batch (live rows at columns [0, count)), rolled so the live
    # block sits at [start, start + count): the oracle input.  The
    # global matrix rebases the extent's slots by +lo and plants live
    # FOREIGN rows on both sides — other shards' slots with nonzero
    # hits — which the kernel must skip purely by lane index.
    m_oracle = np.roll(build_batch(rng, b, count), start, axis=1)
    m_glob = m_oracle.copy()
    m_glob[REQ32_INDEX["slot"], start:start + count] += lo
    if start:
        m_glob[REQ32_INDEX["slot"], :start] = np.sort(
            rng.choice(lo, start, replace=False))
        m_glob[REQ32_INDEX["valid"], :start] = 1
        m_glob[REQ32_INDEX["hits"], :start] = 999
    tail = b - start - count
    if tail:
        m_glob[REQ32_INDEX["slot"], start + count:] = (
            lo + CAP + np.arange(tail))
        m_glob[REQ32_INDEX["valid"], start + count:] = 1
        m_glob[REQ32_INDEX["hits"], start + count:] = 999

    now = jnp.int64(NOW)
    s_f, r_f = RAGGED(state0, jnp.asarray(m_glob),
                      np.int32(start), np.int32(count), np.int32(lo), now)
    s_p, r_p = PLAIN(state0, jnp.asarray(m_oracle), now)

    r_f = np.asarray(r_f)
    np.testing.assert_array_equal(
        r_f[:, start:start + count],
        np.asarray(r_p)[:, start:start + count])
    off = np.ones(b, bool)
    off[start:start + count] = False
    assert (r_f[:, off] == 0).all()
    # the guard row collects masked-lane scatters on both paths; compare
    # only real slots
    np.testing.assert_array_equal(
        np.asarray(s_f.table)[:CAP], np.asarray(s_p.table)[:CAP])


def test_fused_merged_matches_xla_merged(state0):
    """The fused merged kernel (count fold in-register, 15-row resp) and
    the XLA merged rows program agree on state and every output row."""
    rng = np.random.default_rng(21)
    b = W
    fused = jax.jit(make_fused_merged_tick_fn(CAP, chunk=SMALL_CHUNK))
    inner = jax.jit(make_merged_tick32_rows_fn(CAP, "row"))

    def plain(state, mhead, count, now):
        s, rows = inner(state, mhead, count, now)
        return s, jnp.stack(rows)

    m = build_batch(rng, b, b - 3)
    count = np.ones(b, np.int32)
    live = np.asarray(m[REQ32_INDEX["slot"]]) < CAP
    count[live] = rng.integers(1, 9, int(live.sum()))
    now = jnp.int64(NOW)

    s_f, r_f = fused(state0, jnp.asarray(m), jnp.asarray(count), now)
    s_p, r_p = plain(state0, jnp.asarray(m), jnp.asarray(count), now)

    n = int(live.sum())
    # Fused output is the row-major (U, 24) block; rows 0-14 transpose to
    # the XLA program's 15 rows, 15-22 echo the request params.
    r_f = np.asarray(r_f)
    np.testing.assert_array_equal(r_f[:n, :15].T, np.asarray(r_p)[:, :n])
    from gubernator_tpu.ops.engine import REQ32_INDEX as R

    echo_rows = [R["hits"], R["hits"] + 1, R["limit"], R["limit"] + 1,
                 R["created_at"], R["created_at"] + 1, R["algorithm"],
                 R["behavior"]]
    np.testing.assert_array_equal(
        r_f[:n, 15:23].T, np.asarray(m)[echo_rows][:, :n])
    np.testing.assert_array_equal(
        np.asarray(s_f.table), np.asarray(s_p.table))
