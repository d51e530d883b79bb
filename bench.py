"""Benchmark ladder: the BASELINE.md config ladder, end to end.

Rungs (BASELINE.json "configs", benchmark_test.go:30-148):

  kernel_1m            fused tick kernel, 1M slots, unique keys — the
                       device ceiling (headline metric, vs the 50M
                       decisions/s/chip engineered target)
  engine_token_10k     TickEngine end-to-end: key hashing, native slotmap
                       resolve, request packing, device tick, response
                       unpack — token bucket, 10K keys
  engine_leaky_1m      same, leaky bucket, 1M keys, uniform hits
  engine_mixed_10m_zipf  same, mixed token+leaky, 10M keys, Zipf-skewed
                       hits, table at capacity with reclaim live
                       (p99 target: < 2ms per decision batch)
  engine_mixed_algos   all five algorithms (token, leaky, sliding-
                       window, GCRA, concurrency) in one Zipf stream —
                       zoo parity vs the scalar references and the
                       one-dispatch-per-window pin (docs/algorithms.md)
  herd_token_4096 /    thundering herd: 4096 hits of ONE key per tick vs
  herd_leaky_4096      the unique-key tick (benchmark_test.go:122-147)
  snapshot_10m         export_items/load_items round-trip on the big
                       table (Loader.Save/Load at scale; 1M under
                       BENCH_FAST)
  service_grpc         loopback daemon: full gRPC stack, 1000-item
                       batches (the >2k req/s/node + <1ms reference
                       prose, BASELINE.md)
  global_mesh_8        GLOBAL reconciliation over an 8-device mesh
                       (subprocess on the CPU backend with 8 virtual
                       devices — the v5e-8 rung of the ladder, validated
                       the same way the driver's dryrun_multichip is)

Prints ONE JSON line: the headline metric plus a ``ladder`` field carrying
every rung.  ``BENCH_FAST=1`` shrinks the big rungs for quick iteration.
"""

import asyncio
import json
import os
import subprocess
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

TARGET_DECISIONS = 50_000_000.0  # BASELINE.json: >= 50M decisions/s/chip
TARGET_P99_MS = 2.0              # BASELINE.json: p99 < 2ms at 10M hot keys
FAST = bool(os.environ.get("BENCH_FAST"))


def _pcts(samples_ms):
    a = np.sort(np.asarray(samples_ms))
    return (
        float(a[int(0.50 * (len(a) - 1))]),
        float(a[int(0.99 * (len(a) - 1))]),
    )


def _trimmed_spread(samples, k):
    """Dispersion of the ``k`` samples nearest the median, as
    (max-min)/max — the spread of the measurement's core, insensitive to
    a single latency spike the median already rejects.  Callers record it
    alongside the full-range spread so the record shows both."""
    med = float(np.median(samples))
    core = sorted(samples, key=lambda s: abs(s - med))[:k]
    return (max(core) - min(core)) / max(core)


def diff_time(chain, state, n, resolve, attempts=10, spread_goal=0.20,
              min_samples=5):
    """Shared chained-differential methodology for device rungs.

    ``chain(iters)`` builds a jitted runner of ``iters`` chained ticks;
    per-op = (t(2n) - t(n)) / n with best-of-3 per length so dispatch
    and device round-trip cancel; ``resolve(out)`` materializes a
    host-side value (block_until_ready returns early on this platform).
    Collects positive samples until >= ``min_samples`` agree (trimmed
    spread, :func:`_trimmed_spread`) within ``spread_goal`` or attempts
    run out; returns (median_seconds, spread, samples) — spread is the
    trimmed core's — or (None, None, samples) when fewer than 3 clean
    samples emerged (noise won; not a measurement).
    """
    runs = {k: chain(k) for k in (n, 2 * n)}
    for r in runs.values():  # compile + warm
        resolve(r(state))

    def timed(r):
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            resolve(r(state))
            best = min(best, time.perf_counter() - t0)
        return best

    samples = []
    for _ in range(attempts):
        per = (timed(runs[2 * n]) - timed(runs[n])) / n
        if per > 0:
            samples.append(per)
        if (len(samples) >= min_samples
                and _trimmed_spread(samples, min_samples) < spread_goal):
            break
    if len(samples) < 3:
        return None, None, samples
    per = float(np.median(samples))
    spread = _trimmed_spread(samples, min(min_samples, len(samples)))
    return per, spread, samples


# ----------------------------------------------------------------------
# Rung 1: device kernel ceiling
# ----------------------------------------------------------------------
def _tick_for_chain(capacity, layout, batch):
    """(tick_fn, zero_resp_carry) for a chained-fori_loop rung.  The XLA
    tick variants carry the response as six unstacked rows: stacking
    inside the loop would hand XLA:CPU a concatenate-rooted mega-fusion
    it emits as a per-element tree walk (~0.2 s/element — see
    ops/tick32.make_tick32_rows_fn), which would make the CPU fast-mode
    CI gate unusable.  The fused Pallas row kernel packs its (6, B)
    response in-kernel and carries the matrix."""
    from gubernator_tpu.ops.tick32 import (
        _resolve_fused, make_tick32_fn, make_tick32_rows_fn)

    if layout == "row" and _resolve_fused(None):
        return (make_tick32_fn(capacity, layout),
                jnp.zeros((6, batch), jnp.int32))
    return (make_tick32_rows_fn(capacity, layout),
            tuple(jnp.zeros(batch, jnp.int32) for _ in range(6)))


def _resolve_chain(out):
    """Materialize one element of the chained run's response carry (works
    for both the (6, B) matrix and the six-row-tuple carry)."""
    leaf = jax.tree.leaves(out[1])[0]
    return np.asarray(leaf[(slice(0, 1),) * leaf.ndim])


def rung_kernel():
    from jax import lax

    from gubernator_tpu.ops.buckets import BucketState
    from gubernator_tpu.ops.engine import (
        REQ32_INDEX as R32, REQ32_ROWS, make_layout_choice)
    from gubernator_tpu.ops.rowtable import RowState

    capacity = 1 << 20
    batch = 1 << 15
    now = 1_700_000_000_000

    # Compact i32 request matrix, slot-sorted unique keys — exactly what
    # engine._build_cols hands the production unique-batch program (the
    # fused Pallas tick on the row layout, ops/fusedtick.py).
    rng = np.random.default_rng(0)
    m = np.zeros((REQ32_ROWS, batch), np.int32)
    m[R32["slot"]] = np.sort(rng.permutation(capacity)[:batch])
    m[R32["known"]] = 1
    m[R32["algorithm"]] = rng.integers(0, 2, batch)
    m[R32["valid"]] = 1
    from gubernator_tpu.ops.engine import pack_wide_rows

    for name, v in (("hits", 1), ("limit", 1_000_000),
                    ("duration", 3_600_000), ("created_at", now)):
        pack_wide_rows(m, name, np.full(batch, v, np.int64), slice(None))

    layout = make_layout_choice("auto", capacity, jax.devices()[0], batch)
    tick, zero_resp = _tick_for_chain(capacity, layout, batch)
    zeros = RowState.zeros if layout == "row" else BucketState.zeros
    state = jax.tree.map(jnp.asarray, zeros(capacity))
    packed = jnp.asarray(m)

    # Honest timing of a device tick requires BOTH: (a) chaining ticks
    # inside one compiled fori_loop so per-dispatch latency can't dominate,
    # and (b) timing to a host-side D2H materialization — on this platform
    # ``block_until_ready`` returns before execution completes, so any
    # number not closed by an np.asarray measures dispatch, not the chip.
    # The constant dispatch+roundtrip cost cancels differentially:
    # per-tick = (t(2N) - t(N)) / N.
    def chain(iters):
        @jax.jit
        def run(st):
            # Carry the response matrix too: dropping it would let XLA
            # dead-code-eliminate the whole response side of the tick and
            # measure less work than a production tick performs.
            def body(i, carry):
                s, _ = carry
                return tick(s, packed, jnp.int64(now) + i)

            return lax.fori_loop(0, iters, body, (st, zero_resp))

        return run

    n = 20 if FAST else 100
    # Median-of-k with recorded spread (round-3 verdict: single-shot
    # differentials carried unquantified noise).
    per_tick, spread, samples = diff_time(chain, state, n, _resolve_chain)
    if per_tick is None:
        # Jitter swamped the differentials (non-positive samples):
        # a spike in the short chain's best makes the long chain look
        # free.  Fewer than 3 clean samples is not a measurement — report
        # it as such, never a fictional rate.
        return {
            "rung": "kernel_1m",
            "decisions_per_sec": 0,
            "tick_ms": None,
            "batch": batch,
            "unreliable": True,
            "vs_target_50m": 0,
        }
    rate = batch / per_tick
    return {
        "rung": "kernel_1m",
        "decisions_per_sec": round(rate, 1),
        "tick_ms": round(per_tick * 1000, 4),
        "batch": batch,
        "samples": len(samples),
        "spread": round(spread, 3),
        "spread_all": round(_trimmed_spread(samples, len(samples)), 3),
        # Chip-health context: the tick is ~98% random row DMA, so
        # ns/row exposes the device's per-descriptor floor for THIS run
        # (measured 21.5 ns on an idle chip, ~33 ns on a shared/slow
        # day — a 1.5x swing that is environment, not code).
        "ns_per_row": round(per_tick * 1e9 / batch, 2),
        "vs_target_50m": round(rate / TARGET_DECISIONS, 4),
    }


# ----------------------------------------------------------------------
# Engine-level rungs: the full host path (keys → slotmap → pack → tick)
# ----------------------------------------------------------------------
def rung_kernel_zipf():
    """BASELINE config #3 measured at the device: mixed token+leaky keys,
    Zipf(1.2)-skewed hits, grouped (scatter-add) tick — unique heads
    through the fused kernel with the closed-form duplicate fold, then
    the per-member expansion program, all chained inside one fori_loop
    (kernel_1m methodology).  Every duplicate member counts as a decision
    because every member gets its own reference-semantics response
    (tests/test_group_plan.py proves response identity with the
    sequential program).  kernel_1m remains the worst-case-unique figure;
    this rung is the production-shaped one the north star names
    ("hot-key scatter-add")."""
    from jax import lax

    from gubernator_tpu.ops.buckets import BucketState
    from gubernator_tpu.ops.engine import (
        REQ32_INDEX as R32, REQ32_ROWS, build_group_plan,
        make_layout_choice, pack_wide_rows)
    from gubernator_tpu.ops.rowtable import RowState
    from gubernator_tpu.ops.tick32 import (
        _resolve_fused, make_merged_tick32_rows_fn)
    from gubernator_tpu.ops.transition32 import expand32_rows

    capacity = 1 << 20 if FAST else 10_000_000
    # Zipf unique-head counts grow sub-linearly in batch width, so wide
    # batches amortize the per-member expansion over fewer device rows:
    # 32K decisions touch ~6.5K heads, 128K touch ~19.7K (3.3x the
    # decisions for 3x the rows and 4x the expansion, measured 49.8 vs
    # 44 M/s on the same chip).  FAST keeps the small shape.
    batch = 1 << 15 if FAST else 1 << 17
    K = 4
    now = 1_700_000_000_000

    rng = np.random.default_rng(7)
    plans = []
    for _ in range(K):
        ids = np.minimum(rng.zipf(1.2, batch) - 1, capacity - 1)
        m = np.zeros((REQ32_ROWS, batch), np.int32)
        slots = np.sort(ids)
        m[R32["slot"]] = slots
        m[R32["known"]] = 1
        m[R32["algorithm"]] = (slots % 2).astype(np.int32)  # mixed per key
        m[R32["valid"]] = 1
        for name, v in (("hits", 1), ("limit", 1_000_000),
                        ("duration", 3_600_000), ("created_at", now)):
            pack_wide_rows(m, name, np.full(batch, v, np.int64),
                           slice(None))
        plan = build_group_plan(m, batch, capacity, now)
        assert plan is not None
        plans.append(plan)
    # Common head width for the chained plans: chunk-pair multiples,
    # NOT a power of two — pow2 padding at U ~ 20K would DMA
    # 16384-vs-20480 = 20-40% dead guard rows per tick.  (The ENGINE
    # keeps pow2 quantization: serving must bound its compiled-shape
    # count; the rung compiles one shape.)
    uniq = round(float(np.mean([p[4] for p in plans])), 1)
    # Multiple of 4096 = an EVEN number of the kernel's 2048-row chunks
    # (the fused pipeline pairs chunks; nc must be 1 or even), with a
    # 2048 floor for the nc == 1 case.
    maxu = max(p[4] for p in plans)
    upad = 2048 if maxu <= 2048 else -(-maxu // 4096) * 4096
    # Layout by the KERNEL's staged width: the merged kernel sees upad
    # head rows (~B/6 under Zipf), never the full member batch — the
    # expansion handling members is plain XLA.
    layout = make_layout_choice("auto", capacity, jax.devices()[0], upad)

    def repad(p):
        mhead, count, uidx, rank, u = p
        mh = np.zeros((REQ32_ROWS, upad), np.int32)
        mh[:, :u] = mhead[:, :u]
        mh[R32["slot"], u:] = capacity
        cnt = np.ones(upad, np.int32)
        cnt[:u] = count[:u]
        return mh, cnt, uidx, rank

    plans = [repad(p) for p in plans]
    # Per-plan device constants, NOT one stacked array: the old
    # dynamic_index_in_dim selection copied the (19, upad) head block
    # plus three (B,) expansion vectors out of the stack EVERY tick
    # (~2.5 MB of HBM traffic per iteration — ~10% of the tick's own
    # row DMA at these shapes).  Unrolling the K plans inside the loop
    # body binds each plan as a constant operand instead, so the chain
    # measures the tick, and the donated state carry flows buffer-free
    # through all K sub-ticks of a trip.
    MHs = [jnp.asarray(p[0]) for p in plans]
    CNTs = [jnp.asarray(p[1]) for p in plans]
    UIXs = [jnp.asarray(p[2]) for p in plans]
    RNKs = [jnp.asarray(p[3]) for p in plans]

    if layout == "row" and _resolve_fused(None):
        from gubernator_tpu.ops.fusedtick import make_fused_merged_tick_fn
        from gubernator_tpu.ops.transition32 import expand32_rowmajor

        mtick = make_fused_merged_tick_fn(capacity)

        def tick_expand(s, mh, cnt, uix, rnk, t):
            s2, r24 = mtick(s, mh, cnt, t)
            return s2, expand32_rowmajor(r24, uix, rnk)
    else:
        mtick = make_merged_tick32_rows_fn(capacity, layout)

        def tick_expand(s, mh, cnt, uix, rnk, t):
            s2, rows = mtick(s, mh, cnt, t)
            return s2, expand32_rows(rows, mh, uix, rnk)

    zeros = RowState.zeros if layout == "row" else BucketState.zeros
    state = jax.tree.map(jnp.asarray, zeros(capacity))

    def chain(iters):
        assert iters % K == 0  # diff_time divides by the exact tick count

        @jax.jit
        def run(st):
            def body(i, carry):
                s, r = carry
                for k in range(K):  # K ticks per trip, plans as constants
                    s, r = tick_expand(
                        s, MHs[k], CNTs[k], UIXs[k], RNKs[k],
                        jnp.int64(now) + i * K + k,
                    )
                return s, r

            init = (st, tuple(jnp.zeros(batch, jnp.int32) for _ in range(6)))
            return lax.fori_loop(0, iters // K, body, init)

        return run

    n = 12 if FAST else 20
    per_tick, spread, samples = diff_time(chain, state, n, _resolve_chain)
    if per_tick is None:
        return {"rung": "kernel_zipf_10m", "decisions_per_sec": 0,
                "batch": batch, "unreliable": True, "vs_target_50m": 0}
    rate = batch / per_tick
    return {
        "rung": "kernel_zipf_10m",
        "keys": capacity,
        "decisions_per_sec": round(rate, 1),
        "tick_ms": round(per_tick * 1000, 4),
        "batch": batch,
        "unique_slots_mean": uniq,
        "layout": layout,
        "samples": len(samples),
        "spread": round(spread, 3),
        "spread_all": round(_trimmed_spread(samples, len(samples)), 3),
        "vs_target_50m": round(rate / TARGET_DECISIONS, 4),
    }


def _key_pack(ids, name="bench"):
    """Vectorized (blob, offsets) for name_<id> hash keys."""
    strs = np.char.add(name + "_", ids.astype(np.str_)).tolist()
    lens = np.fromiter(map(len, strs), np.int64, count=len(strs))
    offsets = np.zeros(len(strs) + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    return "".join(strs).encode(), offsets


def _cols(ids, limit, duration, algo, hits=1):
    """Columnar batch for a set of key ids — the production-shaped input
    (the transport parses wire bytes straight into this; no per-request
    Python objects).  algo: 0 token, 1 leaky, None mixed — a key's
    algorithm is a function of the key (real deployments pin one
    algorithm per limit name)."""
    from gubernator_tpu.ops.reqcols import CREATED_UNSET, ReqColumns

    ids = np.asarray(ids, np.int64)
    blob, offsets = _key_pack(ids)
    n = len(ids)

    def full(v):
        return np.full(n, v, np.int64)

    return ReqColumns(
        blob, offsets, full(hits), full(limit), full(duration),
        (ids & 1) if algo is None else full(algo),
        full(0), full(CREATED_UNSET), full(0),
        name_len=full(len("bench")),
    )


def _prefill(engine, n_keys, algo, now, chunk=4096, depth=16):
    """Insert n_keys distinct keys through the columnar path, resolving
    responses ``depth`` ticks at a time in one D2H each (per-transfer
    latency, not device work, is the wall-clock bound on a remote
    device)."""
    from gubernator_tpu.ops.engine import resolve_ticks

    t0 = time.perf_counter()
    pending = []
    for start in range(0, n_keys, chunk):
        ids = np.arange(start, min(start + chunk, n_keys))
        pending.append(
            engine.submit_columns(_cols(ids, 1_000_000, 3_600_000, algo), now)
        )
        if len(pending) >= depth:
            resolve_ticks(pending)
            pending.clear()
    resolve_ticks(pending)
    return time.perf_counter() - t0


def rung_engine(label, n_keys, algo, ticks, zipf=False, fresh_frac=0.0, batch=4096):
    """algo: 0 token, 1 leaky, None mixed.  fresh_frac>0 keeps the table at
    capacity so TTL/LRU reclaim runs during the measured window.

    Reports BOTH regimes: ``decisions_per_sec`` from pipelined submission
    (throughput = max(host, device), the production steady state) and
    p50/p99 from serial awaited ticks (per-batch latency incl. one
    device roundtrip each)."""
    from collections import deque

    from gubernator_tpu.ops.engine import TickEngine

    now = 1_700_000_000_000
    capacity = n_keys  # table exactly at the rung's key count
    fill_chunk = 4 * batch if n_keys >= (1 << 20) else batch
    engine = TickEngine(capacity=capacity, max_batch=fill_chunk)
    fill_s = _prefill(engine, n_keys, algo, now, chunk=fill_chunk)

    rng = np.random.default_rng(2)
    batches = []
    n_fresh = int(batch * fresh_frac)
    fresh_next = n_keys
    n_batches = min(ticks, 32)
    for _ in range(n_batches):
        if zipf:
            ids = np.minimum(rng.zipf(1.2, batch) - 1, n_keys - 1)
        else:
            ids = rng.integers(0, n_keys, batch)
        if n_fresh:
            # Fresh keys against a full table force the reclaim path.
            ids = ids.copy()
            ids[:n_fresh] = np.arange(fresh_next, fresh_next + n_fresh)
            fresh_next += n_fresh
        batches.append(_cols(ids, 1_000_000, 3_600_000, algo))

    # Throughput: pipelined — dispatch runs ahead, responses resolved 16
    # ticks at a time in one D2H transfer each (engine.resolve_ticks).
    # Timed in 5 segments so the record carries the run-to-run
    # spread (round-3 verdict: single-shot transport rungs can't gate a
    # 200% threshold under 300% link noise); the rate is the median
    # segment's, its spread the middle-3 segments' dispersion (the
    # full-range figure is spread_all).
    from gubernator_tpu.ops.engine import resolve_ticks

    seg_rates = []
    done = 0
    tick_i = 0
    t0 = time.perf_counter()
    for seg_ticks in [ticks // 5] * 4 + [ticks - 4 * (ticks // 5)]:
        s0 = time.perf_counter()
        seg_done = 0
        pending = []
        for _ in range(seg_ticks):
            c = batches[tick_i % n_batches]
            pending.append(engine.submit_columns(c, now + tick_i))
            seg_done += len(c)
            tick_i += 1
            if len(pending) >= 16:
                resolve_ticks(pending)
                pending.clear()
        resolve_ticks(pending)
        seg_rates.append(seg_done / max(time.perf_counter() - s0, 1e-9))
        done += seg_done
    dt = time.perf_counter() - t0

    # Latency: serial, each tick awaited (includes one D2H roundtrip).
    lat = []
    lat_ticks = min(ticks, 100)
    for i in range(lat_ticks):
        c = batches[i % n_batches]
        t1 = time.perf_counter()
        engine.process_columns(c, now=now + ticks + i)
        lat.append((time.perf_counter() - t1) * 1e3)
    p50, p99 = _pcts(lat)
    seg = sorted(seg_rates)
    core = seg[1:-1] if len(seg) >= 5 else seg
    out = {
        "rung": label,
        "keys": n_keys,
        "fill_s": round(fill_s, 1),
        "decisions_per_sec": round(seg[len(seg) // 2], 1),
        "decisions_per_sec_overall": round(done / dt, 1),
        "spread": round((core[-1] - core[0]) / max(core[-1], 1e-9), 3),
        "spread_all": round((seg[-1] - seg[0]) / max(seg[-1], 1e-9), 3),
        "batch": batch,
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "evictions": engine.metric_unexpired_evictions,
    }
    if fresh_frac:
        out["p99_vs_2ms_target"] = round(p99 / TARGET_P99_MS, 4)
    return out, engine


def rung_herd(unique_dps, algo, label):
    """One hot key hit 4096× per tick (benchmark_test.go:122-147's
    thundering-herd scenario, scaled) — the merge fast path should hold it
    near unique-key throughput for both algorithms.  Measured the same
    pipelined way as the unique-key rungs so the ratio compares like with
    like."""
    from gubernator_tpu.ops.engine import TickEngine, resolve_ticks

    now = 1_700_000_000_000
    batch = 4096
    engine = TickEngine(capacity=1 << 14, max_batch=batch)
    cols = _cols(np.zeros(batch, np.int64), 10**12, 3_600_000, algo)
    engine.process_columns(cols, now=now)  # install the key
    ticks = 48
    seg_rates = []
    i = 0
    for _ in range(3):  # segment medians: see rung_engine's spread note
        s0 = time.perf_counter()
        pending = []
        for _ in range(ticks // 3):
            pending.append(engine.submit_columns(cols, now + i))
            i += 1
            if len(pending) >= 16:
                resolve_ticks(pending)
                pending.clear()
        resolve_ticks(pending)
        seg_rates.append(
            batch * (ticks // 3) / max(time.perf_counter() - s0, 1e-9))
    seg = sorted(seg_rates)
    dps = seg[1]
    return {
        "rung": label,
        "decisions_per_sec": round(dps, 1),
        "spread": round((seg[-1] - seg[0]) / max(seg[-1], 1e-9), 3),
        "vs_unique_key_engine": round(dps / unique_dps, 4) if unique_dps else None,
    }


def rung_engine_mixed_algos(label="engine_mixed_algos"):
    """All five algorithms in one Zipf-skewed stream through a single
    TickEngine — the algorithm zoo's acceptance rung
    (docs/algorithms.md).  A key's algorithm is a function of the key
    (``id % 5``), so every window mixes token, leaky, sliding-window,
    GCRA, and concurrency lanes, with Zipf duplicates of all five.

    Exports the zoo gates (scripts/check_bench_regression.py):

      mixed_algo_parity_errors        zoo-lane decisions vs the scalar
                                      Python references replaying the
                                      identical stream, compared with
                                      ``==`` — all-integer math, no
                                      tolerance (ABSOLUTE_ZERO)
      mixed_algo_dispatches_per_step  device tick programs per window —
                                      a mixed-policy batch, duplicates
                                      and all, stays ONE dispatch
                                      (absolute ceiling 1.0)
    """
    from gubernator_tpu.algos import reference
    from gubernator_tpu.ops import tick32
    from gubernator_tpu.ops.engine import TickEngine

    now = 1_700_000_000_000
    batch = 1024
    n_keys = 4096
    iters = 10 if FAST else 40
    rng = np.random.default_rng(11)
    # capacity >= 2^14 keeps the layered mixed-duplicate path live (the
    # production dispatch for Zipf zoo duplicates, which are fold-exempt
    # and ride size-1 units — docs/algorithms.md).
    engine = TickEngine(capacity=1 << 15, max_batch=batch)

    def window():
        ids = np.minimum(rng.zipf(1.2, batch) - 1, n_keys - 1)
        blob, offsets = _key_pack(ids)
        n = len(ids)
        hits = rng.choice([1, 1, 1, 2, 0, -1], n).astype(np.int64)
        from gubernator_tpu.ops.reqcols import CREATED_UNSET, ReqColumns

        def full(v):
            return np.full(n, v, np.int64)

        return ReqColumns(
            blob, offsets, hits, full(100), full(60_000),
            (ids % 5).astype(np.int64), full(0), full(CREATED_UNSET),
            full(0), name_len=full(len("bench")),
        )

    windows = [window() for _ in range(8)]

    # Count device tick programs per window: wrap the three engine-held
    # programs and the layered-pipeline factory (the four tick paths a
    # submit can take) — any mixed-policy fallback to per-algorithm
    # sub-batches would show up as a second dispatch.
    dispatches = [0]

    def counted(fn):
        def run(*a, **kw):
            dispatches[0] += 1
            return fn(*a, **kw)
        return run

    for name in ("_tick32", "_tick32m", "_tick"):
        setattr(engine, name, counted(getattr(engine, name)))
    orig_layered = tick32.jitted_layered_pipeline

    def layered(*a, **kw):
        return counted(orig_layered(*a, **kw))

    tick32.jitted_layered_pipeline = layered
    try:
        for c in windows:  # warm/compile every shape the loop replays
            engine.process_columns(c, now=now)
        d0, t0 = dispatches[0], time.perf_counter()
        resps = []
        for i in range(iters):
            got, _ = engine.process_columns(
                windows[i % len(windows)], now=now + 1 + i
            )
            resps.append(got)
        dt = time.perf_counter() - t0
        steps = iters
        dps = dispatches[0] - d0
    finally:
        tick32.jitted_layered_pipeline = orig_layered

    # Replay the identical schedule (warmup included — the engine table
    # carries its state) through the scalar references, zoo lanes only;
    # token/leaky parity is the layout-fuzz suite's job.
    model = {}

    def replay(c, t):
        want = []
        n = len(c.hits)
        for j in range(n):
            alg = int(c.algorithm[j])
            if alg < 2:
                want.append(None)
                continue
            key = bytes(
                c.key_blob[c.key_offsets[j]:c.key_offsets[j + 1]]
            )
            ns, resp = reference.transition(
                model.get(key),
                dict(hits=int(c.hits[j]), limit=int(c.limit[j]),
                     duration=int(c.duration[j]), algorithm=alg,
                     behavior=int(c.behavior[j]), burst=int(c.burst[j]),
                     created_at=t),
                t,
            )
            model[key] = ns
            want.append(
                (resp["status"], resp["remaining"], resp["reset_time"])
            )
        return want

    for c in windows:
        replay(c, now)
    parity_errors = 0
    for i in range(iters):
        c = windows[i % len(windows)]
        want = replay(c, now + 1 + i)
        got = resps[i]
        for j, w in enumerate(want):
            if w is None:
                continue
            g = (int(got[0, j]), int(got[2, j]), int(got[3, j]))
            if g != w:
                parity_errors += 1
    return {
        "rung": label,
        "keys": n_keys,
        "batch": batch,
        "decisions_per_sec": round(iters * batch / dt, 1),
        "mixed_algo_parity_errors": int(parity_errors),
        "mixed_algo_dispatches_per_step": round(dps / max(steps, 1), 3),
    }


def rung_churn(label="engine_churn_4x", capacity=None, ws_mult=4,
               batch=4096, ticks=None):
    """Key-churn ladder: working set ``ws_mult``x the device table, with
    the tiered cold store (docs/tiering.md) absorbing the overflow — the
    regime where the old blind-zeroing reclaim silently reset every
    recycled key's budget.  Uniform-random traffic over the working set
    keeps ~(1 - 1/ws_mult) of each batch cold, so every tick exercises
    the demote readback AND the batched promote scatter.

    Besides throughput/latency the rung reports the exact work counts
    the CI gate pins (scripts/check_bench_regression.py COUNT_KEYS):

    * ``churn_continuity_errors`` — probe keys whose consumed budget did
      NOT survive a hot→cold→hot round trip (must be 0: a fresh-bucket
      reset is the rate-limit bypass the tier exists to close),
    * ``promote_dispatches_per_hit_tick`` — restore scatters per tick
      that had cold hits (must stay 1.0: promotion is one batched
      scatter, never per-key dispatch),
    * ``demote_readbacks_per_reclaim`` — readback dispatches per reclaim
      round with LRU victims (must stay ~1.0: reclaim-free ticks never
      pay a readback)."""
    from gubernator_tpu.ops.engine import TickEngine, resolve_ticks

    now = 1_700_000_000_000
    capacity = capacity or (1 << 13 if FAST else 1 << 16)
    ticks = ticks or (24 if FAST else 96)
    n_keys = ws_mult * capacity
    engine = TickEngine(
        capacity=capacity, max_batch=batch, cold_capacity=n_keys
    )

    # Continuity probes: consume budget on keys OUTSIDE the churn id
    # range, churn them out of the hot tier, then re-touch and check the
    # budget survived the round trip.
    n_probe = 8
    probe_ids = np.arange(10**9, 10**9 + n_probe)
    engine.process_columns(
        _cols(probe_ids, 1_000_000, 3_600_000, 0, hits=7), now=now
    )
    fill_s = _prefill(engine, n_keys, 0, now, chunk=batch)  # cycles probes cold
    mat, _ = engine.process_columns(
        _cols(probe_ids, 1_000_000, 3_600_000, 0, hits=1), now=now
    )
    continuity_errors = int(np.sum(mat[2] != 1_000_000 - 7 - 1))

    rng = np.random.default_rng(7)
    batches = [
        _cols(rng.integers(0, n_keys, batch), 1_000_000, 3_600_000, 0)
        for _ in range(min(ticks, 16))
    ]
    seg_rates = []
    tick_i = 0
    for seg_ticks in [ticks // 3] * 2 + [ticks - 2 * (ticks // 3)]:
        s0 = time.perf_counter()
        pending = []
        for _ in range(seg_ticks):
            pending.append(
                engine.submit_columns(batches[tick_i % len(batches)],
                                      now + tick_i)
            )
            tick_i += 1
            if len(pending) >= 16:
                resolve_ticks(pending)
                pending.clear()
        resolve_ticks(pending)
        seg_rates.append(
            seg_ticks * batch / max(time.perf_counter() - s0, 1e-9))

    lat = []
    for i in range(min(ticks, 48)):
        t1 = time.perf_counter()
        engine.process_columns(
            batches[i % len(batches)], now=now + ticks + i)
        lat.append((time.perf_counter() - t1) * 1e3)
    p50, p99 = _pcts(lat)
    seg = sorted(seg_rates)
    out = {
        "rung": label,
        "keys": n_keys,
        "capacity": capacity,
        "batch": batch,
        "fill_s": round(fill_s, 1),
        "decisions_per_sec": round(seg[len(seg) // 2], 1),
        "spread": round((seg[-1] - seg[0]) / max(seg[-1], 1e-9), 3),
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "cold_hits": engine.metric_cold_hits,
        "promotions": engine.metric_promotions,
        "demotions": engine.cold.metric_demotions,
        "cold_size": engine.cold_size(),
        "evictions": engine.metric_unexpired_evictions,
        # Exact work counts (lower is better; gated without slack).
        "churn_continuity_errors": continuity_errors,
        "promote_dispatches_per_hit_tick": round(
            engine.metric_promote_dispatches
            / max(1, engine.metric_promote_ticks), 4),
        "demote_readbacks_per_reclaim": round(
            engine.metric_demote_readbacks
            / max(1, engine.metric_evict_reclaims), 4),
    }
    engine.close()
    return out


def rung_churn_ssd(label="engine_churn_ssd"):
    """Three-tier churn ladder (docs/tiering.md): working set 8x the
    combined RAM tiers (hot + cold), with the SSD slab store absorbing
    everything RAM can't hold.  Uniform-random traffic over the working
    set keeps most of each batch out of the hot tier, so every tick
    exercises the full demote chain (hot → cold → SSD write-behind) AND
    the three-hop miss path (hot miss → cold miss → batched slab
    lookup → one merged restore scatter).

    Gated invariants (scripts/check_bench_regression.py):

    * ``ssd_continuity_errors`` — probe keys whose consumed budget did
      NOT survive a hot→cold→SSD→hot round trip through the slab files
      (ABSOLUTE_ZERO: an SSD-tier reset is the same rate-limit bypass
      the cold tier closed one level up),
    * ``ssd_tick_path_reads`` — slab lookups observed inside the
      tick-dispatch block (ABSOLUTE_ZERO: SSD I/O must never land in
      the tick or pack stages),
    * ``ssd_promote_batches_per_miss_tick`` — slab lookups per tick
      that had cold misses (ceiling 1.0: the third hop is ONE batched
      lookup, never per-key reads),
    * ``churn_ssd_rss_mb`` — resident-set growth across the rung
      (absolute ceiling: the 8x working set lives on flash, not RAM).
    """
    import resource
    import shutil
    import tempfile

    from gubernator_tpu.ops.engine import TickEngine, resolve_ticks
    from gubernator_tpu.tiering import SsdStore

    def rss_mb():
        try:  # current residency, not the process-lifetime peak (other
            # rungs ran first); falls back to ru_maxrss off-Linux.
            with open("/proc/self/statm") as f:
                pages = int(f.read().split()[1])
            return pages * os.sysconf("SC_PAGE_SIZE") / (1 << 20)
        except (OSError, ValueError):
            return resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss / 1024

    now = 1_700_000_000_000
    hot = 1 << 12 if FAST else 1 << 14
    cold = hot
    n_keys = 8 * (hot + cold)
    batch = 4096
    ticks = 24 if FAST else 96
    tmpdir = tempfile.mkdtemp(prefix="guber-bench-ssd-")
    ssd = SsdStore(tmpdir, capacity_bytes=1 << 31)
    engine = TickEngine(
        capacity=hot, max_batch=batch, cold_capacity=cold, ssd=ssd
    )
    try:
        rss0 = rss_mb()
        # Continuity probes: consume budget on keys OUTSIDE the churn id
        # range, push them hot → cold → SSD with the prefill, then
        # re-touch and check the budget survived the full round trip.
        n_probe = 8
        probe_ids = np.arange(10**9, 10**9 + n_probe)
        engine.process_columns(
            _cols(probe_ids, 1_000_000, 3_600_000, 0, hits=7), now=now
        )
        fill_s = _prefill(engine, n_keys, 0, now, chunk=batch)
        ssd.flush()  # probes read back from slab files, not RAM staging
        mat, _ = engine.process_columns(
            _cols(probe_ids, 1_000_000, 3_600_000, 0, hits=1), now=now
        )
        continuity_errors = int(np.sum(mat[2] != 1_000_000 - 7 - 1))

        rng = np.random.default_rng(11)
        batches = [
            _cols(rng.integers(0, n_keys, batch), 1_000_000, 3_600_000, 0)
            for _ in range(min(ticks, 16))
        ]
        seg_rates = []
        tick_i = 0
        for seg_ticks in [ticks // 3] * 2 + [ticks - 2 * (ticks // 3)]:
            s0 = time.perf_counter()
            pending = []
            for _ in range(seg_ticks):
                pending.append(
                    engine.submit_columns(batches[tick_i % len(batches)],
                                          now + tick_i)
                )
                tick_i += 1
                if len(pending) >= 16:
                    resolve_ticks(pending)
                    pending.clear()
            resolve_ticks(pending)
            seg_rates.append(
                seg_ticks * batch / max(time.perf_counter() - s0, 1e-9))
        rss1 = rss_mb()
        seg = sorted(seg_rates)
        st = ssd.stats()
        return {
            "rung": label,
            "keys": n_keys,
            "capacity": hot,
            "cold_capacity": cold,
            "batch": batch,
            "fill_s": round(fill_s, 1),
            "decisions_per_sec": round(seg[len(seg) // 2], 1),
            "spread": round((seg[-1] - seg[0]) / max(seg[-1], 1e-9), 3),
            "cold_hits": engine.metric_cold_hits,
            "ssd_hits": engine.metric_ssd_hits,
            "ssd_size": st["size"],
            "ssd_bytes": st["bytes"],
            "ssd_slabs": st["slabs"],
            "ssd_write_batches": st["write_batches"],
            "ssd_backpressure": st["backpressure"],
            "ssd_compactions": st["compactions"],
            # Exact work counts / invariants (gated without slack).
            "ssd_continuity_errors": continuity_errors,
            "ssd_tick_path_reads": engine.metric_ssd_tick_path_reads,
            "ssd_promote_batches_per_miss_tick": round(
                engine.metric_ssd_lookups
                / max(1, engine.metric_ssd_miss_ticks), 4),
            "churn_ssd_rss_mb": round(max(0.0, rss1 - rss0), 1),
        }
    finally:
        engine.close()
        shutil.rmtree(tmpdir, ignore_errors=True)


def rung_herd_device():
    """Transport-free herd evidence: chained-``fori_loop`` differential
    ticks (the kernel_1m methodology) for 4096-batch shapes on one
    1<<17-slot table, each through the program the ENGINE would run on
    the auto layout (fused row kernels on real TPU, columns on CPU) —

      unique          4096 distinct keys, production unique program
                      (the baseline the others divide by)
      herd            one hot key x4096, identical requests, through
                      the sorted chained-unit FALLBACK program
                      (production routes this shape to the GROUPED
                      program — kernel_zipf_10m is that evidence)
      herd_mixed      one hot key x~3700 with RESET rows sprinkled in
                      plus unique cold keys (round 3's 6.5 s
                      head-of-line corner) through the LAYERED pipeline
                      — the production path for mixed duplicate groups:
                      one narrow merged tick per unit layer, chained
                      through the table
      herd_mixed_seq  the same shape through the sequential chained-unit
                      program — the always-correct fallback the layered
                      plan's eligibility gate retreats to

    The engine-level herd rungs include host transfers whose 3x run-to-run
    swing made the O(1)-rounds claim unfalsifiable from the ladder
    (round-3 verdict weak #5); this rung measures the chip."""
    from jax import lax

    from gubernator_tpu.ops.buckets import BucketState
    from gubernator_tpu.ops.engine import (
        REQ32_INDEX as R32, REQ32_ROWS, build_layer_plan,
        make_layout_choice, pack_wide_rows)
    from gubernator_tpu.ops.rowtable import RowState
    from gubernator_tpu.ops.tick32 import (
        jitted_layered_pipeline, make_sorted_tick32_rows_fn)
    from gubernator_tpu.types import Behavior

    capacity = 1 << 17
    batch = 4096
    now = 1_700_000_000_000
    layout = make_layout_choice("auto", capacity, jax.devices()[0], batch)
    zeros = RowState.zeros if layout == "row" else BucketState.zeros

    def build(slots, behavior=None):
        m = np.zeros((REQ32_ROWS, batch), np.int32)
        m[R32["slot"]] = np.sort(slots)
        m[R32["known"]] = 1
        m[R32["valid"]] = 1
        for name, v in (("hits", 1), ("limit", 10**9),
                        ("duration", 3_600_000), ("created_at", now)):
            pack_wide_rows(m, name, np.full(batch, v, np.int64),
                           slice(None))
        if behavior is not None:
            m[R32["behavior"]] = behavior
        return m

    rng = np.random.default_rng(3)
    m_unique = build(rng.permutation(capacity)[:batch])
    m_herd = build(np.zeros(batch, np.int64))
    hot = np.zeros(batch, np.int64)
    hot[: batch // 10] = rng.permutation(np.arange(1, capacity))[: batch // 10]
    behavior = np.zeros(batch, np.int32)
    # ~8 RESET rows inside the hot group (resets ride hot keys here on
    # purpose: that IS the adversarial corner)
    reset_at = rng.choice(np.flatnonzero(np.sort(hot) == 0), 8,
                          replace=False)
    behavior[reset_at] = int(Behavior.RESET_REMAINING)
    m_mixed = build(hot, behavior)

    # Unique: the production program via _tick_for_chain (fused on TPU).
    uniq_tick, uniq_zero = _tick_for_chain(capacity, layout, batch)
    sort_rows = make_sorted_tick32_rows_fn(capacity, layout)
    rows_zero = tuple(jnp.zeros(batch, jnp.int32) for _ in range(6))

    plan = build_layer_plan(m_mixed, batch, capacity, now)
    assert plan is not None
    mh0, cnt0, mhk, cntk, uidx, rank, kpad = plan
    layered = jitted_layered_pipeline(capacity, layout, mh0.shape[1], kpad)
    MH0, CNT0 = jnp.asarray(mh0), jnp.asarray(cnt0)
    MHK, CNTK = jnp.asarray(mhk), jnp.asarray(cntk)
    UIDX, RNK = jnp.asarray(uidx), jnp.asarray(rank)

    def layered_tick(s, m32, t):
        return layered(s, MH0, CNT0, MHK, CNTK, m32, UIDX, RNK, t)

    cases = {
        "unique": (uniq_tick, m_unique, uniq_zero),
        "herd": (sort_rows, m_herd, rows_zero),
        "herd_mixed": (layered_tick, m_mixed,
                       jnp.zeros((6, batch), jnp.int32)),
        "herd_mixed_seq": (sort_rows, m_mixed, rows_zero),
    }

    n = 10 if FAST else 40
    out = {"rung": "herd_device", "batch": batch, "layout": layout}
    base = None
    for label, (tick, m_np, zero_resp) in cases.items():
        packed = jnp.asarray(m_np)

        def chain(iters, packed=packed, tick=tick, zero_resp=zero_resp):
            @jax.jit
            def run(st):
                def body(i, carry):
                    s, _ = carry
                    return tick(s, packed, jnp.int64(now) + i)

                return lax.fori_loop(0, iters, body, (st, zero_resp))

            return run

        state = jax.tree.map(jnp.asarray, zeros(capacity))
        per, spread, _ = diff_time(chain, state, n, _resolve_chain)
        if per is None:
            out[label] = {"unreliable": True}
            continue
        entry = {
            "tick_ms": round(per * 1000, 4),
            "decisions_per_sec": round(batch / per, 1),
            "spread": round(spread, 3),
        }
        if label == "unique":
            base = per
        elif base:
            entry["vs_unique_device"] = round(base / per, 4)
        out[label] = entry
    return out


def rung_p99_projection():
    """Device-side p99 evidence at service widths (round-3 verdict #6).

    The 2 ms p99 target has not been judged end to end on a local chip
    (not measured), so this rung isolates what the design
    delivers: chained-differential device tick time at the service batch
    widths on a 10M-slot table, plus a projected LOCAL p99

        p99_projected_local_ms =
            host_pack + tick_ms + wire_bytes / 16 GB/s

    Assumptions recorded with the number: dedicated PCIe Gen4 x16
    (16 GB/s), the measured host columnar pack (~0.084 us/request,
    docs/tpu-performance.md), compact wire formats (76 B/req down,
    24 B/decision up), worst-case unique random keys."""
    from jax import lax

    from gubernator_tpu.ops.engine import (
        REQ32_INDEX as R32, REQ32_ROWS, make_layout_choice, pack_wide_rows)
    from gubernator_tpu.ops.rowtable import RowState
    from gubernator_tpu.ops.buckets import BucketState

    capacity = 1 << 20 if FAST else 10_000_000
    now = 1_700_000_000_000
    layout = make_layout_choice("auto", capacity, jax.devices()[0], 4096)
    zeros = RowState.zeros if layout == "row" else BucketState.zeros

    out = {"rung": "p99_projection", "capacity": capacity,
           "layout": layout,
           "assumptions": "PCIe Gen4 x16 16 GB/s; host pack 0.084us/req; "
                          "compact wire 76B/req + 24B/decision; unique keys"}
    rng = np.random.default_rng(11)
    n = 20 if FAST else 60
    for width in (1024, 4096):
        m = np.zeros((REQ32_ROWS, width), np.int32)
        m[R32["slot"]] = np.sort(rng.permutation(capacity)[:width])
        m[R32["known"]] = 1
        m[R32["algorithm"]] = rng.integers(0, 2, width)
        m[R32["valid"]] = 1
        for name, v in (("hits", 1), ("limit", 10**9),
                        ("duration", 3_600_000), ("created_at", now)):
            pack_wide_rows(m, name, np.full(width, v, np.int64),
                           slice(None))
        packed = jnp.asarray(m)
        state = jax.tree.map(jnp.asarray, zeros(capacity))
        tick, zero_resp = _tick_for_chain(capacity, layout, width)

        def chain(iters, packed=packed, tick=tick, zero_resp=zero_resp):
            @jax.jit
            def run(st):
                def body(i, carry):
                    s, _ = carry
                    return tick(s, packed, jnp.int64(now) + i)

                return lax.fori_loop(0, iters, body, (st, zero_resp))

            return run

        per, spread, _ = diff_time(chain, state, n, _resolve_chain)
        if per is None:
            out[f"w{width}"] = {"unreliable": True}
            continue
        wire_bytes = width * (REQ32_ROWS + 6) * 4
        pcie_ms = wire_bytes / 16e9 * 1e3
        host_ms = width * 0.084e-3
        proj = host_ms + per * 1e3 + pcie_ms
        out[f"w{width}"] = {
            "tick_ms": round(per * 1e3, 4),
            "spread": round(spread, 3),
            "wire_kb": round(wire_bytes / 1024, 1),
            # device-only component (tick + PCIe, no host pack) — what
            # main() adds the service rung's measured codec CPU onto.
            "device_ms": round(per * 1e3 + pcie_ms, 4),
            "p99_projected_local_ms": round(proj, 4),
            "vs_2ms_target": round(proj / TARGET_P99_MS, 4),
        }
    return out


def rung_snapshot(engine, label):
    """Columnar snapshot round-trip (Loader v2: export_columns/
    load_columns — numpy columns + key blob, no per-item dicts)."""
    from gubernator_tpu.ops.engine import TickEngine

    t0 = time.perf_counter()
    snap = engine.export_columns()
    export_s = time.perf_counter() - t0
    items = len(snap["key_offsets"]) - 1
    # D2H payload: what the schema-specialized export actually moved
    # (engine.last_export_stats) — the record says how many bytes
    # crossed so a slow-link day is distinguishable from a regression.
    d2h_mb = getattr(engine, "last_export_stats", {}).get(
        "d2h_bytes", items * 80
    ) / 1e6
    fresh = TickEngine(capacity=engine.capacity, max_batch=engine.max_batch)
    t0 = time.perf_counter()
    fresh.load_columns(snap, now=1_700_000_000_000)
    load_s = time.perf_counter() - t0
    del snap

    # Incremental export after a ~1%-of-table touch: a delta must move
    # bytes proportional to the touched working set, not the table
    # (store.go:49-65 OnChange trickle analog).
    now = 1_700_000_000_000
    rng = np.random.default_rng(13)
    touch = max(1024, items // 100)
    batch = 4096
    from gubernator_tpu.ops.engine import resolve_ticks

    pending = []
    for start in range(0, touch, batch):
        ids = rng.integers(0, max(items, 1), min(batch, touch - start))
        pending.append(engine.submit_columns(
            _cols(ids, 1_000_000, 3_600_000, None), now))
        if len(pending) >= 16:
            resolve_ticks(pending)
            pending.clear()
    resolve_ticks(pending)
    t0 = time.perf_counter()
    delta = engine.export_columns(dirty_only=True)
    delta_s = time.perf_counter() - t0
    delta_items = len(delta["key_offsets"]) - 1
    delta_mb = getattr(engine, "last_export_stats", {}).get(
        "d2h_bytes", delta_items * 80
    ) / 1e6
    return {
        "rung": label,
        "items": items,
        "export_s": round(export_s, 2),
        "export_d2h_mb": round(d2h_mb, 1),
        "export_mbps": round(d2h_mb / max(export_s, 1e-9), 2),
        "load_s": round(load_s, 2),
        "delta_touched": touch,
        "delta_items": delta_items,
        "delta_export_s": round(delta_s, 2),
        "delta_d2h_mb": round(delta_mb, 2),
        # ~0.01 = the delta moved ~1% of the full export's bytes
        "delta_vs_full_bytes": round(delta_mb / max(d2h_mb, 1e-9), 4),
    }


# ----------------------------------------------------------------------
# Rung: 100M keys (the top of the BASELINE.md config ladder)
# ----------------------------------------------------------------------
def rung_100m():
    """100M keys, columns layout, DRAIN_OVER_LIMIT on all traffic,
    RESET_REMAINING on 1/64, multi-region picker on the lookup path.

    Memory budget: the column table stores 20 int32 words/slot = 80 B/slot
    → **8.0 GB HBM at 100M** (v5e has 16 GB; the row layout would need
    512 B/slot = 51 GB, which is why make_layout_choice caps it at 6 GB
    and auto falls back to columns here).  Host side: C++ slotmap ≈8 GB
    (hash buckets + SSO key strings) + 0.8 GB last-access.

    The table is populated DEVICE-SIDE — one donated jitted init writes
    synthetic bucket state straight into HBM — while the native slotmap
    assigns the same 100M keys host-side, so host and device agree on
    key→slot.  Pushing 100M real inserts through the serving path would
    measure the load phase, not the engine at that table size.
    """
    from functools import partial

    from gubernator_tpu.ops.buckets import BucketState, to_stored
    from gubernator_tpu.ops.engine import TickEngine, resolve_ticks
    from gubernator_tpu.parallel.hashring import HASH_FUNCTIONS, RegionPicker
    from gubernator_tpu.types import Behavior, PeerInfo

    cap = 100_000_000
    now = 1_700_000_000_000
    limit = 1_000_000
    duration = 3_600_000
    batch = 4096
    eng = TickEngine(capacity=cap, max_batch=batch, table_layout="columns")

    @partial(jax.jit, donate_argnums=(0,))
    def synth(state, t):
        idx = jnp.arange(cap, dtype=jnp.int64)
        algo = (idx & 1).astype(jnp.int32)
        leaky = algo == 1

        def f64(v):
            return jnp.full(cap, v, jnp.int64)

        return BucketState(
            algorithm=algo,
            limit=to_stored(f64(limit), "limit"),
            remaining=to_stored(
                jnp.where(leaky, jnp.int64(0), jnp.int64(limit)), "remaining"
            ),
            remaining_f=to_stored(
                jnp.where(leaky, float(limit), 0.0), "remaining_f"
            ),
            duration=to_stored(f64(duration), "duration"),
            created_at=to_stored(f64(now), "created_at"),
            updated_at=to_stored(
                jnp.where(leaky, t, jnp.int64(0)), "updated_at"
            ),
            burst=to_stored(
                jnp.where(leaky, jnp.int64(limit), jnp.int64(0)), "burst"
            ),
            status=jnp.zeros(cap, jnp.int32),
            expire_at=to_stored(f64(now + duration), "expire_at"),
            in_use=jnp.ones(cap, jnp.bool_),
        )

    t0 = time.perf_counter()
    eng.state = synth(eng.state, jnp.int64(now))
    jax.block_until_ready(jax.tree.leaves(eng.state)[0])
    dev_fill_s = time.perf_counter() - t0

    # Host slotmap: assign the same keys, chunked to bound transients.
    # The C++ free list hands out slots 0,1,2,... in insertion order, so
    # key bench_<i> lands in slot i — matching the synthetic device fill.
    t0 = time.perf_counter()
    step = 10_000_000
    for start in range(0, cap, step):
        ids = np.arange(start, min(start + step, cap))
        blob, offsets = _key_pack(ids)
        slots = eng.slots.assign_blob(blob, offsets)
        assert slots[0] == start and slots[-1] == ids[-1], "slot order broke"
    key_fill_s = time.perf_counter() - t0

    # Multi-region picker: 3 DCs x 3 peers, the MULTI_REGION lookup hook
    # (region_picker.go:57-69) exercised per measured batch.
    picker: RegionPicker = RegionPicker(HASH_FUNCTIONS["fnv1"], 512)
    for dc in ("us-east-1", "us-west-2", "eu-west-1"):
        for p in range(3):
            picker.add(PeerInfo(grpc_address=f"{dc}-{p}:81", datacenter=dc))
    pickers = list(picker.pickers().values())

    DRAIN = int(Behavior.DRAIN_OVER_LIMIT)
    RESET = int(Behavior.RESET_REMAINING)
    # Warm tick: the FIRST fresh key against the exactly-full table pays
    # the one-time synchronous reclaim (capacity//16 ≈ 6M frees at 100M);
    # after it the background reclaimer keeps headroom off the hot path.
    eng.process_columns(
        _cols(np.arange(cap, cap + batch), limit, duration, None), now=now + 1
    )
    rng = np.random.default_rng(7)
    batches = []
    fresh_next = cap + batch
    for _ in range(16):
        ids = np.minimum(rng.zipf(1.2, batch) * 1000 - 1, cap - 1)
        ids[: batch // 100] = np.arange(
            fresh_next, fresh_next + batch // 100
        )  # 1% fresh keys: keeps background reclaim live at capacity
        fresh_next += batch // 100
        c = _cols(ids, limit, duration, None)
        c.behavior[:] = DRAIN
        # RESET_REMAINING rides the fresh (unique-per-batch) rows: resets
        # target specific keys in practice, and a RESET row inside a
        # zipf-hot duplicate group would break that group's closed-form
        # herd merge and degenerate the tick into per-duplicate rank
        # rounds (measured 6.5 s/tick at 100M) — a worst case no real
        # reset traffic exhibits.
        c.behavior[: batch // 100] |= RESET
        keys = ["bench_" + str(i) for i in ids]
        batches.append((c, keys))

    ticks = 10 if FAST else 50
    done = 0
    seg_rates = []
    tick_i = 0
    t0 = time.perf_counter()
    # 5 segments → median + middle-3 spread, like rung_engine (this rung
    # previously recorded a single window, so its r3→r4 swings could not
    # be told apart from run-to-run noise).
    for seg_ticks in [ticks // 5] * 4 + [ticks - 4 * (ticks // 5)]:
        s0 = time.perf_counter()
        seg_done = 0
        pending = []
        for _ in range(seg_ticks):
            c, keys = batches[tick_i % len(batches)]
            for ring in pickers:  # every region resolves its owner
                ring.get_batch(keys)
            pending.append(eng.submit_columns(c, now + 1 + tick_i))
            seg_done += len(c)
            tick_i += 1
            # Depth 8, not 16: a 10-tick segment must still overlap
            # dispatch with resolution mid-segment or the median
            # measures drain-at-boundary, not the pipelined steady
            # state the pre-segmented window measured.
            if len(pending) >= 8:
                resolve_ticks(pending)
                pending.clear()
        resolve_ticks(pending)
        seg_rates.append(seg_done / max(time.perf_counter() - s0, 1e-9))
        done += seg_done
    dt = time.perf_counter() - t0

    lat = []
    for i in range(min(ticks, 30)):
        c, keys = batches[i % len(batches)]
        t1 = time.perf_counter()
        eng.process_columns(c, now=now + 1000 + i)
        lat.append((time.perf_counter() - t1) * 1e3)
    p50, p99 = _pcts(lat)
    seg = sorted(seg_rates)
    core = seg[1:-1] if len(seg) >= 5 else seg
    out = {
        "rung": "engine_100m_drain_reset_region",
        "keys": cap,
        "dev_fill_s": round(dev_fill_s, 1),
        "key_fill_s": round(key_fill_s, 1),
        "decisions_per_sec": round(seg[len(seg) // 2], 1),
        "decisions_per_sec_overall": round(done / dt, 1),
        "spread": round((core[-1] - core[0]) / max(core[-1], 1e-9), 3),
        "spread_all": round((seg[-1] - seg[0]) / max(seg[-1], 1e-9), 3),
        "batch": batch,
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "evictions": eng.metric_unexpired_evictions,
        "hbm_table_gb": round(cap * 80 / 2**30, 2),
        "regions": len(pickers),
    }
    eng.close()
    return out


# ----------------------------------------------------------------------
# Service-level rung: loopback gRPC through a real daemon
# ----------------------------------------------------------------------
async def _service_bench(n_batches, batch, concurrency):
    from gubernator_tpu.config import BehaviorConfig, Config, DaemonConfig
    from gubernator_tpu.transport.daemon import DaemonClient, spawn_daemon
    from gubernator_tpu.types import RateLimitRequest

    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="",
        peer_discovery_type="none",
    )
    # 2^20 matches the leaky rung's table so the daemon's engine reuses the
    # already-compiled tick program instead of paying a fresh XLA compile
    # (a new capacity = a new program; compiles run minutes on slow hosts).
    conf.config = Config(behaviors=BehaviorConfig(), cache_size=1 << 20)
    d = await spawn_daemon(conf)
    client = DaemonClient(d.advertise_address)
    # Everything after the daemon exists runs under try/finally: r02's
    # DEADLINE_EXCEEDED escaped before d.close(), leaking the grpc.aio
    # server into interpreter shutdown where Server.__del__ aborts the
    # whole process (rc=134) after the headline JSON already printed.
    try:
        # Steady-state serving: pre-install the whole key space through
        # the engine so both client windows measure warm-key traffic (the
        # reference's >2k req/s figure is steady state too), then draw
        # both payload sets from the SAME id streams.
        now = 1_700_000_000_000
        _prefill(d.instance.engine, 100_000, 0, now)
        rng = np.random.default_rng(3)
        id_sets = [
            rng.integers(0, 100_000, batch) for _ in range(min(n_batches, 32))
        ]
        payloads = [
            _cols(ids, 1_000_000, 3_600_000, 0) for ids in id_sets
        ]
        obj_payloads = [
            [
                RateLimitRequest(
                    name="bench", unique_key=str(k), hits=1,
                    limit=1_000_000, duration=3_600_000,
                )
                for k in ids
            ]
            for ids in id_sets[:8]
        ]
        # Warm both client paths (compiles the tick program too).  When
        # the native codec can't build (no toolchain), the rung degrades
        # to measuring the object client — marked in the record.
        try:
            await client.get_rate_limits_columns(payloads[0], timeout=120.0)
            columnar = True
        except RuntimeError:
            columnar = False
        await client.get_rate_limits(obj_payloads[0], timeout=120.0)

        lat = []
        sem = asyncio.Semaphore(concurrency)

        async def one(i):
            async with sem:
                t0 = time.perf_counter()
                # Generous deadline: queued batches stack behind the tick.
                if columnar:
                    await client.get_rate_limits_columns(
                        payloads[i % len(payloads)], timeout=60.0
                    )
                else:
                    await client.get_rate_limits(
                        obj_payloads[i % len(obj_payloads)], timeout=60.0
                    )
                lat.append((time.perf_counter() - t0) * 1e3)

        t0 = time.perf_counter()
        await asyncio.gather(*(one(i) for i in range(n_batches)))
        dt = time.perf_counter() - t0

        # Object-API comparison point: same daemon and key streams,
        # pb-message client (the pre-r5 measurement shape) over a
        # shorter window.
        n_obj = max(10, n_batches // 4)

        async def one_obj(i):
            async with sem:
                await client.get_rate_limits(
                    obj_payloads[i % len(obj_payloads)], timeout=60.0
                )

        t1 = time.perf_counter()
        await asyncio.gather(*(one_obj(i) for i in range(n_obj)))
        obj_rps = n_obj * batch / (time.perf_counter() - t1)
    finally:
        await client.close()
        await d.close()
    p50, p99 = _pcts(lat)

    # The serving path's own CPU, measured inline: the gRPC edge now
    # rides the native wire codec (transport/fastwire.py) — raw bytes →
    # columns → (tick) → response bytes with no protobuf objects.  The
    # pb-object equivalent of this batch cost ~3-4.7 ms in r3/r4 records.
    from gubernator_tpu.transport import fastwire

    wire_req = fastwire.encode_req(payloads[0])
    resp_mat = np.zeros((5, batch), np.int64)
    resp_mat[1] = 1_000_000
    resp_mat[2] = 999_999
    resp_mat[3] = 1_700_000_003_600_000
    cpu_best = 1e9
    if wire_req is not None:
        for _ in range(7):
            c0 = time.perf_counter()
            cols, _e, _s = fastwire.parse_req(wire_req)
            fastwire.encode_resp(resp_mat)
            cpu_best = min(cpu_best, time.perf_counter() - c0)
    cpu_ms = cpu_best * 1e3 if wire_req is not None else None

    out = {
        "rung": "service_grpc",
        "batch": batch,
        "client": "columnar" if columnar else "object",
        "concurrency": concurrency,
        "requests_per_sec": round(n_batches * batch / dt, 1),
        "requests_per_sec_obj_client": round(obj_rps, 1),
        "batches_per_sec": round(n_batches / dt, 1),
        "batch_p50_ms": round(p50, 3),
        "batch_p99_ms": round(p99, 3),
        "vs_ref_2k_reqs_per_node": round((n_batches * batch / dt) / 2000.0, 1),
    }
    if cpu_ms is not None:
        out["serve_cpu_ms_per_batch"] = round(cpu_ms, 3)
        # Projected local batch p99: this bench's N concurrent batches
        # serialize on one serving core (worst case: a batch waits out
        # all N-1 peers' codec CPU) + a conservative 1.2 ms device tick
        # + PCIe.  main() replaces the device term with the
        # p99_projection rung's MEASURED w4096 figure when available.
        out["batch_p99_projected_local_ms"] = round(
            concurrency * cpu_ms + 1.2, 2)
    return out


def rung_service():
    n_batches = 50 if FAST else 200
    return asyncio.run(_service_bench(n_batches, 1000, 8))


# ----------------------------------------------------------------------
# Loopback serving rung: the MEASURED end-to-end p99 (in-process)
# ----------------------------------------------------------------------
async def _loopback_bench(engine, n_keys):
    """Drive the full serving instance in-process — fastwire framing,
    zero-copy arena ingest, tick-loop batching, pipelined device
    dispatch — with no sockets between client and server, so the
    latency numbers are the system's own.
    This replaces the projected p99 as the ladder's headline latency:
    every sample here is a real wire-bytes→decision→wire-bytes round
    trip against the 10M-key table.

    Reuses the engine_mixed_10m_zipf rung's prefilled engine (the
    instance owns and closes it), so the rung itself stays inside its
    ~30 s ladder budget instead of re-filling 10M keys.

    Reports the three gated serving-path counters
    (scripts/check_bench_regression.py): ``loopback_p99_ms`` (measured,
    lower is better), ``serve_cpu_ms_per_batch`` (host codec+arena CPU
    per 1000-item batch), and ``h2d_overlap_ratio`` (fraction of
    windows whose request upload overlapped an earlier window's
    still-running tick — the double-buffered steady state; must stay
    high)."""
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.pb import gubernator_pb2 as pb
    from gubernator_tpu.service.instance import InstanceConfig, V1Instance
    from gubernator_tpu.transport import convert, fastwire
    from gubernator_tpu.utils import flightrec

    batch = 1000  # the public API batch cap (types.MAX_BATCH_SIZE)
    now = 1_700_000_000_000
    # Slab budget sized to this rung's drive pattern: leases are held
    # from decode until the tick loop packs the window, so the arena
    # needs roughly the concurrent-client count (an operator sizes
    # GUBER_INGEST_ARENA_SLABS the same way; default 8 fits depth-4
    # pipelines of modest concurrency).
    prev_slabs = os.environ.get("GUBER_INGEST_ARENA_SLABS")
    os.environ["GUBER_INGEST_ARENA_SLABS"] = "48"
    try:
        inst = await V1Instance.create(
            InstanceConfig(behaviors=BehaviorConfig()), engine=engine
        )
    finally:
        if prev_slabs is None:
            os.environ.pop("GUBER_INGEST_ARENA_SLABS", None)
        else:
            os.environ["GUBER_INGEST_ARENA_SLABS"] = prev_slabs
    try:
        arena = inst.ingest_arena
        rng = np.random.default_rng(17)
        payload_cols = [
            _cols(rng.integers(0, n_keys, batch), 1_000_000, 3_600_000, 0)
            for _ in range(16)
        ]
        raws = [fastwire.encode_req(c) for c in payload_cols]
        native = all(r is not None for r in raws)
        if not native:  # no native codec: protobuf framing, marked below
            raws = [
                pb.GetRateLimitsReq(requests=[
                    pb.RateLimitReq(
                        name="bench", unique_key=str(k), hits=1,
                        limit=1_000_000, duration=3_600_000,
                    )
                    for k in rng.integers(0, n_keys, batch)
                ]).SerializeToString()
                for _ in range(4)
            ]

        async def serve(raw):
            """One server round trip: the V1Servicer fast path inline.
            Records the transport edges (decode/encode) when a flight
            recorder is installed — the daemon's servicer does the same,
            so the telemetry-on phase below measures the real
            instrumented path."""
            fr = flightrec.get()
            t0 = time.perf_counter() if fr is not None else 0.0
            parsed = fastwire.parse_req(raw, arena)
            if fr is not None:
                fr.edge("decode", time.perf_counter() - t0)
            if parsed is None:
                msg = pb.GetRateLimitsReq.FromString(raw)
                parsed = convert.columns_from_pb(msg.requests)
            cols, errors, special = parsed
            mat, errs = await inst.get_rate_limits_columns(cols)
            t1 = time.perf_counter() if fr is not None else 0.0
            out, _over = fastwire.encode_resp(mat)
            if fr is not None:
                fr.edge("encode", time.perf_counter() - t1)
            # Client-side decode closes the loop (the response bytes
            # must be real and parseable, or the rung measures a write
            # into the void).
            if fastwire.parse_resp(out) is None:
                pb.GetRateLimitsResp.FromString(out)
            return out

        for r in raws[:3]:  # warm: compiles + first-D2H setup
            await serve(r)

        # Measured end-to-end latency: serial, each batch awaited.
        n_lat = 30 if FAST else 150
        lat = []
        t_budget = time.perf_counter() + (6 if FAST else 12)
        for i in range(n_lat):
            t1 = time.perf_counter()
            await serve(raws[i % len(raws)])
            lat.append((time.perf_counter() - t1) * 1e3)
            if time.perf_counter() > t_budget:
                break
        p50, p99 = _pcts(lat)

        # Sustained serving: C concurrent clients, 3 segments for the
        # recorded spread; overlap counters deltaed across the phase.
        # Concurrency exceeds one tick window's worth of batches (the
        # 4096-request window holds 4 of these) so the backlog forms
        # MULTIPLE dispatched windows and the pipeline actually runs
        # deep — synchronous round-trippers at low concurrency would
        # hand the loop one window at a time and measure serial
        # dispatch, not the serving steady state.
        concurrency = 32
        n_tp = 32 if FAST else 96
        sem = asyncio.Semaphore(concurrency)

        async def one(i):
            async with sem:
                await serve(raws[i % len(raws)])

        # Concurrent warm wave: the first coalesced window compiles/
        # first-transfers at the wide program width — off the record.
        await asyncio.gather(*(one(i) for i in range(concurrency)))
        h2d_w0 = getattr(engine, "metric_h2d_windows", 0)
        h2d_o0 = getattr(engine, "metric_h2d_overlapped", 0)
        seg_rates = []
        for _ in range(4):
            s0 = time.perf_counter()
            await asyncio.gather(*(one(i) for i in range(n_tp)))
            seg_rates.append(
                n_tp * batch / max(time.perf_counter() - s0, 1e-9))
        seg = sorted(seg_rates)
        core = seg[1:-1]  # middle segments: drop the residual-compile
        # (first) and any GC-spiked outlier, like rung_engine's spread
        windows = getattr(engine, "metric_h2d_windows", 0) - h2d_w0
        overlapped = getattr(engine, "metric_h2d_overlapped", 0) - h2d_o0

        # Telemetry-on phase (docs/observability.md): the same drive
        # pattern with a flight recorder installed, so the record
        # carries (a) per-stage p50/p99 from real serving windows and
        # (b) the measured cost of the instrumentation itself.  The
        # overhead ratio compares best segment against best segment —
        # medians would fold scheduler noise into a number whose gate
        # (≤1.05×, check_bench_regression.py) is tight.
        prev_rec = flightrec.get()
        rec = flightrec.FlightRecorder(windows=512)
        flightrec.install(rec)
        try:
            await asyncio.gather(*(one(i) for i in range(concurrency)))
            on_rates = []
            for _ in range(3):
                s0 = time.perf_counter()
                await asyncio.gather(*(one(i) for i in range(n_tp)))
                on_rates.append(
                    n_tp * batch / max(time.perf_counter() - s0, 1e-9))
            stage_pcts = rec.stage_percentiles()
        finally:
            if prev_rec is not None:
                flightrec.install(prev_rec)
            else:
                flightrec.uninstall()

        # Host serving CPU per batch, codec + arena decode inline (the
        # same metric the service rung records; the device never runs).
        cpu_best = 1e9
        if native:
            for _ in range(7):
                c0 = time.perf_counter()
                out = fastwire.parse_req(raws[0], arena)
                fastwire.encode_resp(_zero_resp_mat(batch))
                cpu_best = min(cpu_best, time.perf_counter() - c0)
                if out is not None:
                    out[0].release()

        rate = seg[len(seg) // 2]
        out = {
            "rung": "serve_loopback_10m",
            "keys": n_keys,
            "batch": batch,
            "client": "columnar" if native else "object",
            "concurrency": concurrency,
            "measured": True,  # wall clock through the full instance
            "decisions_per_sec": round(rate, 1),
            "spread": round(
                (core[-1] - core[0]) / max(core[-1], 1e-9), 3),
            "spread_all": round(
                (seg[-1] - seg[0]) / max(seg[-1], 1e-9), 3),
            "loopback_p50_ms": round(p50, 3),
            "loopback_p99_ms": round(p99, 3),
            "p99_vs_2ms_target": round(p99 / TARGET_P99_MS, 4),
            "vs_1m_served_target": round(rate / 1e6, 4),
            "h2d_overlap_ratio": round(
                overlapped / max(1, windows), 4),
            "arena_leases": getattr(arena, "metric_leases", 0),
            "arena_misses": getattr(arena, "metric_misses", 0),
            "telemetry_overhead_ratio": round(
                max(seg) / max(max(on_rates), 1e-9), 4),
        }
        for s in ("decode", "pack", "h2d", "tick", "encode"):
            pct = stage_pcts.get(s, {})
            out[f"stage_{s}_p50_ms"] = pct.get("p50_ms", 0.0)
            out[f"stage_{s}_p99_ms"] = pct.get("p99_ms", 0.0)
        if native:
            out["serve_cpu_ms_per_batch"] = round(cpu_best * 1e3, 3)
        return out
    finally:
        await inst.close()  # owns (and closes) the passed engine


def _zero_resp_mat(batch):
    m = np.zeros((5, batch), np.int64)
    m[1] = 1_000_000
    m[2] = 999_999
    m[3] = 1_700_000_003_600_000
    return m


def rung_serve_loopback(engine, n_keys):
    return asyncio.run(_loopback_bench(engine, n_keys))


# ----------------------------------------------------------------------
# Multi-process edge serving rung (docs/edge.md)
# ----------------------------------------------------------------------
def rung_serve_multiproc():
    """Served throughput through the shared-memory edge plane: N worker
    PROCESSES decode fastwire frames into shm slab rings concurrently
    (no GIL between them) while the owner drains every ring into one
    tick loop — the serving path whose decode ceiling the loopback rung
    measures one process at a time.

    Exact-work invariants, all gated at ABSOLUTE ZERO
    (scripts/check_bench_regression.py):

    * ``multiproc_parity_errors`` — after the drive drains, a zero-hit
      probe of every key reads the engine's applied hits; the total
      must equal the sum of worker-acked hits (each worker drives a
      disjoint keyspace, so the split is exact).
    * ``multiproc_double_served`` — responses for windows not pending
      (served twice or never published).
    * ``multiproc_dropped_acked`` — published windows that never came
      back.
    """
    from gubernator_tpu.edge.plane import EdgeConfig, EdgePlane
    from gubernator_tpu.ops.engine import TickEngine
    from gubernator_tpu.ops.reqcols import (
        CREATED_UNSET, ReqColumns, key_blob_from_parts,
    )
    from gubernator_tpu.service.tickloop import TickLoop
    from gubernator_tpu.transport import fastwire
    from gubernator_tpu.utils import flightrec

    if fastwire.load() is None:
        return {"rung": "serve_multiproc", "skipped": "no native codec"}
    workers = 2 if FAST else 4
    batch = 1000                      # the public API batch cap
    windows = 100 if FAST else 2500   # per worker
    n_keys = 4096                     # per worker, disjoint by prefix
    limit = 1 << 40
    duration = 3_600_000
    engine = TickEngine(capacity=1 << 16, max_batch=4096)
    loop = TickLoop(engine, batch_limit=4096)
    plane = EdgePlane(loop, EdgeConfig(
        workers=workers, slabs=8, ring_depth=16, max_batch=batch,
        mode="drive",
        drive={
            "batch": batch, "windows": windows, "keys": n_keys,
            "hits": 1, "limit": limit, "duration": duration, "frames": 8,
        },
    ))
    rec = flightrec.FlightRecorder(windows=512)
    prev_rec = flightrec.get()
    flightrec.install(rec)
    try:
        plane.start()
        if not plane.wait_ready(60):
            raise RuntimeError("edge workers never became ready")
        t0 = time.perf_counter()
        plane.go()
        if not plane.wait_drive_done(600):
            raise RuntimeError("edge drive did not complete")
        elapsed = time.perf_counter() - t0
        # Counter snapshot BEFORE close: teardown unmaps the shm views
        # the counter block lives in.
        tot = plane.totals()
        plane.close()
        stage_pcts = rec.stage_percentiles()
    finally:
        if prev_rec is not None:
            flightrec.install(prev_rec)
        else:
            flightrec.uninstall()

    # Zero-hit probe: read back every bucket's remaining and compare the
    # engine-applied total against the workers' acked-hit accounting.
    consumed = 0
    for wid in range(workers):
        for at in range(0, n_keys, batch):
            keys = [f"w{wid}_{k}" for k in range(at, min(at + batch, n_keys))]
            n = len(keys)
            blob, off = key_blob_from_parts(["edge"] * n, keys)
            z = np.zeros(n, np.int64)
            cols = ReqColumns(
                blob, off, z, np.full(n, limit, np.int64),
                np.full(n, duration, np.int64), z, z,
                np.full(n, CREATED_UNSET, np.int64), z,
                name_len=np.full(n, 4, np.int64),
            )
            mat, errs = loop.submit_columns(cols).result(timeout=60)
            if errs:
                raise RuntimeError(f"probe errors: {errs}")
            consumed += int((limit - mat[2]).sum())
    loop.close()
    engine.close()

    rate = tot["rows_acked"] / max(elapsed, 1e-9)
    out = {
        "rung": "serve_multiproc",
        "workers": workers,
        "batch": batch,
        "windows_per_worker": windows,
        "measured": True,
        "decisions_per_sec": round(rate, 1),
        "elapsed_s": round(elapsed, 3),
        "vs_5m_served_target": round(rate / 5e6, 4),
        "windows_published": int(tot["windows_published"]),
        "windows_acked": int(tot["windows_acked"]),
        "hits_published": int(tot["hits_published"]),
        "hits_acked": int(tot["hits_acked"]),
        "engine_applied_hits": consumed,
        "decode_seconds_total": round(tot["decode_seconds"], 4),
        "backpressure_waits": int(tot["backpressure_waits"]),
        "worker_restarts": int(tot["restarts"]),
        # -- ABSOLUTE_ZERO-gated exact-work counters --
        "multiproc_parity_errors": abs(consumed - int(tot["hits_acked"])),
        "multiproc_double_served": int(tot["double_served"]),
        "multiproc_dropped_acked": int(
            tot["windows_published"] - tot["windows_acked"]
        ),
    }
    for s in ("decode", "pack", "h2d", "tick", "encode"):
        pct = stage_pcts.get(s, {})
        out[f"stage_{s}_p50_ms"] = pct.get("p50_ms", 0.0)
        out[f"stage_{s}_p99_ms"] = pct.get("p99_ms", 0.0)
    return out


# ----------------------------------------------------------------------
# Chaos rung: partition the GLOBAL owner, then prove zero hit loss
# ----------------------------------------------------------------------
async def _chaos_bench():
    """Fault-injected 2-daemon cluster (docs/resilience.md): the GLOBAL
    owner runs at 100% injected RPC failure while a non-owner serves
    degraded local answers and buffers hits; after recovery every hit
    must land on the owner.  ``hit_redelivery_loss`` is the exact count
    of hits that failed to land — check_bench_regression.py gates it at
    0 absolutely (a lost hit is lost accounting, baseline or not)."""
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.resilience import FaultInjector, ResilienceConfig
    from gubernator_tpu.types import Behavior, RateLimitRequest

    behaviors = BehaviorConfig(global_sync_wait=0.02, batch_wait=0.001)
    resilience = ResilienceConfig(
        breaker_open_for=0.05, breaker_open_cap=0.1, breaker_min_requests=3,
    )
    inj = FaultInjector(seed=7)
    c = await Cluster.start(2, behaviors=behaviors, resilience=resilience,
                            fault_injector=inj)
    try:
        name, key = "chaosbench", "ck"
        owner = c.find_owning_daemon(name, key)
        non_owner = c.list_non_owning_daemons(name, key)[0]
        ni = c.daemons.index(non_owner)
        owner_addr = owner.conf.grpc_listen_address
        inj.set_fault(owner_addr, partition=True)

        def greq(hits):
            return RateLimitRequest(
                name=name, unique_key=key, hits=hits, limit=1_000_000,
                duration=3_600_000, behavior=Behavior.GLOBAL,
            )

        client = non_owner.client()
        n_req = 50 if FAST else 300
        sent = 0
        t0 = time.perf_counter()
        for _ in range(n_req):
            out = await client.get_rate_limits([greq(1)])
            if out[0].error:
                raise RuntimeError(f"degraded answer errored: {out[0].error}")
            sent += 1
        degraded_dt = time.perf_counter() - t0
        await client.close()

        inj.clear()
        oc = owner.client()
        landed = 0
        deadline = time.perf_counter() + 15
        while time.perf_counter() < deadline:
            r = (await oc.get_rate_limits([greq(0)]))[0]
            landed = 1_000_000 - r.remaining
            if landed == sent:
                break
            await asyncio.sleep(0.02)
        await oc.close()

        m = non_owner.metrics
        loops_alive = all(
            not t.done() for t in non_owner.instance.global_mgr._tasks
        )
        return {
            "rung": "chaos_redelivery",
            # Degraded-mode serving rate: local answers while the owner
            # is 100% unavailable (bounded degradation, not an outage).
            "requests_per_sec": round(sent / degraded_dt, 1),
            "hits_sent": sent,
            "hits_landed": int(landed),
            "hit_redelivery_loss": int(sent - landed),
            "redelivered_hits": m.sample(
                "gubernator_global_redelivered_hits_total"),
            "dropped_hits": m.sample("gubernator_global_dropped_hits_total"),
            "breaker_opens": m.sample(
                "gubernator_breaker_transitions_total",
                {"peerAddr": owner_addr, "to": "open"}),
            "loops_alive": loops_alive,
        }
    finally:
        await c.stop()


def rung_chaos():
    return asyncio.run(_chaos_bench())


# ----------------------------------------------------------------------
# Federation rung: two regions, WAN partition, bounded over-admission
# and exactly-zero hit loss after the heal (docs/federation.md)
# ----------------------------------------------------------------------
async def _federation_bench():
    """Two-region federated cluster under a full WAN partition.  Both
    regions keep serving from local state; drift is bounded by
    staleness × local rate.  Two keys measure the two halves of the
    guarantee:

    * an unconstrained key counts every hit taken on both sides during
      the partition — after the heal both regions must converge on the
      exact union (``federation_hit_loss_after_heal``, gated at 0
      absolutely: over-admission overshoots, loss undershoots);
    * a small-limit key is driven to OVER_LIMIT on both sides — the
      combined admissions beyond one limit's worth are the partition's
      over-admission (``federation_over_admission_ratio`` = extra/limit,
      structurally <= 1.0 for a 2-region split; gated at 1.0)."""
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.resilience import FaultInjector, ResilienceConfig
    from gubernator_tpu.types import Behavior, RateLimitRequest, Status

    behaviors = BehaviorConfig(global_sync_wait=0.02, batch_wait=0.001)
    resilience = ResilienceConfig(
        breaker_open_for=0.05, breaker_open_cap=0.1, breaker_min_requests=3,
        forward_backoff_base=0.002, forward_backoff_cap=0.02,
    )
    inj = FaultInjector(seed=11)
    c = await Cluster.start(
        4, datacenters=["us", "us", "eu", "eu"], behaviors=behaviors,
        resilience=resilience, fault_injector=inj, federation=True,
        federation_interval=0.02,
    )
    try:
        name = "fedbench"
        small_limit = 24 if FAST else 60

        def mr(key, hits, limit):
            return RateLimitRequest(
                name=name, unique_key=key, hits=hits, limit=limit,
                duration=3_600_000, behavior=Behavior.MULTI_REGION,
            )

        owners = {
            r: {
                "loss": c.find_owning_daemon_in_region(name, "loss", r),
                "over": c.find_owning_daemon_in_region(name, "over", r),
            }
            for r in ("us", "eu")
        }

        # Healthy warm-up: one hit each side compiles the programs and
        # proves the exchange is live before the partition starts.
        for r in ("us", "eu"):
            cl = owners[r]["loss"].client()
            out = await cl.get_rate_limits(
                [mr("loss", 1, 1_000_000)], timeout=30.0)
            if out[0].error:
                raise RuntimeError(f"warm-up errored: {out[0].error}")
            await cl.close()

        # WAN partition: directional schedules cut every cross-region
        # link; intra-region links stay up.
        for da in c.daemons:
            for db in c.daemons:
                if da.conf.data_center == "us" and db.conf.data_center == "eu":
                    inj.set_fault(db.conf.grpc_listen_address,
                                  from_peer=da.advertise_address,
                                  partition=True)
                    inj.set_fault(da.conf.grpc_listen_address,
                                  from_peer=db.advertise_address,
                                  partition=True)

        n_loss = {"us": 20 if FAST else 120, "eu": 15 if FAST else 90}
        sent = 2  # warm-up hits
        t0 = time.perf_counter()
        for r in ("us", "eu"):
            cl = owners[r]["loss"].client()
            for _ in range(n_loss[r]):
                out = await cl.get_rate_limits(
                    [mr("loss", 1, 1_000_000)], timeout=30.0)
                if out[0].error:
                    raise RuntimeError(f"degraded answer errored: "
                                       f"{out[0].error}")
                sent += 1
            await cl.close()
        degraded_dt = time.perf_counter() - t0

        # Over-admission key: each isolated region admits up to one full
        # limit; drive both sides to OVER_LIMIT and count admissions.
        admitted = 0
        for r in ("us", "eu"):
            cl = owners[r]["over"].client()
            for _ in range(2 * small_limit):
                out = await cl.get_rate_limits(
                    [mr("over", 1, small_limit)], timeout=30.0)
                if out[0].error:
                    raise RuntimeError(f"over key errored: {out[0].error}")
                if out[0].status == Status.OVER_LIMIT:
                    break
                admitted += 1
            await cl.close()
        over_ratio = max(0, admitted - small_limit) / small_limit

        # Heal: buffered envelopes replay, the receive ledger dedupes,
        # and both regions converge on the exact union of loss-key hits.
        inj.clear()
        landed = {}
        for r in ("us", "eu"):
            cl = owners[r]["loss"].client()
            landed[r] = 0
            deadline = time.perf_counter() + 20
            while time.perf_counter() < deadline:
                resp = (await cl.get_rate_limits(
                    [mr("loss", 0, 1_000_000)], timeout=30.0))[0]
                landed[r] = 1_000_000 - resp.remaining
                if landed[r] == sent:
                    break
                await asyncio.sleep(0.02)
            await cl.close()
        loss = abs(sent - landed["us"]) + abs(sent - landed["eu"])

        def total(metric, labels=None):
            return sum(
                d.metrics.sample(metric, labels) or 0 for d in c.daemons)

        return {
            "rung": "federation_2r",
            "requests_per_sec": round(
                (n_loss["us"] + n_loss["eu"]) / degraded_dt, 1),
            "hits_sent": sent,
            "hits_landed_us": int(landed["us"]),
            "hits_landed_eu": int(landed["eu"]),
            # The two gated headline numbers (check_bench_regression.py).
            "federation_hit_loss_after_heal": int(loss),
            "federation_over_admission_ratio": round(over_ratio, 4),
            "over_admitted": int(admitted),
            "over_limit": small_limit,
            "envelopes_sent": total(
                "gubernator_tpu_federation_envelopes_total",
                {"result": "sent"}),
            "envelopes_applied": total(
                "gubernator_tpu_federation_envelopes_total",
                {"result": "applied"}),
            "redeliveries": total(
                "gubernator_tpu_federation_redeliveries_total"),
        }
    finally:
        await c.stop()


def rung_federation():
    return asyncio.run(_federation_bench())


# ----------------------------------------------------------------------
# Restart-recovery rung: traffic -> SIGTERM -> restart -> verify, plus a
# ring-swap ownership handoff — both losses gated at exactly 0
# ----------------------------------------------------------------------
async def _restart_bench():
    """Crash-safe persistence acceptance (docs/persistence.md): (1) a
    daemon with snapshots enabled takes traffic, drains gracefully (the
    SIGTERM path), and a restart from the same directory must account
    every hit — ``restart_state_loss`` is the exact number of keys whose
    consumed budget regressed; (2) a 3-node cluster swaps its ring out
    from under a GLOBAL owner and the accumulated state must continue on
    the new owner — ``ownership_transfer_loss`` is the exact number of
    hits that reset.  check_bench_regression.py gates both at 0
    absolutely (a restart or ring change that forgets accounting is a
    rate-limit bypass, baseline or not)."""
    import tempfile

    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.config import BehaviorConfig, Config, DaemonConfig
    from gubernator_tpu.transport.daemon import Daemon
    from gubernator_tpu.types import Behavior, RateLimitRequest

    snap_dir = tempfile.mkdtemp(prefix="guber-restart-bench-")

    def dconf():
        conf = DaemonConfig(
            grpc_listen_address="127.0.0.1:0",
            http_listen_address="",
            peer_discovery_type="none",
        )
        conf.config = Config(
            cache_size=1 << 13, snapshot_dir=snap_dir,
            snapshot_interval=0.05,
        )
        return conf

    def lreq(key, hits):
        return RateLimitRequest(
            name="restart", unique_key=key, hits=hits, limit=1_000_000,
            duration=3_600_000,
        )

    # --- Part 1: traffic -> graceful drain -> restart -> verify -------
    n_keys = 64 if FAST else 256
    hits_per_key = 3
    d = Daemon(dconf())
    await d.start()
    await d.wait_for_connect()
    client = d.client()
    t0 = time.perf_counter()
    for i in range(n_keys):
        out = await client.get_rate_limits([lreq(f"k{i}", hits_per_key)])
        if out[0].error:
            raise RuntimeError(out[0].error)
    traffic_dt = time.perf_counter() - t0
    await client.close()
    t0 = time.perf_counter()
    await d.close()  # the SIGTERM handler's path: drain + final base
    drain_s = time.perf_counter() - t0

    d2 = Daemon(dconf())
    t0 = time.perf_counter()
    await d2.start()
    restore_s = time.perf_counter() - t0
    await d2.wait_for_connect()
    c2 = d2.client()
    out = await c2.get_rate_limits(
        [lreq(f"k{i}", 0) for i in range(n_keys)]
    )
    await c2.close()
    restart_loss = sum(
        1 for r in out if 1_000_000 - r.remaining != hits_per_key
    )
    restored_items = d2.instance.restore_stats.get("restored_items", 0)
    await d2.close()

    # --- Part 2: ring swap -> ownership handoff -> verify -------------
    behaviors = BehaviorConfig(global_sync_wait=0.02, batch_wait=0.001)
    c = await Cluster.start(3, behaviors=behaviors)
    transfer_loss = 0
    transferred = 0
    try:
        name, key = "restartbench", "ok"
        owner = c.find_owning_daemon(name, key)
        oi = c.daemons.index(owner)
        sent = 20 if FAST else 60

        def greq(hits):
            return RateLimitRequest(
                name=name, unique_key=key, hits=hits, limit=1_000_000,
                duration=3_600_000, behavior=Behavior.GLOBAL,
            )

        oc = owner.client()
        for _ in range(sent):
            out = await oc.get_rate_limits([greq(1)])
            if out[0].error:
                raise RuntimeError(out[0].error)
        await oc.close()

        new_peers = [
            p for p in c.peers
            if p.grpc_address != owner.conf.grpc_listen_address
        ]
        for dmn in c.daemons:
            dmn.set_peers(new_peers)
        new_owner_peer = owner.instance.get_peer(f"{name}_{key}")
        new_owner = next(
            dmn for dmn in c.daemons
            if dmn.conf.grpc_listen_address
            == new_owner_peer.info.grpc_address
        )
        nc = new_owner.client()
        landed = 0
        deadline = time.perf_counter() + 15
        while time.perf_counter() < deadline:
            r = (await nc.get_rate_limits([greq(0)]))[0]
            landed = 1_000_000 - r.remaining
            if landed >= sent:
                break
            await asyncio.sleep(0.02)
        await nc.close()
        transfer_loss = int(sent - landed)
        transferred = owner.metrics.sample(
            "gubernator_tpu_ownership_transfers_total",
            {"result": "pushed"})
    finally:
        await c.stop()

    import shutil

    shutil.rmtree(snap_dir, ignore_errors=True)
    return {
        "rung": "restart_recovery",
        "keys": n_keys,
        "requests_per_sec": round(n_keys / traffic_dt, 1),
        "restart_state_loss": int(restart_loss),
        "ownership_transfer_loss": transfer_loss,
        "restored_items": int(restored_items),
        "transferred_keys": transferred,
        "drain_s": round(drain_s, 3),
        "restore_s": round(restore_s, 3),
    }


def rung_restart_recovery():
    return asyncio.run(_restart_bench())


# ----------------------------------------------------------------------
# Overload rung: ~10x sustainable load against the admission plane
# ----------------------------------------------------------------------
async def _overload_bench():
    """Saturation acceptance for the admission plane (docs/overload.md):
    drive the full serving instance far past its sustainable rate with
    tight propagated budgets and a small bounded queue, and prove the
    overload control plane degrades instead of collapsing.  Gated keys
    (scripts/check_bench_regression.py):

      expired_served            requests whose deadline had passed but
                                were served real answers anyway —
                                ABSOLUTE_ZERO (a served-after-expiry
                                answer is wasted device work AND a lie
                                about the caller's outcome)
      overload_admitted_p99_ms  p99 latency of requests ADMITTED while
                                ~10x load was offered (lower-better;
                                the bounded queue + expiry shed keep it
                                near the unloaded figure instead of
                                queueing-delay collapse)
      overload_goodput_ratio    decisions served within their budget
                                under overload / the same instance's
                                unloaded rate (direction-aware floor +
                                absolute-min 0.7: shed answers are
                                cheap, so goodput must survive)
      overload_rss_growth_mb    peak-RSS growth across the overload
                                phase (ABSOLUTE_MAX: a saturated daemon
                                must shed, not buffer, the excess)
    """
    import resource

    from gubernator_tpu.admission import SHED_EXPIRED_MSG
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.service.instance import InstanceConfig, V1Instance

    batch = 1000
    # The leaky/service rungs' table size: the narrow serving program at
    # this capacity is already XLA-compiled by the earlier rungs, so
    # this rung pays measurement time, not compile time.
    n_keys = 1 << 17 if FAST else 1 << 20
    # Small bounded queue (4 windows) + the AIMD limiter on: saturation
    # becomes shed decisions within a few windows instead of an
    # unbounded backlog, and the limiter path is exercised end to end.
    knobs = {
        "GUBER_PENDING_LIMIT": str(4 * batch),
        "GUBER_TARGET_P99_MS": "25",
        "GUBER_SHED_POLICY": "fail-open",
    }
    prev = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    try:
        inst = await V1Instance.create(
            InstanceConfig(behaviors=BehaviorConfig(), cache_size=n_keys)
        )
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    try:
        loop_ = inst.tick_loop
        rng = np.random.default_rng(23)
        payloads = [
            _cols(rng.integers(0, n_keys, batch), 1_000_000, 3_600_000, 0)
            for _ in range(16)
        ]
        for p in payloads[:3]:  # warm: residual compiles, first D2H
            await inst.get_rate_limits_columns(p)

        # --- Unloaded reference: modest closed-loop concurrency -------
        async def drive(concurrency, n_calls, budget_s):
            """Closed-loop clients; returns (served, shed, in_budget,
            admitted latencies ms, wall seconds).  Served vs shed is
            decided from the response itself: expired sheds carry the
            retriable error, fail-open overflow sheds answer
            remaining == limit (a real decision always consumes its
            hit, so remaining <= limit - 1)."""
            served = shed = in_budget = 0
            lats = []
            idx = 0

            async def one():
                nonlocal served, shed, in_budget, idx
                i = idx = (idx + 1) % len(payloads)
                deadline = (
                    time.monotonic() + budget_s if budget_s else None)
                t0 = time.perf_counter()
                mat, errs = await inst.get_rate_limits_columns(
                    payloads[i], deadline=deadline)
                dt = time.perf_counter() - t0
                if errs and any(
                        "request shed" in m for m in errs.values()):
                    shed += len(errs)
                    served += mat.shape[1] - len(errs)
                elif bool((mat[2] == 1_000_000).all()):
                    shed += mat.shape[1]  # fail-open policy answers
                else:
                    served += mat.shape[1]
                    lats.append(dt * 1e3)
                    if budget_s is None or dt <= budget_s:
                        in_budget += mat.shape[1]

            sem = asyncio.Semaphore(concurrency)

            async def worker():
                async with sem:
                    await one()

            t0 = time.perf_counter()
            await asyncio.gather(*(worker() for _ in range(n_calls)))
            return served, shed, in_budget, lats, time.perf_counter() - t0

        n_ref = 24 if FAST else 96
        ref_served, _, _, ref_lats, ref_dt = await drive(4, n_ref, None)
        unloaded_rate = ref_served / max(ref_dt, 1e-9)
        _, ref_p99 = _pcts(ref_lats)

        # --- Pre-expired probe: the ABSOLUTE_ZERO invariant -----------
        # Requests whose budget is already spent at submit time must be
        # shed with the retriable error, never answered for real.
        expired_extra = 0
        for i in range(4):
            mat, errs = await inst.get_rate_limits_columns(
                payloads[i], deadline=time.monotonic() - 1.0)
            expired_extra += sum(
                1 for j in range(mat.shape[1])
                if errs.get(j) != SHED_EXPIRED_MSG
            )

        # --- Overload: ~10x the sustainable closed-loop concurrency ---
        # Budgets sized a few unloaded-p99s out: long enough that an
        # admitted window completes, short enough that a deep backlog
        # expires in the queue instead of being served late.
        budget_s = max(4 * ref_p99 / 1e3, 0.05)
        rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        shed0 = dict(loop_.metric_shed_admission)
        n_over = 120 if FAST else 480
        served, shed, in_budget, lats, over_dt = await drive(
            40, n_over, budget_s)
        rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        _, over_p99 = _pcts(lats or [0.0])
        goodput = in_budget / max(over_dt, 1e-9)
        shed_delta = {
            k: loop_.metric_shed_admission.get(k, 0) - shed0.get(k, 0)
            for k in loop_.metric_shed_admission
        }
        return {
            "rung": "overload_shed",
            "keys": n_keys,
            "batch": batch,
            "measured": True,
            "unloaded_rate": round(unloaded_rate, 1),
            "unloaded_p99_ms": round(ref_p99, 3),
            "offered_vs_served": round(
                (served + shed) / max(served, 1), 2),
            "decisions_per_sec": round(goodput, 1),
            "overload_goodput_ratio": round(
                goodput / max(unloaded_rate, 1e-9), 4),
            "overload_admitted_p99_ms": round(over_p99, 3),
            "expired_served": int(
                loop_.metric_expired_served + expired_extra),
            "shed_total": int(sum(shed_delta.values())),
            "shed_by_reason": {k: int(v) for k, v in shed_delta.items()},
            "window_limit_final": loop_.limiter.window_limit,
            "limiter_decreases": loop_.limiter.metric_decreases,
            "overload_rss_growth_mb": round((rss1 - rss0) / 1024.0, 1),
        }
    finally:
        await inst.close()


def rung_overload():
    return asyncio.run(_overload_bench())


# ----------------------------------------------------------------------
# Cooperative quota-lease rung (docs/leases.md)
# ----------------------------------------------------------------------
def rung_engine_leases():
    """Client-side cooperative leases vs per-request server decisions.

    Phase 1 (baseline) serves every admission as an ordinary engine
    decision: server-served items == client admissions.  Phase 2 serves
    the same admission stream through a LeaseCache backed by
    LeaseManager.grant_local/sync_local — the server sees only the lease
    *edges* (grants, delta syncs, the shutdown release round), an order
    of magnitude fewer served items at identical bucket accounting.

    Exported gates (scripts/check_bench_regression.py):

      lease_traffic_reduction    baseline served items / lease-mode
                                 served items — HIGHER is better, with
                                 an absolute >=10x floor (the headline)
      lease_over_admission       sum over keys of max(0, local
                                 admissions - granted budget): the
                                 never-over-admit invariant
                                 (ABSOLUTE_ZERO)
      lease_dispatch_per_window  device dispatches per lease column
                                 window — batched on-device accounting
                                 means exactly one (absolute max 1.0)
      lease_bucket_drift         max over keys of |bucket remaining -
                                 (limit - admissions)| after the release
                                 round settles: the constant-decision-
                                 correctness observable (ABSOLUTE_ZERO)
    """
    from gubernator_tpu.leases import (
        LeaseCache, LeaseConfig, LeaseManager, LeaseSigner, LeaseSpec)
    from gubernator_tpu.ops.engine import TickEngine
    from gubernator_tpu.types import RateLimitRequest

    n_keys = 64 if FAST else 512
    per_key = 50 if FAST else 200
    limit, duration = 1_000_000, 3_600_000
    now = [1_700_000_000_000]  # virtual ms; both tiers see this clock

    eng = TickEngine(capacity=1 << 12, max_batch=max(64, n_keys))

    def reqs(prefix, hits=1):
        return [RateLimitRequest(
            name="lease_bench", unique_key=f"{prefix}{i}", hits=hits,
            limit=limit, duration=duration, algorithm=0,
        ) for i in range(n_keys)]

    # -- Phase 1: every admission is a server-served decision ----------
    eng.process(reqs("warm_"), now=now[0])  # compile the batch width
    t0 = time.perf_counter()
    for r in range(per_key):
        eng.process(reqs("base_"), now=now[0] + r)
    base_dt = time.perf_counter() - t0
    base_items = n_keys * per_key

    # -- Phase 2: the same admission stream through the lease tier -----
    mgr = LeaseManager(
        eng,
        config=LeaseConfig(
            ttl_ms=60_000, max_budget=per_key, secret=b"bench-lease"),
        signer=LeaseSigner(secret=b"bench-lease"),
        clock=lambda: now[0] / 1000.0,
    )
    served = {"items": 0}
    granted = {}

    def grant_fn(specs):
        served["items"] += len(specs)
        toks = mgr.grant_local(specs, now_ms=now[0])
        for s, t in zip(specs, toks):
            if t is not None:
                granted[s.key] = granted.get(s.key, 0) + t.budget
        return toks

    def sync_fn(syncs):
        served["items"] += len(syncs)
        return mgr.sync_local(syncs, now_ms=now[0])

    cache = LeaseCache(
        grant_fn, sync_fn, clock=lambda: now[0] / 1000.0,
        verifier=mgr.verifier(), want_budget=per_key,
    )
    specs = [LeaseSpec(name="lease_bench", key=f"lease_{i}", limit=limit,
                       duration=duration) for i in range(n_keys)]
    # Warm the 1-wide grant/sync/column programs outside the timing.
    cache.admit(LeaseSpec(name="lease_bench", key="lease_warm",
                          limit=limit, duration=duration))
    served["items"] = 0
    granted.clear()
    disp0, win0 = eng.metric_lease_dispatches, eng.metric_lease_windows

    admits = {s.key: 0 for s in specs}
    t0 = time.perf_counter()
    for r in range(per_key):
        now[0] += 1
        for s in specs:
            if cache.admit(s):
                admits[s.key] += 1
    lost = cache.close()  # release round: one batched sync window
    lease_dt = time.perf_counter() - t0
    lease_items = served["items"]

    over = sum(
        max(0, admits[s.key] - granted.get(s.key, 0)) for s in specs)
    disp = eng.metric_lease_dispatches - disp0
    wins = eng.metric_lease_windows - win0

    # Constant correctness: after the release round settles, every lease
    # bucket holds exactly limit - per_key — the same accounting a
    # per-request phase leaves behind (hits=0 probes consume nothing).
    probe = eng.process(
        [RateLimitRequest(
            name="lease_bench", unique_key=s.key, hits=0, limit=limit,
            duration=duration, algorithm=0) for s in specs],
        now=now[0])
    drift = max(abs((limit - per_key) - r.remaining) for r in probe)

    return {
        "rung": "engine_leases",
        "keys": n_keys,
        "admissions_per_key": per_key,
        "measured": True,
        "baseline_served_items": base_items,
        "lease_served_items": lease_items,
        "baseline_served_rps": round(base_items / max(base_dt, 1e-9), 1),
        "lease_served_rps": round(lease_items / max(lease_dt, 1e-9), 1),
        "lease_traffic_reduction": round(
            base_items / max(1, lease_items), 2),
        "lease_over_admission": int(over),
        "lease_dispatch_per_window": round(disp / max(1, wins), 4),
        "lease_bucket_drift": int(drift),
        "lease_sync_lost": int(lost),
        "local_admits": cache.metric_local_admits,
        "grants": mgr.metric_grants,
        "backend": jax.default_backend(),
    }


# ----------------------------------------------------------------------
# Sharded-table mesh rung (8 virtual devices, CPU backend, subprocess)
# ----------------------------------------------------------------------
def child_mesh_tick():
    """Runs in the subprocess: MeshTickEngine over an 8-device mesh —
    the multi-chip WorkerPool analog, on the ragged flat serving path
    (one slot-sorted batch + extent offsets per tick, each shard walks
    only its own extent on device, responses gathered with one psum).

    Exports the scaling story and the exact-work invariants the CI gate
    holds (scripts/check_bench_regression.py):

      mesh_scaling_efficiency     8-dev rate / (8 x 1-dev rate) — the
                                  near-linear-scaling observable
                                  (direction-aware gate: must not decay)
      mesh_routing_parity_errors  device-derived ownership vs the host
                                  hash ring on a served-key sample
                                  (ABSOLUTE_ZERO)
      mesh_dropped_keys /         issued vs resolved decision counts
      mesh_double_served          (ABSOLUTE_ZERO both ways)
    """
    jax.config.update("jax_platforms", "cpu")
    from gubernator_tpu.ops.engine import resolve_ticks
    from gubernator_tpu.parallel.mesh_engine import MeshTickEngine, make_mesh

    batch = 1024
    n_keys = 1 << 12   # fits the 1-dev table too: scaling, not reclaim
    now = 1_700_000_000_000
    iters = 5 if FAST else 20
    rng = np.random.default_rng(5)
    # Unique-key windows (permutations of the keyspace): both rungs run
    # the parts-native unique program, and every key is served — the
    # parity sweep can audit the whole keyspace.
    window_ids = [rng.permutation(n_keys) for _ in range(4)]
    windows = [_cols(ids, 1_000_000, 3_600_000, 0) for ids in window_ids]

    def run(devs):
        eng = MeshTickEngine(
            mesh=make_mesh(devs), local_capacity=1 << 13, max_batch=batch,
        )
        for c in windows:  # warm/compile + make all keys known
            eng.process_columns(c, now=now)
        h0, m0 = eng.metric_hits, eng.metric_misses
        t0 = time.perf_counter()
        done = 0
        pending = []
        for i in range(iters):
            c = windows[i % len(windows)]
            pending.extend(eng.submit_cols(c, now=now + 1 + i).handles())
            done += len(c)
            if len(pending) >= 16:
                resolve_ticks(pending)
                pending.clear()
        resolve_ticks(pending)
        dt = time.perf_counter() - t0
        resolved = (eng.metric_hits - h0) + (eng.metric_misses - m0)
        return eng, done / dt, done, resolved

    eng1, rate1, _, _ = run(jax.devices()[:1])
    del eng1  # release each table before building the next
    n_nodes = len(jax.devices())
    eng8, rate8, done8, resolved8 = run(jax.devices())
    work_delta = resolved8 - done8
    sample = ["bench_" + str(i) for i in range(n_keys)]
    print(
        json.dumps(
            {
                "rung": "mesh_tick_8",
                "shards": n_nodes,
                "batch": batch,
                "decisions_per_sec": round(rate8, 1),
                "decisions_per_sec_1dev": round(rate1, 1),
                # 8-dev vs ideal 8 x 1-dev.  NOTE the venue: the 8
                # "devices" are XLA CPU virtual devices time-slicing ONE
                # host core, so the physical ceiling here is 1/shards
                # (0.125) minus routing/psum overhead — the gate holds
                # the figure from decaying run-over-run; the >=6x
                # near-linear target is the real-multichip (MULTICHIP_r*)
                # acceptance, where per-shard lanes execute in parallel.
                "mesh_scaling_efficiency": round(
                    rate8 / max(n_nodes * rate1, 1e-9), 4
                ),
                "mesh_routing_parity_errors": int(
                    eng8.routing_parity_errors(sample)
                ),
                "mesh_dropped_keys": int(max(-work_delta, 0)),
                "mesh_double_served": int(max(work_delta, 0)),
                "routed_windows": eng8.metric_routed_windows,
                "routed_overflows": eng8.metric_routed_overflows,
                "layout": eng8.layout,
                "backend": "cpu-8dev",
            }
        )
    )


def child_mesh_zipf():
    """Runs in the subprocess: the ragged dispatch under Zipf-1.2
    traffic over an 8-device mesh — the skew regime that used to
    overflow the routed path's per-shard width and fall back to
    host-blocked packing.  The ragged extent walk has no width, so the
    skewed window IS the fast path.

    Exports the ragged acceptance gates
    (scripts/check_bench_regression.py):

      mesh_routed_overflows       pinned-zero canary — the retired
                                  fallback must never fire
                                  (ABSOLUTE_ZERO)
      mesh_ragged_parity_errors   decision mismatches vs a single-chip
                                  TickEngine replaying the same traffic
                                  (ABSOLUTE_ZERO)
      mesh_trace_retraces         ShardedOps.trace_counts growth during
                                  serving — every window reuses the one
                                  warmup-compiled program per variant
                                  (ABSOLUTE_ZERO)
    """
    jax.config.update("jax_platforms", "cpu")
    from gubernator_tpu.ops.engine import TickEngine, resolve_ticks
    from gubernator_tpu.parallel.mesh_engine import MeshTickEngine, make_mesh

    batch = 1024
    n_keys = 1 << 12
    now = 1_700_000_000_000
    iters = 5 if FAST else 20
    rng = np.random.default_rng(7)
    # Zipf 1.2 ids (rung_kernel_zipf's traffic shape): a handful of ids
    # dominate every window, so per-shard extents are maximally skewed.
    window_ids = [
        np.minimum(rng.zipf(1.2, batch) - 1, n_keys - 1)
        for _ in range(4)
    ]
    windows = [_cols(ids, 1_000_000, 3_600_000, 0) for ids in window_ids]

    eng = MeshTickEngine(
        mesh=make_mesh(jax.devices()), local_capacity=1 << 13,
        max_batch=batch,
    )
    for c in windows:  # warm/compile + make all keys known
        eng.process_columns(c, now=now)
    trace0 = dict(eng.ops.trace_counts)
    t0 = time.perf_counter()
    done = 0
    pending = []
    for i in range(iters):
        c = windows[i % len(windows)]
        pending.extend(eng.submit_cols(c, now=now + 1 + i).handles())
        done += len(c)
        if len(pending) >= 16:
            resolve_ticks(pending)
            pending.clear()
    resolve_ticks(pending)
    dt = time.perf_counter() - t0
    retraces = sum(
        eng.ops.trace_counts[k] - trace0.get(k, 0)
        for k in eng.ops.trace_counts
    )

    # Parity reference: a single-chip TickEngine replays the identical
    # schedule — warmup AND the timed loop, so both tables carry the
    # same hit history — then per-request decisions must match exactly
    # (the mesh path only re-partitions the table; duplicate
    # sequencing, window arithmetic, and over_limit cuts are the same
    # math).
    ref = TickEngine(capacity=8 << 13, max_batch=batch)
    for c in windows:
        ref.process_columns(c, now=now)
    for i in range(iters):
        ref.process_columns(windows[i % len(windows)], now=now + 1 + i)
    parity_errors = 0
    for i in range(iters):
        c = windows[i % len(windows)]
        got, _ = eng.process_columns(c, now=now + 10_000 + i)
        want, _ = ref.process_columns(c, now=now + 10_000 + i)
        parity_errors += int((got != want).sum())
    print(
        json.dumps(
            {
                "rung": "mesh_zipf_8",
                "shards": len(jax.devices()),
                "batch": batch,
                "decisions_per_sec": round(done / dt, 1),
                "mesh_routed_overflows": int(eng.metric_routed_overflows),
                "mesh_ragged_parity_errors": int(parity_errors),
                "mesh_trace_retraces": int(retraces),
                "routed_windows": eng.metric_routed_windows,
                "layout": eng.layout,
                "backend": "cpu-8dev",
            }
        )
    )


def child_reshard_live():
    """Runs in the subprocess: elastic live resharding under traffic
    (docs/resharding.md) — an 8-device mesh serving continuously while
    the coordinator runs 8→4 and then 4→8 transitions through the full
    freeze → drain → cutover → verify protocol.

    Exports the transition's correctness gates
    (scripts/check_bench_regression.py):

      reshard_state_loss       rows live at relayout time missing after
                               either cutover (ABSOLUTE_ZERO; both the
                               coordinator's audit and an independent
                               before/after key-set sweep feed it)
      reshard_double_served    keys resident more than once after a
                               cutover (ABSOLUTE_ZERO)
      reshard_parity_errors    routed-path ownership vs the host ring on
                               the post-transition layout (ABSOLUTE_ZERO)
      reshard_p99_during_ms    p99 of client windows SERVED while the
                               transitions run (sheds answer retriable
                               errors and are counted separately) —
                               lower-better with slack; a blowup means
                               the freeze window stopped being bounded
    """
    jax.config.update("jax_platforms", "cpu")
    import threading

    from gubernator_tpu.parallel.mesh_engine import MeshTickEngine, make_mesh
    from gubernator_tpu.parallel.reshard import ReshardCoordinator
    from gubernator_tpu.service.tickloop import TickLoop
    from gubernator_tpu.types import RateLimitRequest

    n_keys = 1 << 11
    window = 256
    rng = np.random.default_rng(17)

    def reqs_for(ids):
        return [
            RateLimitRequest(
                name="bench", unique_key=str(int(k)), hits=1,
                limit=1_000_000, duration=3_600_000,
            )
            for k in ids
        ]

    eng = MeshTickEngine(
        mesh=make_mesh(), local_capacity=1 << 9, max_batch=window,
    )
    loop = TickLoop(eng, batch_limit=window)
    coord = ReshardCoordinator(eng, tick_loop=loop, freeze_timeout=60.0,
                               verify=True)
    # Prefill + warm the serving program on the 8-shard layout.
    for start in range(0, n_keys, window):
        loop.submit(reqs_for(range(start, start + window))).result(timeout=120)
    keys_before = {it["key"] for it in eng.export_items()}

    lat_ms = []
    shed = [0]
    served = [0]
    stop = threading.Event()

    def drive():
        while not stop.is_set():
            ids = rng.integers(0, n_keys, size=window)
            t0 = time.perf_counter()
            try:
                out = loop.submit(reqs_for(ids)).result(timeout=120)
            except Exception:
                continue
            dt_ms = (time.perf_counter() - t0) * 1e3
            n_err = sum(1 for r in out if r.error)
            if n_err:
                shed[0] += n_err  # retriable freeze sheds, not losses
                time.sleep(0.005)  # a well-behaved client backs off
            else:
                served[0] += 1
                lat_ms.append(dt_ms)

    driver = threading.Thread(target=drive, name="reshard-driver")
    driver.start()
    t0 = time.perf_counter()
    try:
        res_down = coord.reshard(4)
        time.sleep(0.5)  # serve on the 4-shard layout mid-measurement
        res_up = coord.reshard(8)
        time.sleep(0.5)
    finally:
        stop.set()
        driver.join()
    transition_s = time.perf_counter() - t0

    results = [res_down, res_up]
    committed = sum(1 for r in results if r.get("outcome") == "committed")
    loss = sum(r.get("state_loss", 0) for r in results)
    dup = sum(r.get("double_served", 0) for r in results)
    parity = sum(r.get("parity_errors", 0) for r in results)
    # Independent sweep: every key resident before the transitions must
    # still be resident after both (the driver only touches known keys).
    keys_after = {it["key"] for it in eng.export_items()}
    loss = max(loss, len(keys_before - keys_after))
    parity = max(parity, int(eng.routing_parity_errors(sorted(keys_after))))
    _, p99 = _pcts(lat_ms) if lat_ms else (0.0, 0.0)
    loop.close()
    out = {
        "rung": "reshard_live",
        "shards_path": "8->4->8",
        "reshard_committed": committed,
        "reshard_state_loss": int(loss),
        "reshard_double_served": int(dup),
        "reshard_parity_errors": int(parity),
        "reshard_p99_during_ms": round(p99, 2),
        "reshard_shed_retriable": int(shed[0]),
        "served_windows_during": int(served[0]),
        "live_items": len(keys_after),
        "transition_wall_s": round(transition_s, 2),
        "reshard_s_8to4": round(res_down.get("duration_s", 0.0), 2),
        "reshard_s_4to8": round(res_up.get("duration_s", 0.0), 2),
        "backend": "cpu-8dev",
    }
    if committed != 2:
        out["error"] = (
            f"expected 2 committed transitions, got {committed}: "
            f"{[r.get('outcome') for r in results]}"
            f" {[r.get('reason') for r in results]}"
        )
    print(json.dumps(out))


def child_diurnal_autoscale():
    """Runs in the subprocess: the closed autoscaling loop
    (docs/autoscaling.md) replaying a compressed day on a ManualClock —
    demand ramps up and back down twice (night → morning peak → midday
    dip → evening peak → night) and the full sample → policy →
    guardrails → actuate chain drives REAL live reshards (the same
    freeze → drain → cutover → verify protocol the reshard_live rung
    exercises) on the 8-device CPU mesh while a driver thread serves
    continuously.

    The demand SIGNAL is a recorded diurnal trace run through a simple
    queueing model (p99 ≈ base/(1-utilisation), queue depth = backlog
    over capacity) so the loop actually closes — an actuation changes
    capacity, which changes the next sample.  Everything the gates
    measure is real: every transition is a live engine relayout, state
    loss comes from the coordinator audit plus an independent key-set
    sweep, and the transition-window p99 is measured on windows the
    driver actually served while the coordinator held the lock.

    Exported gates (scripts/check_bench_regression.py):

      autoscale_transitions     committed autonomous transitions — the
                                rung errors below 2 (a loop that never
                                acts proves nothing)
      autoscale_state_loss      rows lost across ALL autonomous
                                transitions (ABSOLUTE_ZERO)
      autoscale_flaps           rolling-hour actuation-cap breaches,
                                computed from the committed actuation
                                timestamps (ABSOLUTE_ZERO — the flap
                                suppressor must hold)
      autoscale_p99_during_transition_ms
                                p99 of windows served while a
                                transition held the coordinator lock —
                                lower-better with slack
      chip_seconds_saved        ∫(8 − shards(t))dt over the simulated
                                day vs the static-8-shard baseline —
                                the headline the controller earns;
                                HIGHER is better, absolute floor > 0
    """
    jax.config.update("jax_platforms", "cpu")
    import asyncio
    import threading

    from gubernator_tpu.autoscale import (
        Autoscaler, AutoscalePolicy, PolicyConfig, SignalSnapshot,
    )
    from gubernator_tpu.autoscale.controller import FLAP_WINDOW_S
    from gubernator_tpu.parallel.mesh_engine import MeshTickEngine, make_mesh
    from gubernator_tpu.parallel.reshard import ReshardCoordinator
    from gubernator_tpu.resilience import ManualClock
    from gubernator_tpu.service.tickloop import TickLoop
    from gubernator_tpu.types import RateLimitRequest

    n_keys = 1 << 10
    window = 256
    rng = np.random.default_rng(23)

    def reqs_for(ids):
        return [
            RateLimitRequest(
                name="bench", unique_key=str(int(k)), hits=1,
                limit=1_000_000, duration=3_600_000,
            )
            for k in ids
        ]

    eng = MeshTickEngine(
        mesh=make_mesh(), local_capacity=1 << 9, max_batch=window,
    )
    loop = TickLoop(eng, batch_limit=window)
    coord = ReshardCoordinator(eng, tick_loop=loop, freeze_timeout=60.0,
                               verify=True)
    for start in range(0, n_keys, window):
        loop.submit(reqs_for(range(start, start + window))).result(timeout=120)
    keys_before = {it["key"] for it in eng.export_items()}

    # -- the compressed day: 96 control windows x 15 simulated minutes.
    # Demand is "offered windows/s"; each shard serves CAP of them, so
    # utilisation = demand / (CAP x shards) closes the loop through the
    # coordinator's real shard count.
    STEP_S = 900.0
    N_STEPS = 96
    CAP = 100.0
    BASE_MS = 1.0

    def demand_at(i):
        if i < 16:
            return 100.0                       # night
        if i < 32:
            return 100.0 + 31.25 * (i - 15)    # morning ramp -> 600
        if i < 48:
            return 600.0                       # morning peak
        if i < 60:
            return 200.0                       # midday dip
        if i < 68:
            return 200.0 + 50.0 * (i - 59)     # evening ramp -> 600
        if i < 76:
            return 600.0                       # evening peak
        return 100.0                           # night again

    clock = ManualClock()
    cur = {"demand": demand_at(0)}

    def sample():
        shards = int(coord.status()["shards"])
        util = cur["demand"] / (CAP * shards)
        return SignalSnapshot(
            ts=clock(),
            queue_depth=int(max(0.0, cur["demand"] - CAP * shards) * 2.0),
            p99_ms=min(50.0, BASE_MS / max(0.02, 1.0 - util)),
            hot_occupancy=min(1.0, util),
            shards=shards,
            reshard_busy=coord.is_busy(),
        )

    # -- live traffic while the day plays out: every window's latency is
    # tagged with whether a transition held the lock at any point, so
    # the rung can report the p99 the clients saw THROUGH the cutovers.
    lat_busy = []
    shed = [0]
    served = [0]
    stop = threading.Event()

    def drive():
        while not stop.is_set():
            ids = rng.integers(0, n_keys, size=window)
            busy = coord.is_busy()
            t0 = time.perf_counter()
            try:
                out = loop.submit(reqs_for(ids)).result(timeout=120)
            except Exception:
                continue
            dt_ms = (time.perf_counter() - t0) * 1e3
            busy = busy or coord.is_busy()
            n_err = sum(1 for r in out if r.error)
            if n_err:
                shed[0] += n_err  # retriable freeze sheds, not losses
                time.sleep(0.005)
            else:
                served[0] += 1
                if busy:
                    lat_busy.append(dt_ms)

    actuations = []  # (sim_ts, coordinator result dict)

    def exec_reshard(target):
        res = coord.try_reshard(int(target))
        actuations.append((clock(), res))
        time.sleep(0.25)  # serve a beat on the new layout mid-measurement
        return res

    max_per_hour = 4
    scaler = Autoscaler(
        sample, exec_reshard,
        policy=AutoscalePolicy(PolicyConfig(
            windows=3, target_p99_ms=5.0, queue_high=100, hysteresis=0.5,
            occupancy_low=0.3, min_shards=4, max_shards=8,
        )),
        interval=STEP_S, cooldown_up=1800.0, cooldown_down=3600.0,
        max_per_hour=max_per_hour, dry_run=False, ring_size=N_STEPS,
        clock=clock, sleep=clock.sleep,
    )

    saved = [0.0]
    shards_path = [int(coord.status()["shards"])]

    async def day():
        for i in range(N_STEPS):
            cur["demand"] = demand_at(i)
            before = int(coord.status()["shards"])
            await scaler.step()
            after = int(coord.status()["shards"])
            if after != before:
                shards_path.append(after)
            # The step's capacity bill: whatever layout served it.
            saved[0] += (8 - after) * STEP_S
            clock.advance(STEP_S)

    driver = threading.Thread(target=drive, name="autoscale-driver")
    driver.start()
    try:
        asyncio.run(day())
    finally:
        stop.set()
        driver.join()

    results = [r for _, r in actuations]
    committed = sum(1 for r in results if r.get("outcome") == "committed")
    loss = sum(r.get("state_loss", 0) for r in results)
    # Independent sweep, same as reshard_live: every key resident before
    # the day must survive every autonomous transition.
    keys_after = {it["key"] for it in eng.export_items()}
    loss = max(loss, len(keys_before - keys_after))
    # Flap breaches: committed actuations in any rolling hour beyond the
    # cap the guardrail promised — must be 0 if the suppressor works.
    acts = [t for t, r in actuations if r.get("outcome") == "committed"]
    flaps = 0
    for t0 in acts:
        in_hour = sum(1 for t in acts if 0 <= t - t0 <= FLAP_WINDOW_S)
        flaps = max(flaps, in_hour - max_per_hour)
    flaps = max(0, flaps)
    _, p99 = _pcts(lat_busy) if lat_busy else (0.0, 0.0)
    vetoes = {}
    for d in scaler.ring:
        if d.action == "veto":
            vetoes[d.reason] = vetoes.get(d.reason, 0) + 1
    loop.close()
    out = {
        "rung": "diurnal_autoscale",
        "shards_path": "->".join(str(s) for s in shards_path),
        "autoscale_transitions": committed,
        "autoscale_state_loss": int(loss),
        "autoscale_flaps": int(flaps),
        "autoscale_p99_during_transition_ms": round(p99, 2),
        "chip_seconds_saved": round(saved[0], 1),
        "static8_chip_seconds": round(8 * STEP_S * N_STEPS, 1),
        "autoscale_vetoes": vetoes,
        "autoscale_shed_retriable": int(shed[0]),
        "served_windows_during": int(served[0]),
        "live_items": len(keys_after),
        "sim_day_s": STEP_S * N_STEPS,
        "backend": "cpu-8dev",
    }
    if committed < 2:
        out["error"] = (
            f"expected >= 2 autonomous transitions, got {committed}: "
            f"{[r.get('outcome') for r in results]}"
        )
    print(json.dumps(out))


def child_mesh_100m():
    """Runs in the subprocess: the 100M-key multichip rung — the full
    sharded SoA table (8 shards x 12.5M slots, columns layout: 80 B/slot
    = 8 GB total, ~1 GB/shard HBM on real chips) under device-routed
    serving traffic, with the same exact-work gates as mesh_tick_8.

    The table is populated DEVICE-SIDE per shard (one donated shard_map
    init writes synthetic bucket state straight into every shard's
    slice, the rung_100m trick) while the host assigns the keys into
    each shard's slotmap grouped by the SAME CRC-32 route the serving
    path uses, so host and device agree on key→shard→slot.  BENCH_FAST
    shrinks to 2M keys (the shape key keeps the gate like-for-like)."""
    jax.config.update("jax_platforms", "cpu")
    from functools import partial

    from gubernator_tpu.native import crc32_batch
    from gubernator_tpu.ops.buckets import BucketState, to_stored
    from gubernator_tpu.ops.engine import resolve_ticks
    from gubernator_tpu.parallel.mesh_engine import MeshTickEngine, make_mesh
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_nodes = 8
    total = 2_000_000 if FAST else 100_000_000
    local_cap = total // n_nodes
    now = 1_700_000_000_000
    limit = 1_000_000
    duration = 3_600_000
    batch = 4096
    t_build0 = time.perf_counter()
    eng = MeshTickEngine(
        mesh=make_mesh(), local_capacity=local_cap, max_batch=batch,
        table_layout="columns",
    )

    def synth_local(state):
        # All-token fill: the key→slot map is hash-routed here (unlike
        # rung_100m's identity mapping), so per-slot algorithm choices
        # can't be tied to key ids — one algorithm keeps request and
        # stored state consistent for every key.
        def f64(v):
            return jnp.full(local_cap, v, jnp.int64)

        return BucketState(
            algorithm=jnp.zeros(local_cap, jnp.int32),
            limit=to_stored(f64(limit), "limit"),
            remaining=to_stored(f64(limit), "remaining"),
            remaining_f=to_stored(jnp.zeros(local_cap), "remaining_f"),
            duration=to_stored(f64(duration), "duration"),
            created_at=to_stored(f64(now), "created_at"),
            updated_at=to_stored(f64(0), "updated_at"),
            burst=to_stored(f64(0), "burst"),
            status=jnp.zeros(local_cap, jnp.int32),
            expire_at=to_stored(f64(now + duration), "expire_at"),
            in_use=jnp.ones(local_cap, jnp.bool_),
        )

    state_spec = eng.ops.state_spec
    synth = jax.jit(
        shard_map(
            lambda st: synth_local(st), mesh=eng.mesh,
            in_specs=(state_spec,), out_specs=state_spec, check_vma=False,
        ),
        donate_argnums=(0,),
    )
    eng.state = synth(eng.state)
    jax.block_until_ready(jax.tree.leaves(eng.state)[0])
    dev_fill_s = time.perf_counter() - t_build0

    # Host side: route every key with the vectorized CRC-32 batch and
    # assign it into its shard's slotmap (hash imbalance overflows a
    # shard for the last ~sqrt fraction; those ids are simply not part
    # of the traffic set — the rung measures serving, not insert).
    t0 = time.perf_counter()
    served_ids = []
    step = 10_000_000
    for start in range(0, total, step):
        ids = np.arange(start, min(start + step, total))
        blob, offsets = _key_pack(ids)
        sh = (
            crc32_batch(blob, offsets) % np.uint32(n_nodes)
        ).astype(np.int64)
        blob_arr = np.frombuffer(blob, np.uint8)
        offs = offsets
        lens = np.diff(offs)
        for s in range(n_nodes):
            rows = np.flatnonzero(sh == s)
            if not len(rows):
                continue
            lo = lens[rows]
            cum = np.cumsum(lo)
            gather = (
                np.arange(int(cum[-1]), dtype=np.int64)
                - np.repeat(cum - lo, lo)
                + np.repeat(offs[:-1][rows], lo)
            )
            s_off = np.concatenate([np.zeros(1, np.int64), cum])
            got = eng.slots[s].assign_blob(
                blob_arr[gather].tobytes(), s_off
            )
            served_ids.append(ids[rows[got >= 0]])
    served = np.concatenate(served_ids)
    key_fill_s = time.perf_counter() - t0

    rng = np.random.default_rng(7)
    cols_windows = [
        _cols(served[rng.integers(0, len(served), batch)],
              limit, duration, 0)
        for _ in range(8)
    ]

    eng.process_columns(cols_windows[0], now=now)  # warm/compile
    h0, m0 = eng.metric_hits, eng.metric_misses
    done = 0
    pending = []
    iters = 6 if FAST else 24
    t0 = time.perf_counter()
    for i in range(iters):
        c = cols_windows[i % len(cols_windows)]
        pending.extend(eng.submit_cols(c, now=now + 1 + i).handles())
        done += len(c)
        if len(pending) >= 8:
            resolve_ticks(pending)
            pending.clear()
    resolve_ticks(pending)
    dt = time.perf_counter() - t0
    resolved = (eng.metric_hits - h0) + (eng.metric_misses - m0)
    work_delta = resolved - done
    sample = ["bench_" + str(i) for i in served[:4096]]
    print(
        json.dumps(
            {
                "rung": "mesh_100m_multichip",
                "keys": total,
                "shards": n_nodes,
                "batch": batch,
                "decisions_per_sec": round(done / dt, 1),
                "mesh_routing_parity_errors": int(
                    eng.routing_parity_errors(sample)
                ),
                "mesh_dropped_keys": int(max(-work_delta, 0)),
                "mesh_double_served": int(max(work_delta, 0)),
                "routed_windows": eng.metric_routed_windows,
                "routed_overflows": eng.metric_routed_overflows,
                "device_fill_s": round(dev_fill_s, 1),
                "key_fill_s": round(key_fill_s, 1),
                "layout": eng.layout,
                "backend": "cpu-8dev",
            }
        )
    )


# ----------------------------------------------------------------------
# GLOBAL mesh rung (8 virtual devices, CPU backend, subprocess)
# ----------------------------------------------------------------------
def child_mesh():
    """Runs in the subprocess: 8-device mesh, GLOBAL windows + reconcile."""
    from gubernator_tpu.parallel.global_mesh import MeshGlobalEngine, make_global_mesh
    from gubernator_tpu.types import Behavior, RateLimitRequest

    n_nodes = 8
    batch = 256
    eng = MeshGlobalEngine(
        mesh=make_global_mesh(n_nodes), capacity=1 << 13, max_batch=batch
    )
    rng = np.random.default_rng(4)
    now = 1_700_000_000_000

    def window(i):
        return [
            [
                RateLimitRequest(
                    name="g",
                    unique_key=str(k),
                    hits=1,
                    limit=1_000_000,
                    duration=3_600_000,
                    behavior=Behavior.GLOBAL,
                )
                for k in rng.integers(0, 4096, batch)
            ]
            for _ in range(n_nodes)
        ]

    eng.process_blocks(window(0), now=now)  # warm/compile
    eng.reconcile(now=now)

    windows = [window(i) for i in range(8)]
    iters = 10 if FAST else 25
    d0, r0 = eng.metric_reconcile_dispatches, eng.metric_reconciles
    t0 = time.perf_counter()
    for i in range(iters):
        eng.process_blocks(windows[i % len(windows)], now=now + i)
        eng.reconcile(now=now + i)
    dt = time.perf_counter() - t0
    steps = eng.metric_reconciles - r0
    print(
        json.dumps(
            {
                "rung": "global_mesh_8",
                "nodes": n_nodes,
                "decisions_per_sec": round(iters * n_nodes * batch / dt, 1),
                "reconciles_per_sec": round(iters / dt, 2),
                "dispatches_per_step": round(
                    (eng.metric_reconcile_dispatches - d0) / max(steps, 1), 3
                ),
                "backend": "cpu-8dev",
            }
        )
    )


def child_global_sparse():
    """Runs in the subprocess: sparse-reconcile scaling evidence.  Same
    traffic (fixed hit-slot count) against a 2^18 and a 2^22 table: the
    sparse step's cost must track the HITS, not the capacity (the dense
    step is O(capacity x nodes) and is also timed at 2^18 for contrast —
    at 2^22 it would move the whole 4M-slot table per step)."""
    jax.config.update("jax_platforms", "cpu")
    from gubernator_tpu.parallel.global_mesh import (
        MeshGlobalEngine, make_global_mesh)
    from gubernator_tpu.types import Behavior, RateLimitRequest

    n_nodes = 8
    per_node = 64
    now = 1_700_000_000_000
    rng = np.random.default_rng(9)

    def window():
        return [
            [
                RateLimitRequest(
                    name="gs", unique_key=str(k), hits=1, limit=1_000_000,
                    duration=3_600_000, behavior=Behavior.GLOBAL,
                )
                for k in rng.integers(0, 4096, per_node)
            ]
            for _ in range(n_nodes)
        ]

    def measure(capacity, sparse_k, reps):
        """(loaded_ms, empty_ms): reconcile cost with the fixed traffic
        vs with zero traffic.  The empty figure isolates the backend's
        per-step buffer-copy floor (the CPU emulation rewrites the
        donated replica/accumulator buffers at host-memcpy speed; a real
        TPU does the same at HBM speed, ~3 ms at 2^22) so the
        traffic-dependent component — what the sparse design actually
        bounds — is the loaded-minus-empty delta."""
        eng = MeshGlobalEngine(
            mesh=make_global_mesh(n_nodes), capacity=capacity,
            max_batch=per_node, sparse_k=sparse_k,
        )
        eng.process_blocks(window(), now=now)
        eng.reconcile(now=now)  # warm/compile

        def step(load, i):
            if load:
                eng.process_blocks(window(), now=now + i + 1)
            # reconcile() dispatches async; bracket with blocking so the
            # sample is the step's device time, not queue latency.
            jax.block_until_ready(eng.state)
            t0 = time.perf_counter()
            eng.reconcile(now=now + i + 1)
            jax.block_until_ready(eng.state)
            return time.perf_counter() - t0

        d0, r0 = eng.metric_reconcile_dispatches, eng.metric_reconciles
        loaded = [step(True, i) for i in range(reps)]
        empty = [step(False, reps + i) for i in range(reps)]
        steps = eng.metric_reconciles - r0
        dps = (eng.metric_reconcile_dispatches - d0) / max(steps, 1)
        return (float(np.median(loaded)) * 1e3,
                float(np.median(empty)) * 1e3, dps)

    reps = 3 if FAST else 5
    cap_small, cap_big = 1 << 18, 1 << 22
    sp_small, sp_small_0, sp_dps = measure(cap_small, 1024, reps)
    dn_small, _, _ = measure(cap_small, 0, reps)
    sp_big, sp_big_0, _ = measure(cap_big, 1024, reps)
    out = {
        "rung": "global_sparse_reconcile",
        "nodes": n_nodes,
        "hit_slots_per_node": per_node,
        # Mesh programs per non-overflowing sparse step.  1.0 = the
        # fused probe+reconcile (one compaction/gather pass); 2.0 would
        # mean the probe re-gathers the envelope as a separate program —
        # the regression the fusion removed (check_bench_regression.py
        # gates this count exactly).
        "dispatches_per_step": round(sp_dps, 3),
        "sparse_ms_cap_2e18": round(sp_small, 2),
        "sparse_ms_cap_2e22": round(sp_big, 2),
        # loaded-minus-empty at 2^18: the traffic-dependent term the
        # sparse design bounds (at 2^22 this backend's multi-second copy
        # floor buries the delta; on a real TPU the floor is ~3 ms of
        # HBM rewrites).
        "sparse_traffic_ms_2e18": round(max(sp_small - sp_small_0, 0), 2),
        "copy_floor_ms_2e18": round(sp_small_0, 2),
        "copy_floor_ms_2e22": round(sp_big_0, 2),
        "dense_ms_cap_2e18": round(dn_small, 2),
        "sparse_vs_dense_2e18": round(dn_small / sp_small, 2),
        "backend": "cpu-8dev",
    }
    if os.environ.get("GUBER_BENCH_SPARSE_DENSE22"):
        # One dense step at 2^22 — the number the sparse step deletes
        # (O(capacity x nodes): the full 4M-slot table moves and
        # transitions on every node, every 100 ms cadence tick).
        # Opt-in: building + warming a dense 2^22 engine costs ~7 min
        # of an 8-virtual-device CPU backend, and the figure is stable
        # (BENCH_local_r05.json records 146 s/step, 34x the sparse
        # step) — the default ladder must fit the driver's budget.
        dn_big, _, _ = measure(cap_big, 0, 1)
        out["dense_ms_cap_2e22"] = round(dn_big, 2)
        out["sparse_vs_dense_2e22"] = round(dn_big / sp_big, 2)
    print(json.dumps(out))


_ACTIVE_CHILD = None  # the running bench subprocess, for SIGTERM cleanup


def _run_child(flag: str, rung: str, timeout: int = 600):
    """Run one bench child on the 8-virtual-device CPU backend."""
    global _ACTIVE_CHILD
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), flag],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        _ACTIVE_CHILD = proc
        try:
            stdout, stderr = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            raise
        finally:
            _ACTIVE_CHILD = None
        out = subprocess.CompletedProcess(
            proc.args, proc.returncode, stdout, stderr)
        lines = out.stdout.strip().splitlines()
        if not lines:
            tail = out.stderr.strip().splitlines()[-8:]
            return {"rung": rung, "error": " | ".join(tail)[:500]}
        return json.loads(lines[-1])
    except Exception as e:
        return {"rung": rung, "error": str(e)[:200]}


def rung_global_mesh():
    return _run_child("--child-mesh", "global_mesh_8")


def rung_mesh_tick():
    return _run_child("--child-mesh-tick", "mesh_tick_8")


def rung_mesh_zipf():
    return _run_child("--child-mesh-zipf", "mesh_zipf_8")


def rung_reshard_live():
    # Two full transitions (each pays a fresh shard-set build + warmup
    # on the CPU venue) under a live driver thread; give the child room.
    return _run_child("--child-reshard-live", "reshard_live", timeout=1200)


def rung_diurnal_autoscale():
    # Five-ish autonomous transitions across the compressed day, each a
    # full live reshard with a fresh shard-set build + warmup on the CPU
    # venue; budget accordingly.
    return _run_child("--child-diurnal-autoscale", "diurnal_autoscale",
                      timeout=1800)


def rung_mesh_100m():
    # 8 GB of sharded table + ~8 GB of native slotmaps, populated
    # device-side; the dominant cost is the 100M host key inserts.
    return _run_child("--child-mesh-100m", "mesh_100m_multichip",
                      timeout=1800)


def rung_global_sparse():
    # 2^22-capacity engines on the 8-virtual-device CPU backend spend
    # minutes in whole-buffer copies alone; give the child room.
    return _run_child("--child-global-sparse", "global_sparse_reconcile",
                      timeout=1800)


# ----------------------------------------------------------------------
def probe_roundtrip():
    """One synchronous dispatch+D2H on a trivial program: the latency floor
    under every per-tick engine number (on a local chip: not measured)."""
    f = jax.jit(lambda a: a + 1)
    x = jnp.zeros(8)
    np.asarray(f(x))
    t0 = time.perf_counter()
    for _ in range(10):
        np.asarray(f(x))
    return round((time.perf_counter() - t0) / 10 * 1e3, 2)


def probe_bandwidth():
    """Host↔device transfer bandwidth (MB/s each way).  The engine rungs
    move ~550 KB per 4096-request tick (request matrix down, responses
    up); where the link is slow, TRANSPORT — not host packing and not the
    kernel — is the engine-rung ceiling.  These probes let the record say
    which regime the numbers were taken in."""
    mb = 4 * 1024 * 1024
    a = np.random.randint(0, 1 << 30, mb // 8).astype(np.int64)
    d = jnp.asarray(a)  # warm both paths
    np.asarray(d)
    t0 = time.perf_counter()
    d = jnp.asarray(a)
    np.asarray(d.sum())  # force the H2D to complete (1-element D2H back)
    h2d_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(d)
    d2h_s = time.perf_counter() - t0
    return (
        round(mb / h2d_s / 1e6, 2),
        round(mb / d2h_s / 1e6, 2),
    )


def _safe(label, fn):
    """One rung: never let a failure zero the whole ladder."""
    t0 = time.perf_counter()
    try:
        out = fn()
    except Exception as e:
        out = {"rung": label, "error": repr(e)[:300]}
    print(f"[bench] {label}: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    return out


def main():
    import signal

    ladder = []
    rt_ms = probe_roundtrip()
    h2d_mbps, d2h_mbps = probe_bandwidth()

    # A driver timeout must still yield a parseable record: on SIGTERM/
    # SIGINT, emit the compact headline from whatever rungs completed
    # (marked truncated) instead of dying with nothing on stdout.
    def _on_term(signum, frame):
        try:
            if _ACTIVE_CHILD is not None:
                # Don't orphan a bench child (the sparse rung holds
                # 2^22-capacity engines for up to 30 min).
                try:
                    _ACTIVE_CHILD.kill()
                except OSError:
                    pass
            _finish(list(ladder), rt_ms, h2d_mbps, d2h_mbps,
                    truncated=True)
            sys.stdout.flush()
        finally:
            os._exit(0)

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _on_term)
        except (ValueError, OSError):
            pass  # non-main thread / restricted environment

    ladder.append(_safe("kernel_1m", rung_kernel))
    ladder.append(_safe("kernel_zipf_10m", rung_kernel_zipf))

    state = {}

    def eng(label, *a, **kw):
        r, e = rung_engine(label, *a, **kw)
        state[label] = (r, e)
        return r

    ladder.append(_safe(
        "engine_token_10k",
        lambda: eng("engine_token_10k", 10_000, 0, ticks=100 if FAST else 400),
    ))
    unique_dps = ladder[-1].get("decisions_per_sec", 0)

    n_leaky = 1 << 17 if FAST else 1 << 20
    ladder.append(_safe(
        "engine_leaky_1m",
        lambda: eng("engine_leaky_1m", n_leaky, 1, ticks=50 if FAST else 200),
    ))
    unique_leaky_dps = ladder[-1].get("decisions_per_sec", 0)

    ladder.append(_safe("engine_mixed_algos", rung_engine_mixed_algos))

    n_big = 1 << 20 if FAST else 10_000_000
    ladder.append(_safe(
        "engine_mixed_10m_zipf",
        lambda: eng(
            "engine_mixed_10m_zipf", n_big, None,
            ticks=30 if FAST else 100, zipf=True, fresh_frac=0.01,
        ),
    ))

    ladder.append(_safe("p99_projection", rung_p99_projection))
    ladder.append(_safe("engine_churn_4x", rung_churn))
    ladder.append(_safe("engine_churn_ssd", rung_churn_ssd))
    ladder.append(_safe("herd_device", rung_herd_device))
    ladder.append(_safe(
        "herd_token_4096", lambda: rung_herd(unique_dps, 0, "herd_token_4096")
    ))
    ladder.append(_safe(
        "herd_leaky_4096",
        lambda: rung_herd(unique_leaky_dps, 1, "herd_leaky_4096"),
    ))
    if "engine_mixed_10m_zipf" in state:
        big_engine = state.pop("engine_mixed_10m_zipf")[1]
        ladder.append(_safe(
            "snapshot_10m", lambda: rung_snapshot(big_engine, "snapshot_10m")
        ))
        # Measured-latency headline: the loopback serving rung reuses
        # the prefilled 10M-key engine (and closes it via the
        # instance), so it costs measurement time only.
        ladder.append(_safe(
            "serve_loopback_10m",
            lambda: rung_serve_loopback(big_engine, n_big),
        ))
        if hasattr(big_engine, "close"):
            big_engine.close()  # idempotent; covers a failed rung
        del big_engine
    state.clear()

    # Multi-process edge serving: own (small) engine, placed after the
    # 10M engines are released so the worker fleet never competes with
    # a prefill for host cores.
    ladder.append(_safe("serve_multiproc", rung_serve_multiproc))

    if not FAST:
        # Top of the ladder: needs 8 GB HBM free — runs after the 10M
        # engines are released, before the (small) service daemon.
        ladder.append(_safe("engine_100m_drain_reset_region", rung_100m))

    ladder.append(_safe("service_grpc", rung_service))
    # Right after the service rung: the overload rung reuses its
    # already-compiled narrow serving program at the same capacity.
    ladder.append(_safe("overload_shed", rung_overload))
    # Lease tier headline: server-served traffic drops >=10x while the
    # bucket accounting stays exact (docs/leases.md).
    ladder.append(_safe("engine_leases", rung_engine_leases))
    ladder.append(_safe("chaos_redelivery", rung_chaos))
    ladder.append(_safe("federation_2r", rung_federation))
    ladder.append(_safe("restart_recovery", rung_restart_recovery))
    ladder.append(_safe("mesh_tick_8", rung_mesh_tick))
    ladder.append(_safe("mesh_zipf_8", rung_mesh_zipf))
    ladder.append(_safe("reshard_live", rung_reshard_live))
    # The closed loop over the same transition machinery: telemetry →
    # policy → guardrails → live reshard across a compressed day.
    ladder.append(_safe("diurnal_autoscale", rung_diurnal_autoscale))
    ladder.append(_safe("mesh_100m_multichip", rung_mesh_100m))
    ladder.append(_safe("global_mesh_8", rung_global_mesh))
    ladder.append(_safe("global_sparse_reconcile", rung_global_sparse))

    _finish(ladder, rt_ms, h2d_mbps, d2h_mbps)


def _finish(ladder, rt_ms, h2d_mbps, d2h_mbps, truncated=False):
    """Assemble + emit the record from whatever rungs completed (the
    normal exit path, and the SIGTERM path when a driver timeout cuts
    the run short)."""
    import signal

    # A signal landing while THIS function writes the record must not
    # re-enter it (double headline, half-written record file).
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, signal.SIG_DFL)
        except (ValueError, OSError):
            pass
    # Headline: the better of the worst-case-unique kernel and the
    # BASELINE-config Zipf grouped kernel (both are chained device
    # differentials; the record names which one led).
    kerns = [r for r in ladder
             if r.get("rung") in ("kernel_1m", "kernel_zipf_10m")]
    head = max(
        kerns, key=lambda r: r.get("decisions_per_sec", 0) or 0,
    ) if kerns else {}
    big_p99 = next(
        (r.get("p99_ms") for r in ladder
         if r.get("rung") == "engine_mixed_10m_zipf"), None)

    # Replace the service projection's conservative 1.2 ms device-tick
    # constant with the p99_projection rung's measured w4096 figure
    # (device tick + PCIe at the serving width) when both rungs ran.
    svc = next((r for r in ladder if r.get("rung") == "service_grpc"), None)
    proj = next(
        (r for r in ladder if r.get("rung") == "p99_projection"), None
    )
    if (svc and proj and "serve_cpu_ms_per_batch" in svc
            and proj.get("w4096", {}).get("device_ms")):
        # device_ms excludes the projection rung's own host-pack term —
        # the service rung's measured codec CPU replaces it, not joins it.
        svc["batch_p99_projected_local_ms"] = round(
            svc["concurrency"] * svc["serve_cpu_ms_per_batch"]
            + proj["w4096"]["device_ms"], 2,
        )

    # Measured end-to-end latency: the loopback serving rung's p99 —
    # wire bytes → decision → wire bytes through the full instance with
    # no sockets.  THE headline latency figure (README/docs cite it);
    # the projection fields below remain as transport-free context.
    loop_rung = next(
        (r for r in ladder if r.get("rung") == "serve_loopback_10m"), None
    )

    record = {
        "metric": "rate_limit_decisions_per_sec_per_chip",
        "value": head.get("decisions_per_sec", 0),
        "unit": "decisions/s",
        "headline_rung": head.get("rung"),
        "p99_measured_loopback_ms": (
            loop_rung.get("loopback_p99_ms") if loop_rung else None
        ),
        # BENCH_FAST shortens the kernel rung's differential
        # chains (n=20 vs 100) below the jitter floor —
        # fast-mode headlines carry ~4x noise and are marked so
        # they are never read as the record.
        "fast_mode": FAST,
        "vs_baseline": head.get("vs_target_50m", 0),
        "p99_ms_at_10m_keys": big_p99,
        # Engine latencies ride one device dispatch+D2H per tick; the
        # net figure takes that measured roundtrip (rt_ms) out.
        "p99_net_of_roundtrip_ms": (
            round(max(0.0, big_p99 - rt_ms), 3)
            if isinstance(big_p99, (int, float)) else None
        ),
        "p99_target_ms": TARGET_P99_MS,
        # Transport-free device evidence for the 2 ms bar: the
        # p99_projection rung's 4096-wide projected-local figure.
        "p99_projected_local_ms": next(
            (r.get("w4096", {}).get("p99_projected_local_ms")
             for r in ladder if r.get("rung") == "p99_projection"),
            None,
        ),
        "device_roundtrip_ms": rt_ms,
        "h2d_mbps": h2d_mbps,
        "d2h_mbps": d2h_mbps,
        "ladder": ladder,
    }
    if truncated:
        record["truncated"] = True
    # Full ladder record goes to a FILE; the final stdout line is a
    # compact headline that fits the driver's 2000-char tail capture —
    # round 4's record came back "parsed": null because the full ladder
    # outgrew the tail (the only place the driver reads the result from).
    out_path = os.environ.get(
        "BENCH_LOCAL_OUT",
        # Fast-mode (CI gate) runs must not clobber the round record.
        "BENCH_local_fast.json" if FAST else "BENCH_local_r05.json",
    )
    if truncated:
        # A timeout-truncated partial ladder never overwrites a complete
        # record (explicit BENCH_LOCAL_OUT included).
        out_path += ".truncated"
    try:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    except OSError as e:
        print(f"[bench] ladder file write failed: {e}", file=sys.stderr)
    print(json.dumps(compact_headline(record, out_path)))


def compact_headline(record, ladder_file):
    """Distill the full record into a ≲1.5 KB summary: the headline metric
    plus [rate, spread] per throughput rung and the latency/link context —
    enough for the regression gate and the round record without the
    ladder's bulk (which lives in ``ladder_file``)."""
    rungs = {}
    extras = {}
    errors = []
    for r in record["ladder"]:
        name = r.get("rung", "?")
        if "error" in r:
            errors.append(name)
            continue
        rate = r.get("decisions_per_sec") or r.get("requests_per_sec")
        if rate:
            rungs[name] = [rate, r.get("spread")]
        if name == "herd_device" and "herd_mixed" in r:
            extras["herd_mixed_vs_unique"] = (
                r["herd_mixed"].get("vs_unique_device"))
        if name == "service_grpc":
            extras["serve_cpu_ms_per_batch"] = r.get(
                "serve_cpu_ms_per_batch")
            extras["grpc_p99_projected_local_ms"] = r.get(
                "batch_p99_projected_local_ms")
        if name == "snapshot_10m":
            extras["snapshot_export_s"] = r.get("export_s")
    head = {
        k: record[k]
        for k in (
            "metric", "value", "unit", "headline_rung", "fast_mode",
            "vs_baseline", "p99_measured_loopback_ms",
            "p99_ms_at_10m_keys", "p99_projected_local_ms",
            "device_roundtrip_ms", "h2d_mbps", "d2h_mbps",
        )
    }
    for r in record["ladder"]:
        if r.get("rung") == record.get("headline_rung"):
            head["headline_samples"] = r.get("samples")
            head["headline_spread"] = r.get("spread")
            head["headline_spread_all"] = r.get("spread_all")
    head["rungs"] = rungs
    head.update(extras)
    # Exact work-count metrics ride the compact record too (the driver's
    # tail capture is all the regression gate may get): rung → {key: val}
    # for every COUNT-gated key present in the full ladder.
    count_keys = (
        "dispatches_per_step", "churn_continuity_errors",
        "promote_dispatches_per_hit_tick", "demote_readbacks_per_reclaim",
        "hit_redelivery_loss", "restart_state_loss",
        "ownership_transfer_loss",
        # Serving-path perf gates (direction-aware in the gate script):
        # host codec CPU and measured loopback p99 must not regress,
        # the H2D overlap ratio must not collapse.
        "serve_cpu_ms_per_batch", "loopback_p99_ms", "h2d_overlap_ratio",
        # Sharded-serving gates: routing parity with the host ring and
        # the issued-vs-resolved work deltas are ABSOLUTE_ZERO; scaling
        # efficiency is direction-aware (must not decay vs baseline).
        "mesh_routing_parity_errors", "mesh_dropped_keys",
        "mesh_double_served", "mesh_scaling_efficiency",
        # Ragged-dispatch gates (docs/tpu-performance.md round 15): the
        # retired skew fallback is a pinned-zero canary, decision parity
        # vs a single-chip replay is exact, and serving never retraces
        # past the warmup-compiled programs.
        "mesh_routed_overflows", "mesh_ragged_parity_errors",
        "mesh_trace_retraces",
        # Elastic resharding gates (docs/resharding.md): zero bucket loss
        # and zero double-residency through an n->m cutover are
        # ABSOLUTE_ZERO, client p99 through the transition is
        # lower-better with slack.
        "reshard_state_loss", "reshard_double_served",
        "reshard_parity_errors", "reshard_p99_during_ms",
        # Overload control gates (docs/overload.md): expired-but-served
        # is ABSOLUTE_ZERO, admitted p99 is lower-better, goodput under
        # ~10x load must hold its floor, RSS growth is bounded.
        "expired_served", "overload_admitted_p99_ms",
        "overload_goodput_ratio", "overload_rss_growth_mb",
        # SSD-tier gates (docs/tiering.md): continuity through the slab
        # files and zero tick-path reads are ABSOLUTE_ZERO, the batched
        # third hop is capped at one lookup per miss tick, RSS growth
        # across the 8x working set is absolutely bounded.
        "ssd_continuity_errors", "ssd_tick_path_reads",
        "ssd_promote_batches_per_miss_tick", "churn_ssd_rss_mb",
        # Algorithm-zoo gates (docs/algorithms.md): zoo-lane parity vs
        # the scalar references is ABSOLUTE_ZERO, and a mixed-policy
        # window must stay ONE device dispatch (ceiling 1.0).
        "mixed_algo_parity_errors", "mixed_algo_dispatches_per_step",
        # Autoscaler gates (docs/autoscaling.md): zero state loss and
        # zero flap-cap breaches across the autonomous transitions are
        # ABSOLUTE_ZERO, the in-transition p99 is lower-better with
        # slack, and chip_seconds_saved vs the static-8 baseline is the
        # headline the controller must keep earning (absolute floor).
        "autoscale_transitions", "autoscale_state_loss",
        "autoscale_flaps", "autoscale_p99_during_transition_ms",
        "chip_seconds_saved",
    )
    count_map = {}
    for r in record["ladder"]:
        for k in count_keys:
            if r.get(k) is not None:
                count_map.setdefault(r["rung"], {})[k] = r[k]
    if count_map:
        head["counts"] = count_map
    if errors:
        head["rung_errors"] = errors
    if record.get("truncated"):
        head["truncated"] = True
    head["ladder_file"] = ladder_file
    return head


if __name__ == "__main__":
    if "--child-mesh-100m" in sys.argv:
        child_mesh_100m()
    elif "--child-mesh-tick" in sys.argv:
        child_mesh_tick()
    elif "--child-mesh-zipf" in sys.argv:
        child_mesh_zipf()
    elif "--child-reshard-live" in sys.argv:
        child_reshard_live()
    elif "--child-diurnal-autoscale" in sys.argv:
        child_diurnal_autoscale()
    elif "--child-mesh" in sys.argv:
        child_mesh()
    elif "--child-global-sparse" in sys.argv:
        child_global_sparse()
    else:
        main()
