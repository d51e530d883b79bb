#!/usr/bin/env python
"""CI benchmark regression gate: compare two bench.py result files.

The reference fails pull requests at >200% slowdown vs master via
benchmark-action (/root/reference/.github/workflows/on-pull-request.yml,
alert-threshold "200%"); this is the same gate over the BENCH_r*.json
ladder:

    python scripts/check_bench_regression.py BENCH_r02.json BENCH_r03.json

Exits 1 if the headline metric or any shared throughput rung regressed
past the threshold (default 2.0x, override with --threshold).  Rungs
present in only one file are reported but don't gate (the ladder grows
between rounds).
"""

import argparse
import json
import re
import sys

RATE_KEYS = ("decisions_per_sec", "requests_per_sec")

# Exact per-step work counts (lower is better, no measurement noise):
# a candidate exceeding its baseline re-introduced dispatch work — e.g.
# un-fusing the sparse reconcile's overflow probe doubles
# dispatches_per_step from 1.0 to 2.0.  Gated without spread slack.
# The churn-ladder keys pin the tiering invariants (docs/tiering.md):
#   churn_continuity_errors        0   — re-promoted keys keep their
#                                        consumed budget (no fresh-bucket
#                                        rate-limit bypass under churn)
#   promote_dispatches_per_hit_tick 1.0 — cold-hit promotion stays ONE
#                                        batched restore scatter per tick,
#                                        never a per-key dispatch
#   demote_readbacks_per_reclaim   1.0 — the demote readback runs only in
#                                        reclaim rounds with LRU victims;
#                                        reclaim-free ticks never pay it
#   hit_redelivery_loss            0   — the chaos rung's partitioned-owner
#                                        GLOBAL hits all land after recovery
#                                        (docs/resilience.md redelivery)
#   restart_state_loss             0   — graceful SIGTERM + restart keeps
#                                        every key's consumed budget
#                                        (docs/persistence.md final base)
#   ownership_transfer_loss        0   — a set_peers ring swap hands owned
#                                        GLOBAL state to the new owner with
#                                        no reset (ownership handoff)
#   mesh_routing_parity_errors     0   — device-derived shard ownership
#                                        (global slot // local_capacity)
#                                        agrees with the host hash ring
#                                        for every served key (a split
#                                        route double-serves a bucket)
#   mesh_dropped_keys /            0   — every decision issued to the
#   mesh_double_served                   sharded table resolves exactly
#                                        once (issued == hits+misses)
#   reshard_state_loss /           0   — an elastic n→m shard transition
#   reshard_double_served                (docs/resharding.md) keeps every
#                                        live bucket exactly once through
#                                        the cutover
#   reshard_parity_errors          0   — routed-path ownership agrees
#                                        with the host ring on the
#                                        post-transition layout
#   mesh_routed_overflows          0   — pinned-zero canary: the ragged
#                                        dispatch has no per-shard width,
#                                        so the retired routed path's
#                                        skew fallback can never fire —
#                                        even on the Zipf-1.2 rung
#   mesh_ragged_parity_errors      0   — the mesh_zipf_8 rung's per-
#                                        request decisions match a
#                                        single-chip TickEngine replay
#                                        of the same schedule exactly
#   mesh_trace_retraces            0   — serving windows reuse the
#                                        warmup-compiled ragged programs;
#                                        trace_counts never grows after
#                                        warmup (one program per batch
#                                        capacity, not per width)
#   expired_served                 0   — the overload rung's requests
#                                        whose deadline passed before
#                                        packing must be shed, never
#                                        served real answers
#                                        (docs/overload.md)
#   lease_over_admission           0   — the lease rung's clients never
#                                        admit more than their granted
#                                        budgets (docs/leases.md: the
#                                        never-over-admit invariant)
#   lease_bucket_drift             0   — after the lease release round
#                                        settles, every bucket holds
#                                        exactly what a per-request
#                                        phase would leave (constant
#                                        decision correctness)
#   lease_dispatch_per_window      1.0 — lease grant/sync accounting is
#                                        ONE batched column scatter per
#                                        window, never per-key dispatch
#   ssd_continuity_errors          0   — keys promoted back from the SSD
#                                        slab tier keep their consumed
#                                        budget (the cold-tier invariant,
#                                        one level down on flash)
#   ssd_tick_path_reads            0   — slab lookups never run inside
#                                        the tick-dispatch block (SSD I/O
#                                        stays out of tick/pack stages)
#   ssd_promote_batches_per_miss_tick 1.0 — the miss path's third hop is
#                                        ONE batched slab lookup per miss
#                                        tick, never per-key reads
#   mixed_algo_parity_errors       0   — zoo-lane decisions (sliding
#                                        window, GCRA, concurrency) are
#                                        bit-identical to the scalar
#                                        references (docs/algorithms.md)
#   mixed_algo_dispatches_per_step 1.0 — a window mixing all five
#                                        algorithms stays ONE device
#                                        dispatch, never per-algorithm
#                                        sub-batches
#   federation_hit_loss_after_heal 0   — the federation_2r rung's two
#                                        regions converge on the exact
#                                        union of all partition-era hits
#                                        after the heal (docs/federation.md
#                                        exactly-once envelope replay)
#   federation_over_admission_ratio <=1.0 — partition-era over-admission
#                                        on the contended key stays within
#                                        the staleness budget: each
#                                        isolated region admits at most
#                                        one limit's worth, so a 2-region
#                                        split caps the extra at 1.0x
#   autoscale_state_loss           0   — the diurnal_autoscale rung's
#                                        AUTONOMOUS transitions (policy-
#                                        driven, not operator-driven)
#                                        keep every live bucket, same
#                                        sweep as reshard_state_loss
#                                        (docs/autoscaling.md)
#   autoscale_flaps                0   — committed actuations in any
#                                        rolling hour never exceed the
#                                        flap suppressor's cap; a breach
#                                        means the guardrail chain let
#                                        the controller react to noise
COUNT_KEYS = (
    "dispatches_per_step",
    "churn_continuity_errors",
    "promote_dispatches_per_hit_tick",
    "demote_readbacks_per_reclaim",
    "hit_redelivery_loss",
    "restart_state_loss",
    "ownership_transfer_loss",
    "mesh_routing_parity_errors",
    "mesh_dropped_keys",
    "mesh_double_served",
    "mesh_routed_overflows",
    "mesh_ragged_parity_errors",
    "mesh_trace_retraces",
    "reshard_state_loss",
    "reshard_double_served",
    "reshard_parity_errors",
    "expired_served",
    "lease_over_admission",
    "lease_bucket_drift",
    "lease_dispatch_per_window",
    "ssd_continuity_errors",
    "ssd_tick_path_reads",
    "ssd_promote_batches_per_miss_tick",
    "multiproc_parity_errors",
    "multiproc_double_served",
    "multiproc_dropped_acked",
    "mixed_algo_parity_errors",
    "mixed_algo_dispatches_per_step",
    "federation_hit_loss_after_heal",
    "federation_over_admission_ratio",
    "autoscale_state_loss",
    "autoscale_flaps",
)

# Serving-path perf keys (PR 6's zero-copy/pipelined serving path).
# Unlike COUNT_KEYS these carry timing noise, so each gets its own
# direction-aware slack instead of the exact 1.05 count comparison:
#   serve_cpu_ms_per_batch  host codec+arena CPU per 1000-item batch —
#                           lower is better, 1.3x slack (sub-ms figure
#                           on a shared CI host jitters)
#   loopback_p99_ms         the loopback rung's MEASURED end-to-end
#                           batch p99 — lower is better, 1.5x slack
#                           (tail latency is the noisiest honest number
#                           in the ladder)
#   stage_*_p99_ms          per-stage pipeline p99 from the loopback
#                           rung's telemetry-on phase (flight recorder,
#                           docs/observability.md) — lower is better,
#                           1.5x slack each (stage tails are at least as
#                           noisy as the end-to-end p99 they decompose)
#   telemetry_overhead_ratio  off-phase rate / instrumented-phase rate —
#                           lower is better (1.0 = free); relative slack
#                           is generous because the ratio of two noisy
#                           rates flaps, but the ABSOLUTE_MAX_KEYS cap
#                           below holds it at 1.05 regardless
#   overload_admitted_p99_ms  p99 of requests ADMITTED while the
#                           overload rung offers ~10x sustainable load —
#                           lower is better, 1.5x slack (same tail-noise
#                           argument as loopback_p99_ms); a collapse
#                           here means the bounded queue stopped
#                           bounding queueing delay (docs/overload.md)
#   reshard_p99_during_ms   p99 of client windows served while the
#                           reshard_live rung's 8→4→8 transitions run —
#                           lower is better, 1.5x slack (tail noise); a
#                           blowup means the freeze/cutover window
#                           stopped being bounded (docs/resharding.md)
#   autoscale_p99_during_transition_ms  the same bound over the
#                           diurnal_autoscale rung's AUTONOMOUS
#                           transitions — lower is better, 1.5x slack;
#                           the controller must not make the freeze
#                           window worse than an operator-driven one
LOWER_BETTER_SLACK = {
    "serve_cpu_ms_per_batch": 1.3,
    "loopback_p99_ms": 1.5,
    "overload_admitted_p99_ms": 1.5,
    "reshard_p99_during_ms": 1.5,
    "autoscale_p99_during_transition_ms": 1.5,
    "stage_decode_p99_ms": 1.5,
    "stage_pack_p99_ms": 1.5,
    "stage_h2d_p99_ms": 1.5,
    "stage_tick_p99_ms": 1.5,
    "stage_encode_p99_ms": 1.5,
    "telemetry_overhead_ratio": 1.3,
}
#   h2d_overlap_ratio       fraction of serving windows whose request
#                           upload overlapped an earlier window's tick
#                           — HIGHER is better; candidate must keep
#                           >= 0.9x the baseline's ratio...
#   mesh_scaling_efficiency 8-dev mesh throughput / (8 x the 1-dev mesh
#                           baseline measured in the same child — the
#                           near-linear-scaling observable of the
#                           sharded serving table; HIGHER is better,
#                           candidate must keep >= 0.9x the baseline
#   overload_goodput_ratio  decisions served within budget under ~10x
#                           load / the same instance's unloaded rate —
#                           HIGHER is better (shed answers are cheap;
#                           goodput must survive saturation), candidate
#                           keeps >= 0.9x the baseline's ratio
#   lease_traffic_reduction baseline server-served items / lease-mode
#                           served items on the same admission stream —
#                           the lease tier's headline (docs/leases.md);
#                           HIGHER is better, candidate keeps >= 0.9x
#                           the baseline, and the >=10x absolute floor
#                           below holds regardless
#   chip_seconds_saved      ∫(8 − shards(t))dt over the diurnal rung's
#                           simulated day vs an always-8-shard static
#                           deployment — the autoscaler's headline
#                           (docs/autoscaling.md); HIGHER is better,
#                           candidate keeps >= 0.9x the baseline, and
#                           the absolute floor below demands it stay
#                           positive regardless
HIGHER_BETTER_FLOOR = {
    "h2d_overlap_ratio": 0.9,
    "mesh_scaling_efficiency": 0.9,
    "overload_goodput_ratio": 0.9,
    "lease_traffic_reduction": 0.9,
    "chip_seconds_saved": 0.9,
}
# ...and, baseline or not, a pipelined dispatch that stops overlapping
# at all is a regression in its own right: absolute floor on the
# candidate (the rung drives depth-8 concurrency, so a healthy pipeline
# sits near 1.0; 0.5 is the alarm threshold, not the target).
ABSOLUTE_MIN_KEYS = {
    "h2d_overlap_ratio": 0.5,
    # Overload protection that degrades past this is a failed build no
    # matter what the baseline measured: under ~10x offered load the
    # instance must keep serving >= 0.7x its own unloaded rate.
    "overload_goodput_ratio": 0.7,
    # The lease tier's acceptance bar (docs/leases.md): the cooperative
    # tier must cut server-served traffic by at least an order of
    # magnitude on the steady-state admission stream.
    "lease_traffic_reduction": 10.0,
    # An autoscaler that never gives capacity back is a static
    # deployment with extra steps: the diurnal day must bank SOME
    # chip-seconds vs always-8-shards, baseline or not.
    "chip_seconds_saved": 1.0,
}
# Absolute ceilings on the candidate, the MIN keys' mirror: telemetry
# must stay effectively free (≤5% serving-rate cost with the flight
# recorder installed) no matter what the baseline measured — a baseline
# that already regressed must not grant the candidate a free pass.
ABSOLUTE_MAX_KEYS = {
    "telemetry_overhead_ratio": 1.05,
    # A saturated daemon sheds the excess; it must not buffer it into
    # RSS.  The overload phase may not grow peak RSS past this bound.
    "overload_rss_growth_mb": 2048,
    # Lease accounting is batched on-device column work: one jitted
    # scatter per grant/sync window, exactly — a candidate above 1.0
    # re-introduced per-key dispatch (docs/leases.md).
    "lease_dispatch_per_window": 1.0,
    # The SSD miss hop is ONE batched slab lookup per miss tick — above
    # 1.0 the tier re-introduced per-key reads (docs/tiering.md).
    "ssd_promote_batches_per_miss_tick": 1.0,
    # A mixed-policy window is ONE tick program — above 1.0 the zoo
    # re-introduced per-algorithm sub-batches (docs/algorithms.md).
    "mixed_algo_dispatches_per_step": 1.0,
    # The SSD churn rung's 8x working set lives on flash: resident-set
    # growth across the rung stays bounded by the two RAM tiers no
    # matter what the baseline measured.
    "churn_ssd_rss_mb": 512,
    # A 2-region partition admits at most one extra limit's worth on a
    # contended key (staleness × local rate, and each isolated region
    # stops at its own limit) — above 1.0 the region-local answer path
    # stopped enforcing the local limit during a partition.
    "federation_over_admission_ratio": 1.0,
}

GATED_VALUE_KEYS = (
    COUNT_KEYS + tuple(LOWER_BETTER_SLACK) + tuple(HIGHER_BETTER_FLOOR)
    + tuple(ABSOLUTE_MAX_KEYS)
)

# Keys gated ONLY by their absolute bound above, never baseline-relative:
# a 1 MB -> 3 MB RSS wiggle is allocator noise, not a 3x regression, so
# a relative comparison on a near-zero base would flap forever.
ABSOLUTE_ONLY_KEYS = ("overload_rss_growth_mb", "churn_ssd_rss_mb")

# Keys gated at exactly 0 in the CANDIDATE even when the baseline lacks
# the rung: each is an absolute correctness invariant, not a relative
# performance figure.
ABSOLUTE_ZERO_KEYS = (
    "churn_continuity_errors",
    "hit_redelivery_loss",
    "restart_state_loss",
    "ownership_transfer_loss",
    "mesh_routing_parity_errors",
    "mesh_dropped_keys",
    "mesh_double_served",
    "mesh_routed_overflows",
    "mesh_ragged_parity_errors",
    "mesh_trace_retraces",
    "reshard_state_loss",
    "reshard_double_served",
    "reshard_parity_errors",
    "expired_served",
    "lease_over_admission",
    "lease_bucket_drift",
    "ssd_continuity_errors",
    "ssd_tick_path_reads",
    "multiproc_parity_errors",
    "multiproc_double_served",
    "multiproc_dropped_acked",
    "mixed_algo_parity_errors",
    "federation_hit_loss_after_heal",
    "autoscale_state_loss",
    "autoscale_flaps",
)


def load_bench(path):
    """Accept either bench.py's raw JSON line or the driver's BENCH_r*.json
    wrapper (which captures that line inside its "tail" field)."""
    with open(path) as f:
        doc = json.load(f)
    if "value" in doc:
        return doc
    for line in reversed(doc.get("tail", "").splitlines()):
        # The headline may not be the last line (r02's abort traceback
        # followed it) and may be truncated by the tail capture — salvage
        # whatever parses.
        i = line.find('{"metric"')
        if i < 0:
            continue
        try:
            return json.loads(line[i:])
        except json.JSONDecodeError:
            continue
    # Truncated tail (r02's was cut mid-ladder): salvage every complete
    # {"rung": ...} object so partial rounds still gate their rungs.
    rungs = []
    for m in re.finditer(r'\{"rung":.*?\}', doc.get("tail", "")):
        try:
            rungs.append(json.loads(m.group(0)))
        except json.JSONDecodeError:
            continue
    if rungs:
        return {"value": None, "ladder": rungs, "salvaged": True}
    raise SystemExit(f"{path}: no bench result found")


def rates(doc):
    """rung → (rate, shape_key, spread).  The shape key carries the
    workload parameters (key count, batch width) so a BENCH_FAST
    candidate is never gated against a full-size baseline under the same
    rung name — mismatched shapes are reported, not judged (the reference
    gate compares like-for-like PR-vs-master runs on one runner).  The
    spread is the rung's recorded sample dispersion ((max-min)/max of its
    median-of-k samples, bench.diff_time); the gate widens its threshold
    by both files' spreads so a noisy-but-honest rung doesn't flap.

    The headline's shape key is its ``headline_rung`` (when recorded):
    bench.py headlines the max of several kernel rungs, so two records
    whose leading rung differs would compare different workloads under
    one name — the shape mismatch path reports that instead of judging
    it."""
    out = {}
    if doc.get("value") is not None:
        shape = ()
        if doc.get("headline_rung"):
            shape = (("headline_rung", doc["headline_rung"]),)
        out["headline"] = (float(doc["value"]), shape, 0.0)
    for rung in doc.get("ladder", []):
        shape = tuple(
            (k, rung[k]) for k in ("keys", "batch", "nodes") if k in rung
        )
        for k in RATE_KEYS:
            if rung.get(k):
                out[rung["rung"]] = (
                    float(rung[k]), shape, float(rung.get("spread") or 0.0)
                )
                break
    # Compact headline records (bench.py's final stdout line, the only
    # thing the driver's BENCH_r*.json tail holds) carry {rung: [rate,
    # spread]} without workload shapes; None marks "shape unknown" so
    # the gate can wildcard it against a shaped record of the same rung.
    for name, rs in doc.get("rungs", {}).items():
        if name not in out and rs and rs[0]:
            out[name] = (float(rs[0]), None,
                         float(rs[1] or 0.0) if len(rs) > 1 else 0.0)
    return out


def counts(doc):
    """(rung, key) → value for the gated per-rung value metrics: the
    exact work counts (COUNT_KEYS, compared directly — no sampling
    noise) plus the direction-aware serving-path perf keys."""
    out = {}
    for rung in doc.get("ladder", []):
        for k in GATED_VALUE_KEYS:
            if rung.get(k) is not None:
                out[(rung["rung"], k)] = float(rung[k])
    # Compact headline records carry the same counts under "counts"
    # (rung → {key: value}) — the full ladder wins on conflicts.
    for name, kv in doc.get("counts", {}).items():
        for k, v in kv.items():
            if k in GATED_VALUE_KEYS and v is not None:
                out.setdefault((name, k), float(v))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("baseline")
    ap.add_argument("candidate")
    ap.add_argument("--threshold", type=float, default=2.0,
                    help="fail when baseline/candidate exceeds this")
    ap.add_argument("--allow-empty", action="store_true",
                    help="don't fail when no rung was actually gated "
                         "(manual cross-shape comparisons)")
    args = ap.parse_args()

    base_doc, cand_doc = load_bench(args.baseline), load_bench(args.candidate)
    modes_known = (base_doc.get("fast_mode") is not None
                   and cand_doc.get("fast_mode") is not None)
    if modes_known and base_doc["fast_mode"] != cand_doc["fast_mode"]:
        # Shapeless compact records can't rely on per-rung shape keys to
        # catch a FAST-vs-full mismatch; the mode flag is the guard.
        if args.allow_empty:
            print("fast_mode differs between records — skipped")
            sys.exit(0)
        print("fast_mode differs between records — not comparable; FAIL")
        sys.exit(1)
    base = rates(base_doc)
    cand = rates(cand_doc)

    failed = False
    gated = 0
    for name in sorted(set(base) | set(cand)):
        bs, cs = base.get(name), cand.get(name)
        if bs is None or cs is None:
            print(f"  {name}: only in "
                  f"{'candidate' if bs is None else 'baseline'} — not gated")
            continue
        (b, b_shape, b_spread), (c, c_shape, c_spread) = bs, cs
        if name == "headline" and (not b_shape or not c_shape):
            # Legacy records (r01–r04) don't carry headline_rung; a
            # missing value is a wildcard, not a mismatch — only two
            # records that BOTH name their leading rung differently
            # compare different workloads.
            b_shape = c_shape = ()
        if b_shape is None or c_shape is None:
            # Compact record: shape unknown.  Wildcard it ONLY when both
            # records declared a (matching) fast_mode — a salvaged tail
            # without the flag could be full-size while the compact side
            # is FAST, and gating those cross-shape is exactly what the
            # shape keys exist to prevent.
            if modes_known:
                b_shape = c_shape = ()
            else:
                print(f"  {name}: shapeless compact rung vs record "
                      "without fast_mode — not gated")
                continue
        if b_shape != c_shape:
            print(f"  {name}: workload shape differs "
                  f"({dict(b_shape)} vs {dict(c_shape)}) — not gated")
            continue
        gated += 1
        if c <= 0:
            print(f"  {name}: candidate rate is 0 — FAIL")
            failed = True
            continue
        # Spread-aware slack: a rung whose own samples disperse by s can
        # legitimately move by (1+s) run-to-run; both runs contribute.
        # Each side's slack is capped at 1.5x so a wildly noisy rung
        # (r04 spreads ~0.75 → ~6x allowed slowdown) can't neuter the
        # gate — a measurement that bad should fail and force a re-run
        # or a tighter rung, not wave regressions through.
        allowed = (args.threshold
                   * min(1 + b_spread, 1.5) * min(1 + c_spread, 1.5))
        slowdown = b / c
        mark = "FAIL" if slowdown > allowed else "ok"
        if slowdown > allowed:
            failed = True
        print(f"  {name}: {b:,.0f} -> {c:,.0f} "
              f"({1 / slowdown:.2f}x, allowed {1 / allowed:.2f}x, {mark})")
    base_counts, cand_counts = counts(base_doc), counts(cand_doc)
    for key in sorted(set(base_counts) & set(cand_counts)):
        if key[1] in ABSOLUTE_ONLY_KEYS:
            continue  # gated by its absolute bound below, never relatively
        b, c = base_counts[key], cand_counts[key]
        name = f"{key[0]}.{key[1]}"
        gated += 1
        if key[1] in LOWER_BETTER_SLACK:
            allowed = b * LOWER_BETTER_SLACK[key[1]] + 1e-9
            mark = "FAIL" if c > allowed else "ok"
            kind = "perf, lower is better"
        elif key[1] in HIGHER_BETTER_FLOOR:
            allowed = b * HIGHER_BETTER_FLOOR[key[1]] - 1e-9
            mark = "FAIL" if c < allowed else "ok"
            kind = "perf, higher is better"
        else:
            # Exact counts: tiny slack only for the rare-overflow steps
            # that can legitimately land inside a sample window.
            mark = "FAIL" if c > b * 1.05 + 1e-9 else "ok"
            kind = "count, lower is better"
        if mark == "FAIL":
            failed = True
        print(f"  {name}: {b:g} -> {c:g} ({kind}, {mark})")
    # Absolute floors hold for the candidate even when BOTH records
    # carry the key (a baseline that already collapsed must not grant
    # the candidate a free pass).
    for key, v in sorted(cand_counts.items()):
        floor = ABSOLUTE_MIN_KEYS.get(key[1])
        if floor is not None:
            gated += 1
            mark = "FAIL" if v < floor else "ok"
            if v < floor:
                failed = True
            print(f"  {key[0]}.{key[1]}: {v:g} "
                  f"(absolute floor {floor:g}, {mark})")
        ceil = ABSOLUTE_MAX_KEYS.get(key[1])
        if ceil is not None:
            gated += 1
            mark = "FAIL" if v > ceil else "ok"
            if v > ceil:
                failed = True
            print(f"  {key[0]}.{key[1]}: {v:g} "
                  f"(absolute ceiling {ceil:g}, {mark})")
    for key in sorted(set(base_counts) ^ set(cand_counts)):
        if key in cand_counts and key[1] in ABSOLUTE_ZERO_KEYS:
            # Absolute invariants — a re-promoted key losing its consumed
            # budget is a rate-limit bypass, and a GLOBAL hit that never
            # lands after peer recovery is lost accounting; baseline rung
            # or not, the candidate must report exactly 0.
            gated += 1
            v = cand_counts[key]
            mark = "FAIL" if v > 0 else "ok"
            if v > 0:
                failed = True
            print(f"  {key[0]}.{key[1]}: {v:g} "
                  f"(absolute invariant, must be 0, {mark})")
            continue
        if key in cand_counts and key[1] in ABSOLUTE_ONLY_KEYS:
            continue  # already judged against its absolute bound above
        side = "candidate" if key not in base_counts else "baseline"
        print(f"  {key[0]}.{key[1]}: only in {side} — not gated")
    if gated == 0 and not args.allow_empty:
        # A gate that judged nothing must not report success (the CI job
        # would pass vacuously whenever shapes diverge — advisor r3).
        print("no rungs were gated (all skipped/mismatched) — FAIL; "
              "regenerate the like-for-like baseline or pass --allow-empty")
        failed = True
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
