"""Prometheus metrics with the reference's family names.

Reproduces the metric catalog spread through the reference
(``gubernator.go:60-111``, ``lrucache.go:48-59``, ``global.go:50-67``,
``grpc_stats.go:41-121``; full list in ``docs/prometheus.md``) so existing
dashboards/alerts — and the metrics-as-test-oracle pattern the reference's
distributed tests rely on (``functional_test.go:2184-2276``) — carry over
unchanged.  Each daemon gets its own registry (the in-process test cluster
runs many daemons per process, like ``cluster/cluster.go``).
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Dict, Optional, Sequence, Tuple

from gubernator_tpu.utils import sanitize

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Summary,
    generate_latest,
)
from prometheus_client.core import HistogramMetricFamily

CONTENT_TYPE_LATEST = "text/plain; version=0.0.4; charset=utf-8"

# The one-chip engine's dispatch branches (TickEngine.submit_columns):
# its counter of each is ``metric_<branch>_ticks``, its program in a
# device trace ``jit_tick32_<branch>``.
TICK_BRANCHES = ("unique", "grouped", "sequential", "layered")


def log_buckets(lo: float, hi: float, per_decade: int = 4) -> Tuple[float, ...]:
    """Fixed log-spaced bucket bounds from ``lo`` up to at least ``hi``."""
    step = 10.0 ** (1.0 / per_decade)
    out = [lo]
    while out[-1] < hi:
        out.append(out[-1] * step)
    return tuple(round(b, 12) for b in out)


# 100 µs … ~56 s at 4 buckets/decade — covers fastwire decode (~10 µs at
# the floor bucket) through a pathological multi-second window.
DEFAULT_BUCKETS = log_buckets(100e-6, 56.0)


class _HistogramChild:
    """One label-combination series.  The observe path takes no lock:
    a single ``list[i] += 1`` is serialized by the GIL, and the worst
    race outcome is one scrape reading a bucket/sum pair mid-update —
    acceptable skew for telemetry, and what keeps the hot serving path
    lock-free."""

    __slots__ = ("_bounds", "_counts", "_sum", "_exemplars")

    def __init__(self, bounds: Sequence[float]):
        self._bounds = bounds
        self._counts = [0] * (len(bounds) + 1)  # last slot = +Inf
        self._sum = 0.0
        # Per-bucket last exemplar: (trace_id, value, unix_ts) or None.
        self._exemplars: list = [None] * (len(bounds) + 1)

    def observe(self, value: float, trace_id: Optional[str] = None) -> None:
        i = bisect.bisect_left(self._bounds, value)
        self._counts[i] += 1
        self._sum += value
        if trace_id is None:
            trace_id = _current_trace_id()
        if trace_id is not None:
            self._exemplars[i] = (trace_id, value, time.time())


def _current_trace_id() -> Optional[str]:
    """Trace id of the active span, or None when tracing is off.  Late
    import keeps utils.metrics importable without utils.tracing."""
    from gubernator_tpu.utils import tracing

    if not tracing.enabled():
        return None
    span = tracing.current_span()
    return None if span is None else span.context.trace_id


class Histogram:
    """Lock-light fixed-bucket histogram with optional OpenMetrics
    exemplars.

    Buckets are log-spaced and fixed at construction (DEFAULT_BUCKETS:
    100 µs – 56 s, 4/decade).  Registered as a custom collector so
    ``Metrics.expose()`` / ``Metrics.sample()`` see the standard
    ``_bucket``/``_sum``/``_count`` series; ``openmetrics()`` renders the
    OpenMetrics exposition including ``# {trace_id="…"}`` exemplars so a
    bad p99 bucket links back to the trace that landed in it."""

    def __init__(
        self,
        name: str,
        documentation: str,
        labelnames: Sequence[str] = (),
        registry: Optional[CollectorRegistry] = None,
        buckets: Optional[Sequence[float]] = None,
    ):
        self._name = name
        self._doc = documentation
        self._labelnames = tuple(labelnames)
        self._bounds = tuple(buckets if buckets is not None else DEFAULT_BUCKETS)
        if list(self._bounds) != sorted(self._bounds):
            raise ValueError("histogram buckets must be sorted")
        self._lock = sanitize.lock("Histogram._lock")  # guards child creation only
        self._children: Dict[Tuple[str, ...], _HistogramChild] = {}
        if not self._labelnames:
            self._children[()] = _HistogramChild(self._bounds)
        if registry is not None:
            registry.register(self)

    # -- write path ----------------------------------------------------
    def labels(self, **labelvalues: str) -> _HistogramChild:
        key = tuple(str(labelvalues[n]) for n in self._labelnames)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.setdefault(
                    key, _HistogramChild(self._bounds))
        return child

    def observe(self, value: float, trace_id: Optional[str] = None) -> None:
        if self._labelnames:
            raise ValueError(f"{self._name} needs labels(); has labelnames")
        self._children[()].observe(value, trace_id)

    # -- read path -----------------------------------------------------
    def collect(self):
        fam = HistogramMetricFamily(
            self._name, self._doc, labels=list(self._labelnames))
        for key, child in list(self._children.items()):
            cum = 0
            rows = []
            counts = list(child._counts)
            for bound, n in zip(self._bounds, counts):
                cum += n
                rows.append((_fmt_le(bound), cum))
            rows.append(("+Inf", cum + counts[-1]))
            fam.add_metric(list(key), rows, sum_value=child._sum)
        yield fam

    def openmetrics(self) -> str:
        """OpenMetrics exposition for this family, with exemplars."""
        lines = [f"# TYPE {self._name} histogram",
                 f"# HELP {self._name} {self._doc}"]
        for key, child in sorted(self._children.items()):
            base = list(zip(self._labelnames, key))
            cum = 0
            counts = list(child._counts)
            bounds = list(self._bounds) + [float("inf")]
            for i, bound in enumerate(bounds):
                cum += counts[i]
                le = "+Inf" if bound == float("inf") else _fmt_le(bound)
                labels = "".join(f'{k}="{v}",' for k, v in base)
                line = f'{self._name}_bucket{{{labels}le="{le}"}} {cum}'
                ex = child._exemplars[i]
                if ex is not None:
                    tid, val, ts = ex
                    line += (f' # {{trace_id="{tid}"}} {_fmt_le(val)}'
                             f" {ts:.3f}")
                lines.append(line)
            label_str = ",".join(f'{k}="{v}"' for k, v in base)
            braces = f"{{{label_str}}}" if label_str else ""
            lines.append(f"{self._name}_count{braces} {cum}")
            lines.append(f"{self._name}_sum{braces} {_fmt_le(child._sum)}")
        return "\n".join(lines) + "\n"


def _fmt_le(v: float) -> str:
    """Shortest float repr (Prometheus le label convention)."""
    s = repr(float(v))
    return s[:-2] if s.endswith(".0") else s


class Metrics:
    """Per-daemon metric registry (names match the reference catalog)."""

    def __init__(self):
        self.registry = CollectorRegistry()
        reg = self.registry

        # Build stamp (Prometheus build_info convention; the reference
        # stamps Version via ldflags and logs it at startup,
        # cmd/gubernator/main.go:39,53).
        import platform as _platform

        from gubernator_tpu.version import VERSION

        self.build_info = Gauge(
            "gubernator_build_info",
            "Build/version stamp; value is always 1.",
            ["version", "python", "machine"],
            registry=reg,
        )
        self.build_info.labels(
            version=VERSION,
            python=_platform.python_version(),
            machine=_platform.machine(),
        ).set(1)

        # gubernator.go:60-111 service families.
        self.getratelimit_counter = Counter(
            "gubernator_getratelimit_counter",
            "The count of getLocalRateLimit() calls. Label \"calltype\" may "
            "be \"local\" for calls handled by the same peer, \"forward\" for "
            "calls forwarded to another peer, or \"global\" for global rate limits.",
            ["calltype"],
            registry=reg,
        )
        self.func_duration = Summary(
            "gubernator_func_duration",
            "The timings of key functions in Gubernator in seconds.",
            ["name"],
            registry=reg,
        )
        self.over_limit_counter = Counter(
            "gubernator_over_limit_counter",
            "The number of rate limit checks that are over the limit.",
            registry=reg,
        )
        self.concurrent_checks = Gauge(
            "gubernator_concurrent_checks_counter",
            "The number of concurrent GetRateLimits API calls.",
            registry=reg,
        )
        self.check_error_counter = Counter(
            "gubernator_check_error_counter",
            "The number of errors while checking rate limits.",
            ["error"],
            registry=reg,
        )
        self.command_counter = Counter(
            "gubernator_command_counter",
            "The count of commands processed by each worker in WorkerPool.",
            ["worker", "method"],
            registry=reg,
        )
        self.worker_queue_length = Gauge(
            "gubernator_worker_queue_length",
            "The count of requests queued up in WorkerPool.",
            ["method", "worker"],
            registry=reg,
        )

        # Batch-forwarding families (gubernator.go:95-110).
        self.batch_send_duration = Summary(
            "gubernator_batch_send_duration",
            "The timings of batch send operations to a remote peer.",
            ["peerAddr"],
            registry=reg,
        )
        self.batch_send_retries = Counter(
            "gubernator_batch_send_retries",
            "The count of retries occurred in asyncRequest() forwarding a "
            "request to another peer.",
            registry=reg,
        )
        self.batch_queue_length = Gauge(
            "gubernator_batch_queue_length",
            "The getRateLimitsBatch() queue length in PeerClient.",
            ["peerAddr"],
            registry=reg,
        )

        # GLOBAL manager families (global.go:50-67).
        self.global_send_duration = Summary(
            "gubernator_global_send_duration",
            "The duration of GLOBAL async sends in seconds.",
            registry=reg,
        )
        self.broadcast_duration = Summary(
            "gubernator_broadcast_duration",
            "The duration of GLOBAL broadcasts to peers in seconds.",
            registry=reg,
        )
        self.global_send_queue_length = Gauge(
            "gubernator_global_send_queue_length",
            "The count of requests queued up for global broadcast.",
            registry=reg,
        )
        self.global_queue_length = Gauge(
            "gubernator_global_queue_length",
            "The count of requests queued up for update all peers.",
            registry=reg,
        )

        # Cache families (lrucache.go:48-59 + collector :180-214).
        self.cache_size = Gauge(
            "gubernator_cache_size",
            "The number of items in LRU Cache which holds the rate limits.",
            registry=reg,
        )
        self.cache_access_count = Counter(
            "gubernator_cache_access_count",
            "Cache access counts. Label \"type\" = \"miss\" or \"hit\".",
            ["type"],
            registry=reg,
        )
        self.unexpired_evictions = Counter(
            "gubernator_unexpired_evictions_count",
            "Count the number of cache items which were evicted while "
            "unexpired.",
            registry=reg,
        )

        # gRPC stats families (grpc_stats.go:41-121).
        self.grpc_request_counts = Counter(
            "gubernator_grpc_request_counts",
            "The count of gRPC requests.",
            ["status", "method"],
            registry=reg,
        )
        self.grpc_request_duration = Summary(
            "gubernator_grpc_request_duration",
            "The timings of gRPC requests in seconds.",
            ["method"],
            registry=reg,
        )

        # TPU-native additions (no reference analog): device tick telemetry.
        self.tick_duration = Summary(
            "gubernator_tpu_tick_duration",
            "Wall time of one device tick (H2D + kernel + D2H) in seconds.",
            registry=reg,
        )
        self.tick_batch_size = Summary(
            "gubernator_tpu_tick_batch_size",
            "Requests applied per device tick.",
            registry=reg,
        )
        # Algorithm zoo (docs/algorithms.md): per-policy traffic split of
        # the mixed-policy device table.  Label \"algorithm\" is the enum
        # name (token_bucket, leaky_bucket, sliding_window, gcra,
        # concurrency); out-of-range wire values are rejected at the edge
        # and never counted here.
        self.algorithm_requests = Counter(
            "gubernator_tpu_algorithm_requests",
            "Rate-limit items accepted for ticking, by algorithm.",
            ["algorithm"],
            registry=reg,
        )
        # GLOBAL mesh reconcile telemetry: steps this daemon drove, mesh
        # programs those steps launched, and dense-fallback steps.  One
        # dispatch per step is the fused sparse/dense normal case; 2 means
        # an envelope overflow ran the dense fallback (rare by design) —
        # a sustained dispatch/step ratio near 2.0 means the envelope is
        # under-sized for the traffic (or the probe fusion regressed).
        self.mesh_reconcile_count = Counter(
            "gubernator_tpu_mesh_reconcile_count",
            "GLOBAL mesh reconcile steps driven by this daemon.",
            registry=reg,
        )
        self.mesh_reconcile_dispatches = Counter(
            "gubernator_tpu_mesh_reconcile_dispatches",
            "Jitted mesh programs launched by this daemon's reconcile "
            "steps (1 per fused sparse or dense step; +1 when an "
            "envelope overflow runs the dense fallback).",
            registry=reg,
        )
        self.mesh_dense_fallbacks = Counter(
            "gubernator_tpu_mesh_dense_fallbacks",
            "Sparse reconcile steps that overflowed the envelope and "
            "fell back to the dense program.",
            registry=reg,
        )
        # Sharded serving table (parallel/mesh_engine.py): the ragged
        # flat tick is the ONE serving format — each shard walks its
        # own extent of the slot-sorted batch, so there is no per-shard
        # width to overflow.  The overflow counter survives as a
        # pinned-zero canary (tests/test_mesh_engine.py holds it at 0).
        self.mesh_routed_windows = Counter(
            "gubernator_tpu_mesh_routed_windows",
            "Serving windows dispatched through the ragged flat tick "
            "(each shard walks its own extent of the slot-sorted "
            "batch on device).",
            registry=reg,
        )
        self.mesh_dup_windows = Counter(
            "gubernator_tpu_mesh_dup_windows",
            "Sharded serving windows with duplicate keys, answered by "
            "the sorted 32-bit duplicate program.",
            registry=reg,
        )
        self.mesh_unique_windows = Counter(
            "gubernator_tpu_mesh_unique_windows",
            "Sharded serving windows without duplicate keys, answered "
            "by the duplicate-free program.",
            registry=reg,
        )
        self.mesh_native_pack_windows = Counter(
            "gubernator_tpu_mesh_native_pack_windows",
            "Sharded serving windows whose host side (keys to shard and "
            "slot, the slot-sorted slab) was the one native window pass "
            "(over mesh_routed_windows: the share it answered).",
            registry=reg,
        )
        self.mesh_h2d_uploads = Counter(
            "gubernator_tpu_mesh_h2d_uploads",
            "Host-to-device uploads the sharded engine issued for its "
            "serving windows (over mesh_routed_windows: uploads a "
            "window).",
            registry=reg,
        )
        self.mesh_routed_overflows = Counter(
            "gubernator_tpu_mesh_routed_overflows",
            "Pinned-zero canary: the retired routed path's skew "
            "fallback count. The ragged dispatch has no per-shard "
            "width, so any increment is a bug.",
            registry=reg,
        )

        # Tiered bucket state (docs/tiering.md): demote/promote traffic
        # between the device table and the host-side cold store, tier
        # occupancy, and requests shed with per-item errors when the
        # table is truly full (eviction freed nothing).
        self.cold_demotions = Counter(
            "gubernator_tpu_cold_demotions",
            "Bucket rows demoted from the device table into the "
            "host-side cold store (readback-then-evict).",
            registry=reg,
        )
        self.cold_promotions = Counter(
            "gubernator_tpu_cold_promotions",
            "Bucket rows promoted from the cold store back into the "
            "device table (batched restore scatter on the miss path).",
            registry=reg,
        )
        self.cold_hits = Counter(
            "gubernator_tpu_cold_hits",
            "Cache misses that found their bucket in the cold store.",
            registry=reg,
        )
        self.cold_size = Gauge(
            "gubernator_tpu_cold_size",
            "The number of entries currently held by the cold store.",
            registry=reg,
        )
        # SSD third tier (docs/tiering.md): demote/promote traffic
        # between the cold store and the slab store, slab occupancy in
        # bytes, compaction rounds, and the writer queue level.
        self.ssd_demotions = Counter(
            "gubernator_tpu_ssd_demotions",
            "Bucket rows demoted from the cold store into the SSD slab "
            "store (batched write-behind on cold-tier overflow).",
            registry=reg,
        )
        self.ssd_promotions = Counter(
            "gubernator_tpu_ssd_promotions",
            "Bucket rows promoted from the SSD slab store back up the "
            "tiers (one batched lookup per miss tick).",
            registry=reg,
        )
        self.ssd_hits = Counter(
            "gubernator_tpu_ssd_hits",
            "Miss-path SSD lookups that found their bucket in a slab.",
            registry=reg,
        )
        self.ssd_compactions = Counter(
            "gubernator_tpu_ssd_compactions",
            "Log-structured compaction rounds (a sealed slab's live "
            "rows rewritten forward, the file retired).",
            registry=reg,
        )
        self.ssd_bytes = Gauge(
            "gubernator_tpu_ssd_bytes",
            "Bytes currently held across SSD slab files.",
            registry=reg,
        )
        self.ssd_queue_depth = Gauge(
            "gubernator_tpu_ssd_queue_depth",
            "Demote batches waiting on the SSD writer queue (at the "
            "configured depth, demote sweeps block — backpressure).",
            registry=reg,
        )
        self.hot_occupancy = Gauge(
            "gubernator_tpu_hot_occupancy",
            "Fraction of device bucket-table slots holding a mapped key "
            "(0.0-1.0).",
            registry=reg,
        )
        self.h2d_overlap_ratio = Gauge(
            "gubernator_tpu_h2d_overlap_ratio",
            "Fraction of serving windows whose request upload was "
            "dispatched while an earlier window's tick was still "
            "unresolved (0.0 serial, ~1.0 pipelined steady state).",
            registry=reg,
        )
        self.shed_requests = Counter(
            "gubernator_tpu_shed_requests",
            "Requests answered with a per-item 'table full' error "
            "because the table was full and eviction freed nothing "
            "(the rest of their batch was still served).",
            registry=reg,
        )
        self.leaky_rows = Counter(
            "gubernator_tpu_leaky_rows",
            "Rows dispatched to the device with algorithm LEAKY_BUCKET: "
            "the decisions that take the float64 leaky path.",
            registry=reg,
        )
        self.tick_windows = Counter(
            "gubernator_tpu_tick_windows",
            "Serving windows of the one-chip engine by the dispatch "
            "branch that answered them; label \"program\" is "
            "unique/grouped/sequential/layered, the jit_tick32_<program> "
            "of a device trace.",
            ["program"],
            registry=reg,
        )

        # Fault-tolerant peer path (docs/resilience.md): per-peer breaker
        # state, redelivery accounting for GLOBAL hits/broadcasts that
        # failed to flush, degraded GLOBAL answers served while the
        # owner's breaker was open, and background-loop crash restarts.
        self.breaker_state = Gauge(
            "gubernator_breaker_state",
            "Circuit breaker state per peer: 0=closed, 1=half-open, 2=open.",
            ["peerAddr"],
            registry=reg,
        )
        self.breaker_transitions = Counter(
            "gubernator_breaker_transitions",
            "Circuit breaker state transitions per peer; label \"to\" is "
            "the state entered (closed/half_open/open).",
            ["peerAddr", "to"],
            registry=reg,
        )
        self.degraded_answers = Counter(
            "gubernator_degraded_answers",
            "GLOBAL requests answered from local non-owner state while "
            "the owning peer's circuit breaker was open (degraded mode).",
            registry=reg,
        )
        self.global_redelivered_hits = Counter(
            "gubernator_global_redelivered_hits",
            "GLOBAL hit records re-enqueued into the redelivery buffer "
            "after a failed flush to the owning peer.",
            registry=reg,
        )
        self.global_dropped_hits = Counter(
            "gubernator_global_dropped_hits",
            "GLOBAL hit records dropped because the redelivery buffer "
            "was at its cap (GUBER_REDELIVERY_LIMIT) — lost accounting.",
            registry=reg,
        )
        self.global_redelivered_broadcasts = Counter(
            "gubernator_global_redelivered_broadcasts",
            "GLOBAL update records re-enqueued for broadcast after a "
            "failed push to one or more peers.",
            registry=reg,
        )
        self.global_dropped_broadcasts = Counter(
            "gubernator_global_dropped_broadcasts",
            "GLOBAL update records dropped because the broadcast "
            "redelivery buffer was at its cap.",
            registry=reg,
        )
        # Crash-safe persistence (docs/persistence.md): snapshot write
        # traffic, restore damage, and GLOBAL ownership handoff on ring
        # churn.
        self.snapshot_writes = Counter(
            "gubernator_tpu_snapshot_writes",
            "Snapshot records durably written; label \"kind\" is \"delta\" "
            "(incremental dirty export) or \"base\" (full compaction / "
            "final shutdown snapshot).",
            ["kind"],
            registry=reg,
        )
        self.snapshot_items = Counter(
            "gubernator_tpu_snapshot_items",
            "Bucket rows carried by durably written snapshot records, "
            "by record kind.",
            ["kind"],
            registry=reg,
        )
        self.snapshot_duration = Summary(
            "gubernator_tpu_snapshot_duration",
            "Wall time of one snapshot write (engine export + encode + "
            "fsync) in seconds, by record kind.",
            ["kind"],
            registry=reg,
        )
        self.snapshot_corrupt_records = Counter(
            "gubernator_tpu_snapshot_corrupt_records",
            "Corrupt or truncated snapshot records skipped during "
            "startup restore (replay stops at the last good prefix; "
            "the service still starts).",
            registry=reg,
        )
        self.snapshot_restored_items = Counter(
            "gubernator_tpu_snapshot_restored_items",
            "Bucket rows replayed from the snapshot store at startup "
            "(before TTL expiry filtering).",
            registry=reg,
        )
        self.ownership_transfers = Counter(
            "gubernator_tpu_ownership_transfers",
            "GLOBAL keys whose accumulated state was handed to a new "
            "owning peer after a ring change; label \"result\" is "
            "\"pushed\" (landed on the new owner), \"requeued\" (push "
            "failed; retried via the broadcast redelivery buffer), or "
            "\"untracked\" (tracker at GUBER_REDELIVERY_LIMIT when the "
            "key updated — its state will not ride a handoff).",
            ["result"],
            registry=reg,
        )
        # Elastic live resharding (docs/resharding.md): transition
        # outcomes, the running transition's phase/size, verification
        # counters held at zero by tests/test_reshard.py, and the
        # transition wall time.
        self.reshard_transitions = Counter(
            "gubernator_tpu_reshard_transitions",
            "Reshard transitions by terminal outcome: \"committed\" (new "
            "layout serving), \"aborted\" (rolled back to the old "
            "layout), \"interrupted\" (a begin record with no terminal "
            "record found at startup — the process died mid-transition "
            "and restarted on the last snapshot).",
            ["result"],
            registry=reg,
        )
        self.reshard_phase = Gauge(
            "gubernator_tpu_reshard_phase",
            "Current reshard protocol phase: 0=idle, 1=freeze, 2=drain, "
            "3=relayout, 4=cutover, 5=verify (returns to 0 on commit or "
            "abort).",
            registry=reg,
        )
        self.reshard_shards = Gauge(
            "gubernator_tpu_reshard_shards",
            "Serving shard count after the most recent committed "
            "transition (the engine's live mesh width).",
            registry=reg,
        )
        self.reshard_state_loss = Counter(
            "gubernator_tpu_reshard_state_loss",
            "Bucket rows live before a transition but missing from the "
            "post-cutover table (verify phase). Must stay 0.",
            registry=reg,
        )
        self.reshard_double_served = Counter(
            "gubernator_tpu_reshard_double_served",
            "Keys resident on more than one shard after a cutover "
            "(verify phase) — each is a potential double-serve. Must "
            "stay 0.",
            registry=reg,
        )
        self.reshard_duration = Summary(
            "gubernator_tpu_reshard_duration",
            "Wall time of one reshard transition (freeze through verify) "
            "in seconds, by terminal outcome.",
            ["result"],
            registry=reg,
        )
        # Multi-region federation (docs/federation.md): envelope traffic,
        # redelivery attempts, worst-case cross-region drift age, and
        # MULTI_REGION answers served while a peer region was down.
        self.federation_envelopes = Counter(
            "gubernator_tpu_federation_envelopes",
            "Federation envelopes by outcome: \"sent\" (acked by the "
            "remote owning peer), \"applied\" (received from a peer "
            "region and applied locally), \"duplicate\" (received again "
            "after a lost ack; acked without re-applying).",
            ["result"],
            registry=reg,
        )
        self.federation_redeliveries = Counter(
            "gubernator_tpu_federation_redeliveries",
            "Federation envelope send attempts that failed (breaker "
            "open, RPC error, malformed ack) and will retry the same "
            "envelope after a jittered backoff.",
            registry=reg,
        )
        self.federation_staleness = Gauge(
            "gubernator_tpu_federation_staleness_seconds",
            "Age of the oldest cross-region hit delta not yet acked by "
            "its target region (pending or in flight); the live bound "
            "on inter-region over-admission drift.",
            registry=reg,
        )
        self.federation_degraded_answers = Counter(
            "gubernator_tpu_federation_degraded_answers",
            "MULTI_REGION requests answered from region-local state "
            "while at least one peer region was unreachable (its "
            "channel failing or breaker open) — each may over-admit up "
            "to the staleness budget.",
            registry=reg,
        )
        # Guardrailed shard autoscaler (docs/autoscaling.md): every
        # control decision, every actuated transition, and every
        # guardrail veto by name — the outside view of the controller.
        self.autoscale_decisions = Counter(
            "gubernator_tpu_autoscale_decisions",
            "Autoscaler control decisions by action: \"act\" (a "
            "transition was actuated, or would have been in dry-run), "
            "\"hold\" (no sustained pressure / already at a bound), "
            "\"veto\" (a guardrail blocked an otherwise-justified "
            "transition).",
            ["action"],
            registry=reg,
        )
        self.autoscale_transitions = Counter(
            "gubernator_tpu_autoscale_transitions",
            "Committed shard transitions actuated by the autoscaler, by "
            "direction (\"up\"/\"down\"); dry-run decisions and aborted "
            "transitions are not counted here.",
            ["direction"],
            registry=reg,
        )
        self.autoscale_vetoes = Counter(
            "gubernator_tpu_autoscale_vetoes",
            "Autoscaler decisions blocked by a guardrail, by reason: "
            "breaker_open, reshard_busy, cooldown_up, cooldown_down, "
            "flap_cap, reshard_error.",
            ["reason"],
            registry=reg,
        )
        self.loop_restarts = Counter(
            "gubernator_loop_restarts",
            "Background loops (global_hits, global_broadcast, peer_batch) "
            "restarted by their crash supervisor after an unexpected "
            "exception.",
            ["loop"],
            registry=reg,
        )

        # Serving telemetry plane (docs/observability.md): per-method RPC
        # latency and per-stage window latency as log-spaced histograms
        # (exemplars link a bad bucket to its trace when tracing is on),
        # plus the slow-window watchdog counter.
        self.grpc_duration_hist = Histogram(
            "gubernator_tpu_grpc_duration_seconds",
            "Per-method gRPC request latency histogram (log-spaced "
            "buckets; OpenMetrics exemplars carry the request span's "
            "trace id).",
            ["method"],
            registry=reg,
        )
        self.stage_duration = Histogram(
            "gubernator_tpu_stage_duration_seconds",
            "Per-stage serving-window latency histogram (label stage: "
            "every name in utils/flightrec.py STAGES, the tick-loop "
            "cycle's stages and the overlays), fed by the flight "
            "recorder when one is installed.",
            ["stage"],
            registry=reg,
        )
        self.slow_windows = Counter(
            "gubernator_tpu_slow_windows",
            "Serving windows whose summed stage time exceeded "
            "GUBER_SLOW_WINDOW_MS; each one's flight record is dumped to "
            "the log by the watchdog.",
            registry=reg,
        )
        # Stalls of the whole serving process, from the flight
        # recorder's own counters (its gc.callbacks and jax.monitoring
        # listeners; synced at scrape time, Daemon._sync_stalls).
        self.gc_pause_seconds = Counter(
            "gubernator_tpu_gc_pause_seconds",
            "Seconds the serving process spent in garbage collections "
            "since its first window, by generation; every thread stops "
            "for them. Counted while a flight recorder is installed.",
            ["generation"],
            registry=reg,
        )
        self.gc_collections = Counter(
            "gubernator_tpu_gc_collections",
            "Garbage collections of the serving process since its first "
            "window, by generation. Counted while a flight recorder is "
            "installed.",
            ["generation"],
            registry=reg,
        )
        self.serving_compile_seconds = Counter(
            "gubernator_tpu_serving_compile_seconds",
            "Seconds of jax trace, lowering and backend compile met "
            "after the first window: a shape the warm-up did not cover. "
            "Counted while a flight recorder is installed.",
            registry=reg,
        )
        self.serving_compiles = Counter(
            "gubernator_tpu_serving_compiles",
            "Backend compiles (one a first-met shape, cache hit or not) "
            "after the first window. Counted while a flight recorder is "
            "installed.",
            registry=reg,
        )

        # Overload control plane (docs/overload.md): bounded-ingest
        # fallback accounting, shed verdicts by reason, and the adaptive
        # limiter's admitted window width / queue occupancy.
        self.arena_fallbacks = Counter(
            "gubernator_tpu_arena_fallbacks",
            "Wire-decode batches served from plain numpy allocations "
            "because every arena slab was busy; capped per window by "
            "GUBER_INGEST_FALLBACK_LIMIT, shed beyond the cap.",
            registry=reg,
        )
        self.edge_calls = Counter(
            "gubernator_tpu_edge_calls",
            "Calls through the raw-bytes rate-limit edges, by path: "
            "native (one native decode and one native encode answered "
            "the call) or fallback (codec library missing, malformed "
            "frame, per-item errors, GLOBAL / MULTI_REGION / metadata "
            "items, clustered routing, a shed or refused call).",
            ["path"],
            registry=reg,
        )
        self.admission_shed = Counter(
            "gubernator_tpu_admission_shed",
            "Requests shed by the admission plane, by reason: expired "
            "(deadline passed before packing), overflow (bounded queue "
            "full), shutdown (drained at close), backpressure (ingest "
            "arena exhausted past the fallback cap).",
            ["reason"],
            registry=reg,
        )
        self.admission_queue_depth = Gauge(
            "gubernator_tpu_admission_queue_depth",
            "Requests waiting in the bounded two-class admission queue "
            "(peer reconcile traffic + client traffic).",
            registry=reg,
        )
        self.admission_window_limit = Gauge(
            "gubernator_tpu_admission_window_limit",
            "Current AIMD-admitted window width in requests (static "
            "batch_limit when GUBER_TARGET_P99_MS is 0).",
            registry=reg,
        )
        self.admission_expired_served = Counter(
            "gubernator_tpu_admission_expired_served",
            "Invariant violations: requests whose deadline had already "
            "expired at pack time but that reached the engine anyway. "
            "Must stay 0.",
            registry=reg,
        )

        # Cooperative quota-lease families (docs/leases.md).
        self.lease_grants = Counter(
            "gubernator_tpu_lease_grants",
            "Quota leases minted: budget delegated to a client for "
            "TTL-bounded local self-enforcement.",
            registry=reg,
        )
        self.lease_renewals = Counter(
            "gubernator_tpu_lease_renewals",
            "Cheap lease extensions: held budget re-signed with a "
            "pushed-out TTL instead of a fresh decision (the overload "
            "degrade path).",
            registry=reg,
        )
        self.lease_revocations = Counter(
            "gubernator_tpu_lease_revocations",
            "Lease generations bumped (limit config changed or explicit "
            "revoke); outstanding tokens die at their next sync.",
            registry=reg,
        )
        self.lease_sync_loss = Counter(
            "gubernator_tpu_lease_sync_loss",
            "Admissions reported by lease syncs beyond the granted "
            "budget (stale-generation or misbehaving clients); "
            "force-charged to the bucket on reconcile.",
            registry=reg,
        )
        self.lease_sync_dropped = Counter(
            "gubernator_tpu_lease_sync_dropped",
            "Lease reconcile accounting that never reached the bucket: "
            "credit/charge decisions shed under overload, force-charges "
            "bounced off the bucket floor, or excess synced against a "
            "key with no known config.",
            registry=reg,
        )

        # Multi-process streaming edge families (docs/edge.md): worker
        # processes write a shm counter block; the owner's supervisor
        # delta-syncs it into these, labelled per worker so one hot
        # worker is visible as itself.
        self.edge_decode_seconds = Counter(
            "gubernator_tpu_edge_decode_seconds",
            "Wire-decode CPU spent inside edge worker processes "
            "(off the device-owner's GIL).",
            ["worker"],
            registry=reg,
        )
        self.edge_windows = Counter(
            "gubernator_tpu_edge_windows",
            "Request windows decoded and published into the shm slab "
            "ring by each edge worker.",
            ["worker"],
            registry=reg,
        )
        self.edge_rows = Counter(
            "gubernator_tpu_edge_rows",
            "Request rows (rate-limit items) published by each edge "
            "worker.",
            ["worker"],
            registry=reg,
        )
        self.edge_acked_windows = Counter(
            "gubernator_tpu_edge_acked_windows",
            "Windows whose response matrix came back through the shm "
            "response ring and was acked by the worker.",
            ["worker"],
            registry=reg,
        )
        self.edge_backpressure_waits = Counter(
            "gubernator_tpu_edge_backpressure_waits",
            "Worker waits on its own full slab ring or response depth — "
            "the per-producer backpressure bound engaging.",
            ["worker"],
            registry=reg,
        )
        self.edge_shed = Counter(
            "gubernator_tpu_edge_shed",
            "Edge rows shed retriably, by reason: 'local' (worker spun "
            "out on its full ring), 'crash' (in-flight slabs of a dead "
            "worker), 'shutdown' (plane close).",
            ["worker", "reason"],
            registry=reg,
        )
        self.edge_worker_restarts = Counter(
            "gubernator_tpu_edge_worker_restarts",
            "Edge worker processes respawned by the supervisor after a "
            "crash.",
            ["worker"],
            registry=reg,
        )

    def register_flag_collectors(self, metric_flags: int) -> None:
        """Register OS / runtime collectors behind ``GUBER_METRIC_FLAGS``
        (reference flags.go:20-23 + daemon.go:276-287).  "os" → process
        collector under the ``gubernator`` namespace; "golang" → the
        host-runtime collectors (Python GC + platform, the analog of Go's
        GoCollector)."""
        from gubernator_tpu.config import FLAG_OS_METRICS, FLAG_RUNTIME_METRICS

        if metric_flags & FLAG_OS_METRICS:
            from prometheus_client import ProcessCollector

            ProcessCollector(namespace="gubernator", registry=self.registry)
        if metric_flags & FLAG_RUNTIME_METRICS:
            from prometheus_client import GCCollector, PlatformCollector

            GCCollector(registry=self.registry)
            PlatformCollector(registry=self.registry)

    def sample(self, name: str, labels: dict | None = None) -> float:
        """Read one sample value (0.0 when unobserved) — the oracle the
        reference's distributed tests poll instead of sleeping
        (functional_test.go:2184-2276 waitForBroadcast/waitForUpdate).
        Summaries expose ``<name>_count`` / ``<name>_sum``."""
        v = self.registry.get_sample_value(name, labels or {})
        return 0.0 if v is None else v

    def expose(self) -> bytes:
        """Render the registry in Prometheus text exposition format."""
        return generate_latest(self.registry)
