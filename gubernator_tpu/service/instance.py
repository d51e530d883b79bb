"""V1Instance: the request router (ownership decision + 3-way dispatch).

The service brain (reference ``gubernator.go:183-295``): for every item in
a GetRateLimits batch decide — local (we own the key), GLOBAL (answer from
local state, reconcile async), or forward (batched RPC to the owning peer,
≤5 retries with ownership re-resolution, ``gubernator.go:311-391``).

TPU-native deltas from the reference:

* All local work flows through the :class:`TickLoop` — one device tick per
  batch window instead of per-key worker dispatch.  Local items in one call
  are submitted *together*.
* A standalone instance (``set_peers`` never called) treats every key as
  local, so a single-node service needs no cluster bootstrap.
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import grpc

from gubernator_tpu.admission import (
    CLASS_CLIENT,
    CLASS_PEER,
    BudgetExhaustedError,
    batch_deadline,
)
from gubernator_tpu.config import BehaviorConfig, Config
from gubernator_tpu.resilience import (
    BreakerOpenError,
    DecorrelatedJitterBackoff,
    ResilienceConfig,
)
from gubernator_tpu.parallel.hashring import (
    HASH_FUNCTIONS,
    RegionPicker,
    ReplicatedConsistentHash,
)
from gubernator_tpu.service.global_manager import GlobalManager
from gubernator_tpu.service.peer_client import PeerClient
from gubernator_tpu.service.tickloop import TickLoop
import numpy as np

from gubernator_tpu.algos import algorithm_error, invalid_algorithm_mask
from gubernator_tpu.types import (
    ALGORITHM_MAX,
    MAX_BATCH_SIZE,
    Algorithm,
    Behavior,
    GlobalUpdate,
    HealthCheckResponse,
    PeerInfo,
    RateLimitRequest,
    RateLimitResponse,
    Status,
    has_behavior,
    set_behavior,
)
from gubernator_tpu.utils import timeutil, tracing
from gubernator_tpu.utils.metrics import Metrics

log = logging.getLogger("gubernator.instance")


class BatchTooLargeError(ValueError):
    """Maps to gRPC OutOfRange at the transport edge (gubernator.go:189-193)."""


@dataclass
class InstanceConfig:
    """Wiring for one V1Instance (reference Config, config.go:73-123)."""

    behaviors: BehaviorConfig = field(default_factory=BehaviorConfig)
    cache_size: int = 50_000
    data_center: str = ""
    advertise_address: str = ""          # this node's own grpc address
    picker_hash: str = "fnv1"
    replicas: int = 512
    tpu_max_batch: int = 4096
    tpu_mesh_shards: int = 0             # 0 = single-chip engine
    tpu_platform: str = ""               # force jax platform ("cpu" for tests)
    tpu_table_layout: str = "auto"       # bucket-table storage (engine.py)
    tpu_bg_reclaim: str = "auto"         # background reclamation (engine.py)
    cold_cache_size: int = 0             # tiered cold store (docs/tiering.md)
    # SSD third tier (docs/tiering.md): slab directory (empty = off),
    # byte budget, compaction threshold, writer queue depth.
    ssd_dir: str = ""
    ssd_capacity_bytes: int = 1 << 30
    ssd_compact_ratio: float = 0.5
    ssd_queue_depth: int = 8
    # Crash-safe persistence (docs/persistence.md): snapshot directory
    # (empty = off), delta-flush cadence, compaction threshold, and the
    # graceful-drain budget for GlobalManager.close.
    snapshot_dir: str = ""
    snapshot_interval: float = 5.0
    snapshot_deltas_per_base: int = 64
    drain_timeout: float = 2.0
    # Elastic live resharding (docs/resharding.md): quiesce budget
    # before the cutover aborts, and the post-cutover table audit.
    reshard_freeze_timeout: float = 5.0
    reshard_verify: bool = True
    # GLOBAL collectives data plane (parallel/global_mesh.py): a shared
    # MeshGlobalEngine (mesh-resident peers) + this node's index on it.
    # When set, GLOBAL requests bypass the gRPC hits/broadcast loops.
    global_mesh: Optional[object] = None
    global_mesh_node: int = 0
    tpu_global_mesh_nodes: int = 0       # >0: build own engine at startup
    tpu_global_mesh_node: int = -1       # -1 = auto (jax.process_index())
    tpu_global_mesh_capacity: int = 1 << 16
    loader: Optional[object] = None
    store: Optional[object] = None
    metrics: Optional[Metrics] = None
    peer_credentials: Optional[grpc.ChannelCredentials] = None
    # Fault-tolerant peer path (docs/resilience.md): breaker/backoff/
    # redelivery knobs, plus the optional chaos-test fault injector the
    # peer clients consult before every RPC.
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    fault_injector: Optional[object] = None
    # Multi-region GLOBAL federation (docs/federation.md): inter-region
    # bounded-staleness envelope exchange over the breaker path.  Off by
    # default; requires data_center (setup_daemon_config enforces it).
    federation_enabled: bool = False
    federation_interval: float = 1.0
    federation_batch_limit: int = 1000
    federation_timeout: float = 1.0
    # Guardrailed shard autoscaler (docs/autoscaling.md): closes the
    # telemetry → reshard loop.  Off by default; dry-run by default
    # when on (decisions recorded, nothing actuated).
    autoscale_enabled: bool = False
    autoscale_interval: float = 10.0
    autoscale_windows: int = 3
    autoscale_target_p99_ms: float = 5.0
    autoscale_queue_high: int = 1000
    autoscale_hysteresis: float = 0.5
    autoscale_occupancy_low: float = 0.3
    autoscale_min_shards: int = 1
    autoscale_max_shards: int = 8
    autoscale_cooldown_up: float = 60.0
    autoscale_cooldown_down: float = 300.0
    autoscale_max_per_hour: int = 4
    autoscale_dry_run: bool = True

    @classmethod
    def from_config(cls, conf: Config, advertise_address: str = "", **kw):
        return cls(
            behaviors=conf.behaviors,
            resilience=conf.resilience,
            fault_injector=conf.fault_injector,
            cache_size=conf.cache_size,
            data_center=conf.data_center,
            advertise_address=advertise_address,
            picker_hash=conf.local_picker_hash,
            replicas=conf.replicas,
            tpu_max_batch=conf.tpu_max_batch,
            tpu_mesh_shards=conf.tpu_mesh_shards,
            tpu_platform=conf.tpu_platform,
            tpu_table_layout=conf.tpu_table_layout,
            tpu_bg_reclaim=conf.tpu_bg_reclaim,
            cold_cache_size=conf.cold_cache_size,
            ssd_dir=conf.ssd_dir,
            ssd_capacity_bytes=conf.ssd_capacity_bytes,
            ssd_compact_ratio=conf.ssd_compact_ratio,
            ssd_queue_depth=conf.ssd_queue_depth,
            snapshot_dir=conf.snapshot_dir,
            snapshot_interval=conf.snapshot_interval,
            snapshot_deltas_per_base=conf.snapshot_deltas_per_base,
            drain_timeout=conf.drain_timeout,
            reshard_freeze_timeout=conf.reshard_freeze_timeout,
            reshard_verify=conf.reshard_verify,
            tpu_global_mesh_nodes=conf.tpu_global_mesh_nodes,
            tpu_global_mesh_node=conf.tpu_global_mesh_node,
            tpu_global_mesh_capacity=conf.tpu_global_mesh_capacity,
            loader=conf.loader,
            store=conf.store,
            federation_enabled=conf.federation_enabled,
            federation_interval=conf.federation_interval,
            federation_batch_limit=conf.federation_batch_limit,
            federation_timeout=conf.federation_timeout,
            autoscale_enabled=conf.autoscale_enabled,
            autoscale_interval=conf.autoscale_interval,
            autoscale_windows=conf.autoscale_windows,
            autoscale_target_p99_ms=conf.autoscale_target_p99_ms,
            autoscale_queue_high=conf.autoscale_queue_high,
            autoscale_hysteresis=conf.autoscale_hysteresis,
            autoscale_occupancy_low=conf.autoscale_occupancy_low,
            autoscale_min_shards=conf.autoscale_min_shards,
            autoscale_max_shards=conf.autoscale_max_shards,
            autoscale_cooldown_up=conf.autoscale_cooldown_up,
            autoscale_cooldown_down=conf.autoscale_cooldown_down,
            autoscale_max_per_hour=conf.autoscale_max_per_hour,
            autoscale_dry_run=conf.autoscale_dry_run,
            **kw,
        )


def _make_engine(conf: InstanceConfig):
    import gubernator_tpu.jaxinit  # noqa: F401  (x64 + cache before jax use)
    import jax

    if conf.tpu_platform:
        # GUBER_TPU_PLATFORM: pin the jax platform before any device use
        # (e.g. "cpu" for tests/CI hosts without a TPU).
        jax.config.update("jax_platforms", conf.tpu_platform)
    devices = jax.devices()
    if not jax.config.jax_platforms and devices[0].platform != "tpu":
        # jax itself carries on on the CPU when it finds no chip; a
        # daemon that nobody asked to run there must not.
        raise RuntimeError(
            f"no TPU found (jax picked {devices[0].platform!r}) and no "
            "platform was named: set GUBER_TPU_PLATFORM=cpu (or "
            "JAX_PLATFORMS=cpu) to serve from the CPU backend on purpose"
        )
    if conf.tpu_mesh_shards > 1:
        from gubernator_tpu.parallel.mesh_engine import MeshTickEngine, make_mesh

        if conf.cold_cache_size:
            log.warning(
                "GUBER_COLD_CACHE_SIZE is not supported by the sharded "
                "mesh engine yet; tiering disabled"
            )
        if conf.ssd_dir:
            # Hard error (setup_daemon_config rejects this combination
            # too): a silently absent third tier is a robustness trap —
            # the operator sized the deployment around capacity the
            # engine never had.
            raise ValueError(
                "GUBER_SSD_DIR is not supported by the sharded mesh "
                "engine (GUBER_TPU_MESH_SHARDS > 1): the SSD tier "
                "hangs off the single-chip cold store; unset one"
            )
        if len(devices) < conf.tpu_mesh_shards:
            raise ValueError(
                f"GUBER_TPU_MESH_SHARDS={conf.tpu_mesh_shards} but only "
                f"{len(devices)} {devices[0].platform} device(s) are "
                "visible to this process"
            )
        devices = devices[: conf.tpu_mesh_shards]
        local_cap = max(1, conf.cache_size // len(devices))
        return MeshTickEngine(
            mesh=make_mesh(devices),
            local_capacity=local_cap,
            max_batch=conf.tpu_max_batch,
            store=conf.store,
            table_layout=conf.tpu_table_layout,
        )
    from gubernator_tpu.ops.engine import TickEngine

    bg = {"auto": None, "on": True, "off": False}[conf.tpu_bg_reclaim]
    ssd = None
    if conf.ssd_dir and conf.cold_cache_size > 0:
        from gubernator_tpu.tiering import SsdStore

        ssd = SsdStore(
            conf.ssd_dir,
            capacity_bytes=conf.ssd_capacity_bytes,
            compact_ratio=conf.ssd_compact_ratio,
            queue_depth=conf.ssd_queue_depth,
            metrics=conf.metrics,
        )
    return TickEngine(
        capacity=conf.cache_size,
        max_batch=conf.tpu_max_batch,
        store=conf.store,
        table_layout=conf.tpu_table_layout,
        bg_reclaim=bg,
        cold_capacity=conf.cold_cache_size,
        ssd=ssd,
    )


class V1Instance:
    """One service instance: engine + tick loop + pickers + GLOBAL manager.

    Create inside a running event loop (the GLOBAL manager starts its asyncio
    tasks immediately, like the reference's ``NewV1Instance`` spawning its
    loops, gubernator.go:115-148) — or via :meth:`create` which also runs the
    Loader restore.
    """

    def __init__(self, conf: InstanceConfig, engine=None):
        self.conf = conf
        self.log = log
        self.metrics = conf.metrics or Metrics()
        self.engine = engine if engine is not None else _make_engine(conf)
        # The window fills to the DEVICE program width by default, not
        # the peer-protocol BatchLimit: the device tick amortizes best
        # when several callers' batches coalesce into one program
        # invocation (the reference's worker pool has no analogous cap —
        # it drains whatever queued, workers.go:125-147).  An operator
        # who explicitly set GUBER_BATCH_LIMIT — even to the reference
        # default of 1000 — caps the window with it.
        window_limit = (
            conf.behaviors.batch_limit
            if conf.behaviors.batch_limit_set
            else conf.tpu_max_batch
        )
        self.tick_loop = TickLoop(
            self.engine,
            batch_wait=conf.behaviors.batch_wait,
            batch_limit=window_limit,
            metrics=self.metrics,
        )
        # Zero-copy ingest (docs/architecture.md): the transport's
        # wire→columns decode lands in these preallocated slabs instead
        # of fresh per-batch allocations; the tick loop releases each
        # slab once the engine has packed it.  Sized to the public API
        # batch cap; slab count covers the tick pipeline depth plus
        # decode concurrency (GUBER_INGEST_ARENA_SLABS, 0 = off).
        from gubernator_tpu.config import env_knob
        from gubernator_tpu.ops.reqcols import ColumnArena

        try:
            slabs = env_knob("GUBER_INGEST_ARENA_SLABS", 8, parse=int)
        except ValueError:
            slabs = 8
        try:
            fallback_limit = env_knob(
                "GUBER_INGEST_FALLBACK_LIMIT", 32, parse=int)
        except ValueError:
            fallback_limit = 32
        self.ingest_arena = (
            ColumnArena(MAX_BATCH_SIZE, slabs=slabs,
                        fallback_limit=fallback_limit)
            if slabs > 0 else None
        )
        # Calls through the raw-bytes edge, and those of them that the
        # two native passes answered (count_edge_call; /debug/state).
        self.metric_edge_calls = 0
        self.metric_edge_native_calls = 0
        # Multi-process streaming edge (docs/edge.md): attached by the
        # daemon when GUBER_EDGE_WORKERS > 0; closed before the tick
        # loop so in-flight shm windows resolve while it still runs.
        self.edge_plane = None
        hash_fn = HASH_FUNCTIONS[conf.picker_hash]
        self._standalone = True  # no peers installed yet; see set_peers
        self.local_picker: ReplicatedConsistentHash[PeerClient] = (
            ReplicatedConsistentHash(hash_fn, conf.replicas)
        )
        self.region_picker: RegionPicker[PeerClient] = RegionPicker(
            hash_fn, conf.replicas
        )
        self.global_mgr = GlobalManager(
            self, conf.behaviors, self.metrics, resilience=conf.resilience
        )
        # Inter-region federation (docs/federation.md): constructed only
        # when GUBER_FEDERATION_ENABLED is set AND this node knows its
        # own datacenter — the transport rejects FederationSync frames
        # (and MULTI_REGION items, _get_rate_limits) when None.  Wired
        # into the GlobalManager so every owner-side GLOBAL update feeds
        # the inter-region pending buffers.
        self.federation = None
        if conf.federation_enabled and conf.data_center:
            from gubernator_tpu.federation import FederationManager

            self.federation = FederationManager(self, metrics=self.metrics)
        self.global_mgr.federation = self.federation
        # GLOBAL collectives data plane: use the shared engine if provided,
        # else build one when GUBER_TPU_GLOBAL_MESH_NODES asks for it.
        self.global_mesh = conf.global_mesh
        if self.global_mesh is None and conf.tpu_global_mesh_nodes > 0:
            from gubernator_tpu.parallel.global_mesh import (
                MeshGlobalEngine,
                make_global_mesh,
            )

            self.global_mesh = MeshGlobalEngine(
                mesh=make_global_mesh(conf.tpu_global_mesh_nodes),
                capacity=conf.tpu_global_mesh_capacity,
                max_batch=conf.tpu_max_batch,
                min_reconcile_ms=int(conf.behaviors.global_sync_wait * 500),
            )
            if conf.global_mesh_node == 0 and conf.tpu_global_mesh_node != 0:
                # Env-configured mode: this node's identity on the mesh is
                # its jax process index (multi-host meshes have one service
                # process per host); -1 means exactly that auto-default.
                import gubernator_tpu.jaxinit  # noqa: F401
                import jax

                conf.global_mesh_node = (
                    jax.process_index()
                    if conf.tpu_global_mesh_node < 0
                    else conf.tpu_global_mesh_node
                )
        self._mesh_task: Optional[asyncio.Task] = None
        if self.global_mesh is not None:
            self._mesh_task = asyncio.create_task(
                self._mesh_reconcile_loop(), name="global-mesh-reconcile"
            )
        # Doomed-peer shutdowns and ring-change ownership transfers run
        # as tasks (set_peers is sync); tracked here so close() awaits
        # them instead of abandoning work (and so tests can assert no
        # pending-task warnings).
        self._peer_shutdown_tasks: set = set()
        self._transfer_tasks: set = set()
        # Per-item forward tasks (_async_request): the dispatch loop
        # normally awaits each one, but an exception out of an EARLIER
        # await in _get_rate_limits would abandon the rest mid-flight —
        # tracked + done-callback-logged (the doomed-peer pattern) so no
        # forward ever dies silently, and close() can await stragglers.
        self._forward_tasks: set = set()
        # Cooperative quota leases (docs/leases.md): mints signed
        # TTL-bounded budget delegations, reconciles consumption as
        # batched engine work, and degrades to cheap TTL extension when
        # the tick loop reports pressure.  Always constructed — with
        # GUBER_LEASE_ENABLED=0 every grant is declined, which clients
        # read as "no lease tier here".
        from gubernator_tpu.leases import LeaseManager

        self.lease_mgr = LeaseManager(
            self.engine, tick_loop=self.tick_loop, metrics=self.metrics,
        )
        # Elastic live resharding (docs/resharding.md): the n→m
        # transition coordinator over this instance's engine + tick
        # loop.  The transition journal shares the snapshot directory;
        # peer breakers gate the cutover (a mid-transfer peer death
        # aborts rather than cutting over blind).
        from gubernator_tpu.parallel.reshard import ReshardCoordinator
        from gubernator_tpu.persistence import TransitionLog

        self.reshard_coord = ReshardCoordinator(
            self.engine,
            tick_loop=self.tick_loop,
            transition_log=TransitionLog(conf.snapshot_dir or None),
            breaker_check=lambda: any(
                p.breaker.is_open() for p in self.get_peer_list()),
            global_engine=self.global_mesh,
            # Reshard × federation interlock (docs/federation.md): no
            # envelope may be compacted from half-relayouted owner
            # state — sends pause for FREEZE→CUTOVER.
            federation=self.federation,
            metrics=self.metrics,
            freeze_timeout=conf.reshard_freeze_timeout,
            verify=conf.reshard_verify,
        )
        # Guardrailed shard autoscaler (docs/autoscaling.md): closes the
        # telemetry → reshard loop.  Constructed and started by
        # create() when enabled (spawn_supervised needs a running event
        # loop); None otherwise so /debug/autoscaler can answer 404.
        self.autoscaler = None
        # Crash-safe persistence (docs/persistence.md): wired by create().
        self._snapshot_writer = None
        self.restore_stats: dict = {}
        self._closed = False

    @classmethod
    async def create(cls, conf: InstanceConfig, engine=None) -> "V1Instance":
        inst = cls(conf, engine)
        if conf.loader is not None:
            # Columnar Loaders (v2) restore without dict materialization.
            if hasattr(conf.loader, "load_columns") and hasattr(
                inst.engine, "load_columns"
            ):
                snap = conf.loader.load_columns()
                if snap is not None:
                    inst.engine.load_columns(snap)
            else:
                items = conf.loader.load()
                inst.engine.load_items(list(items))
        # The snapshot writer needs the columnar export and its dirty
        # deltas, which the sharded engine lacks.
        if conf.snapshot_dir and hasattr(inst.engine, "export_columns"):
            await inst._start_persistence()
        # Crash-mid-cutover detection (docs/resharding.md): a begin
        # record with no terminal record means the process died inside a
        # reshard transition — the snapshot just restored (never mutated
        # mid-flight) is authoritative; count and clear the stale
        # journal.
        from gubernator_tpu.persistence import check_interrupted

        rec = check_interrupted(inst.reshard_coord.transition_log)
        if rec is not None:
            inst.reshard_coord.record_interrupted(rec)
        if conf.autoscale_enabled:
            inst._start_autoscaler()
        return inst

    async def _start_persistence(self) -> None:
        """Restore base + deltas from the snapshot store (corrupt tails
        are counted, never fatal; ``load_columns`` TTL-expires stale
        rows), then start the supervised delta-flush loop.  Runs before
        the daemon flips ready — a restoring node answers 503 on
        /readyz, not fresh-bucket allows."""
        from gubernator_tpu.persistence import SnapshotStore, SnapshotWriter

        store = SnapshotStore(self.conf.snapshot_dir)
        loop = asyncio.get_running_loop()
        result = await loop.run_in_executor(None, store.load)
        for snap in result.snapshots:
            await loop.run_in_executor(None, self.engine.load_columns, snap)
        self.restore_stats = {
            "generation": result.generation,
            "restored_items": result.items,
            "delta_records": result.delta_records,
            "corrupt_records": result.corrupt_records,
            "manifest_missing": result.manifest_missing,
        }
        if result.corrupt_records:
            self.metrics.snapshot_corrupt_records.inc(result.corrupt_records)
            self.log.warning(
                "snapshot restore skipped %d corrupt/truncated records "
                "(kept the last good prefix)", result.corrupt_records,
            )
        if result.items:
            self.metrics.snapshot_restored_items.inc(result.items)
            self.log.info(
                "restored %d bucket rows from %s (generation %d, %d "
                "delta records)", result.items, self.conf.snapshot_dir,
                result.generation, result.delta_records,
            )
        self._snapshot_writer = SnapshotWriter(
            self.engine, store,
            interval=self.conf.snapshot_interval,
            deltas_per_base=self.conf.snapshot_deltas_per_base,
            metrics=self.metrics,
        )
        self._snapshot_writer.start()

    # ------------------------------------------------------------------
    # Public API: GetRateLimits
    # ------------------------------------------------------------------
    async def get_rate_limits(
        self, requests: Sequence[RateLimitRequest]
    ) -> List[RateLimitResponse]:
        """The 3-way dispatch (gubernator.go:183-295); responses in request
        order."""
        if len(requests) > MAX_BATCH_SIZE:
            self.metrics.check_error_counter.labels(error="Request too large").inc()
            raise BatchTooLargeError(
                f"Requests.RateLimits list too large; max size is '{MAX_BATCH_SIZE}'"
            )
        self.metrics.concurrent_checks.inc()
        t0 = time.perf_counter()
        try:
            with tracing.maybe_span(
                "V1Instance.GetRateLimits", {"batch.size": len(requests)}
            ):
                return await self._get_rate_limits(requests)
        finally:
            self.metrics.concurrent_checks.dec()
            self.metrics.func_duration.labels(
                name="V1Instance.GetRateLimits"
            ).observe(time.perf_counter() - t0)

    async def _get_rate_limits(
        self, requests: Sequence[RateLimitRequest]
    ) -> List[RateLimitResponse]:
        created_at = timeutil.now_ms()
        out: List[Optional[RateLimitResponse]] = [None] * len(requests)
        local_idx: List[int] = []
        mesh_idx: List[int] = []       # GLOBAL over the collectives plane
        global_idx: List[tuple] = []   # (i, owner_addr)
        forward: List[tuple] = []      # (i, peer, req, key)

        for i, req in enumerate(requests):
            key = req.hash_key()
            if req.unique_key == "":
                self.metrics.check_error_counter.labels(error="Invalid request").inc()
                out[i] = RateLimitResponse(error="field 'unique_key' cannot be empty")
                continue
            if req.name == "":
                self.metrics.check_error_counter.labels(error="Invalid request").inc()
                out[i] = RateLimitResponse(error="field 'namespace' cannot be empty")
                continue
            if invalid_algorithm_mask(int(req.algorithm)):
                # Reject unknown enum values here: past the edge, the
                # kernels' branchless per-lane dispatch would silently
                # run them as token-bucket (algos/__init__.py).
                self.metrics.check_error_counter.labels(error="Invalid request").inc()
                out[i] = RateLimitResponse(error=algorithm_error(req.algorithm))
                continue
            if has_behavior(req.behavior, Behavior.MULTI_REGION):
                # Edge validation (docs/federation.md): past this point
                # MULTI_REGION is a silent no-op bit, so a node that
                # cannot federate must say so per item rather than
                # quietly serving region-local answers forever.
                if self.federation is None:
                    self.metrics.check_error_counter.labels(
                        error="Invalid request").inc()
                    out[i] = RateLimitResponse(
                        error="Behavior.MULTI_REGION requires "
                        "GUBER_DATA_CENTER and GUBER_FEDERATION_ENABLED "
                        "on this node"
                    )
                    continue
                # MULTI_REGION rides the GLOBAL plane inside the region:
                # region-local answer now, inter-region envelope later.
                req.behavior = set_behavior(req.behavior, Behavior.GLOBAL, True)
                if self.federation.is_degraded():
                    # A peer region is unreachable: this answer may
                    # over-admit up to the staleness budget.
                    self.metrics.federation_degraded_answers.inc()
            if req.created_at is None or req.created_at == 0:
                req.created_at = created_at
            if self.conf.behaviors.force_global:
                req.behavior = set_behavior(req.behavior, Behavior.GLOBAL, True)

            if self.global_mesh is not None and has_behavior(
                req.behavior, Behavior.GLOBAL
            ):
                # Mesh-resident GLOBAL: ownership is the slot range on the
                # device mesh, not the consistent-hash ring; every node
                # answers from its replica and reconciles via collectives.
                mesh_idx.append(i)
                continue

            peer = self.get_peer(key)
            if peer is None or peer.info.is_owner:
                local_idx.append(i)
            elif has_behavior(req.behavior, Behavior.GLOBAL):
                if peer.breaker.is_open():
                    # Degraded GLOBAL mode: the local answer below is the
                    # partition-tolerant fallback — count it so operators
                    # can see how much traffic runs on stale state.
                    self.metrics.degraded_answers.inc()
                global_idx.append((i, peer.info.grpc_address))
            else:
                forward.append((i, peer, req, key))

        # Local items: one tick-loop submission for the whole call.
        locals_done = None
        if local_idx:
            locals_done = self._submit_local(
                [requests[i] for i in local_idx], is_owner=True
            )

        # GLOBAL non-owner items: answer from local state, reconcile async.
        globals_done = None
        if global_idx:
            globals_done = asyncio.ensure_future(
                self._get_global_rate_limits(
                    [requests[i] for i, _ in global_idx]
                )
            )

        # GLOBAL items on the mesh data plane: one device tick, no RPC.
        mesh_done = None
        if mesh_idx:
            mesh_reqs = [requests[i] for i in mesh_idx]
            mesh_done = asyncio.get_running_loop().run_in_executor(
                None,
                lambda: self.global_mesh.process(
                    mesh_reqs, self.conf.global_mesh_node
                ),
            )

        # Forwarded items: per-item task with retry/ownership-reresolution,
        # retained and supervised (G003): tracked set + logged exceptions.
        fwd_tasks = [
            self._spawn_forward(peer, req, key)
            for _, peer, req, key in forward
        ]

        if locals_done is not None:
            for i, resp in zip(local_idx, await locals_done):
                out[i] = resp
        if globals_done is not None:
            for (i, owner), resp in zip(global_idx, await globals_done):
                resp.metadata = {"owner": owner}
                out[i] = resp
        if mesh_done is not None:
            for i, resp in zip(mesh_idx, await mesh_done):
                self.metrics.getratelimit_counter.labels(calltype="global").inc()
                if resp.status == Status.OVER_LIMIT:
                    self.metrics.over_limit_counter.inc()
                out[i] = resp
        for (i, _, _, _), t in zip(forward, fwd_tasks):
            out[i] = await t
        return out  # type: ignore[return-value]

    def columns_fast_path_ok(self) -> bool:
        """Whether GetRateLimits may run wire→columns→device with no
        per-request objects: requires every key to be local (standalone —
        an empty peer set, or one containing only this node's own
        entry, which discovery type "none" installs), no server-forced
        GLOBAL, no Store (read-through takes request objects), and an
        engine speaking columns.  The transport additionally falls back
        per batch when an item carries GLOBAL behavior, metadata (trace
        context), or a validation error."""
        return (
            self._standalone
            and self.global_mesh is None
            and not self.conf.behaviors.force_global
            and self.conf.store is None
            and hasattr(self.engine, "submit_cols")
        )

    async def get_rate_limits_columns(self, cols, deadline: float = None,
                                      over_from_encode: bool = False,
                                      edge_call=None):
        """Columnar GetRateLimits (the fast path; see
        columns_fast_path_ok).  Returns ``((5, n) matrix, errors)`` in
        request order; the transport writes wire responses straight from
        the matrix.  ``deadline`` is the batch's absolute admission
        deadline stamped at the serving edge (docs/overload.md);
        ``over_from_encode`` and ``edge_call`` as in
        :meth:`_columns_tick`."""
        if len(cols) > MAX_BATCH_SIZE:
            self.metrics.check_error_counter.labels(error="Request too large").inc()
            raise BatchTooLargeError(
                f"Requests.RateLimits list too large; max size is '{MAX_BATCH_SIZE}'"
            )
        return await self._columns_tick(
            cols, deadline=deadline, over_from_encode=over_from_encode,
            edge_call=edge_call)

    async def _columns_tick(self, cols, public: bool = True,
                            deadline: float = None,
                            over_from_encode: bool = False,
                            edge_call=None):
        """One tick-loop submission for a columnar batch + metrics.

        ``over_from_encode``: the caller encodes an answer without
        per-item errors with ``fastwire.encode_resp`` and adds the
        over-limit count that pass returns to ``over_limit_counter``
        itself; only an answer with errors, which it does not encode,
        is counted here.

        ``public`` marks the public GetRateLimits edge, which alone
        carries the concurrent-checks gauge and the GetRateLimits
        duration family (reference gubernator.go:188-199); the peer
        relay edge records only the local-handling metrics its object
        path does (_submit_local).  It also picks the admission class:
        relayed peer batches outrank client traffic under overload.

        ``edge_call`` (``flightrec.edge_call()``, or None) is paused for
        the wait on the tick: the handler's time is what it runs on the
        event loop."""
        if public:
            self.metrics.concurrent_checks.inc()
        t0 = time.perf_counter()
        try:
            ticked = asyncio.wrap_future(
                self.tick_loop.submit_columns(
                    cols, deadline=deadline,
                    klass=CLASS_CLIENT if public else CLASS_PEER,
                )
            )
            if edge_call is not None:
                edge_call.pause()
            mat, errors = await ticked
            if edge_call is not None:
                edge_call.resume()
            self.metrics.getratelimit_counter.labels(calltype="local").inc(
                len(cols) - len(errors)
            )
            self._count_algorithms(cols.algorithm, cols.algo_hist)
            if errors or not over_from_encode:
                from gubernator_tpu.ops.engine import masked_over_limit

                over = masked_over_limit(mat, errors)
                if over:
                    self.metrics.over_limit_counter.inc(over)
            return mat, errors
        finally:
            dt = time.perf_counter() - t0
            if public:
                self.metrics.concurrent_checks.dec()
                self.metrics.func_duration.labels(
                    name="V1Instance.GetRateLimits"
                ).observe(dt)
            self.metrics.func_duration.labels(
                name="V1Instance.getLocalRateLimit"
            ).observe(dt)

    def _submit_local(self, reqs: List[RateLimitRequest], *, is_owner: bool,
                      klass: int = CLASS_CLIENT):
        """Send a batch through the tick loop; wraps the future for await and
        handles GLOBAL owner-side queueing + metrics.  The batch inherits
        its most urgent member's propagated deadline (docs/overload.md)."""

        async def run():
            t0 = time.perf_counter()
            resps = await asyncio.wrap_future(self.tick_loop.submit(
                reqs, deadline=batch_deadline(reqs), klass=klass))
            self.metrics.func_duration.labels(
                name="V1Instance.getLocalRateLimit"
            ).observe(time.perf_counter() - t0)
            self._count_algorithms([r.algorithm for r in reqs])
            for req, resp in zip(reqs, resps):
                if has_behavior(req.behavior, Behavior.GLOBAL):
                    self.global_mgr.queue_update(req)
                if is_owner:
                    self.metrics.getratelimit_counter.labels(calltype="local").inc()
                    if resp.status == Status.OVER_LIMIT:
                        self.metrics.over_limit_counter.inc()
            return resps

        return asyncio.ensure_future(run())

    def _count_algorithms(self, algorithms, hist=None) -> None:
        """Per-algorithm traffic split (gubernator_tpu_algorithm_requests).

        ``algorithms`` is host-side (a list or the batch's numpy column —
        never a device value).  Out-of-range lanes were rejected with
        per-item errors at the edge and are skipped here.  ``hist`` is
        the same count where the native decode already made it
        (``ReqColumns.algo_hist``): no pass over the column then.
        """
        if hist is None:
            a = np.asarray(algorithms, np.int64)
            ok = (a >= 0) & (a <= int(ALGORITHM_MAX))
            hist = np.bincount(
                a[ok], minlength=int(ALGORITHM_MAX) + 1).tolist()
        for v, c in enumerate(hist):
            if c:
                self.metrics.algorithm_requests.labels(
                    algorithm=Algorithm(v).name.lower()
                ).inc(c)

    def count_edge_call(self, native: bool) -> None:
        """One call through the raw-bytes edge (transport/daemon.py
        ``_raw_columns_edge``); ``native`` when the two native passes
        answered it, decode and encode, with no fallback between."""
        self.metric_edge_calls += 1
        self.metric_edge_native_calls += native
        self.metrics.edge_calls.labels(
            path="native" if native else "fallback").inc()

    async def apply_local(
        self, reqs: List[RateLimitRequest]
    ) -> List[RateLimitResponse]:
        """Apply requests to the local engine with no routing/queueing — the
        GLOBAL manager's state re-read path (global.go:241-249).  Peer
        admission class: reconcile traffic outranks client traffic."""
        t0 = time.perf_counter()
        try:
            return await asyncio.wrap_future(self.tick_loop.submit(
                reqs, deadline=batch_deadline(reqs), klass=CLASS_PEER))
        finally:
            self.metrics.func_duration.labels(
                name="V1Instance.getLocalRateLimit"
            ).observe(time.perf_counter() - t0)

    async def _get_global_rate_limits(
        self, reqs: List[RateLimitRequest]
    ) -> List[RateLimitResponse]:
        """Non-owner GLOBAL path (gubernator.go:395-421): answer from local
        state as if we owned it, then queue the hits for reconciliation.
        Span parity: gubernator.go:396 getGlobalRateLimit."""
        sp = tracing.current_span()
        if sp is not None:
            sp.add_event("getGlobalRateLimit", {"count": len(reqs)})
        clones = []
        for r in reqs:
            c = RateLimitRequest(**vars(r))
            c.behavior = set_behavior(c.behavior, Behavior.NO_BATCHING, True)
            c.behavior = set_behavior(c.behavior, Behavior.GLOBAL, False)
            clones.append(c)
        resps = await asyncio.wrap_future(self.tick_loop.submit(
            clones, deadline=batch_deadline(clones)))
        for r in reqs:
            self.global_mgr.queue_hit(r)
            self.metrics.getratelimit_counter.labels(calltype="global").inc()
        return resps

    async def _mesh_reconcile_loop(self) -> None:
        """Drive the collective reconcile at the GlobalSyncWait cadence
        (global.go:193-283's loops, collapsed into one device step).  Every
        mesh-resident instance runs this; the engine's min-interval gate
        dedupes concurrent drivers."""
        loop = asyncio.get_running_loop()
        while not self._closed:
            await asyncio.sleep(self.conf.behaviors.global_sync_wait)
            try:
                await loop.run_in_executor(None, self._mesh_reconcile_once)
            except Exception:
                self.log.exception("global mesh reconcile failed")

    def _mesh_reconcile_once(self) -> None:
        """One cadence tick: reconcile + export the engine's step/dispatch
        counters to this daemon's registry (the engine is shared across
        co-resident daemons, so each driver exports only the deltas of
        the steps its own call performed)."""
        eng = self.global_mesh
        before = (eng.metric_reconcile_dispatches, eng.metric_dense_fallbacks)
        if not eng.maybe_reconcile():
            return
        self.metrics.mesh_reconcile_count.inc()
        self.metrics.mesh_reconcile_dispatches.inc(
            max(0, eng.metric_reconcile_dispatches - before[0]))
        self.metrics.mesh_dense_fallbacks.inc(
            max(0, eng.metric_dense_fallbacks - before[1]))

    def _spawn_forward(
        self, peer: PeerClient, req: RateLimitRequest, key: str
    ) -> "asyncio.Task":
        """Spawn one supervised forward task (the doomed-peer pattern,
        set_peers): handle retained in ``_forward_tasks`` and failures
        logged on completion, so a forward abandoned by an exception
        earlier in the dispatch loop is never GC'd mid-flight with a
        swallowed error."""
        t = asyncio.ensure_future(self._async_request(peer, req, key))
        self._forward_tasks.add(t)

        def _done(task: "asyncio.Task") -> None:
            self._forward_tasks.discard(task)
            if task.cancelled():
                return
            exc = task.exception()
            if exc is not None:
                self.log.warning(
                    "forwarded request for %r failed: %s", key, exc,
                    exc_info=exc,
                )

        t.add_done_callback(_done)
        return t

    async def _async_request(
        self, peer: PeerClient, req: RateLimitRequest, key: str
    ) -> RateLimitResponse:
        """Forward one item to its owner with decorrelated-jitter backoff
        between attempts (≤ forward_max_attempts retries), fresh owner
        resolution per retry, self-upgrading if ownership moved here
        (gubernator.go:311-391), and breaker-aware degraded fallback for
        GLOBAL keys (docs/resilience.md).  Span parity: gubernator.go:315
        asyncRequest."""
        with tracing.maybe_span(
            "V1Instance.asyncRequest",
            {"ratelimit.key": req.unique_key, "ratelimit.name": req.name,
             "peer": peer.info.grpc_address},
        ):
            return await self._async_request_traced(peer, req, key)

    async def _async_request_traced(
        self, peer: PeerClient, req: RateLimitRequest, key: str
    ) -> RateLimitResponse:
        rconf = self.conf.resilience
        backoff = DecorrelatedJitterBackoff(
            rconf.forward_backoff_base, rconf.forward_backoff_cap
        )
        attempts = 0
        last_err: Optional[Exception] = None

        async def retry(err: Exception) -> None:
            # Decorrelated-jitter sleep, then re-resolve ownership: the
            # peer set may have changed while the RPC was failing (the
            # reference re-resolves too, gubernator.go:311-391 — but with
            # no backoff, hammering a dead peer in a tight loop).
            nonlocal attempts, last_err, peer
            attempts += 1
            last_err = err
            self.metrics.batch_send_retries.inc()
            await asyncio.sleep(backoff.next())
            peer = self.get_peer(key) or peer

        while True:
            if attempts > rconf.forward_max_attempts:
                self.metrics.check_error_counter.labels(error="Peer not connected").inc()
                return RateLimitResponse(
                    error=f"GetPeer() keeps returning peers that are not "
                    f"connected for '{key}': {last_err}"
                )
            # Deadline-aware retry budget (docs/overload.md): once the
            # caller's propagated budget is spent, stop riding the
            # backoff ladder — the client already gave up; answer a
            # retriable error instead of hammering a dead peer.
            if (
                attempts != 0
                and req.deadline is not None
                and time.monotonic() >= req.deadline
            ):
                self.metrics.check_error_counter.labels(
                    error="Deadline exceeded").inc()
                return RateLimitResponse(
                    error=f"deadline budget spent while forwarding "
                    f"'{key}': {last_err}"
                )
            if attempts != 0 and peer.info.is_owner:
                resps = await self._submit_local([req], is_owner=True)
                return resps[0]
            try:
                resp = await peer.get_peer_rate_limit(req)
            except BudgetExhaustedError as e:
                self.metrics.check_error_counter.labels(
                    error="Deadline exceeded").inc()
                return RateLimitResponse(
                    error=f"deadline budget spent while forwarding "
                    f"'{key}': {e}"
                )
            except BreakerOpenError as e:
                if has_behavior(req.behavior, Behavior.GLOBAL):
                    # Degraded mode: the non-owner GLOBAL state is a
                    # serviceable local answer (DRAIN_OVER_LIMIT semantics
                    # ride the behavior bits unchanged); hits queue for
                    # redelivery once the owner recovers.
                    self.metrics.degraded_answers.inc()
                    resp = (await self._get_global_rate_limits([req]))[0]
                    resp.metadata = {
                        "owner": peer.info.grpc_address, "degraded": "true"
                    }
                    return resp
                await retry(e)
                continue
            except grpc.aio.AioRpcError as e:
                if e.code() in (
                    grpc.StatusCode.DEADLINE_EXCEEDED,
                    grpc.StatusCode.CANCELLED,
                    grpc.StatusCode.UNAVAILABLE,
                ):
                    await retry(e)
                    continue
                return RateLimitResponse(
                    error=f"Error while fetching rate limit '{key}' from peer: "
                    f"{e.details()}"
                )
            except Exception as e:
                return RateLimitResponse(
                    error=f"Error while fetching rate limit '{key}' from peer: {e}"
                )
            self.metrics.getratelimit_counter.labels(calltype="forward").inc()
            resp.metadata = {"owner": peer.info.grpc_address}
            return resp

    # ------------------------------------------------------------------
    # Peer API (PeersV1)
    # ------------------------------------------------------------------
    def peer_columns_fast_path_ok(self) -> bool:
        """Whether GetPeerRateLimits may run wire→columns→device: unlike
        the public gate (columns_fast_path_ok) this does NOT require
        standalone — a relayed batch is processed locally regardless of
        ring ownership (the reference's peer side just processes what
        arrives, gubernator.go:497-536).  The transport still falls back
        per batch for GLOBAL/metadata/error items (GLOBAL owner-side
        queueing and trace extraction need request objects)."""
        return (
            self.conf.store is None
            and not self.conf.behaviors.force_global
            and self.global_mesh is None
            and hasattr(self.engine, "submit_cols")
        )

    async def get_peer_rate_limits_columns(self, cols, deadline: float = None,
                                           over_from_encode: bool = False,
                                           edge_call=None):
        """Columnar owner-side handling of a relayed batch (the peer-edge
        twin of get_rate_limits_columns; eligibility per
        peer_columns_fast_path_ok).  Peer admission class: relayed
        reconcile traffic outranks client traffic under overload."""
        if len(cols) > MAX_BATCH_SIZE:
            self.metrics.check_error_counter.labels(error="Request too large").inc()
            raise BatchTooLargeError(
                f"'PeerRequest.rate_limits' list too large; max size is "
                f"'{MAX_BATCH_SIZE}'"
            )
        return await self._columns_tick(
            cols, public=False, deadline=deadline,
            over_from_encode=over_from_encode, edge_call=edge_call)

    async def get_peer_rate_limits(
        self, requests: Sequence[RateLimitRequest]
    ) -> List[RateLimitResponse]:
        """Owner-side handling of relayed batches (gubernator.go:462-539):
        forwarded GLOBAL hits get DRAIN_OVER_LIMIT forced."""
        if len(requests) > MAX_BATCH_SIZE:
            self.metrics.check_error_counter.labels(error="Request too large").inc()
            raise BatchTooLargeError(
                f"'PeerRequest.rate_limits' list too large; max size is "
                f"'{MAX_BATCH_SIZE}'"
            )
        created_at = timeutil.now_ms()
        # Continue the caller's trace: each forwarded request carries W3C
        # TraceContext in its metadata (extracted per request, the
        # reference's prop.Extract at gubernator.go:502-504).
        tracer = tracing.get_tracer()
        traced = tracing.enabled()  # skip span objects entirely when untraced
        spans = []
        for req in requests:
            remote = tracing.extract(req.metadata) if traced else None
            if remote is not None:
                spans.append(tracer.start_detached(
                    "PeersV1.GetPeerRateLimit",
                    {"ratelimit.key": req.unique_key,
                     "ratelimit.name": req.name},
                    parent=remote,
                ))
            if has_behavior(req.behavior, Behavior.GLOBAL):
                req.behavior = set_behavior(
                    req.behavior, Behavior.DRAIN_OVER_LIMIT, True
                )
            if req.created_at is None or req.created_at == 0:
                req.created_at = created_at
        try:
            return await self._submit_local(
                list(requests), is_owner=True, klass=CLASS_PEER)
        finally:
            for s in spans:
                tracer.finish(s)

    async def update_peer_globals(self, updates: Sequence[GlobalUpdate]) -> None:
        """Install owner-pushed GLOBAL state (gubernator.go:425-459).

        Runs in a worker thread: install is device work (and may trigger a
        one-off XLA compile for a new scatter width) — it must not stall the
        event loop.
        """
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.install_globals, list(updates)
        )

    # ------------------------------------------------------------------
    # Cooperative quota leases (docs/leases.md)
    # ------------------------------------------------------------------
    async def lease_grant(self, specs):
        """Mint quota leases: [LeaseSpec] → [Optional[LeaseToken]].
        Delegation is an ordinary batched decision through the tick
        loop (UNDER_LIMIT charges the slice up front; OVER_LIMIT
        declines with None), so grants ride the same admission plane
        as everything else."""
        return await self.lease_mgr.grant(list(specs))

    async def lease_sync(self, syncs):
        """Reconcile lease consumption: [LeaseSync] → [LeaseSyncAck].
        Credit-backs and excess force-charges flow through the tick
        loop in the peer class."""
        return await self.lease_mgr.sync(list(syncs))

    # ------------------------------------------------------------------
    # Elastic live resharding (docs/resharding.md)
    # ------------------------------------------------------------------
    async def reshard(self, new_shards: int) -> dict:
        """Run one n→m transition (admin-triggered via POST
        /debug/reshard, or the autoscaler).  The coordinator's
        freeze/drain/cutover is blocking device + lock work, so it runs
        in a worker thread; the event loop keeps serving the
        shed-with-retriable answers the freeze produces.  A concurrent
        transition returns the coordinator's ``{"result": "busy"}``
        dict — the coordinator lock is the single busy source of truth,
        so the autoscaler and the admin endpoint can never race into a
        double-freeze.  After a committed transition, tracked GLOBAL
        keys re-broadcast through the PR 4 ownership-handoff path so
        any peer holding pre-transition state converges."""
        result = await asyncio.get_running_loop().run_in_executor(
            None, self.reshard_coord.try_reshard, int(new_shards)
        )
        if result.get("result") == "busy":
            return result
        if result.get("outcome") == "committed" and self.global_mgr._owned:
            t = asyncio.get_running_loop().create_task(
                self.global_mgr.transfer_ownership(),
                name="reshard-ownership-rebroadcast",
            )
            self._transfer_tasks.add(t)
            t.add_done_callback(self._transfer_tasks.discard)
        return result

    def reshard_status(self) -> dict:
        """Coordinator phase/outcome snapshot for /debug/state."""
        return self.reshard_coord.status()

    def _start_autoscaler(self) -> None:
        """Construct and start the guardrailed autoscaler
        (docs/autoscaling.md) over this instance's telemetry and
        :meth:`reshard`.  Requires a running event loop (called from
        :meth:`create`); :meth:`close` stops it first."""
        from gubernator_tpu.autoscale import (
            Autoscaler,
            AutoscalePolicy,
            PolicyConfig,
            instance_sampler,
        )

        conf = self.conf
        policy = AutoscalePolicy(PolicyConfig(
            windows=conf.autoscale_windows,
            target_p99_ms=conf.autoscale_target_p99_ms,
            queue_high=conf.autoscale_queue_high,
            hysteresis=conf.autoscale_hysteresis,
            occupancy_low=conf.autoscale_occupancy_low,
            min_shards=conf.autoscale_min_shards,
            max_shards=conf.autoscale_max_shards,
        ))
        self.autoscaler = Autoscaler(
            instance_sampler(self, time.monotonic),
            self.reshard,
            policy=policy,
            interval=conf.autoscale_interval,
            cooldown_up=conf.autoscale_cooldown_up,
            cooldown_down=conf.autoscale_cooldown_down,
            max_per_hour=conf.autoscale_max_per_hour,
            dry_run=conf.autoscale_dry_run,
            metrics=self.metrics,
        )
        self.autoscaler.start()
        self.log.info(
            "autoscaler started (interval=%.1fs, dry_run=%s, shards "
            "[%d, %d])", conf.autoscale_interval, conf.autoscale_dry_run,
            conf.autoscale_min_shards, conf.autoscale_max_shards,
        )

    # ------------------------------------------------------------------
    # Health / peers
    # ------------------------------------------------------------------
    def health_check(self) -> HealthCheckResponse:
        """Aggregate recent per-peer errors (gubernator.go:542-586), plus
        the breaker quorum rule: when more than half of the local picker's
        peers have OPEN circuit breakers this node is partitioned from the
        cluster majority and reports unhealthy (the daemon's /healthz
        returns 503 so orchestrators rotate it out)."""
        errs: List[str] = []
        local_peers = self.local_picker.peers()
        for p in local_peers:
            for msg in p.get_last_err():
                errs.append(f"error returned from local peer.GetLastErr: {msg}")
        region_peers = self.region_picker.peers()
        for p in region_peers:
            for msg in p.get_last_err():
                errs.append(f"error returned from region peer.GetLastErr: {msg}")
        open_breakers = sum(1 for p in local_peers if p.breaker.is_open())
        if local_peers and open_breakers * 2 > len(local_peers):
            errs.append(
                f"{open_breakers}/{len(local_peers)} local peers have open "
                f"circuit breakers"
            )
        return HealthCheckResponse(
            status="unhealthy" if errs else "healthy",
            message="|".join(errs),
            peer_count=len(local_peers) + len(region_peers),
        )

    def occupancy(self) -> dict:
        """Tier occupancy snapshot (docs/tiering.md): device-table fill,
        cold-store size, and shed count — surfaced by the daemon's
        /healthz JSON and mirrored into the Prometheus gauges."""
        eng = self.engine
        return {
            "cache_size": eng.cache_size(),
            "hot_occupancy": round(
                getattr(eng, "hot_occupancy", lambda: 0.0)(), 4
            ),
            "cold_size": getattr(eng, "cold_size", lambda: 0)(),
            "shed_requests": getattr(eng, "metric_shed_requests", 0),
        }

    def set_peers(self, peer_info: Sequence[PeerInfo]) -> None:
        """Install a new peer set (gubernator.go:616-711): reuse existing
        clients, mark our own entry as owner, shut down removed peers."""
        local = self.local_picker.new()
        region = self.region_picker.new()
        replaced: List[PeerClient] = []
        for info in peer_info:
            if info.grpc_address == self.conf.advertise_address:
                info = PeerInfo(
                    grpc_address=info.grpc_address,
                    http_address=info.http_address,
                    datacenter=info.datacenter,
                    is_owner=True,
                )
            if info.datacenter and info.datacenter != self.conf.data_center:
                peer = self.region_picker.get_by_address(info.grpc_address)
                if peer is None:
                    peer = self._new_peer_client(info)
                region.add(peer)
                continue
            peer = self.local_picker.get_by_address(info.grpc_address)
            if peer is not None and peer.info != info:
                replaced.append(peer)  # same address, changed info: re-dial
                peer = None
            if peer is None:
                peer = self._new_peer_client(info)
            local.add(peer)

        old_local, old_region = self.local_picker, self.region_picker
        # Standalone = no peers, or only our own entry (discovery "none"
        # installs self): the columns fast path's gate, recomputed at the
        # sole mutation point so the hot path reads one bool.  Ordering
        # matters: when remote peers arrive, clear the flag BEFORE the
        # picker swap; when they leave, set it AFTER — either way the
        # fast path never sees standalone=True with remote peers live
        # (worst case it conservatively takes the slow path for a beat).
        standalone = all(p.info.is_owner for p in local.peers())
        if not standalone:
            self._standalone = False
        self.local_picker, self.region_picker = local, region
        if standalone:
            self._standalone = True
        if self.federation is not None:
            # Reroute federation channels whose target peer left its
            # region's ring: in-flight records requeue to the pending
            # buffer and rehash to the new remote owner on the next
            # flush instead of retrying a dead address forever.
            self.federation.on_ring_update()

        # Gracefully drain removed (and replaced) peers.
        doomed = replaced + [
            p
            for p in old_local.peers()
            if local.get_by_address(p.info.grpc_address) is None
        ]
        for picker in old_region.pickers().values():
            doomed.extend(
                p
                for p in picker.peers()
                if region.get_by_address(p.info.grpc_address) is None
            )
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return  # no loop (tests building instances synchronously)
        for p in doomed:
            # Tracked, not fire-and-forget: close() awaits these, and a
            # failed shutdown is logged instead of silently swallowed
            # (a bare create_task drops the exception with the task).
            t = loop.create_task(
                self._shutdown_peer(p),
                name=f"peer-shutdown:{p.info.grpc_address}",
            )
            self._peer_shutdown_tasks.add(t)
            t.add_done_callback(self._peer_shutdown_tasks.discard)
        # Ownership handoff: GLOBAL keys we owned whose new owner is a
        # different peer get their accumulated state pushed there (the
        # ring swap must not reset their accounting).  Skipped when no
        # owned keys are tracked — the overwhelmingly common set_peers.
        if self.global_mgr._owned:
            t = loop.create_task(
                self.global_mgr.transfer_ownership(),
                name="ownership-transfer",
            )
            self._transfer_tasks.add(t)
            t.add_done_callback(self._transfer_tasks.discard)

    async def _shutdown_peer(self, peer: PeerClient) -> None:
        try:
            await peer.shutdown()
        except Exception:
            self.log.warning(
                "shutdown of removed peer %s failed",
                peer.info.grpc_address, exc_info=True,
            )

    def _new_peer_client(self, info: PeerInfo) -> PeerClient:
        return PeerClient(
            info,
            behaviors=self.conf.behaviors,
            channel_credentials=self.conf.peer_credentials,
            metrics=self.metrics,
            resilience=self.conf.resilience,
            fault_injector=self.conf.fault_injector,
            self_address=self.conf.advertise_address,
        )

    def get_peer(self, key: str) -> Optional[PeerClient]:
        """Owning peer for a key; None when no peers are set (standalone →
        local processing)."""
        if len(self.local_picker) == 0:
            return None
        return self.local_picker.get(key)

    def get_peer_list(self) -> List[PeerClient]:
        return self.local_picker.peers()

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------
    def attach_edge_plane(self, plane) -> None:
        """Adopt a started :class:`gubernator_tpu.edge.EdgePlane` so
        :meth:`close` tears it down in the right order (before the tick
        loop — its in-flight windows are tick futures over shm views)."""
        self.edge_plane = plane

    async def close(self) -> None:
        """Graceful drain + shutdown (gubernator.go:151-170, extended per
        docs/persistence.md): finish in-flight ring work (ownership
        transfers), flush the GLOBAL buffers under the bounded drain
        deadline, stop peers (awaiting the tracked teardown tasks), write
        the final full base snapshot / run Loader.Save, then stop the
        tick loop and engine."""
        if self._closed:
            return
        self._closed = True
        if self.autoscaler is not None:
            # First out: the controller must not start a transition
            # against an instance that is tearing down.
            await self.autoscaler.stop()
        # Pending ownership transfers need peers and the tick loop alive.
        if self._transfer_tasks:
            await asyncio.gather(
                *list(self._transfer_tasks), return_exceptions=True
            )
        await self.global_mgr.close(drain_timeout=self.conf.drain_timeout)
        if self.federation is not None:
            # After the GLOBAL drain (its final flush may queue the last
            # deltas here) and before peers shut down (the drain sends
            # envelopes through them).
            await self.federation.close(
                drain_timeout=self.conf.drain_timeout)
        if self._mesh_task is not None:
            self._mesh_task.cancel()
            try:
                await self._mesh_task
            except (asyncio.CancelledError, Exception):
                pass
        # Forward tasks abandoned by a failed dispatch loop would outlive
        # the instance; their done-callbacks already log failures.
        if self._forward_tasks:
            await asyncio.gather(
                *list(self._forward_tasks), return_exceptions=True
            )
        # Earlier ring changes spawned doomed-peer shutdowns; await them
        # (each logs its own failure) so no task outlives the instance.
        if self._peer_shutdown_tasks:
            await asyncio.gather(
                *list(self._peer_shutdown_tasks), return_exceptions=True
            )
        for p in set(self.local_picker.peers()) | set(self.region_picker.peers()):
            try:
                await p.shutdown()
            except Exception:
                self.log.warning(
                    "peer %s shutdown failed during close",
                    p.info.grpc_address, exc_info=True,
                )
        if self._snapshot_writer is not None:
            # Final FULL base: graceful shutdown loses zero state.
            await self._snapshot_writer.close(final_base=True)
        if self.conf.loader is not None:
            if hasattr(self.conf.loader, "save_columns") and hasattr(
                self.engine, "export_columns"
            ):
                self.conf.loader.save_columns(self.engine.export_columns())
            elif hasattr(self.conf.loader, "save"):
                self.conf.loader.save(self.engine.export_items())
            else:
                # A columnar-only Loader on the sharded engine, which
                # has no export_columns: a dict of every row is not the
                # save it asked for.
                self.log.warning(
                    "Loader %s saves columns only and %s exports none: "
                    "nothing saved at close",
                    type(self.conf.loader).__name__,
                    type(self.engine).__name__,
                )
        if self.edge_plane is not None:
            # The edge plane's in-flight windows are tick-loop futures
            # holding zero-copy shm views; stop it while the loop can
            # still resolve them (docs/edge.md shutdown ordering).
            await asyncio.get_running_loop().run_in_executor(
                None, self.edge_plane.close
            )
        self.tick_loop.close()
        if hasattr(self.engine, "close"):
            self.engine.close()
        self.metrics.cache_size.set(self.engine.cache_size())
