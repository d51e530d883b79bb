"""Daemon: gRPC server + HTTP-JSON gateway + metrics + lifecycle.

The transport shell (reference ``daemon.go``): one grpc.aio server exposing
``V1`` and ``PeersV1``, an aiohttp JSON gateway mirroring grpc-gateway's
snake_case marshaling (``daemon.go:245-261``), ``/metrics`` in Prometheus
text format, an optional plaintext status listener when mTLS is on
(``daemon.go:305-334``), TLS/mTLS incl. AutoTLS, discovery-pool wiring
(``daemon.go:208-243``), and ``wait_for_connect`` readiness
(``daemon.go:451-488``).
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import List, Optional, Sequence

import grpc
import grpc.aio
from aiohttp import web
from google.protobuf import json_format

from gubernator_tpu.admission import (
    DEADLINE_METADATA_KEY,
    deadline_from_header,
)
from gubernator_tpu.config import DaemonConfig, env_knob
from gubernator_tpu.ops.reqcols import IngestOverloadError
from gubernator_tpu.pb import gubernator_pb2 as pb
from gubernator_tpu.pb import peers_pb2 as peers_pb
from gubernator_tpu.resilience.supervisor import spawn_supervised
from gubernator_tpu.service.instance import (
    BatchTooLargeError,
    InstanceConfig,
    V1Instance,
)
from gubernator_tpu.transport import convert, fastwire
from gubernator_tpu.transport.grpc_api import V1Stub, peers_handler, v1_handler
from gubernator_tpu.transport.tlsutil import TLSBundle, setup_tls
from gubernator_tpu.types import GlobalUpdate, PeerInfo
from gubernator_tpu.utils import flightrec, tracing
from gubernator_tpu.utils.metrics import TICK_BRANCHES, Metrics

log = logging.getLogger("gubernator.daemon")

MAX_RECV_BYTES = 1024 * 1024  # 1 MiB, daemon.go:120-126


class _StatsInterceptor(grpc.aio.ServerInterceptor):
    """Per-RPC count/duration metrics (reference grpc_stats.go:41-121)."""

    def __init__(self, metrics: Metrics):
        self.metrics = metrics

    async def intercept_service(self, continuation, handler_call_details):
        handler = await continuation(handler_call_details)
        if handler is None or handler.unary_unary is None:
            return handler
        method = handler_call_details.method
        inner = handler.unary_unary
        metrics = self.metrics

        async def wrapped(request, context):
            t0 = time.perf_counter()
            failed = False
            try:
                return await inner(request, context)
            except Exception:
                failed = True
                raise
            finally:
                dt = time.perf_counter() - t0
                metrics.grpc_request_duration.labels(method=method).observe(dt)
                # Histogram family with log-spaced buckets: the Summary
                # above keeps reference-catalog parity; the histogram is
                # what per-method p99 dashboards and exemplar linkage
                # read (docs/observability.md).
                metrics.grpc_duration_hist.labels(method=method).observe(dt)
                metrics.grpc_request_counts.labels(
                    status="failed" if failed else "success", method=method
                ).inc()

        return grpc.unary_unary_rpc_method_handler(
            wrapped,
            request_deserializer=handler.request_deserializer,
            response_serializer=handler.response_serializer,
        )


class _TraceInterceptor(grpc.aio.ServerInterceptor):
    """Server span per RPC, continuing a caller's trace when the gRPC
    request metadata carries a W3C ``traceparent`` header (the reference's
    otelgrpc server stats handler, daemon.go:125)."""

    async def intercept_service(self, continuation, handler_call_details):
        handler = await continuation(handler_call_details)
        if handler is None or handler.unary_unary is None:
            return handler
        if not tracing.enabled():  # per-RPC check: dynamic enable still works
            return handler
        method = handler_call_details.method
        parent = tracing.extract(
            {k: v for k, v in (handler_call_details.invocation_metadata or ())
             if isinstance(v, str)}
        )
        inner = handler.unary_unary

        async def wrapped(request, context):
            with tracing.maybe_span(f"grpc.recv{method.replace('/', '.')}",
                                    parent=parent):
                return await inner(request, context)

        return grpc.unary_unary_rpc_method_handler(
            wrapped,
            request_deserializer=handler.request_deserializer,
            response_serializer=handler.response_serializer,
        )


async def _parse_pb(msg_type, raw: bytes, context):
    """Protobuf-parse raw request bytes; malformed input aborts with
    INVALID_ARGUMENT (the status a deserializer failure produced before
    the pass-through deserializers moved parsing into the servicers —
    without this, DecodeError would surface as UNKNOWN plus a server
    traceback per bad request)."""
    try:
        return msg_type.FromString(raw)
    except Exception as e:
        await context.abort(
            grpc.StatusCode.INVALID_ARGUMENT,
            f"failed to parse {msg_type.DESCRIPTOR.name}: {e}",
        )


def _item_responses(mat, errs):
    """Fallback per-item pb responses when a columnar batch carried
    per-item engine errors (rare; carries strings)."""
    status, limit, remaining, reset = (mat[r].tolist() for r in range(4))
    return [
        pb.RateLimitResp(error=errs[i])
        if i in errs
        else pb.RateLimitResp(
            status=status[i],
            limit=limit[i],
            remaining=remaining[i],
            reset_time=reset[i],
        )
        for i in range(len(status))
    ]


def _edge_deadline(context, default_timeout: float):
    """The absolute local admission deadline for one inbound RPC
    (docs/overload.md): an explicit ``guber-deadline-ms`` budget header
    wins (peer hops propagate remaining budget this way, clock-skew
    free), else the caller's own gRPC deadline, else the
    GUBER_REQUEST_TIMEOUT default.  None (never shed) only when the
    default is 0."""
    now = time.monotonic()
    value = None
    try:
        for k, v in context.invocation_metadata() or ():
            if k == DEADLINE_METADATA_KEY:
                value = v
                break
    except Exception:
        pass
    d = deadline_from_header(value, now)
    if d is not None:
        return d
    try:
        rem = context.time_remaining()
    except Exception:
        rem = None
    if rem is not None:
        return now + rem
    if default_timeout > 0:
        return now + default_timeout
    return None


def _sync_arena_metrics(arena, metrics) -> None:
    """Mirror the arena's plain-int fallback counter into the
    gubernator_tpu_arena_fallbacks family (delta sync, the tick loop's
    engine-counter pattern)."""
    if arena is None:
        return
    synced = getattr(arena, "_synced_fallbacks", 0)
    if arena.metric_fallbacks > synced:
        metrics.arena_fallbacks.inc(arena.metric_fallbacks - synced)
        arena._synced_fallbacks = arena.metric_fallbacks


async def _raw_columns_edge(raw, context, instance, gate_ok, tick,
                            msg_type, deadline=None, edge_call=None):
    """The shared raw-bytes fast path of both rate-limit edges: native
    wire parse → columns → device tick → native wire encode, with no
    protobuf objects.  Returns ``(result, msg)``: ``result`` is the
    response (bytes, or a per-item response list for the error
    fallback) or None when the batch needs the object path; ``msg`` is
    the protobuf message if one was already parsed along the way (so
    the caller's object path doesn't parse twice).

    A plain call enters native code twice on this thread
    (docs/architecture.md, "Two native crossings a call"):
    the decode, whose summary says whether Python has to look at the
    columns at all, and the encode, which hands back the over-limit
    count beside the bytes.  ``instance.metric_edge_native_calls`` over
    ``metric_edge_calls`` is the share of calls answered that way.

    The instance's ingest ColumnArena makes the decode land in a
    preallocated slab — zero per-batch allocation.  The tick loop
    releases the slab after packing; batches that bail to the object
    path release it here.  ``edge_call`` (``flightrec.edge_call()``)
    goes to ``tick``, which pauses it across its wait."""
    msg = None
    native = False
    try:
        if not gate_ok:
            return None, msg
        arena, metrics = instance.ingest_arena, instance.metrics
        # Flight-recorder transport edges: per-batch decode/encode CPU
        # (folded into window records — see utils/flightrec.py), and
        # this thread's whole CPU, read by tick-loop once a window.
        flightrec.register_thread("edge")
        with flightrec.stage("decode"):
            try:
                parsed = fastwire.parse_req(raw, arena)
            except IngestOverloadError as e:
                # Bounded ingest (docs/overload.md): arena exhaustion
                # past the fallback budget is backpressure, not an
                # allocation — answer retriable RESOURCE_EXHAUSTED so
                # clients back off.
                metrics.admission_shed.labels(reason="backpressure").inc()
                await context.abort(
                    grpc.StatusCode.RESOURCE_EXHAUSTED, str(e))
            _sync_arena_metrics(arena, metrics)
        decoded = parsed is not None
        if not decoded:  # codec unavailable or malformed bytes
            msg = await _parse_pb(msg_type, raw, context)
            parsed = convert.columns_from_pb(msg.requests)
        cols, errors, special = parsed
        if special or errors:
            cols.release()  # object path re-parses; the slab is dead weight
            return None, msg
        try:
            mat, errs = await tick(cols, deadline=deadline,
                                   over_from_encode=True,
                                   edge_call=edge_call)
        except BatchTooLargeError as e:
            cols.release()  # rejected before the tick loop saw it
            await context.abort(grpc.StatusCode.OUT_OF_RANGE, str(e))
        if errs:
            return _item_responses(mat, errs), msg
        # Native wire encoding straight from the matrix; the method's
        # pass-through serializer ships bytes as-is.  The same pass
        # counts the over-limit items, which the service left to it.
        with flightrec.stage("encode"):
            out, over = fastwire.encode_resp(mat)
        if over:
            metrics.over_limit_counter.inc(over)
        native = decoded
        return out, msg
    finally:
        instance.count_edge_call(native)


class V1Servicer:
    """pb ↔ dataclass edge for the public service.

    ``GetRateLimits`` receives the RAW request bytes (the method handler
    registers a pass-through deserializer, transport/grpc_api.py): the
    hot path never materializes protobuf message objects — native wire
    parse (transport/fastwire.py) → columns → device tick → native wire
    encode.  The object-routing path (clustered / GLOBAL / metadata /
    per-item errors / codec unavailable) parses with protobuf as before.
    """

    def __init__(self, instance: V1Instance):
        self.instance = instance

    def _default_budget(self) -> float:
        return self.instance.tick_loop.admission.request_timeout

    async def GetRateLimits(self, raw: bytes, context):
        # This handler's own time on the event loop, a fast-path call's
        # (flightrec's edge_handler overlays); None with no recorder.
        call = flightrec.edge_call()
        deadline = _edge_deadline(context, self._default_budget())
        fast, msg = await _raw_columns_edge(
            raw, context, self.instance,
            self.instance.columns_fast_path_ok(),
            self.instance.get_rate_limits_columns,
            pb.GetRateLimitsReq,
            deadline=deadline,
            edge_call=call,
        )
        if fast is not None:
            if not isinstance(fast, bytes):
                fast = pb.GetRateLimitsResp(responses=fast)
            if call is not None:
                call.end()
            return fast
        if msg is None:
            msg = await _parse_pb(pb.GetRateLimitsReq, raw, context)
        reqs = convert.reqs_from_pb(msg.requests)
        for r in reqs:
            r.deadline = deadline
        try:
            out = await self.instance.get_rate_limits(reqs)
        except BatchTooLargeError as e:
            await context.abort(grpc.StatusCode.OUT_OF_RANGE, str(e))
        return pb.GetRateLimitsResp(responses=convert.resps_to_pb(out))

    async def HealthCheck(self, request, context):
        h = self.instance.health_check()
        return pb.HealthCheckResp(
            status=h.status, message=h.message, peer_count=h.peer_count
        )

    async def LeaseGrant(self, raw: bytes, context):
        """Quota-lease grant edge (docs/leases.md): raw frame in, raw
        frame out — lease traffic never touches protobuf."""
        specs = fastwire.parse_lease_grant_req(raw)
        if specs is None:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                "malformed LeaseGrant frame")
        tokens = await self.instance.lease_grant(specs)
        return fastwire.encode_lease_grant_resp(tokens)

    async def LeaseSync(self, raw: bytes, context):
        """Quota-lease reconcile edge: consumed counts in, acks out."""
        syncs = fastwire.parse_lease_sync_req(raw)
        if syncs is None:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                "malformed LeaseSync frame")
        acks = await self.instance.lease_sync(syncs)
        return fastwire.encode_lease_sync_resp(acks)

    async def FederationSync(self, raw: bytes, context):
        """Inter-region envelope edge (docs/federation.md): GFE1 frame
        in, GFA1 ack out.  A node without federation enabled rejects the
        RPC — the sender's breaker treats it like any dead peer."""
        env = fastwire.parse_federation_envelope(raw)
        if env is None:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                "malformed FederationSync frame")
        if self.instance.federation is None:
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                "federation is not enabled on this node")
        ack = await self.instance.federation.receive(env)
        return fastwire.encode_federation_ack(ack)


class PeersServicer:
    """pb ↔ dataclass edge for the peer service.

    ``GetPeerRateLimits`` receives RAW bytes like the public edge
    (pass-through deserializer): GetPeerRateLimitsReq shares
    GetRateLimitsReq's wire shape (field 1, repeated RateLimitReq), so
    the native codec parses it directly; GLOBAL/metadata/error batches
    fall back to the object path (trace extraction and owner-side
    GLOBAL queueing need request objects)."""

    def __init__(self, instance: V1Instance):
        self.instance = instance

    def _default_budget(self) -> float:
        return self.instance.tick_loop.admission.request_timeout

    async def GetPeerRateLimits(self, raw: bytes, context):
        deadline = _edge_deadline(context, self._default_budget())
        fast, msg = await _raw_columns_edge(
            raw, context, self.instance,
            self.instance.peer_columns_fast_path_ok(),
            self.instance.get_peer_rate_limits_columns,
            peers_pb.GetPeerRateLimitsReq,
            deadline=deadline,
        )
        if fast is not None:
            if isinstance(fast, bytes):
                # Same wire shape as GetRateLimitsResp (field 1,
                # repeated RateLimitResp) — bytes ship as-is.
                return fast
            return peers_pb.GetPeerRateLimitsResp(rate_limits=fast)
        if msg is None:
            msg = await _parse_pb(peers_pb.GetPeerRateLimitsReq, raw, context)
        reqs = convert.reqs_from_pb(msg.requests)
        for r in reqs:
            r.deadline = deadline
        try:
            out = await self.instance.get_peer_rate_limits(reqs)
        except BatchTooLargeError as e:
            await context.abort(grpc.StatusCode.OUT_OF_RANGE, str(e))
        return peers_pb.GetPeerRateLimitsResp(rate_limits=convert.resps_to_pb(out))

    async def UpdatePeerGlobals(self, request, context):
        updates = [
            GlobalUpdate(
                key=g.key,
                status=convert.resp_from_pb(g.status),
                algorithm=int(g.algorithm),
                duration=g.duration,
                created_at=g.created_at,
            )
            for g in request.globals
        ]
        await self.instance.update_peer_globals(updates)
        return peers_pb.UpdatePeerGlobalsResp()


class Daemon:
    """One running node: instance + listeners + discovery."""

    def __init__(self, conf: DaemonConfig, engine=None, global_mesh=None,
                 global_mesh_node: int = 0):
        self.conf = conf
        self.metrics = Metrics()
        # Optional OS / runtime collectors (daemon.go:276-287).
        self.metrics.register_flag_collectors(conf.metric_flags)
        self.instance: Optional[V1Instance] = None
        self._engine = engine
        self._global_mesh = global_mesh
        self._global_mesh_node = global_mesh_node
        self._grpc_server: Optional[grpc.aio.Server] = None
        self._http_runner: Optional[web.AppRunner] = None
        self._status_runner: Optional[web.AppRunner] = None
        self._pool = None
        self.tls: Optional[TLSBundle] = None
        self.peer_info: List[PeerInfo] = []
        # Readiness is distinct from liveness (docs/persistence.md):
        # /readyz is 503 until the startup restore completed and flips
        # back to 503 the moment graceful drain begins, so orchestrators
        # stop routing new traffic while /healthz (liveness + breaker
        # quorum) stays truthful about the process itself.
        self._ready = False
        self._draining = False
        # /debug introspection surface (docs/observability.md): enabling
        # it also installs the flight recorder and an in-memory trace
        # exporter so /debug/pipeline and /debug/traces have data.  The
        # slow-window watchdog installs the recorder even without the
        # endpoints (its dumps go to the log + slow_windows counter).
        self._debug_enabled = bool(
            env_knob("GUBER_DEBUG_ENDPOINTS", 0, parse=int))
        self._slow_window_ms = env_knob(
            "GUBER_SLOW_WINDOW_MS", 0.0, parse=float)
        self._flight_recorder: Optional[flightrec.FlightRecorder] = None
        self._synced_stalls: dict = {}   # rec.stalls() as last scraped
        self._debug_exporter: Optional[tracing.InMemoryExporter] = None
        self._watchdog_task: Optional[asyncio.Task] = None
        self._profiling = False

    # ------------------------------------------------------------------
    @property
    def advertise_address(self) -> str:
        return self.conf.advertise_address or self.conf.grpc_listen_address

    async def start(self) -> None:
        """Bring up instance, gRPC, gateway, discovery (daemon.go:83-366)."""
        # guber: allow-G002(startup-only TLS material read - runs once before any listener accepts traffic)
        self.tls = setup_tls(self.conf.tls)
        options = [("grpc.max_receive_message_length", MAX_RECV_BYTES)]
        if self.conf.grpc_max_conn_age_sec > 0:
            # Reference parity (daemon.go:128-133): default is infinity;
            # when set, age AND grace both apply so long-lived streams on
            # aged connections are force-closed too.
            age_ms = self.conf.grpc_max_conn_age_sec * 1000
            options.append(("grpc.max_connection_age_ms", age_ms))
            options.append(("grpc.max_connection_age_grace_ms", age_ms))
        server = grpc.aio.server(
            interceptors=[_StatsInterceptor(self.metrics), _TraceInterceptor()],
            options=options,
        )
        if self.tls is not None:
            port = server.add_secure_port(
                self.conf.grpc_listen_address, self.tls.server_credentials()
            )
        else:
            port = server.add_insecure_port(self.conf.grpc_listen_address)
        if port == 0:
            raise RuntimeError(
                f"failed to bind gRPC listener {self.conf.grpc_listen_address}"
            )
        # Rewrite :0 binds to the allocated port so peers/tests can dial it.
        host = self.conf.grpc_listen_address.rsplit(":", 1)[0]
        self.conf.grpc_listen_address = f"{host}:{port}"

        if self._debug_enabled or self._slow_window_ms > 0:
            windows = env_knob(
                "GUBER_FLIGHT_RECORDER_WINDOWS", 256, parse=int)
            rec = flightrec.FlightRecorder(
                windows=max(2, windows),
                slow_threshold_s=self._slow_window_ms / 1e3,
            )
            rec.observer = self._observe_stage
            flightrec.install(rec)
            self._flight_recorder = rec
            self._synced_stalls = rec.stalls()
            self._watchdog_task = spawn_supervised(
                self._watchdog_loop,
                name="flight_watchdog",
                should_restart=lambda: not self._draining,
                metrics=self.metrics,
                loop_label="flight_watchdog",
            )
        if self._debug_enabled:
            self._debug_exporter = tracing.InMemoryExporter()
            tracing.add_exporter(self._debug_exporter)

        # Gateway comes up BEFORE the instance: a snapshot restore can
        # take seconds, and readiness probes must get a real 503 from
        # /readyz during it (not connection-refused ambiguity).
        await self._start_gateway()

        # The instance needs the *bound* address so set_peers can recognize
        # this node's own entry and mark it owner — create it only now.
        iconf = InstanceConfig.from_config(
            self.conf.config,
            advertise_address=self.advertise_address,
            metrics=self.metrics,
            peer_credentials=(
                self.tls.channel_credentials() if self.tls else None
            ),
        )
        iconf.data_center = self.conf.data_center or self.conf.config.data_center
        if self._global_mesh is not None:
            iconf.global_mesh = self._global_mesh
            iconf.global_mesh_node = self._global_mesh_node
        self.instance = await V1Instance.create(iconf, engine=self._engine)
        self._start_edge_plane()
        server.add_generic_rpc_handlers(
            (
                v1_handler(V1Servicer(self.instance)),
                peers_handler(PeersServicer(self.instance)),
            )
        )
        await server.start()
        self._grpc_server = server
        self._ready = True

        await self._start_discovery()
        log.info(
            "gubernator-tpu daemon up: grpc=%s http=%s",
            self.conf.grpc_listen_address,
            self.conf.http_listen_address,
        )
        log.info(
            "engine: %s",
            " ".join(f"{k}={v}" for k, v in
                     self.instance.engine.describe().items()),
        )

    def _start_edge_plane(self) -> None:
        """GUBER_EDGE_WORKERS > 0: bring up the shared-memory ingest
        plane (docs/edge.md) — N decode worker processes, each exposing
        a Unix-socket fastwire endpoint and feeding the tick loop
        through its own shm slab ring.  At 0 (the default) nothing is
        constructed: the serving path is byte-identical to the
        single-process daemon and no shm segment ever exists."""
        conf = self.conf.config
        if conf.edge_workers <= 0:
            return
        from gubernator_tpu.service.instance import MAX_BATCH_SIZE
        from gubernator_tpu.edge import EdgeConfig, EdgePlane

        plane = EdgePlane(
            self.instance.tick_loop,
            EdgeConfig(
                workers=conf.edge_workers,
                slabs=conf.edge_shm_slabs,
                ring_depth=conf.edge_ring_depth,
                max_batch=MAX_BATCH_SIZE,
                mode="socket",
            ),
            metrics=self.metrics,
        )
        plane.start()
        self.instance.attach_edge_plane(plane)
        log.info("edge ingest sockets: %s", ", ".join(plane.socket_paths()))

    # ------------------------------------------------------------------
    # HTTP gateway (grpc-gateway JSON + /metrics, daemon.go:245-292)
    # ------------------------------------------------------------------
    def _gateway_app(self, include_metrics: bool = True) -> web.Application:
        app = web.Application(client_max_size=MAX_RECV_BYTES)
        app.router.add_post("/v1/GetRateLimits", self._h_get_rate_limits)
        app.router.add_get("/v1/HealthCheck", self._h_health_check)
        app.router.add_get("/healthz", self._h_health_check)
        app.router.add_get("/readyz", self._h_readyz)
        if include_metrics:
            app.router.add_get("/metrics", self._h_metrics)
        if self._debug_enabled:
            self._add_debug_routes(app)
        return app

    def _add_debug_routes(self, app: web.Application) -> None:
        app.router.add_get("/debug/pipeline", self._h_debug_pipeline)
        app.router.add_get("/debug/traces", self._h_debug_traces)
        app.router.add_get("/debug/state", self._h_debug_state)
        app.router.add_get("/debug/profile", self._h_debug_profile)
        app.router.add_post("/debug/reshard", self._h_debug_reshard)
        app.router.add_get("/debug/autoscaler", self._h_debug_autoscaler)

    async def _start_gateway(self) -> None:
        if not self.conf.http_listen_address:
            return
        app = self._gateway_app()
        runner = web.AppRunner(app, access_log=None)
        await runner.setup()
        host, _, port = self.conf.http_listen_address.rpartition(":")
        ssl_ctx = self.tls.server_ssl_context() if self.tls else None
        site = web.TCPSite(runner, host or "localhost", int(port), ssl_context=ssl_ctx)
        await site.start()
        self._http_runner = runner
        # Rewrite :0 binds to the allocated port.
        socks = site._server.sockets if site._server is not None else []
        if int(port) == 0 and socks:
            self.conf.http_listen_address = (
                f"{host or 'localhost'}:{socks[0].getsockname()[1]}"
            )
        # Optional plaintext status listener for health probes behind mTLS
        # (daemon.go:305-334).
        if self.conf.http_status_listen_address:
            sapp = web.Application()
            sapp.router.add_get("/v1/HealthCheck", self._h_health_check)
            sapp.router.add_get("/healthz", self._h_health_check)
            sapp.router.add_get("/readyz", self._h_readyz)
            sapp.router.add_get("/metrics", self._h_metrics)
            if self._debug_enabled:
                self._add_debug_routes(sapp)
            srunner = web.AppRunner(sapp, access_log=None)
            await srunner.setup()
            shost, _, sport = self.conf.http_status_listen_address.rpartition(":")
            await web.TCPSite(srunner, shost or "localhost", int(sport)).start()
            self._status_runner = srunner

    async def _h_get_rate_limits(self, request: web.Request) -> web.Response:
        """JSON gateway with snake_case field names (UseProtoNames parity,
        daemon.go:251-261)."""
        if self.instance is None:
            return web.json_response(
                {"error": "starting up", "code": 14}, status=503
            )
        try:
            body = await request.read()
            msg = json_format.Parse(body, pb.GetRateLimitsReq())
        except json_format.ParseError as e:
            return web.json_response({"error": str(e), "code": 3}, status=400)
        try:
            parent = tracing.extract(
                {k.lower(): v for k, v in request.headers.items()}
            )
            with tracing.maybe_span("http.recv./v1/GetRateLimits",
                                    parent=parent):
                out = await self.instance.get_rate_limits(
                    convert.reqs_from_pb(msg.requests)
                )
        except BatchTooLargeError as e:
            return web.json_response({"error": str(e), "code": 11}, status=400)
        resp = pb.GetRateLimitsResp(responses=convert.resps_to_pb(out))
        return web.json_response(
            json_format.MessageToDict(
                resp,
                preserving_proto_field_name=True,
                always_print_fields_with_no_presence=True,
            )
        )

    async def _h_readyz(self, request: web.Request) -> web.Response:
        """Readiness, split from liveness: 503 before the startup restore
        completes and for the whole graceful drain, 200 only while the
        daemon wants new traffic.  /healthz keeps the breaker-majority
        liveness semantics (docs/resilience.md)."""
        ok = self._ready and not self._draining
        body = {
            "ready": ok,
            "draining": self._draining,
        }
        if self.instance is not None and self.instance.restore_stats:
            body["restore"] = self.instance.restore_stats
        return web.json_response(body, status=200 if ok else 503)

    async def _h_health_check(self, request: web.Request) -> web.Response:
        if self.instance is None:
            return web.json_response(
                {"status": "unhealthy", "message": "starting up",
                 "peer_count": 0}, status=503
            )
        h = self.instance.health_check()
        msg = pb.HealthCheckResp(
            status=h.status, message=h.message, peer_count=h.peer_count
        )
        body = json_format.MessageToDict(
            msg,
            preserving_proto_field_name=True,
            always_print_fields_with_no_presence=True,
        )
        # Tier occupancy rides the health JSON as extra keys (the proto
        # message is unchanged — wire-compatible clients ignore them).
        body["occupancy"] = self.instance.occupancy()
        # Unhealthy (e.g. a majority of peers behind open circuit
        # breakers) maps to 503 so plain HTTP probes — k8s liveness,
        # LB health checks — rotate the node without parsing JSON.
        return web.json_response(
            body, status=200 if h.status == "healthy" else 503
        )

    async def _h_metrics(self, request: web.Request) -> web.Response:
        if self.instance is None:
            return web.Response(
                body=self.metrics.expose(), content_type="text/plain"
            )
        self._sync_stalls()
        eng = self.instance.engine
        self.metrics.cache_size.set(eng.cache_size())
        if hasattr(eng, "hot_occupancy"):
            self.metrics.hot_occupancy.set(eng.hot_occupancy())
        if hasattr(eng, "cold_size"):
            self.metrics.cold_size.set(eng.cold_size())
        return web.Response(
            body=self.metrics.expose(), content_type="text/plain"
        )

    # ------------------------------------------------------------------
    # /debug introspection surface (docs/observability.md)
    # ------------------------------------------------------------------
    def _observe_stage(self, stage: str, seconds: float) -> None:
        """Flight-recorder observer: per-stage latency histogram."""
        self.metrics.stage_duration.labels(stage=stage).observe(seconds)

    def _sync_stalls(self) -> None:
        """The recorder's stall counters into this daemon's registry, at
        scrape time (the recorder counts; Prometheus gets the delta)."""
        rec = self._flight_recorder
        if rec is None:
            return
        m, seen, now = self.metrics, self._synced_stalls, rec.stalls()
        for gen in range(flightrec.GC_GENERATIONS):
            m.gc_pause_seconds.labels(generation=str(gen)).inc(
                now["gc_pause_seconds"][gen] - seen["gc_pause_seconds"][gen])
            m.gc_collections.labels(generation=str(gen)).inc(
                now["gc_collections"][gen] - seen["gc_collections"][gen])
        m.serving_compile_seconds.inc(
            now["serving_compile_seconds"] - seen["serving_compile_seconds"])
        m.serving_compiles.inc(
            now["serving_compiles"] - seen["serving_compiles"])
        self._synced_stalls = now

    async def _watchdog_loop(self) -> None:
        """Drain slow-window records parked by FlightRecorder.finish().

        finish() runs on the dispatch hot path so it only does the float
        compare and a bounded-deque append; everything observable — the
        slow_windows counter, the log dump — happens here off the hot
        path, under the supervisor like every other background loop."""
        while not self._draining:
            rec = self._flight_recorder
            if rec is not None:
                for dump in rec.drain_slow():
                    self.metrics.slow_windows.inc()
                    log.warning(
                        "slow window %d: total=%.1fms width=%d depth=%d "
                        "stages_ms=%s",
                        dump["window"], dump["total_ms"], dump["width"],
                        dump["queue_depth"],
                        {s: v for s, v in dump["stages_ms"].items() if v},
                    )
            await asyncio.sleep(0.25)

    async def _h_debug_pipeline(self, request: web.Request) -> web.Response:
        rec = self._flight_recorder
        if rec is None:
            return web.json_response(
                {"error": "flight recorder not installed"}, status=404
            )
        try:
            limit = int(request.query.get("limit", "64"))
        except ValueError:
            return web.json_response({"error": "bad limit"}, status=400)
        return web.json_response({
            "windows": rec.recent(max(1, limit)),
            "stage_percentiles": rec.snapshot()["stages"],
            "slow_windows": rec.slow_total,
            # tick-loop's cycle, disjoint and consecutive; and what lies
            # inside another stage or on another thread: in stages_ms,
            # not in total_ms (nor is "wait") — utils/flightrec.py
            "cycle": list(flightrec.CYCLE),
            "overlays": list(flightrec.OVERLAYS),
        })

    @staticmethod
    def _span_dict(span: tracing.Span) -> dict:
        attrs = {
            k: v if isinstance(v, (str, int, float, bool, type(None)))
            else repr(v)
            for k, v in span.attributes.items()
        }
        return {
            "name": span.name,
            "trace_id": span.trace_id,
            "span_id": span.span_id,
            "parent_span_id": span.parent_span_id,
            "start_ns": span.start_ns,
            "duration_ms": round(span.duration_ms, 4),
            "attributes": attrs,
            "error": span.error,
        }

    async def _h_debug_traces(self, request: web.Request) -> web.Response:
        exp = self._debug_exporter
        if exp is None:
            return web.json_response(
                {"error": "trace exporter not installed"}, status=404
            )
        trace_id = request.query.get("trace_id")
        name = request.query.get("name")
        try:
            limit = int(request.query.get("limit", "128"))
        except ValueError:
            return web.json_response({"error": "bad limit"}, status=400)
        if trace_id:
            spans = exp.by_trace(trace_id)
        elif name:
            spans = exp.by_name(name)
        else:
            with exp._lock:
                spans = list(exp.spans)
        spans = spans[-max(1, limit):]
        return web.json_response({
            "tracing_enabled": tracing.enabled(),
            "count": len(spans),
            "spans": [self._span_dict(s) for s in spans],
        })

    async def _h_debug_state(self, request: web.Request) -> web.Response:
        if self.instance is None:
            return web.json_response({"error": "starting up"}, status=503)
        inst = self.instance
        eng = inst.engine
        body: dict = {
            "ready": self._ready,
            "draining": self._draining,
            "occupancy": inst.occupancy(),
            "restore": inst.restore_stats,
        }
        arena = inst.ingest_arena
        if arena is not None:
            body["ingest_arena"] = {
                "slabs": arena.n_slabs,
                "in_use": arena.in_use(),
                "leases": arena.metric_leases,
                "misses": arena.metric_misses,
            }
        # native over calls: the share of raw-bytes calls that the two
        # native passes answered (_raw_columns_edge)
        body["edge_calls"] = {
            "calls": inst.metric_edge_calls,
            "native": inst.metric_edge_native_calls,
        }
        if inst.edge_plane is not None:
            body["edge"] = inst.edge_plane.debug_state()
        engine_tel: dict = {}
        if hasattr(eng, "h2d_overlap_ratio"):
            engine_tel["h2d_windows"] = eng.metric_h2d_windows
            if hasattr(eng, "metric_h2d_uploads"):
                # over h2d_windows: host→device uploads a window, 1.0
                # where every window is one buffer
                # (TickEngine.submit_columns)
                engine_tel["h2d_uploads"] = eng.metric_h2d_uploads
            engine_tel["h2d_overlap_ratio"] = round(
                eng.h2d_overlap_ratio(), 4)
        if hasattr(eng, "metric_dup_windows"):
            # the sharded engine's windows by the program that answered
            # them; they add up to h2d_windows
            engine_tel["dup_windows"] = eng.metric_dup_windows
            engine_tel["unique_windows"] = eng.metric_unique_windows
        if hasattr(eng, "metric_sequential_ticks"):
            # the one-chip engine's windows by the dispatch branch (and
            # so the program, jit_tick32_<branch>) that answered them;
            # they add up to h2d_windows
            for branch in TICK_BRANCHES:
                engine_tel[f"{branch}_ticks"] = getattr(
                    eng, f"metric_{branch}_ticks")
        if hasattr(eng, "metric_native_pack_windows"):
            # over h2d_windows: the share of windows the native host
            # pack answered (TickEngine._build_cols)
            engine_tel["native_pack_windows"] = eng.metric_native_pack_windows
        if hasattr(eng, "metric_leaky_rows"):
            # over cache hits + misses: the share of rows on the
            # float64 leaky path
            engine_tel["leaky_rows"] = eng.metric_leaky_rows
        if hasattr(eng, "load_rows"):
            # the fills' seconds and the rows they landed (the start-up
            # Loader's, and any later load_columns)
            engine_tel["load_seconds"] = round(eng.load_seconds, 3)
            engine_tel["load_rows"] = eng.load_rows
        staging = getattr(eng, "_staging", None)
        if staging is not None and hasattr(staging, "telemetry"):
            engine_tel["staging_ring"] = staging.telemetry()
        if engine_tel:
            body["engine"] = engine_tel
        if self._flight_recorder is not None:
            # collections and first-met shapes since the first window
            # (the recorder's listeners; also on /metrics)
            body["stalls"] = self._flight_recorder.stalls()
        body["breakers"] = {
            p.info.grpc_address: p.breaker.state.name
            for p in inst.local_picker.peers()
        }
        gm = inst.global_mgr
        body["redelivery"] = {
            "hits": len(gm._hits),
            "updates": len(gm._updates),
            "owned": len(gm._owned),
        }
        writer = getattr(inst, "_snapshot_writer", None)
        if writer is not None:
            body["snapshot"] = {
                "generation": writer.store.generation,
                "delta_writes": writer.metric_delta_writes,
                "base_writes": writer.metric_base_writes,
                "write_failures": writer.metric_write_failures,
            }
        body["reshard"] = inst.reshard_status()
        if inst.autoscaler is not None:
            scaler_state = inst.autoscaler.debug_state()
            scaler_state.pop("decisions", None)  # the ring lives at
            body["autoscaler"] = scaler_state    # /debug/autoscaler
        return web.json_response(body)

    async def _h_debug_autoscaler(self, request: web.Request) -> web.Response:
        """Autoscaler introspection (docs/autoscaling.md): config,
        streaks, and the bounded decision ring — the dry-run rollout
        reads this until the decisions look right."""
        if self.instance is None:
            return web.json_response({"error": "starting up"}, status=503)
        scaler = self.instance.autoscaler
        if scaler is None:
            return web.json_response(
                {"error": "autoscaler disabled (GUBER_AUTOSCALE_ENABLED)"},
                status=404,
            )
        return web.json_response(scaler.debug_state())

    async def _h_debug_reshard(self, request: web.Request) -> web.Response:
        """Admin trigger (docs/resharding.md): POST {"shards": m} runs
        one n→m transition and answers its outcome dict.  409 when a
        transition is already running (the coordinator's busy dict is
        the single source of truth — the autoscaler consults the same
        lock, so the two can never double-freeze); 400 on a bad target.
        The debug plane is operator-only (GUBER_DEBUG_ENDPOINTS), same
        trust level as /debug/profile."""
        if self.instance is None:
            return web.json_response({"error": "starting up"}, status=503)
        try:
            doc = await request.json()
            shards = int(doc["shards"])
        except (ValueError, KeyError, TypeError):
            return web.json_response(
                {"error": "body must be JSON {\"shards\": <int>}"},
                status=400,
            )
        from gubernator_tpu.parallel.reshard import ReshardError

        try:
            result = await self.instance.reshard(shards)
        except ReshardError as e:
            return web.json_response({"error": str(e)}, status=400)
        if result.get("result") == "busy":
            return web.json_response(result, status=409)
        return web.json_response(result)

    async def _h_debug_profile(self, request: web.Request) -> web.Response:
        try:
            seconds = float(request.query.get("seconds", "2"))
        except ValueError:
            return web.json_response({"error": "bad seconds"}, status=400)
        if not 0 < seconds <= 30:
            return web.json_response(
                {"error": "seconds must be in (0, 30]"}, status=400
            )
        if self._profiling:
            return web.json_response(
                {"error": "capture already running"}, status=409
            )
        self._profiling = True
        try:
            import tempfile

            import jax

            out_dir = tempfile.mkdtemp(prefix="guber-profile-")
            jax.profiler.start_trace(out_dir)
            try:
                await asyncio.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            return web.json_response(
                {"trace_dir": out_dir, "seconds": seconds}
            )
        except Exception as exc:  # profiler may be busy / unavailable
            return web.json_response(
                {"error": f"{type(exc).__name__}: {exc}"}, status=500
            )
        finally:
            self._profiling = False

    # ------------------------------------------------------------------
    # Discovery (daemon.go:208-243)
    # ------------------------------------------------------------------
    async def _start_discovery(self) -> None:
        kind = self.conf.peer_discovery_type
        if kind == "none":
            self.set_peers([self._self_info()])
            return
        from gubernator_tpu import discovery

        info = self._self_info()
        if kind == "dns":
            self._pool = discovery.DNSPool(
                fqdn=self.conf.dns_fqdn,
                grpc_port=int(self.conf.grpc_listen_address.rsplit(":", 1)[1]),
                http_port=int(self.conf.http_listen_address.rsplit(":", 1)[1])
                if self.conf.http_listen_address
                else 0,
                on_update=self.set_peers,
            )
        elif kind == "etcd":
            self._pool = discovery.EtcdPool(
                endpoints=self.conf.etcd_endpoints,
                key_prefix=self.conf.etcd_key_prefix,
                info=info,
                on_update=self.set_peers,
            )
        elif kind == "k8s":
            self._pool = discovery.K8sPool(
                namespace=self.conf.k8s_namespace,
                selector=self.conf.k8s_endpoints_selector,
                pod_ip=self.conf.k8s_pod_ip,
                pod_port=self.conf.k8s_pod_port,
                mechanism=self.conf.k8s_watch_mechanism,
                on_update=self.set_peers,
            )
        elif kind == "member-list":
            self._pool = discovery.MemberlistPool(
                bind_address=self.conf.memberlist_address,
                known_nodes=self.conf.memberlist_known_nodes,
                info=info,
                on_update=self.set_peers,
            )
        else:
            raise ValueError(f"unknown peer discovery type {kind!r}")
        await self._pool.start()

    def _self_info(self) -> PeerInfo:
        return PeerInfo(
            grpc_address=self.advertise_address,
            http_address=self.conf.http_listen_address,
            datacenter=self.conf.data_center,
        )

    # ------------------------------------------------------------------
    def set_peers(self, peers: Sequence[PeerInfo]) -> None:
        """Install cluster membership; marks our own entry (daemon.go:399-409)."""
        self.peer_info = list(peers)
        self.instance.set_peers(self.peer_info)

    def client(self) -> "DaemonClient":
        """A client dialing this daemon (reference Daemon.Client, :433-447)."""
        creds = self.tls.channel_credentials() if self.tls else None
        return DaemonClient(self.conf.grpc_listen_address, credentials=creds)

    async def wait_for_connect(self, timeout: float = 10.0) -> None:
        """Readiness: block until the gRPC listener answers HealthCheck."""
        client = self.client()
        deadline = time.monotonic() + timeout
        while True:
            try:
                await client.health_check()
                await client.close()
                return
            except Exception:
                if time.monotonic() > deadline:
                    await client.close()
                    raise
                await asyncio.sleep(0.05)

    async def close(self) -> None:
        """Graceful shutdown (daemon.go:369-396): flip readiness to 503
        first (orchestrators stop routing), then drain — discovery off,
        GLOBAL buffers flushed under the bounded deadline and the final
        base snapshot written inside instance.close — then listeners."""
        self._draining = True
        if self._watchdog_task is not None:
            self._watchdog_task.cancel()
            try:
                await self._watchdog_task
            except (asyncio.CancelledError, Exception):
                pass
            self._watchdog_task = None
        if self._debug_exporter is not None:
            tracing.remove_exporter(self._debug_exporter)
            self._debug_exporter = None
        if (self._flight_recorder is not None
                and flightrec.get() is self._flight_recorder):
            # Only drop the module-global slot if it is still ours — an
            # in-process test cluster shares it across daemons.
            flightrec.uninstall()
        self._flight_recorder = None
        if self._pool is not None:
            await self._pool.close()
        if self.instance is not None:
            await self.instance.close()
        if self._grpc_server is not None:
            await self._grpc_server.stop(grace=1.0)
        if self._http_runner is not None:
            await self._http_runner.cleanup()
        if self._status_runner is not None:
            await self._status_runner.cleanup()


class DaemonClient:
    """Thin async client for the public V1 API (reference client.go)."""

    def __init__(
        self,
        address: str,
        credentials: Optional[grpc.ChannelCredentials] = None,
    ):
        if credentials is not None:
            self.channel = grpc.aio.secure_channel(address, credentials)
        else:
            self.channel = grpc.aio.insecure_channel(address)
        self.stub = V1Stub(self.channel)
        # Raw-bytes method for the columnar client path: the native
        # codec produces/consumes the wire bytes; grpc just ships them.
        self._raw_get_rate_limits = self.channel.unary_unary(
            "/pb.gubernator.V1/GetRateLimits",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )

    async def get_rate_limits(self, reqs, timeout: float = 5.0,
                              budget_ms: int = None):
        """``budget_ms`` (optional) rides the ``guber-deadline-ms``
        metadata key so the server's admission plane sheds work this
        caller will no longer wait for (docs/overload.md)."""
        msg = pb.GetRateLimitsReq(requests=[convert.req_to_pb(r) for r in reqs])
        hdrs: dict = {}
        tracing.inject(hdrs)
        if budget_ms is not None:
            hdrs[DEADLINE_METADATA_KEY] = str(max(0, int(budget_ms)))
        out = await self.stub.GetRateLimits(
            msg, timeout=timeout, metadata=tuple(hdrs.items()) or None
        )
        return [convert.resp_from_pb(r) for r in out.responses]

    async def get_rate_limits_columns(self, cols, timeout: float = 5.0,
                                      budget_ms: int = None):
        """Columnar client fast path: a :class:`ReqColumns` batch (with
        ``name_len``) → native wire encode → raw gRPC → native wire
        decode → ((4, n) status/limit/remaining/reset_time matrix,
        {index: error string}).  Raises RuntimeError when the native
        codec is unavailable — callers keep the object API then."""
        import numpy as np

        raw = fastwire.encode_req(cols)
        if raw is None:
            raise RuntimeError(
                "native wire codec unavailable (build native/ or use "
                "get_rate_limits)"
            )
        hdrs: dict = {}
        tracing.inject(hdrs)
        if budget_ms is not None:
            hdrs[DEADLINE_METADATA_KEY] = str(max(0, int(budget_ms)))
        out = await self._raw_get_rate_limits(
            raw, timeout=timeout, metadata=tuple(hdrs.items()) or None
        )
        parsed = fastwire.parse_resp(out)
        if parsed is None:  # pragma: no cover - encode side proved lib ok
            raise RuntimeError("native wire codec failed to parse response")
        mat, special = parsed
        errors = {}
        if special.any():
            msg = pb.GetRateLimitsResp.FromString(out)
            for i in np.flatnonzero(special):
                if msg.responses[i].error:
                    errors[int(i)] = msg.responses[i].error
        return mat, errors

    async def health_check(self, timeout: float = 5.0):
        return await self.stub.HealthCheck(pb.HealthCheckReq(), timeout=timeout)

    async def lease_grant(self, specs, timeout: float = 5.0):
        """Request quota leases (docs/leases.md): [LeaseSpec] →
        [Optional[LeaseToken]] (None = server declined; fall back to
        per-request decisions)."""
        hdrs: dict = {}
        tracing.inject(hdrs)
        out = await self.stub.LeaseGrant(
            fastwire.encode_lease_grant_req(specs), timeout=timeout,
            metadata=tuple(hdrs.items()) or None,
        )
        tokens = fastwire.parse_lease_grant_resp(out)
        if tokens is None:
            raise RuntimeError("malformed LeaseGrant response frame")
        return tokens

    async def lease_sync(self, syncs, timeout: float = 5.0):
        """Report lease consumption: [LeaseSync] → [LeaseSyncAck]."""
        hdrs: dict = {}
        tracing.inject(hdrs)
        out = await self.stub.LeaseSync(
            fastwire.encode_lease_sync_req(syncs), timeout=timeout,
            metadata=tuple(hdrs.items()) or None,
        )
        acks = fastwire.parse_lease_sync_resp(out)
        if acks is None:
            raise RuntimeError("malformed LeaseSync response frame")
        return acks

    async def close(self) -> None:
        await self.channel.close()


async def spawn_daemon(conf: DaemonConfig, engine=None) -> Daemon:
    """Start a daemon and wait for readiness (reference SpawnDaemon,
    daemon.go:73-81)."""
    d = Daemon(conf, engine=engine)
    await d.start()
    await d.wait_for_connect()
    return d
