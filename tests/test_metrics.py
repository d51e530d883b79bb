"""Metric-flag collectors + metrics-as-oracle helpers.

The reference registers optional OS/runtime collectors behind
``GUBER_METRIC_FLAGS`` (flags.go:20-57, daemon.go:276-287) and its
distributed tests poll metric counters instead of sleeping
(functional_test.go:2184-2276).  Both surfaces are covered here.
"""

import pytest

from gubernator_tpu.config import (
    FLAG_OS_METRICS,
    FLAG_RUNTIME_METRICS,
    DaemonConfig,
    parse_metric_flags,
)
from gubernator_tpu.utils.metrics import TICK_BRANCHES, Metrics


def test_parse_metric_flags_reference_names():
    # flags.go:47-52: "os" and "golang" are the two valid names.
    assert parse_metric_flags(["os"]) == FLAG_OS_METRICS
    assert parse_metric_flags(["golang"]) == FLAG_RUNTIME_METRICS
    assert parse_metric_flags(["os", "golang"]) == (
        FLAG_OS_METRICS | FLAG_RUNTIME_METRICS
    )
    # Native aliases for the runtime collector, plus whitespace tolerance.
    assert parse_metric_flags([" python "]) == FLAG_RUNTIME_METRICS
    assert parse_metric_flags([]) == 0


def test_parse_metric_flags_invalid_ignored(caplog):
    # flags.go:53-55: unknown names are logged and skipped, not fatal.
    with caplog.at_level("ERROR", logger="gubernator"):
        assert parse_metric_flags(["bogus", "os"]) == FLAG_OS_METRICS
    assert any("invalid flag" in r.message for r in caplog.records)


def test_flag_collectors_registered():
    m = Metrics()
    m.register_flag_collectors(FLAG_OS_METRICS | FLAG_RUNTIME_METRICS)
    text = m.expose().decode()
    # ProcessCollector under the gubernator namespace (daemon.go:278-281)...
    assert "gubernator_process_cpu_seconds_total" in text
    # ...and the Python-runtime analog of Go's GoCollector.
    assert "python_info" in text
    assert "python_gc_objects_collected_total" in text


def test_no_flag_collectors_by_default():
    text = Metrics().expose().decode()
    assert "process_cpu_seconds_total" not in text
    assert "python_info" not in text


def test_sample_oracle_reads_counters_and_summaries():
    m = Metrics()
    assert m.sample("gubernator_broadcast_duration_count") == 0.0
    m.broadcast_duration.observe(0.25)
    m.broadcast_duration.observe(0.75)
    assert m.sample("gubernator_broadcast_duration_count") == 2.0
    assert m.sample("gubernator_broadcast_duration_sum") == pytest.approx(1.0)
    m.getratelimit_counter.labels(calltype="local").inc()
    assert m.sample(
        "gubernator_getratelimit_counter_total", {"calltype": "local"}
    ) == 1.0


async def test_service_request_populates_catalog_families():
    """One live request drives the engine/cache/func families the catalog
    documents (docs/prometheus.md) — they must not stay at zero."""
    from gubernator_tpu.config import BehaviorConfig, Config
    from gubernator_tpu.transport.daemon import DaemonClient, spawn_daemon
    from gubernator_tpu.types import RateLimitRequest

    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="",
        peer_discovery_type="none",
    )
    conf.config = Config(behaviors=BehaviorConfig(), cache_size=256)
    d = await spawn_daemon(conf)
    try:
        client = DaemonClient(d.advertise_address)
        reqs = [RateLimitRequest(name="svc", unique_key="k", hits=1,
                                 limit=10, duration=60000)]
        await client.get_rate_limits(reqs)  # miss: installs the bucket
        await client.get_rate_limits(reqs)  # hit
        await client.close()
        m = d.metrics
        assert m.sample("gubernator_cache_access_count_total",
                        {"type": "miss"}) >= 1
        assert m.sample("gubernator_cache_access_count_total",
                        {"type": "hit"}) >= 1
        assert m.sample("gubernator_command_counter_total",
                        {"worker": "0", "method": "GetRateLimits"}) >= 2
        assert m.sample("gubernator_func_duration_count",
                        {"name": "V1Instance.GetRateLimits"}) >= 2
        assert m.sample("gubernator_func_duration_count",
                        {"name": "V1Instance.getLocalRateLimit"}) >= 2
        assert m.sample("gubernator_tpu_tick_batch_size_count") >= 2
    finally:
        await d.close()


async def _edge_traffic(native: bool, monkeypatch):
    """Five calls of mixed algorithms over the gRPC edge, the last with
    an empty key; returns the instance and its metrics after them."""
    from gubernator_tpu.config import BehaviorConfig, Config
    from gubernator_tpu.transport import fastwire
    from gubernator_tpu.transport.daemon import DaemonClient, spawn_daemon
    from gubernator_tpu.types import RateLimitRequest

    if not native:
        monkeypatch.setattr(fastwire, "load", lambda: None)
    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="",
        peer_discovery_type="none",
    )
    conf.config = Config(behaviors=BehaviorConfig(), cache_size=256)
    d = await spawn_daemon(conf)
    try:
        client = DaemonClient(d.advertise_address)
        plain = [
            RateLimitRequest(name="svc", unique_key=f"k{i}", hits=1,
                             limit=2, duration=60_000, algorithm=i % 5)
            for i in range(10)
        ]
        shares = []
        for _ in range(4):  # limit 2: the last two calls are over it
            await client.get_rate_limits(plain)
            shares.append(d.instance.metric_edge_native_calls
                          / d.instance.metric_edge_calls)
        out = await client.get_rate_limits(
            plain[:3] + [RateLimitRequest(name="svc", unique_key="", hits=1,
                                          limit=2, duration=60_000)])
        assert out[3].error and not out[0].error
        await client.close()
        return d.instance, d.metrics, shares
    finally:
        await d.close()


async def test_edge_native_share_and_equal_totals_on_both_paths(monkeypatch):
    """The two-crossing edge answers every plain call (share 1.0) and
    none with a per-item error; the algorithm and over-limit families
    read what the protobuf fallback reads for the same traffic."""
    totals = {}
    for native in (True, False):
        inst, m, shares = await _edge_traffic(native, monkeypatch)
        assert inst.metric_edge_calls == 5
        assert shares == [1.0 if native else 0.0] * 4
        assert inst.metric_edge_native_calls == (4 if native else 0)
        assert m.sample("gubernator_tpu_edge_calls_total",
                        {"path": "native"}) == inst.metric_edge_native_calls
        assert m.sample("gubernator_tpu_edge_calls_total",
                        {"path": "fallback"}) == 5 - inst.metric_edge_native_calls
        totals[native] = {
            a: m.sample("gubernator_tpu_algorithm_requests_total",
                        {"algorithm": a})
            for a in ("token_bucket", "leaky_bucket", "sliding_window",
                      "gcra", "concurrency")
        }
        totals[native]["over"] = m.sample("gubernator_over_limit_counter_total")
        totals[native]["local"] = m.sample(
            "gubernator_getratelimit_counter_total", {"calltype": "local"})
    assert totals[True] == totals[False]
    assert totals[True]["over"] > 0 and totals[True]["token_bucket"] >= 8


async def test_daemon_exposes_flag_collectors():
    """GUBER_METRIC_FLAGS surfaces through the daemon's /metrics page."""
    import aiohttp

    from gubernator_tpu.config import BehaviorConfig, Config
    from gubernator_tpu.transport.daemon import spawn_daemon

    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="127.0.0.1:0",
        peer_discovery_type="none",
        metric_flags=parse_metric_flags(["os", "golang"]),
    )
    conf.config = Config(behaviors=BehaviorConfig(), cache_size=256)
    d = await spawn_daemon(conf)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(
                f"http://{d.conf.http_listen_address}/metrics"
            ) as r:
                text = await r.text()
        assert "gubernator_process_cpu_seconds_total" in text
        assert "python_gc_objects_collected_total" in text
    finally:
        await d.close()


async def test_grpc_max_conn_age_env():
    """GUBER_GRPC_MAX_CONN_AGE_SEC parity (config.go:319): default 0 =
    infinity; a positive value serves traffic with age+grace applied."""
    from gubernator_tpu.config import BehaviorConfig, Config, setup_daemon_config
    from gubernator_tpu.transport.daemon import DaemonClient, spawn_daemon
    from gubernator_tpu.types import RateLimitRequest

    assert setup_daemon_config(environ={}).grpc_max_conn_age_sec == 0
    conf = setup_daemon_config(
        environ={"GUBER_GRPC_MAX_CONN_AGE_SEC": "30"}
    )
    assert conf.grpc_max_conn_age_sec == 30

    # The daemon boots with the option set and serves normally.
    conf.grpc_listen_address = "127.0.0.1:0"
    conf.http_listen_address = ""
    conf.peer_discovery_type = "none"
    conf.config = Config(behaviors=BehaviorConfig(), cache_size=256)
    d = await spawn_daemon(conf)
    try:
        c = DaemonClient(d.advertise_address)
        out = await c.get_rate_limits([RateLimitRequest(
            name="age", unique_key="k", hits=1, limit=5, duration=60_000)])
        assert out[0].remaining == 4
        await c.close()
    finally:
        await d.close()


def test_tiering_families_registered():
    # docs/tiering.md observability table: the tiering counters/gauges
    # exist from construction so dashboards see zeroes, not absences.
    m = Metrics()
    m.cold_demotions.inc(3)
    m.cold_promotions.inc(2)
    m.cold_hits.inc(2)
    m.cold_size.set(1)
    m.hot_occupancy.set(0.5)
    m.shed_requests.inc()
    assert m.sample("gubernator_tpu_cold_demotions_total") == 3
    assert m.sample("gubernator_tpu_cold_promotions_total") == 2
    assert m.sample("gubernator_tpu_cold_hits_total") == 2
    assert m.sample("gubernator_tpu_cold_size") == 1
    assert m.sample("gubernator_tpu_hot_occupancy") == 0.5
    assert m.sample("gubernator_tpu_shed_requests_total") == 1


# ---------------------------------------------------------------------
# Telemetry plane (docs/observability.md): lock-light Histogram with
# OpenMetrics exemplars + the daemon's /debug introspection surface.
# ---------------------------------------------------------------------
def test_histogram_exposition_golden_format():
    """The custom-collector Histogram renders the standard Prometheus
    text shape: cumulative _bucket{le=...} rows ending in +Inf, plus
    _count and _sum — and the sample() oracle reads all three."""
    m = Metrics()
    m.stage_duration.labels(stage="pack").observe(0.003)
    m.stage_duration.labels(stage="pack").observe(0.4)
    m.stage_duration.labels(stage="h2d").observe(70.0)  # above top bucket

    text = m.expose().decode()
    assert "# TYPE gubernator_tpu_stage_duration_seconds histogram" in text
    name = "gubernator_tpu_stage_duration_seconds"
    assert m.sample(f"{name}_count", {"stage": "pack"}) == 2
    assert m.sample(f"{name}_sum", {"stage": "pack"}) == pytest.approx(0.403)
    # A 70 s observation lands only in +Inf (buckets top out at ~56 s).
    assert m.sample(f"{name}_bucket", {"stage": "h2d", "le": "+Inf"}) == 1
    assert m.sample(f"{name}_bucket", {"stage": "h2d", "le": "0.0001"}) == 0
    # Bucket counts are cumulative: parse the pack series back out and
    # check monotonicity with the +Inf row equal to _count.  (The text
    # exposition sorts labels, so match on both labels, not an order.)
    pack = [
        float(line.rsplit(" ", 1)[1])
        for line in text.splitlines()
        if line.startswith(f"{name}_bucket{{") and 'stage="pack"' in line
    ]
    assert pack == sorted(pack)
    assert pack[-1] == 2.0


def test_histogram_exemplars_link_trace_ids():
    """Observations made inside a span carry its trace id as an
    OpenMetrics exemplar on the bucket that counted them."""
    from gubernator_tpu.utils import tracing
    from gubernator_tpu.utils.metrics import Histogram
    from gubernator_tpu.utils.tracing import InMemoryExporter

    h = Histogram("t_exemplar_seconds", "test family", ["stage"])
    exp = InMemoryExporter()
    tracing.add_exporter(exp)
    try:
        with tracing.span("observe") as span:
            h.labels(stage="pack").observe(0.01)
        tid = span.trace_id
    finally:
        tracing.remove_exporter(exp)

    text = h.openmetrics()
    lines = [ln for ln in text.splitlines() if "trace_id" in ln]
    assert len(lines) == 1
    assert f'# {{trace_id="{tid}"}} 0.01' in lines[0]
    assert "_bucket{" in lines[0] and 'stage="pack"' in lines[0]

    # Tracing off (no exporter installed): no exemplar is captured.
    h2 = Histogram("t_noexemplar_seconds", "test family")
    h2.observe(0.01)
    assert "trace_id" not in h2.openmetrics()


async def test_debug_endpoints_serve_populated_json(monkeypatch, caplog):
    """GUBER_DEBUG_ENDPOINTS=1: /debug/pipeline, /debug/state and
    /debug/traces all answer populated JSON on a live daemon after a
    few requests (the issue's acceptance criterion), and the per-method
    gRPC latency histogram saw every call."""
    import asyncio
    import gc

    import aiohttp

    from gubernator_tpu.config import BehaviorConfig, Config
    from gubernator_tpu.transport.daemon import DaemonClient, spawn_daemon
    from gubernator_tpu.types import RateLimitRequest
    from gubernator_tpu.utils import flightrec

    monkeypatch.setenv("GUBER_DEBUG_ENDPOINTS", "1")
    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="127.0.0.1:0",
        peer_discovery_type="none",
    )
    conf.config = Config(behaviors=BehaviorConfig(), cache_size=256)
    d = await spawn_daemon(conf)
    try:
        assert flightrec.enabled()
        client = DaemonClient(d.advertise_address)
        # k1 is a leaky bucket: 3 of the 12 rows take the leaky path
        reqs = [RateLimitRequest(name="dbg", unique_key=f"k{i}", hits=1,
                                 limit=10, duration=60000, algorithm=int(i == 1))
                for i in range(4)]
        for _ in range(3):
            await client.get_rate_limits(reqs)
        await client.close()

        # A window slowed by a forced collection: the watchdog dumps it
        # with "gc" named (the collector's hook notes the pause into the
        # window in dispatch, beside the stage it fell in).
        rec = flightrec.get()
        rec.slow_threshold_s = 1e-4
        wid = rec.begin(width=1, depth=0)
        with flightrec.stage("gather"):
            gc.collect()
        rec.end_dispatch(wid)
        rec.finish(wid)
        rec.slow_threshold_s = 0.0
        with caplog.at_level("WARNING", logger="gubernator.daemon"):
            for _ in range(100):
                if d.metrics.sample("gubernator_tpu_slow_windows_total"):
                    break
                await asyncio.sleep(0.05)
        dumps = [m for m in caplog.messages if m.startswith("slow window")]
        assert dumps and "'gc':" in dumps[-1] and "'gather':" in dumps[-1]

        base = f"http://{d.conf.http_listen_address}"
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/debug/pipeline") as r:
                assert r.status == 200
                pipe = await r.json()
            async with s.get(f"{base}/debug/state") as r:
                assert r.status == 200
                state = await r.json()
            async with s.get(f"{base}/metrics") as r:
                assert r.status == 200
                exposed = await r.text()
            async with s.get(f"{base}/debug/traces") as r:
                assert r.status == 200
                traces = await r.json()

        assert pipe["windows"], pipe
        assert set(pipe["windows"][0]["stages_ms"]) == set(flightrec.STAGES)
        assert set(pipe["stage_percentiles"]) == set(flightrec.STAGES)
        # the served windows show the whole cycle and the overlays, and
        # /debug/pipeline says which names a total leaves out
        assert pipe["cycle"] == list(flightrec.CYCLE)
        assert pipe["overlays"] == list(flightrec.OVERLAYS)
        served = [w["stages_ms"] for w in pipe["windows"][:3]]
        for name in ("gather", "submit_lock", "pack", "h2d", "handle",
                     "handoff", "tick", "resolve", "queue", "finish_lock",
                     "cpu", "lease"):
            assert all(ms[name] > 0 for ms in served), (name, served)
        for w in pipe["windows"]:
            assert w["total_ms"] == pytest.approx(sum(
                v for k, v in w["stages_ms"].items()
                if k not in flightrec.OVERLAYS and k != "wait"), abs=1e-2)
        # the four stall counters: on the recorder, in /debug/state, in
        # Prometheus (synced at the scrape), and the new stage labels
        stalls = state["stalls"]
        assert stalls["gc_collections"][2] >= 1
        assert stalls["gc_pause_seconds"][2] > 0
        assert stalls["serving_compiles"] >= 0
        assert d.metrics.sample(
            "gubernator_tpu_gc_collections_total", {"generation": "2"}
        ) >= stalls["gc_collections"][2]
        assert d.metrics.sample(
            "gubernator_tpu_gc_pause_seconds_total", {"generation": "2"}) > 0
        for family in ("gubernator_tpu_gc_pause_seconds_total{",
                       "gubernator_tpu_gc_collections_total{",
                       "gubernator_tpu_serving_compile_seconds_total ",
                       "gubernator_tpu_serving_compiles_total ",
                       'stage_duration_seconds_count{stage="gather"}',
                       'stage_duration_seconds_count{stage="handle"}',
                       'stage_duration_seconds_count{stage="finish_lock"}',
                       'stage_duration_seconds_count{stage="gc"}'):
            assert family in exposed, family
        assert state["ready"] is True
        assert state["occupancy"]
        assert "breakers" in state and "redelivery" in state
        eng_tel = state["engine"]
        assert 0 < eng_tel["h2d_windows"]
        # metric_h2d_uploads: one upload a window, whatever its kind
        assert eng_tel["h2d_uploads"] == eng_tel["h2d_windows"]
        assert eng_tel["h2d_uploads"] == d.instance.engine.metric_h2d_uploads
        if d.instance.engine.describe()["native_pack"]:
            assert eng_tel["native_pack_windows"] == eng_tel["h2d_windows"]
        # the windows by the dispatch branch that answered them: on the
        # engine, in /debug/state, in Prometheus; four distinct keys a
        # call, so every window here is the unique program's
        by_branch = {b: eng_tel[f"{b}_ticks"] for b in TICK_BRANCHES}
        assert by_branch == dict(dict.fromkeys(TICK_BRANCHES, 0),
                                 unique=eng_tel["h2d_windows"])
        assert d.instance.engine.metric_unique_ticks == by_branch["unique"]
        assert d.metrics.sample("gubernator_tpu_tick_windows_total",
                                {"program": "unique"}) == by_branch["unique"]
        # metric_leaky_rows: on the engine, in /debug/state, in Prometheus
        assert d.instance.engine.metric_leaky_rows == 3
        assert eng_tel["leaky_rows"] == 3
        assert d.instance.engine.describe()["leaky_rows"] == 3
        assert d.metrics.sample("gubernator_tpu_leaky_rows_total") == 3
        # the fill counters, in /debug/state as in describe(): this
        # daemon has no Loader, so nothing was filled
        assert (eng_tel["load_rows"], eng_tel["load_seconds"]) == (0, 0.0)
        assert d.instance.engine.describe()["load_rows"] == 0
        assert traces["tracing_enabled"] is True
        assert traces["count"] > 0 and traces["spans"][0]["trace_id"]
        # Satellite: _StatsInterceptor feeds the RPC latency histogram.
        assert d.metrics.sample(
            "gubernator_tpu_grpc_duration_seconds_count",
            {"method": "/pb.gubernator.V1/GetRateLimits"}) >= 3
    finally:
        await d.close()
    assert not flightrec.enabled()  # close() uninstalled the recorder
