"""Tracing: span lifecycle, W3C TraceContext codec, and cross-peer
propagation through a live cluster.

The reference piggybacks trace context on ``RateLimitReq.Metadata``
(metadata_carrier.go:19-38, injected at peer_client.go:140-141/359-360,
extracted at gubernator.go:502-504) so a forwarded request's owner-side
work reports into the caller's trace.  The cluster test here proves the
same end to end: a traced client call through a non-owner daemon produces
owner-side spans with the client's trace id.
"""

import asyncio

import pytest

from gubernator_tpu.cluster import Cluster
from gubernator_tpu.types import Behavior, RateLimitRequest
from gubernator_tpu.utils import tracing
from gubernator_tpu.utils.tracing import InMemoryExporter, SpanContext, Tracer


# ---------------------------------------------------------------------
# Unit: codec + span tree
# ---------------------------------------------------------------------
def test_traceparent_round_trip():
    t = Tracer()
    exp = InMemoryExporter()
    t.exporters.append(exp)
    carrier = {}
    with t.span("root") as root:
        t.inject(carrier)
    ctx = t.extract(carrier)
    assert ctx is not None
    assert ctx.trace_id == root.trace_id
    assert ctx.span_id == root.span_id
    assert ctx.sampled


@pytest.mark.parametrize(
    "bad",
    [
        "",
        "garbage",
        "00-abc-def-01",                                     # wrong lengths
        "00-" + "0" * 32 + "-" + "1234567890abcdef" + "-01",  # zero trace id
        "00-" + "1" * 32 + "-" + "0" * 16 + "-01",            # zero span id
        "ff-" + "1" * 32 + "-" + "1234567890abcdef" + "-01",  # version ff
        "00-" + "G" * 32 + "-" + "1234567890abcdef" + "-01",  # non-hex
    ],
)
def test_traceparent_malformed_rejected(bad):
    assert Tracer.extract({"traceparent": bad}) is None


def test_span_nesting_and_export():
    t = Tracer()
    exp = InMemoryExporter()
    t.exporters.append(exp)
    with t.span("outer") as outer:
        with t.span("inner", {"k": "v"}) as inner:
            assert t.current_span() is inner
        assert t.current_span() is outer
    assert t.current_span() is None
    names = [s.name for s in exp.spans]
    assert names == ["inner", "outer"]  # inner finishes first
    inner_s, outer_s = exp.spans
    assert inner_s.trace_id == outer_s.trace_id
    assert inner_s.parent_span_id == outer_s.span_id
    assert inner_s.attributes["k"] == "v"
    assert inner_s.duration_ms >= 0


def test_remote_parent_continues_trace():
    t = Tracer()
    remote = SpanContext("ab" * 16, "cd" * 8)
    with t.span("server", parent=remote) as s:
        assert s.trace_id == remote.trace_id
        assert s.parent_span_id == remote.span_id


def test_detached_spans_do_not_become_current():
    t = Tracer()
    exp = InMemoryExporter()
    t.exporters.append(exp)
    remote = SpanContext("12" * 16, "34" * 8)
    s = t.start_detached("batch-item", parent=remote)
    assert t.current_span() is None
    t.finish(s)
    assert exp.spans[0].trace_id == remote.trace_id


def test_sampling_off_propagates_but_records_nothing():
    t = Tracer(ratio=0.0)
    exp = InMemoryExporter()
    t.exporters.append(exp)
    carrier = {}
    with t.span("unsampled") as s:
        assert not s.context.sampled
        t.inject(carrier)
    assert len(exp.spans) == 0
    # Context still crosses the wire, flags=00 (W3C requires propagation).
    ctx = t.extract(carrier)
    assert ctx is not None and not ctx.sampled


def test_exception_recorded():
    t = Tracer()
    exp = InMemoryExporter()
    t.exporters.append(exp)
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("nope")
    assert "ValueError: nope" in exp.spans[0].error


# ---------------------------------------------------------------------
# Cluster: trace id survives a forwarded request
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def event_loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture(scope="module")
def cluster(event_loop):
    c = event_loop.run_until_complete(Cluster.start(3))
    yield c
    event_loop.run_until_complete(c.stop())


@pytest.fixture()
def exporter():
    exp = InMemoryExporter()
    tracing.add_exporter(exp)
    yield exp
    tracing.remove_exporter(exp)


async def test_trace_id_survives_forwarding(cluster, exporter):
    """Client span → non-owner daemon → owner daemon: every hop's spans
    carry the client's trace id (the in-process cluster shares one
    exporter, so both daemons' spans land in it)."""
    non_owner = cluster.list_non_owning_daemons("traced", "tk")[0]
    client = non_owner.client()
    with tracing.span("client.call") as client_span:
        out = await client.get_rate_limits(
            [RateLimitRequest(name="traced", unique_key="tk", hits=1,
                              limit=5, duration=60_000)]
        )
    assert out[0].error == ""
    await client.close()

    trace = exporter.by_trace(client_span.trace_id)
    names = {s.name for s in trace}
    # Non-owner side: server RPC span + the forward span.
    assert "grpc.recv.pb.gubernator.V1.GetRateLimits" in names
    assert "V1Instance.asyncRequest" in names
    # Owner side: the peer handler continued the trace from the request
    # metadata (gubernator.go:502-504 parity).
    assert "PeersV1.GetPeerRateLimit" in names
    peer_span = next(s for s in trace if s.name == "PeersV1.GetPeerRateLimit")
    assert peer_span.attributes["ratelimit.key"] == "tk"


async def test_no_batching_forward_also_propagates(cluster, exporter):
    non_owner = cluster.list_non_owning_daemons("traced-nb", "tk2")[0]
    client = non_owner.client()
    with tracing.span("client.call.nb") as client_span:
        out = await client.get_rate_limits(
            [RateLimitRequest(name="traced-nb", unique_key="tk2", hits=1,
                              limit=5, duration=60_000,
                              behavior=Behavior.NO_BATCHING)]
        )
    assert out[0].error == ""
    await client.close()
    names = {s.name for s in exporter.by_trace(client_span.trace_id)}
    assert "PeersV1.GetPeerRateLimit" in names


async def test_untraced_request_starts_fresh_traces(cluster, exporter):
    """No client context → server spans are roots (no parent leakage)."""
    d = cluster.daemons[0]
    client = d.client()
    out = await client.get_rate_limits(
        [RateLimitRequest(name="untraced", unique_key="u1", hits=1,
                          limit=5, duration=60_000)]
    )
    assert out[0].error == ""
    await client.close()
    rpc_spans = exporter.by_name("grpc.recv.pb.gubernator.V1.GetRateLimits")
    assert rpc_spans, "server RPC span missing"
    assert all(s.parent_span_id is None for s in rpc_spans)


def test_traceparent_future_version_with_trailing_fields_accepted():
    # W3C forward compatibility: higher versions may append fields; parse
    # the first four and ignore the rest.  Version 00 allows no tail.
    tid, sid = "1" * 32, "1234567890abcdef"
    assert Tracer.extract(
        {"traceparent": f"01-{tid}-{sid}-01-extradata"}
    ) == SpanContext(tid, sid, 1)
    assert Tracer.extract({"traceparent": f"00-{tid}-{sid}-01-extra"}) is None


# ---------------------------------------------------------------------
# Flight recorder (docs/observability.md): stage accounting on a
# virtual clock — no daemon, no device, no wall-clock sleeps.
# ---------------------------------------------------------------------
def test_flight_recorder_stage_accounting():
    from gubernator_tpu.resilience.clock import ManualClock
    from gubernator_tpu.utils import flightrec

    clk = ManualClock(start=100.0)
    rec = flightrec.FlightRecorder(windows=4, clock=clk)
    seen = []
    rec.observer = lambda stage, s: seen.append((stage, round(s, 6)))

    # decode happens before any window exists; it folds into the next
    # begin().  encode trails the last finished window.
    rec.edge("decode", 0.001)
    wid = rec.begin(width=8, depth=2)
    assert rec.active() == wid
    rec.note(wid, "lease", 0.0005)
    rec.note(wid, "pack", 0.002)
    rec.note(wid, "h2d", 0.003)
    rec.end_dispatch(wid)
    assert rec.active() is None
    rec.note(wid, "tick", 0.004)
    rec.note(wid, "resolve", 0.001)
    rec.finish(wid)
    rec.edge("encode", 0.0015)

    recs = rec.recent()
    assert len(recs) == 1
    r = recs[0]
    assert r["window"] == wid and r["width"] == 8 and r["queue_depth"] == 2
    assert r["wall"] == 100.0  # stamped from the injected clock
    assert r["stages_ms"]["decode"] == 1.0   # folded-forward edge
    assert r["stages_ms"]["encode"] == 1.5   # attached-backward edge
    assert r["stages_ms"]["pack"] == 2.0
    # the lease lies inside pack: an overlay, recorded and not summed
    assert r["stages_ms"]["lease"] == 0.5
    assert r["total_ms"] == pytest.approx(12.5)
    # finish() pushed every nonzero stage through the observer, and the
    # encode edge reported directly.
    assert ("pack", 0.002) in seen and ("encode", 0.0015) in seen

    snap = rec.snapshot()
    pcts = snap["stages"]
    assert pcts["h2d"] == {"p50_ms": 3.0, "p99_ms": 3.0}
    assert pcts["decode"]["p50_ms"] == 1.0
    assert snap["total"] == {"p50_ms": 12.5, "p99_ms": 12.5}


def test_flight_recorder_ring_wrap_and_staleness():
    from gubernator_tpu.resilience.clock import ManualClock
    from gubernator_tpu.utils import flightrec

    clk = ManualClock()
    rec = flightrec.FlightRecorder(windows=4, clock=clk)
    wids = []
    for i in range(10):
        w = rec.begin(width=1, depth=0)
        rec.note(w, "pack", 0.001 * (i + 1))
        rec.finish(w)
        clk.advance(1.0)
        wids.append(w)
    # Only the last `windows` records survive the wrap.
    recs = rec.recent()
    assert [r["window"] for r in recs] == wids[-4:]
    # Notes against an evicted window are dropped, not misattributed.
    rec.note(wids[0], "pack", 99.0)
    assert all(r["stages_ms"]["pack"] < 90_000 for r in rec.recent())
    # recent(n) bounds the tail.
    assert [r["window"] for r in rec.recent(2)] == wids[-2:]


def test_flight_recorder_slow_window_watchdog_split():
    from gubernator_tpu.utils import flightrec

    rec = flightrec.FlightRecorder(windows=8, slow_threshold_s=0.005)
    fast = rec.begin(width=1, depth=0)
    rec.note(fast, "pack", 0.001)
    rec.finish(fast)
    slow = rec.begin(width=4, depth=1)
    rec.note(slow, "tick", 0.010)
    rec.finish(slow)

    assert rec.slow_total == 1
    dumps = rec.drain_slow()
    assert [d["window"] for d in dumps] == [slow]
    assert dumps[0]["stages_ms"]["tick"] == 10.0
    assert dumps[0]["width"] == 4
    assert rec.drain_slow() == []  # drained exactly once


def test_flight_recorder_global_slot():
    from gubernator_tpu.utils import flightrec

    assert flightrec.get() is None and not flightrec.enabled()
    rec = flightrec.FlightRecorder(windows=2)
    flightrec.install(rec)
    try:
        assert flightrec.get() is rec and flightrec.enabled()
    finally:
        flightrec.uninstall()
    assert flightrec.get() is None


# ---------------------------------------------------------------------
# The whole tick-loop cycle as stages, the overlays beside them, and the
# one helper that times a stage (utils/flightrec.py)
# ---------------------------------------------------------------------
def test_stage_sets_partition_the_names():
    from benchmarks.harness.recorder import TotalsRecorder
    from gubernator_tpu.utils import flightrec

    assert len(set(flightrec.STAGES)) == len(flightrec.STAGES)
    assert set(flightrec.CYCLE) | set(flightrec.OVERLAYS) < set(flightrec.STAGES)
    assert not set(flightrec.CYCLE) & set(flightrec.OVERLAYS)
    # the benchmark's recorder seeds its totals from STAGES: every name
    # is a key, so a reader of a new stage finds it
    assert set(TotalsRecorder().totals()["stage_s"]) == set(flightrec.STAGES)


@pytest.mark.parametrize(
    "name", ["lease", "queue", "finish_lock", "cpu", "compile", "gc", "wait",
             "gather_cpu", "pack_cpu", "tick_cpu"])
def test_overlays_and_wait_are_recorded_and_not_summed(name):
    """An overlay lies inside another stage or on another thread, and
    ``wait`` is the thread idle between windows: each is in ``recent()``
    and the histogram's feed, none in ``total_ms`` or the slow check."""
    from gubernator_tpu.utils import flightrec

    assert name in flightrec.OVERLAYS or name == "wait"
    rec = flightrec.FlightRecorder(windows=4, slow_threshold_s=0.005)
    seen = []
    rec.observer = lambda stage, s: seen.append(stage)
    wid = rec.begin(width=1, depth=0)
    rec.note(wid, "gather", 0.001)
    rec.note(wid, name, 0.050)
    rec.finish(wid)
    r = rec.recent()[-1]
    assert r["stages_ms"][name] == 50.0
    assert r["total_ms"] == pytest.approx(1.0)
    assert name in seen
    assert rec.slow_total == 0 and rec.drain_slow() == []
    assert rec.snapshot()["total"]["p99_ms"] == pytest.approx(1.0)
    # the same seconds in a stage of the window's own work are slow
    wid = rec.begin(width=1, depth=0)
    rec.note(wid, "handle", 0.050)
    rec.finish(wid)
    assert [d["total_ms"] for d in rec.drain_slow()] == [50.0]


class _CountingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: counts what is built."""

    built: list = []

    def __init__(self, name):
        type(self).built.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture()
def annotations(monkeypatch):
    import jax.profiler

    monkeypatch.setattr(_CountingAnnotation, "built", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    return _CountingAnnotation.built


def test_stage_helper_notes_and_annotates_only_while_installed(annotations):
    from gubernator_tpu.utils import flightrec

    assert flightrec.stage("pack") is flightrec.OFF
    with flightrec.stage("pack") as off:
        pass
    assert off.seconds == 0.0 and annotations == []

    rec = flightrec.FlightRecorder(windows=4)
    flightrec.install(rec)
    try:
        wid = rec.begin(width=1, depth=0)
        with flightrec.stage("pack") as st:       # the window in dispatch
            with flightrec.stage("lease"):
                pass
        waited = flightrec.stage("wait", into=None).start()
        waited.stop()                             # noted by the caller
        rec.end_dispatch(wid)
        flightrec.stage("handoff", into=wid).start().stop()
        with flightrec.stage("encode"):           # an edge: fr.edge()
            pass
        rec.finish(wid)
    finally:
        flightrec.uninstall()
    assert annotations == ["guber.pack", "guber.lease", "guber.wait",
                           "guber.handoff", "guber.encode"]
    ms = rec.recent()[-1]["stages_ms"]
    assert st.seconds > 0
    assert ms["pack"] == pytest.approx(st.seconds * 1e3, abs=1e-3)
    assert 0 < ms["lease"] <= ms["pack"]
    assert ms["handoff"] > 0 and ms["encode"] > 0
    assert ms["wait"] == 0.0 and waited.seconds > 0 and waited.t1 > 0
    assert flightrec.stage("pack") is flightrec.OFF


def test_install_sets_and_uninstall_drops_both_listeners():
    """The collector's and jax.monitoring's listeners live exactly while
    a recorder is installed; a forced collection and a first-met shape
    each land in the window in dispatch and in the counters."""
    import gc

    import jax.numpy as jnp
    from jax._src import monitoring

    from gubernator_tpu.utils import flightrec

    def listeners():
        return (flightrec._on_gc in gc.callbacks,
                flightrec._on_compile
                in monitoring.get_event_duration_listeners())

    assert listeners() == (False, False)
    rec = flightrec.FlightRecorder(windows=4)
    flightrec.install(rec)
    try:
        assert listeners() == (True, True)
        flightrec.install(rec)                    # once, however often
        assert gc.callbacks.count(flightrec._on_gc) == 1
        # before the first window: start-up's, not serving's
        gc.collect()
        assert rec.stalls()["gc_collections"] == [0, 0, 0]
        wid = rec.begin(width=1, depth=0)
        gc.collect()
        (jnp.zeros((3, 1237), jnp.float32) + 1).block_until_ready()
        rec.end_dispatch(wid)
        gc.collect(0)                             # no window in dispatch:
        rec.finish(wid)                           # the newest begun
    finally:
        flightrec.uninstall()
    assert listeners() == (False, False)
    gc.collect()                                  # nobody listens
    stalls = rec.stalls()
    assert stalls["gc_collections"][2] == 1 and stalls["gc_collections"][0] >= 1
    assert stalls["gc_pause_seconds"][2] > 0
    assert stalls["serving_compiles"] >= 1
    assert stalls["serving_compile_seconds"] > 0
    ms = rec.recent()[-1]["stages_ms"]
    assert ms["gc"] == pytest.approx(
        sum(stalls["gc_pause_seconds"]) * 1e3, abs=1e-2)
    assert ms["compile"] == pytest.approx(
        stalls["serving_compile_seconds"] * 1e3, abs=1e-2)


# ---------------------------------------------------------------------
# CPU seconds by stage and by thread (PR 39)
# ---------------------------------------------------------------------
def _burn(seconds):
    """Run on the CPU until this thread has executed ``seconds``."""
    import time

    c0 = time.thread_time()
    while time.thread_time() - c0 < seconds:
        pass


@pytest.mark.parametrize("busy", [True, False])
def test_a_stage_notes_its_threads_cpu_beside_its_wall(busy):
    """Round a busy loop ``<stage>_cpu`` is about the stage's wall; round
    a sleep about nothing; never more than the wall."""
    import time

    from gubernator_tpu.utils import flightrec

    rec = flightrec.FlightRecorder(windows=4)
    flightrec.install(rec)
    try:
        wid = rec.begin(width=1, depth=0)
        for name in ("gather", "handle"):
            with flightrec.stage(name):
                _burn(0.03) if busy else time.sleep(0.03)
        rec.finish(wid)
    finally:
        flightrec.uninstall()
    ms = rec.recent()[-1]["stages_ms"]
    for name in ("gather", "handle"):
        assert ms[name + "_cpu"] <= ms[name]
        if busy:
            assert ms[name + "_cpu"] >= 30.0
        else:
            assert ms[name + "_cpu"] < 5.0 and ms[name] >= 30.0


def test_the_cpu_of_a_stage_taken_out_goes_with_its_wall():
    """``ssd`` out of ``pack`` takes its CPU off ``pack_cpu``; the
    mesh's ``route``, a share of ``pack``'s wall by the native pass's
    clock, leaves the range's CPU in ``pack_cpu``."""
    from gubernator_tpu.utils import flightrec

    rec = flightrec.FlightRecorder(windows=4)
    flightrec.install(rec)
    try:
        one_chip = rec.begin(width=1, depth=0)
        with flightrec.stage("pack") as pk1:
            with flightrec.stage("ssd") as read:
                _burn(0.01)
            read.out_of("pack")
            _burn(0.02)
        rec.finish(one_chip)
        mesh = rec.begin(width=1, depth=0)
        with flightrec.stage("pack") as pk2:
            _burn(0.02)
        rec.note(mesh, "route", pk2.seconds / 2)    # as MeshTickEngine
        rec.note(mesh, "pack", -pk2.seconds / 2)
        rec.finish(mesh)
    finally:
        flightrec.uninstall()
    one_chip, mesh = (r["stages_ms"] for r in rec.recent())
    for name in ("pack", "ssd"):
        assert 0 < one_chip[name + "_cpu"] <= one_chip[name] + 1e-3
    assert one_chip["pack_cpu"] == pytest.approx(
        (pk1.cpu - read.cpu) * 1e3, abs=1e-3)
    assert one_chip["pack"] == pytest.approx(
        (pk1.seconds - read.seconds) * 1e3, abs=1e-3)
    assert mesh["pack_cpu"] == pytest.approx(pk2.cpu * 1e3, abs=1e-3)
    assert 0 < mesh["pack_cpu"] <= mesh["pack"] + mesh["route"] + 1e-3
    assert "route_cpu" not in mesh


def test_read_clocks_reads_the_registered_threads_cpu():
    """A registered thread that burns 50 ms of CPU between two readings
    shows at least 40 ms under its overlay, an idle one under 5; the
    process's CPU is at least the three threads'."""
    import threading

    from gubernator_tpu.utils import flightrec

    rec = flightrec.FlightRecorder(windows=4)
    ready = threading.Barrier(3, timeout=60)
    go, done, leave = threading.Event(), threading.Event(), threading.Event()

    def edge():
        rec.register_thread("edge")
        ready.wait()
        assert go.wait(60)
        _burn(0.05)
        done.set()
        assert leave.wait(60)     # alive, so its clock is read

    def resolver():
        rec.register_thread("resolver")
        ready.wait()
        assert leave.wait(60)

    threads = [threading.Thread(target=f) for f in (edge, resolver)]
    for t in threads:
        t.start()
    try:
        ready.wait()
        wid = rec.begin(width=1, depth=0)
        rec.read_clocks()                  # the clocks' first reading
        go.set()
        assert done.wait(60)
        rec.read_clocks()                  # into the newest window begun
        rec.finish(wid)
    finally:
        leave.set()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    ms = rec.recent()[-1]["stages_ms"]
    assert ms["edge_thread_cpu"] >= 40.0
    assert ms["resolver_cpu"] < 5.0
    # every thread is in the process's CPU (the clocks are read one after
    # another, so a window's two intervals differ by microseconds)
    python = sum(ms[k] for k in flightrec.THREADS.values())
    assert ms["process_cpu"] >= python - 0.5
    assert set(rec._threads) == {"tickloop", "edge", "resolver"}
    # clocks bypass the observer and a window's total
    assert rec.recent()[-1]["total_ms"] == 0.0


def test_clocks_read_before_the_first_window_note_nothing():
    """Readings before any window was begun set the clocks' baseline
    and are noted nowhere; the next reading goes to the window begun."""
    from gubernator_tpu.utils import flightrec

    rec = flightrec.FlightRecorder(windows=4)
    rec.read_clocks()
    _burn(0.01)
    rec.read_clocks()
    assert not rec._stage_s.any()
    wid = rec.begin(width=1, depth=0)
    _burn(0.01)
    rec.read_clocks()
    rec.finish(wid)
    ms = rec.recent()[-1]["stages_ms"]
    assert ms["tickloop_thread_cpu"] >= 9.0
    assert ms["process_cpu"] >= ms["tickloop_thread_cpu"] - 0.5


def test_without_a_recorder_no_clock_is_read(monkeypatch):
    """Off, a stage, a thread's registration and the clocks' reading
    read no clock: one check each."""
    from gubernator_tpu.utils import flightrec

    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read with no recorder")

    assert flightrec.get() is None
    monkeypatch.setattr(flightrec, "time", NoClock())
    for name in ("gather", "decode", "encode", "tick", "pack"):
        with flightrec.stage(name) as st:
            pass
        assert st is flightrec.OFF and st.cpu == 0.0
    for role in flightrec.THREADS:
        flightrec.register_thread(role)
    flightrec.read_clocks()


def _drive_windows(engine, n_windows, wrap=None):
    """``n_windows`` one-call windows through a real TickLoop on a
    module-scoped cluster engine; ``wrap(loop)`` may wrap its methods
    before the first call."""
    from gubernator_tpu.ops.reqcols import ReqColumns
    from gubernator_tpu.service.tickloop import TickLoop

    loop = TickLoop(engine, batch_wait=0.0)
    try:
        if wrap is not None:
            wrap(loop)
        for w in range(n_windows):
            cols = ReqColumns.from_requests([
                RateLimitRequest(name="stages", unique_key=f"w{w}k{i}",
                                 hits=1, limit=100, duration=60_000)
                for i in range(8)])
            mat, errs = loop.submit_columns(cols).result(timeout=120)
            assert mat.shape == (5, 8) and not errs
    finally:
        loop.close()
    return loop


def test_disjoint_stages_add_up_to_the_flush(cluster):
    """A window's stages from ``gather`` to ``handoff`` tile ``_flush``
    on the tick-loop thread: their sum is its wall time (the first
    window, which may compile, left out); ``wait`` and the overlays
    ride beside them."""
    import time

    from gubernator_tpu.utils import flightrec

    flush_s = []

    def wrap(loop):
        inner = loop._flush

        def timed(batch, wait=flightrec.OFF):
            t0 = time.perf_counter()
            inner(batch, wait)
            flush_s.append(time.perf_counter() - t0)

        loop._flush = timed

    rec = flightrec.FlightRecorder(windows=16)
    flightrec.install(rec)
    try:
        _drive_windows(cluster.daemons[0].instance.engine, 6, wrap)
    finally:
        flightrec.uninstall()
    recs = rec.recent()
    assert len(recs) == len(flush_s) == 6
    flush = flightrec.CYCLE[1:]
    assert flush[0] == "gather" and flush[-1] == "handoff"
    staged = sum(r["stages_ms"][s] for r in recs[1:] for s in flush) / 1e3
    wall = sum(flush_s[1:])
    assert staged <= wall
    assert wall - staged <= 0.15 * wall + 5e-4, (staged, wall)
    for r in recs:
        ms = r["stages_ms"]
        for s in ("gather", "submit_lock", "pack", "h2d", "handle",
                  "handoff", "lease", "cpu", "queue", "tick", "resolve"):
            assert ms[s] > 0, (s, ms)
        assert ms["lease"] <= ms["pack"]
        assert ms["finish_lock"] > 0 and ms["finish_lock"] <= ms["tick"]
        # what the thread executed is no more than the wall it spanned
        # (the first window's first calls run between its stages)
        if r is not recs[0]:
            assert ms["cpu"] <= sum(ms[s] for s in flush) * 1.05 + 0.05
        for s in ("gather", "pack", "h2d", "handle", "tick"):
            assert 0 < ms[s + "_cpu"] <= ms[s] + 1e-3, (s, ms)
        python = sum(ms[k] for k in flightrec.THREADS.values())
        assert ms["process_cpu"] >= python - 0.5
    # tick-loop reads the clocks at the end of each window's wait, into
    # the window before it: every window but the last has its reading;
    # the resolver registered at its first drain, and is read from then
    assert sum(r["stages_ms"]["resolver_cpu"] for r in recs) > 0
    assert all(r["stages_ms"]["tickloop_thread_cpu"] > 0 for r in recs[:-1])
    # every window after the first waited for its call
    assert all(r["stages_ms"]["wait"] > 0 for r in recs[1:])


def test_without_a_recorder_no_annotation_and_no_stamp(cluster, annotations):
    import gc

    from jax._src import monitoring

    from gubernator_tpu.utils import flightrec

    stamps = []

    def wrap(loop):
        inner = loop._flush

        def spy(batch, wait=flightrec.OFF):
            stamps.extend(it.t_enq for it in batch)
            assert wait is flightrec.OFF
            inner(batch, wait)

        loop._flush = spy

    assert flightrec.get() is None
    _drive_windows(cluster.daemons[0].instance.engine, 2, wrap)
    assert stamps == [0.0, 0.0]
    assert annotations == []
    assert flightrec._on_gc not in gc.callbacks
    assert (flightrec._on_compile
            not in monitoring.get_event_duration_listeners())
    # and with one installed the stamp is taken and the ranges are built
    rec = flightrec.FlightRecorder(windows=4)
    flightrec.install(rec)
    try:
        del stamps[:]
        _drive_windows(cluster.daemons[0].instance.engine, 1)
    finally:
        flightrec.uninstall()
    # one range a stage of the dispatch thread's cycle; the resolver's
    # three stages are seconds only, so the old guber.tick is gone
    assert set(annotations) - {"guber.gc"} == {
        "guber." + s for s in flightrec.CYCLE + ("lease",)
        if s not in ("route", "ssd")}
    ms = rec.recent()[-1]["stages_ms"]
    assert all(ms[s] > 0 for s in flightrec.RESOLVER)
