"""The event loop's whole wall, accounted (utils/flightrec.py): the edge
handler's own time a call (``edge_handler`` / ``edge_handler_cpu``), the
loop's idle in ``select()`` (``edge_idle``), the wall the thread clocks
span (``clock_wall``), and the four per-layer metrics that read them."""

import asyncio
import json
import os
import time
from collections import Counter

import pytest

from benchmarks import run
from gubernator_tpu.utils import flightrec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _CountingRecorder(flightrec.FlightRecorder):
    """Counts the notes of each stage and sums their seconds, whatever
    window they went to."""

    def __init__(self):
        super().__init__(windows=16)
        self.notes = Counter()
        self.seconds = Counter()

    def note(self, wid, stage, seconds):
        self.notes[stage] += 1
        self.seconds[stage] += seconds
        super().note(wid, stage, seconds)


class _NoClock:
    def __getattr__(self, name):
        raise AssertionError(f"time.{name} read with no recorder")


def test_edge_names_are_overlays_and_idle_and_wall_are_clocks():
    """None of the new names is in a window's total or the slow check;
    ``edge_idle`` and ``clock_wall`` are read with the CPU clocks and
    bypass the observer as they do."""
    for name in flightrec.EDGE:
        assert name in flightrec.OVERLAYS and name not in flightrec.CLOCKS
    assert {"edge_idle", "clock_wall"} <= set(flightrec.CLOCKS)
    assert set(flightrec.CLOCKS) <= set(flightrec.OVERLAYS)


async def test_a_served_call_notes_the_handler_once_and_nothing_unrecorded(
        monkeypatch):
    """A fast-path call through the gRPC edge notes ``edge_handler`` and
    ``edge_handler_cpu`` once each while a recorder is installed; with
    none it reads no clock of flightrec's.  The first edge call also
    times the loop's ``select()``, and ``uninstall()`` gives the loop
    its own selector back."""
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.ops.reqcols import ReqColumns
    from gubernator_tpu.transport.daemon import DaemonClient, spawn_daemon
    from gubernator_tpu.types import RateLimitRequest

    conf = DaemonConfig(grpc_listen_address="127.0.0.1:0",
                        http_listen_address="", peer_discovery_type="none")
    d = await spawn_daemon(conf)
    client = DaemonClient(d.advertise_address)
    cols = ReqColumns.from_requests([
        RateLimitRequest(name="edge", unique_key=f"k{i}", hits=1,
                         limit=100, duration=60_000) for i in range(4)])
    try:
        assert d.instance.columns_fast_path_ok()
        await client.get_rate_limits_columns(cols, timeout=30.0)  # warm
        assert flightrec.get() is None
        with monkeypatch.context() as m:
            m.setattr(flightrec, "time", _NoClock())
            mat, errors = await client.get_rate_limits_columns(
                cols, timeout=30.0)
        assert not errors and (mat[2] == 98).all()

        loop = asyncio.get_running_loop()
        own = loop._selector
        rec = _CountingRecorder()
        flightrec.install(rec)
        try:
            rec.begin(width=1, depth=0)   # so the loop's notes find a window
            mat, errors = await client.get_rate_limits_columns(
                cols, timeout=30.0)
            assert isinstance(loop._selector, flightrec._TimedSelector)
            assert loop._selector.inner is own
        finally:
            flightrec.uninstall()
        assert loop._selector is own
    finally:
        await client.close()
        await d.close()
    assert not errors and (mat[2] == 97).all()
    assert rec.notes["edge_handler"] == 1
    assert rec.notes["edge_handler_cpu"] == 1
    assert rec.seconds["edge_handler"] > 0
    assert rec.seconds["edge_handler_cpu"] >= 0
    assert rec.edge_idle_s > 0       # the loop waited in select() for the tick


def test_the_selector_wrapper_times_select_until_uninstall():
    """While installed, ``select()``'s wall adds up in ``edge_idle_s``
    and ``read_clocks`` notes what it moved as ``edge_idle``: a loop that
    sleeps 50 ms between two readings reads at least 40, and no more
    than the wall between them; after ``uninstall()`` the loop's
    selector is the original object and nothing more is added."""
    rec = _CountingRecorder()

    async def main():
        loop = asyncio.get_running_loop()
        own = loop._selector
        flightrec.install(rec)
        try:
            wid = rec.begin(width=1, depth=0)
            flightrec.register_thread("edge")
            assert loop._selector is not own
            flightrec.register_thread("edge")        # once a recorder
            assert loop._selector.inner is own
            rec.read_clocks()
            await asyncio.sleep(0.05)
            rec.read_clocks()
            rec.finish(wid)
        finally:
            flightrec.uninstall()
        assert loop._selector is own
        idle = rec.edge_idle_s
        await asyncio.sleep(0.01)
        return wid, idle

    wid, idle = asyncio.run(main())
    assert rec.edge_idle_s == idle
    assert rec.notes["edge_idle"] == 1
    assert 0.04 <= rec.seconds["edge_idle"] <= rec.seconds["clock_wall"]
    last = rec.recent()[-1]
    assert last["window"] == wid and last["stages_ms"]["edge_idle"] >= 40.0
    assert last["total_ms"] == 0.0


def test_register_thread_off_a_loop_times_no_selector():
    """A thread with no running loop registers as ``edge`` and nothing
    is wrapped (the recorder's own tests register plain threads)."""
    rec = flightrec.FlightRecorder(windows=4)
    flightrec.install(rec)
    try:
        rec.register_thread("edge")
        assert rec._timed == []
    finally:
        flightrec.uninstall()


def test_a_recorder_not_installed_times_no_selector():
    """Only the installed recorder wraps a loop's selector: one that
    ``uninstall()`` cannot reach leaves the loop as it was."""
    rec = flightrec.FlightRecorder(windows=4)

    async def main():
        loop = asyncio.get_running_loop()
        own = loop._selector
        rec.register_thread("edge")
        return loop._selector is own

    assert asyncio.run(main())
    assert rec._timed == []


def test_clock_wall_totals_the_wall_between_the_first_and_last_reading():
    """``clock_wall``'s total over the windows is the wall from the
    first ``read_clocks`` to the last, whatever windows it went into."""
    rec = _CountingRecorder()
    rec.begin(width=1, depth=0)
    t0 = time.perf_counter()
    rec.read_clocks()
    after_first = time.perf_counter()
    time.sleep(0.03)
    rec.begin(width=1, depth=0)
    rec.read_clocks()
    time.sleep(0.02)
    before_last = time.perf_counter()
    rec.read_clocks()
    t1 = time.perf_counter()
    total = rec.seconds["clock_wall"]
    assert rec.notes["clock_wall"] == 2
    assert before_last - after_first <= total <= t1 - t0
    assert total >= 0.05
    # one clock with the threads': the edge thread unregistered, the
    # tick-loop thread (the reader) is, and both span the same readings
    assert rec.notes["tickloop_thread_cpu"] == 2


def test_without_a_recorder_an_edge_call_reads_no_clock(monkeypatch):
    """Off, ``edge_call()`` is one check and ``None``."""
    assert flightrec.get() is None
    monkeypatch.setattr(flightrec, "time", _NoClock())
    assert flightrec.edge_call() is None


def test_an_edge_call_pauses_across_its_wait():
    """The handler's two segments: what runs between ``pause()`` and
    ``resume()`` (the wait on the tick) is in neither clock."""
    rec = _CountingRecorder()
    flightrec.install(rec)
    try:
        rec.begin(width=1, depth=0)
        call = flightrec.edge_call()
        _burn(0.01)
        call.pause()
        _burn(0.05)
        time.sleep(0.05)
        call.resume()
        _burn(0.01)
        call.end()
    finally:
        flightrec.uninstall()
    assert rec.notes["edge_handler"] == rec.notes["edge_handler_cpu"] == 1
    assert 0.015 <= rec.seconds["edge_handler"] < 0.06
    assert rec.seconds["edge_handler_cpu"] < 0.06


def _burn(seconds):
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


# ---------------------------------------------------------------------
# The four readers
# ---------------------------------------------------------------------
# One 10-second run of 6,000 calls: the loop 8 s on the CPU, 1.5 s idle
# in select(), so 0.5 s stalled; the handler 1.8 s of the 8.
STAGE_S = {"edge_thread_cpu": 8.0, "edge_handler_cpu": 1.8,
           "edge_handler": 2.4, "edge_idle": 1.5, "clock_wall": 10.0}
CALLS = 6000
EXPECTED = {
    "edge_thread_busy_pct": 80.0,
    "edge_handler_cpu_us_per_call": 300.0,
    "edge_plumbing_cpu_us_per_call": 1033.3333333333333,
    "edge_stalled_pct": 5.0,
}


def _ctx(stage_s, calls=CALLS):
    return {"recorder": {"stage_s": dict(stage_s), "windows": 1500,
                         "rows": 4000 * 1500,
                         "edge_calls": {"decode": calls, "encode": calls}},
            "wall_s": 10.0}


def _all_stages():
    return {s: STAGE_S.get(s, 0.0) for s in flightrec.STAGES}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_edge_reader_reads_its_overlays(name):
    read = run.reader("layer_metrics", name)
    assert read(_ctx(_all_stages())) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_edge_reader_returns_nothing_without_the_edge_names(name):
    """A recorder with the thread clocks (``edge_thread_cpu``,
    ``process_cpu``) and none of the edge names, as the program before
    them had: the line leaves the metric out; so does an untraced run,
    and a run with no call or no reading."""
    new = set(flightrec.EDGE) | {"clock_wall"}
    parent = {s: 1.0 for s in flightrec.STAGES if s not in new}
    read = run.reader("layer_metrics", name)
    assert read(_ctx(parent)) is None
    assert read({"recorder": None, "wall_s": 10.0}) is None
    assert read(_ctx(dict.fromkeys(flightrec.STAGES, 0.0), calls=0)) is None


def test_the_edge_readers_add_up():
    """busy + idle + stalled is the wall; handler + plumbing is the
    thread's CPU a call."""
    ctx = _ctx(_all_stages())

    def value(name):
        return run.reader("layer_metrics", name)(ctx)

    idle = 100.0 * STAGE_S["edge_idle"] / STAGE_S["clock_wall"]
    assert value("edge_thread_busy_pct") + idle + value("edge_stalled_pct") \
        == pytest.approx(100.0)
    assert value("edge_handler_cpu_us_per_call") \
        + value("edge_plumbing_cpu_us_per_call") \
        == pytest.approx(STAGE_S["edge_thread_cpu"] * 1e6 / CALLS)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_edge_entry_is_read_in_every_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    entry = {m["name"]: m for m in per_layer}[name]
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py"))
    assert entry == {"name": name, "unit": entry["unit"], "better": "lower",
                     "source": "program_counter", "layer": "transport edge",
                     "moves": "decisions_per_s"}
    assert entry["unit"] == ("%" if name.endswith("_pct") else "us/call")
