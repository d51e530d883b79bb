"""Per-window stage timer / flight recorder (docs/observability.md).

A preallocated ring of serving-window records: each window that flows
through ``TickLoop`` → ``TickEngine``/``MeshTickEngine`` gets one row
holding the wall time of every stage of its way, plus queue depth and
batch width.

Gating mirrors ``tracing.enabled()``: recording happens only while a
recorder is installed (``install()``), so an un-instrumented daemon pays
a single ``is None`` check per stage.  The record path itself is
``@hot_path`` code — host-scalar writes into preallocated numpy arrays,
no device syncs, no locks on the per-stage ``note`` path (each
(window, stage) cell has exactly one writer).

One way to time a stage: ``with flightrec.stage("pack"):`` (or its
``start()`` / ``stop()`` pair where the interval is no block).  While a
recorder is installed it notes the wall seconds into the window in
dispatch and holds a ``jax.profiler.TraceAnnotation("guber.pack")`` open
for the same interval, so the stage is a named range on the host plane
of an XProf capture, on the clock of the device's ``XLA Ops``.  With no
recorder it is one ``is None`` check and builds nothing.  The resolver's
three stages (``RESOLVER``) are seconds only: that thread is inside
``tick`` for nearly the whole of the dispatch thread's cycle, so in a
trace reduced by "the host event that overlaps a device gap most"
(benchmarks/harness/xtrace.py) its ranges would own every gap and hide
the stage the device was waiting for; the fetch inside ``tick`` is the
runtime's own event there already.

Stage semantics.  ``CYCLE`` is the ``tick-loop`` thread's whole cycle,
disjoint and consecutive on that one thread, one name on both engines:

- ``wait``: between windows, from the end of one ``_flush`` to the pop
  of the next batch (the condition variable and BatchWait).  Part of
  the thread's cycle, no part of the window's work (an idle service
  waits for as long as nobody calls): left out of ``total_ms`` and the
  slow-window check.
- ``gather``: ``_flush`` up to the call into the engine: the expiry
  partition, the item loop, ``ReqColumns.concat`` of the window's calls.
- ``submit_lock``: ``submit_columns`` waiting to take ``engine._lock``.
- ``route`` is the sharded engine's alone (zero on one chip): keys to
  shards by CRC-32, the batch regrouped by shard, one native slot
  resolve a shard, and the hit/miss accounting; noted from the native
  pass's own clock and taken off the ``pack`` range that holds the call.
- ``pack``: the host pack, the arena ``lease`` inside it; ``ssd`` is the
  miss path's batched slab-store lookup, broken OUT of ``pack`` (the
  engine subtracts it), so a pack regression can't hide SSD I/O and
  vice versa.
- ``h2d``: the upload and the program call, queued and not awaited.
- ``handle``: ``submit_columns`` after ``h2d`` to its return: the
  ``TickHandle``, the slab's retirement, the counters.
- ``handoff``: back in ``_flush``: the parts' ``release()`` and the
  bounded ``_resolve_q.put`` (which blocks when the pipeline is full).

Beside them, on other threads:

- ``decode``/``encode`` are transport edges recorded per request batch
  via ``edge()``; decode time accumulates and folds into the *next*
  window begun, encode attaches to the most recently finished window
  (a window's decode is the CPU that fed it; its encode trails it).
- ``tick`` is the shared D2H wait of the resolver drain that resolved
  the window; windows resolved in one drain report the same tick time.
  ``resolve`` is the window's own un-permute and delivery.

``OVERLAYS`` lie inside another stage or on another thread.  They are
recorded in the ring, the totals and the histogram like any stage, and
left out of a window's ``total_ms`` and of the slow-window check, which
would count their seconds twice:

- ``lease``: the staging slab's lease, inside ``pack``.
- ``queue``: the mean, over the window's items, of pop time less
  enqueue time (``QueueItem.t_enq``, stamped only while a recorder is
  installed).
- ``finish_lock``: the resolver's wait to take ``engine._lock`` in
  ``TickHandle._finish``, inside ``tick``.
- ``cpu``: ``time.thread_time()`` across ``_flush`` on ``tick-loop``:
  what the thread executed, so the stages of ``_flush`` less ``cpu`` is
  what it spent off the CPU (the GIL, a lock, the runtime).
- ``compile``: seconds of trace, lower and backend compile met while
  serving (``jax.monitoring``'s duration events, on whichever thread).
- ``gc``: seconds of garbage collections (``gc.callbacks``), any
  generation, which stop every thread of the process.
- ``<stage>_cpu`` (``_CPU``: ``gather``, ``pack``, ``ssd``, ``h2d``,
  ``handle`` on ``tick-loop``, ``tick`` on the resolver):
  ``time.thread_time()`` across the stage, read inside its wall clock,
  noted where its wall goes.  A stage's wall less its CPU is its thread
  off the CPU inside it; in a stage that makes no blocking call
  (``gather``, ``handle``) that is the wait for the GIL.  On the
  sharded engine ``pack_cpu`` is the CPU of ``pack``'s whole range,
  which holds the native pass whose ``route`` share of the wall is
  noted apart: compare it with ``pack`` + ``route``.
- ``CLOCKS``, noted by ``read_clocks`` as what each moved since its
  previous call, so their totals over a run span the same interval:
  ``tickloop_thread_cpu``, ``edge_thread_cpu``, ``resolver_cpu`` (each
  registered thread's whole CPU: ``register_thread``, read with
  ``time.clock_gettime`` on its ``pthread_getcpuclockid``),
  ``process_cpu`` (``time.process_time()``; less the three, the
  runtime's native threads), ``edge_idle`` (the event loop's wall inside
  its selector's ``select()``, where it waits for events: a running
  total kept by the wrapper that ``register_thread("edge")`` puts on the
  running loop's selector and ``uninstall()`` takes off) and
  ``clock_wall`` (``time.perf_counter()``: the wall the others span).
  ``tick-loop`` reads them at the end of its ``wait`` for each window,
  so that their cost falls in no stage of the window's work.  They are
  clocks, not latencies: the observer does not see them.  Less
  ``edge_thread_cpu`` and ``edge_idle``, ``clock_wall`` is the loop
  neither on the CPU nor idle: waiting for the GIL, a lock or a core.
- ``edge_handler`` / ``edge_handler_cpu`` (``EDGE``): one fast-path
  call's time in ``V1Servicer.GetRateLimits`` on the event loop, wall
  (``perf_counter``) and ``time.thread_time()``, from its entry to its
  suspension on the tick's future and from its resumption to its return
  (``edge_call()``), noted once a call when it returns, into the window
  in dispatch or else the newest begun.

``compile`` and ``gc`` go into the window in dispatch or else the newest
begun; their listeners are set by ``install()`` and dropped by
``uninstall()``, and the recorder's own counters of them
(``gc_pause_s``, ``gc_collections``, ``compile_s``, ``compiles``) are
what ``/metrics`` and ``/debug/state`` read.

The slow-window watchdog is split so the hot path stays cheap:
``finish()`` only compares the row total against ``slow_threshold_s``
and parks offenders in a small deque; a supervised loop in the daemon
drains them (``drain_slow()``), dumps each record, and bumps
``gubernator_tpu_slow_windows``.
"""

from __future__ import annotations

import gc
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

from gubernator_tpu.utils.hotpath import hot_path
from gubernator_tpu.utils import sanitize

CYCLE = (
    "wait", "gather", "submit_lock", "route", "pack", "ssd", "h2d",
    "handle", "handoff",
)
# The stages that note their thread's CPU beside their wall, as the
# overlay "<stage>_cpu".
_CPU = {s: s + "_cpu" for s in (
    "gather", "pack", "ssd", "h2d", "handle", "tick")}
# The Python threads whose CPU clocks ``read_clocks`` reads, by role,
# and the overlay each delta is noted as.
THREADS = {"tickloop": "tickloop_thread_cpu", "edge": "edge_thread_cpu",
           "resolver": "resolver_cpu"}
# Noted by ``read_clocks`` (the deltas since its previous call): clocks,
# not latencies, so they bypass the observer.
CLOCKS = tuple(THREADS.values()) + ("process_cpu", "edge_idle", "clock_wall")
# The edge handler's own time a call: edge_call().
EDGE = ("edge_handler", "edge_handler_cpu")
OVERLAYS = ("lease", "queue", "finish_lock", "cpu", "compile", "gc") \
    + tuple(_CPU.values()) + EDGE + CLOCKS
RESOLVER = ("tick", "resolve", "finish_lock")   # seconds, no range
STAGES = ("decode",) + CYCLE + ("tick", "resolve", "encode") + OVERLAYS
_IDX = {s: i for i, s in enumerate(STAGES)}
_RANGE = {s: "guber." + s for s in STAGES if s not in RESOLVER}
_OBSERVED = [(s, i) for s, i in _IDX.items() if s not in CLOCKS]
_DECODE = _IDX["decode"]
_ENCODE = _IDX["encode"]
# 1.0 for the stages a window's total (and the slow check) adds up.
_IN_TOTAL = np.array(
    [s not in OVERLAYS and s != "wait" for s in STAGES], np.float64)
GC_GENERATIONS = 3
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class FlightRecorder:
    """Preallocated ring of per-window stage records."""

    def __init__(
        self,
        windows: int = 256,
        clock: Callable[[], float] = time.time,
        slow_threshold_s: float = 0.0,
    ):
        if windows < 2:
            raise ValueError("flight recorder needs at least 2 windows")
        self.windows = windows
        self.clock = clock
        self.slow_threshold_s = slow_threshold_s
        # Optional sink: called as observer(stage, seconds) at finish()
        # (the daemon wires it to the per-stage latency histogram).
        self.observer: Optional[Callable[[str, float], None]] = None
        self._lock = sanitize.lock("FlightRecorder._lock")
        self._stage_s = np.zeros((windows, len(STAGES)), np.float64)
        self._width = np.zeros(windows, np.int64)
        self._depth = np.zeros(windows, np.int64)
        self._wall = np.zeros(windows, np.float64)
        self._valid = np.zeros(windows, bool)
        self._seq = 0
        self._active: Optional[int] = None
        self._pending_decode = 0.0
        # role -> [CPU clock id, the clock at the last read]; see
        # register_thread().
        self._threads: Dict[str, list] = {}
        self._process_cpu: Optional[float] = None
        self._clock_wall: Optional[float] = None
        # The event loop's seconds in select() so far (its selector's
        # wrapper adds to it) and at the last read_clocks.
        self.edge_idle_s = 0.0
        self._edge_idle: Optional[float] = None
        # (loop, its _TimedSelector) while installed; see _time_select().
        self._timed: list = []
        self.slow_total = 0
        self._slow: deque = deque(maxlen=32)
        # Stalls met while serving (after the first window was begun),
        # written by the process's collector and jax.monitoring hooks
        # (note_gc / note_compile) and read by /metrics and /debug/state.
        self.gc_pause_s = [0.0] * GC_GENERATIONS
        self.gc_collections = [0] * GC_GENERATIONS
        self.compile_s = 0.0
        self.compiles = 0   # backend compiles: one a first-met shape

    # -- record path (hot) ---------------------------------------------
    @hot_path
    def begin(self, width: int, depth: int) -> int:
        """Open a window record at dispatch time; returns its id."""
        with self._lock:
            wid = self._seq
            self._seq = wid + 1
            slot = wid % self.windows
            self._stage_s[slot, :] = 0.0
            self._valid[slot] = False
            self._width[slot] = width
            self._depth[slot] = depth
            self._wall[slot] = self.clock()
            self._stage_s[slot, _DECODE] = self._pending_decode
            self._pending_decode = 0.0
            self._active = wid
        return wid

    def register_thread(self, role: str) -> None:
        """Name the calling thread as ``role`` (a key of ``THREADS``) so
        that ``read_clocks`` reads its CPU clock.  The first thread to
        register a role keeps it until its clock can no longer be read
        (the thread ended): one serving process, one ``TickLoop``.  The
        event loop's first registration also times its selector's
        ``select()`` (``edge_idle``)."""
        if role not in self._threads:
            self._threads[role] = [
                time.pthread_getcpuclockid(threading.get_ident()), None]
            if role == "edge":
                _time_select(self)

    @hot_path
    def read_clocks(self) -> None:
        """Note, into the newest window begun, what each of ``CLOCKS``
        moved since the previous call: the registered threads' CPU, the
        process's, the event loop's idle and the wall.  Called by the
        thread that begins windows, which is ``tickloop``, before it
        begins the next."""
        self.register_thread("tickloop")
        wid = self._seq - 1
        for role, t in list(self._threads.items()):
            try:
                cpu = time.clock_gettime(t[0])
            except OSError:               # the thread is gone
                self._threads.pop(role, None)
                continue
            if t[1] is not None and cpu >= t[1]:
                self.note(wid, THREADS[role], cpu - t[1])
            t[1] = cpu
        process = time.process_time()
        if self._process_cpu is not None:
            self.note(wid, "process_cpu", process - self._process_cpu)
        self._process_cpu = process
        idle = self.edge_idle_s
        if self._edge_idle is not None:
            self.note(wid, "edge_idle", idle - self._edge_idle)
        self._edge_idle = idle
        wall = time.perf_counter()
        if self._clock_wall is not None:
            self.note(wid, "clock_wall", wall - self._clock_wall)
        self._clock_wall = wall

    @hot_path
    def note(self, wid: Optional[int], stage: str, seconds: float) -> None:
        """Accumulate ``seconds`` into one stage cell of window ``wid``."""
        if wid is None or wid < 0 or self._seq - wid > self.windows:
            return
        self._stage_s[wid % self.windows, _IDX[stage]] += seconds

    @hot_path
    def finish(self, wid: int) -> None:
        """Seal a window record; runs the cheap slow-window check."""
        if wid < 0 or self._seq - wid > self.windows:
            return
        slot = wid % self.windows
        self._valid[slot] = True
        obs = self.observer
        if obs is not None:
            row = self._stage_s[slot]
            for stage, i in _OBSERVED:
                if row[i] > 0.0:
                    obs(stage, row[i])
        thresh = self.slow_threshold_s
        if thresh > 0.0:
            total = self._stage_s[slot] @ _IN_TOTAL
            if total > thresh:
                with self._lock:
                    self.slow_total += 1
                    self._slow.append((
                        wid,
                        self._stage_s[slot].copy(),
                        self._width[slot],
                        self._depth[slot],
                        self._wall[slot],
                    ))

    def active(self) -> Optional[int]:
        """Window id currently in engine dispatch (``None`` between)."""
        return self._active

    def end_dispatch(self, wid: int) -> None:
        if self._active == wid:
            self._active = None

    def edge(self, stage: str, seconds: float) -> None:
        """Record a transport-edge stage (decode/encode) for one batch."""
        if stage == "decode":
            with self._lock:
                self._pending_decode += seconds
        else:
            with self._lock:
                last = self._seq - 1
                if last >= 0:
                    self._stage_s[last % self.windows, _ENCODE] += seconds
        obs = self.observer
        if obs is not None:
            obs(stage, seconds)

    def _current(self) -> Optional[int]:
        """The window a stall of the whole process, or an edge call, is
        noted into: the one in dispatch, else the newest begun;
        None before the first (start-up's compiles and collections are
        not serving's).  Takes no lock: the collector's hook runs
        wherever an allocation happens, ``begin`` included."""
        wid = self._active
        if wid is None:
            wid = self._seq - 1
        return wid if wid >= 0 else None

    def note_gc(self, generation: int, seconds: float) -> None:
        wid = self._current()
        if wid is None:
            return
        self.gc_pause_s[generation] += seconds
        self.gc_collections[generation] += 1
        self.note(wid, "gc", seconds)

    def note_compile(self, event: str, seconds: float) -> None:
        wid = self._current()
        if wid is None:
            return
        self.compile_s += seconds
        if event == _BACKEND_COMPILE:
            self.compiles += 1
        self.note(wid, "compile", seconds)

    # -- read path -----------------------------------------------------
    @staticmethod
    def _record(wid, row, width, depth, wall) -> dict:
        return {
            "window": int(wid),
            "wall": float(wall),
            "width": int(width),
            "queue_depth": int(depth),
            "stages_ms": {
                s: round(float(row[i]) * 1e3, 4) for s, i in _IDX.items()
            },
            # the window's work: OVERLAYS and ``wait`` are not in it
            "total_ms": round(float(row @ _IN_TOTAL) * 1e3, 4),
        }

    def recent(self, n: int = 64) -> List[dict]:
        """Finished window records, oldest→newest, as JSON-ready dicts."""
        out: List[dict] = []
        with self._lock:
            seq = self._seq
            lo = max(0, seq - min(n, self.windows))
            for wid in range(lo, seq):
                slot = wid % self.windows
                if self._valid[slot]:
                    out.append(self._record(
                        wid, self._stage_s[slot], self._width[slot],
                        self._depth[slot], self._wall[slot]))
        return out

    def snapshot(self) -> dict:
        """Per-stage and whole-window p50/p99 (ms) over finished windows
        in the ring, plus ring metadata: /debug/pipeline's and the
        control plane's view (autoscaler, /debug/autoscaler).  Zero
        cells (stage never ran in that window) are excluded.  Not
        ``@hot_path``: one lock-copy on the reader's cadence."""
        with self._lock:
            mask = self._valid.copy()
            stage_s = self._stage_s.copy()
            slow_total = self.slow_total

        def pcts(col):
            col = col[col > 0.0]
            if col.size == 0:
                return {"p50_ms": 0.0, "p99_ms": 0.0}
            return {
                "p50_ms": round(float(np.percentile(col, 50)) * 1e3, 4),
                "p99_ms": round(float(np.percentile(col, 99)) * 1e3, 4),
            }

        done = stage_s[mask]
        return {
            "stages": {s: pcts(done[:, i]) for s, i in _IDX.items()},
            "total": pcts(done @ _IN_TOTAL),
            "windows": int(mask.sum()),
            "ring_size": self.windows,
            "slow_total": slow_total,
        }

    def stalls(self) -> dict:
        """The four stall counters, as /debug/state shows them."""
        return {
            "gc_pause_seconds": list(self.gc_pause_s),
            "gc_collections": list(self.gc_collections),
            "serving_compile_seconds": self.compile_s,
            "serving_compiles": self.compiles,
        }

    def drain_slow(self) -> List[dict]:
        """Pop pending slow-window dumps (watchdog loop calls this)."""
        out: List[dict] = []
        with self._lock:
            while self._slow:
                out.append(self._record(*self._slow.popleft()))
        return out


# ---------------------------------------------------------------------
# Process-global recorder slot (mirrors tracing's global tracer: the
# in-process test cluster shares one recorder across daemons).
_recorder: Optional[FlightRecorder] = None
# jax.profiler.TraceAnnotation, bound at the first install() so that
# importing this module imports no jax.
_annotation = None


class _Stage:
    """One timed stage of the installed recorder: a named range in a
    profiler capture (but for ``RESOLVER``'s) and, at its end, the
    seconds noted.  ``into`` says where: ``ACTIVE`` the window in
    dispatch when the stage ends, a window id that window, ``None``
    nowhere (the caller notes ``seconds`` itself: a stage that ends
    before its window is begun, or belongs to several).  ``decode`` and
    ``encode`` go through ``edge()``."""

    __slots__ = ("_fr", "_name", "_into", "_range", "_t0", "t1", "seconds",
                 "_cpu", "_c0", "cpu")

    def __init__(self, fr: FlightRecorder, name: str, into):
        self._fr = fr
        self._name = name
        self._into = into
        self._cpu = _CPU.get(name)
        self.t1 = self.seconds = self.cpu = 0.0

    @hot_path
    def start(self) -> "_Stage":
        # The wall clock is read first here and last in stop(), the
        # thread's CPU clock inside it: the range's own cost falls inside
        # the stage, not between two stages, so consecutive stages tile
        # the thread's time, and a stage's CPU is never more than its wall.
        self._t0 = time.perf_counter()
        if self._cpu is not None:
            self._c0 = time.thread_time()
        name = _RANGE.get(self._name)
        self._range = rng = None if name is None else _annotation(name)
        if rng is not None:
            rng.__enter__()
        return self

    @hot_path
    def stop(self) -> None:
        if self._range is not None:
            self._range.__exit__(None, None, None)
        cname = self._cpu
        if cname is not None:
            self.cpu = time.thread_time() - self._c0
        self.t1 = t1 = time.perf_counter()
        self.seconds = dt = t1 - self._t0
        fr, name, into = self._fr, self._name, self._into
        if name == "decode" or name == "encode":
            fr.edge(name, dt)
            return
        wid = fr.active() if into is ACTIVE else into
        if wid is not None:
            fr.note(wid, name, dt)
            if cname is not None:
                fr.note(wid, cname, self.cpu)

    @hot_path
    def out_of(self, outer: str) -> None:
        """This stage, ended, lay inside the range of ``outer`` in the
        window in dispatch: take its wall and its CPU off ``outer``'s
        (``ssd`` out of ``pack``)."""
        fr = self._fr
        wid = fr.active()
        fr.note(wid, outer, -self.seconds)
        fr.note(wid, _CPU[outer], -self.cpu)

    __enter__ = start

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False


class _NoStage:
    """What ``stage()`` hands out with no recorder installed."""

    __slots__ = ()
    t1 = seconds = cpu = 0.0

    def start(self) -> "_NoStage":
        return self

    def stop(self) -> None:
        pass

    __enter__ = start

    def __exit__(self, *exc) -> bool:
        return False


ACTIVE = object()
OFF = _NoStage()


@hot_path
def stage(name: str, into=ACTIVE):
    """Time one stage: ``with stage("pack"):`` or ``s = stage(...).start()``
    ... ``s.stop()``.  See :class:`_Stage`; with no recorder installed,
    one check and a shared no-op."""
    fr = _recorder
    if fr is None:
        return OFF
    return _Stage(fr, name, into)


class _EdgeCall:
    """One fast-path call's time in the edge handler on the event loop:
    two segments, from ``V1Servicer.GetRateLimits``'s entry to
    ``pause()`` (its suspension on the tick's future) and from
    ``resume()`` to ``end()`` (its return).  ``end()`` notes their wall
    as ``edge_handler`` and their ``time.thread_time()`` as
    ``edge_handler_cpu``.  The wall clock is read outside the CPU clock
    at both ends of a segment, as in :class:`_Stage`."""

    __slots__ = ("_fr", "_t0", "_c0", "wall", "cpu")

    def __init__(self, fr: FlightRecorder):
        self._fr = fr
        self.wall = self.cpu = 0.0
        self.resume()

    @hot_path
    def resume(self) -> None:
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time()

    @hot_path
    def pause(self) -> None:
        self.cpu += time.thread_time() - self._c0
        self.wall += time.perf_counter() - self._t0

    @hot_path
    def end(self) -> None:
        self.pause()
        fr = self._fr
        wid = fr._current()
        fr.note(wid, "edge_handler", self.wall)
        fr.note(wid, "edge_handler_cpu", self.cpu)


@hot_path
def edge_call() -> Optional[_EdgeCall]:
    """Start timing one edge call (see :class:`_EdgeCall`); with no
    recorder installed, one check and ``None``."""
    fr = _recorder
    if fr is None:
        return None
    return _EdgeCall(fr)


class _TimedSelector:
    """An event loop's selector whose ``select()`` adds its wall to the
    recorder's ``edge_idle_s`` (``read_clocks`` notes it as
    ``edge_idle``); every other attribute is the selector's own."""

    def __init__(self, inner, fr: FlightRecorder):
        self.inner = inner
        self._fr = fr

    def select(self, timeout=None):
        t0 = time.perf_counter()
        try:
            return self.inner.select(timeout)
        finally:
            self._fr.edge_idle_s += time.perf_counter() - t0

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _time_select(fr: FlightRecorder) -> None:
    """Put a :class:`_TimedSelector` on the calling thread's running
    loop while ``fr`` is the installed recorder, where the loop has a
    selector (asyncio's default loop does) that is not timed already;
    ``uninstall()`` puts the original back."""
    import asyncio

    try:
        loop = asyncio.get_running_loop()
    except RuntimeError:
        return
    sel = getattr(loop, "_selector", None)
    if fr is not _recorder or sel is None or isinstance(sel, _TimedSelector):
        return
    timed = _TimedSelector(sel, fr)
    loop._selector = timed
    fr._timed.append((loop, timed))


def register_thread(role: str) -> None:
    """Name the calling thread ``role`` to the installed recorder (see
    :meth:`FlightRecorder.register_thread`); with none, one check."""
    fr = _recorder
    if fr is not None:
        fr.register_thread(role)


def read_clocks() -> None:
    """The installed recorder's :meth:`FlightRecorder.read_clocks`; with
    none, one check."""
    fr = _recorder
    if fr is not None:
        fr.read_clocks()


# A collection stops every thread, so one start/stop pair is in flight
# at a time and module state holds it.
_gc_open = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    fr = _recorder
    if fr is None:
        return
    if phase == "start":
        _gc_open = _Stage(fr, "gc", None).start()
    elif _gc_open is not None:
        st, _gc_open = _gc_open, None
        st.stop()
        fr.note_gc(info["generation"], st.seconds)


def _on_compile(event: str, seconds: float, **_kw) -> None:
    fr = _recorder
    if fr is not None and event.startswith("/jax/core/compile/"):
        fr.note_compile(event, seconds)


def install(recorder: FlightRecorder) -> None:
    """Install ``recorder`` and the two listeners that feed its stall
    overlays (the collector's callbacks, jax.monitoring's durations)."""
    global _recorder, _annotation
    import gubernator_tpu.jaxinit  # noqa: F401  (x64 + cache before jax use)
    import jax.monitoring
    import jax.profiler

    _annotation = jax.profiler.TraceAnnotation
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
    _recorder = recorder


def uninstall() -> None:
    global _recorder, _gc_open
    fr, _recorder = _recorder, None
    _gc_open = None
    while fr is not None and fr._timed:
        loop, timed = fr._timed.pop()
        if getattr(loop, "_selector", None) is timed:
            loop._selector = timed.inner
    if _on_gc in gc.callbacks:
        import jax.monitoring

        gc.callbacks.remove(_on_gc)
        jax.monitoring.unregister_event_duration_listener(_on_compile)


def get() -> Optional[FlightRecorder]:
    return _recorder


def enabled() -> bool:
    return _recorder is not None
