"""The tick loop: batch accumulation in front of the device engine.

Replaces the reference's per-request worker dispatch (``workers.go:190-258``
channel hops) with the BASELINE.json north star: requests accumulate on the
host and flush to the TPU once per tick.  The window policy matches the
reference's peer-batching policy (``peer_client.go:284-337``): flush when
``batch_limit`` requests are waiting or ``batch_wait`` has elapsed since the
first queued request — so an idle service adds zero latency and a busy one
amortizes the device round trip over the whole window.

Two threads pipeline the ticks (SURVEY §7 "may need double-buffered
ticks"): the *dispatch* thread packs window N+1 and queues its device work
while the *resolver* thread waits out window N's D2H and completes the
waiters' futures — so sustained throughput is bounded by
max(host pack, device tick), not their sum.  ``submit`` is thread-safe and
returns a ``concurrent.futures.Future`` the caller can await.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from concurrent.futures import Future
from typing import List, Sequence

import numpy as np

from gubernator_tpu.admission import (
    CLASS_CLIENT,
    POLICY_FAIL_CLOSED,
    SHED_EXPIRED_MSG,
    SHED_RESHARD_MSG,
    SHED_SHUTDOWN_MSG,
    AdmissionConfig,
    AdmissionQueue,
    AimdLimiter,
    QueueItem,
    under_pressure,
)
from gubernator_tpu.types import RateLimitRequest, RateLimitResponse, Status
from gubernator_tpu.utils import flightrec
from gubernator_tpu.utils.hotpath import hot_path
from gubernator_tpu.utils.metrics import TICK_BRANCHES

_EMPTY_MATRIX = np.zeros((5, 0), np.int64)

# Default for how many dispatched-but-unresolved windows may be in
# flight.  2 is full double-buffering; deeper rides out D2H jitter AND
# matters directly on high-RTT links: the resolver drains every queued
# window into ONE device-to-host transfer, so depth bounds how many
# windows amortize each round trip.  The bound is the backpressure:
# when the device falls behind, dispatch blocks instead of queueing
# unbounded work.  GUBER_TICK_PIPELINE_DEPTH overrides — read via the
# config registry at TickLoop construction (NOT import: an import-time
# read froze the knob for the whole process, so config changes and
# tests silently saw the stale value).
from gubernator_tpu.config import env_knob
from gubernator_tpu.utils import sanitize

DEFAULT_PIPELINE_DEPTH = 4


def resolve_pipeline_depth(depth=None) -> int:
    """The effective tick pipeline depth: an explicit constructor value
    wins, else GUBER_TICK_PIPELINE_DEPTH, else the default — evaluated
    at call time so the environment is re-read per constructed loop."""
    if depth is not None:
        return max(1, int(depth))
    try:
        return max(1, env_knob(
            "GUBER_TICK_PIPELINE_DEPTH", DEFAULT_PIPELINE_DEPTH,
            parse=int))
    except ValueError:
        return DEFAULT_PIPELINE_DEPTH


def _complete(fut: Future, result) -> None:
    """set_result tolerating a concurrent cancel: asyncio.wrap_future
    propagates waiter cancellation to the concurrent Future at any moment
    (it is never 'running'), so check-then-set is inherently racy."""
    try:
        if not fut.cancelled():
            fut.set_result(result)
    except Exception:  # InvalidStateError: cancelled between check and set
        pass


def _fail_waiters(waiters, exc: Exception) -> None:
    for _, fut in waiters:
        try:
            if not fut.cancelled():
                fut.set_exception(exc)
        except Exception:
            pass


class TickLoop:
    """Accumulates request batches and applies them to an engine per tick."""

    def __init__(
        self,
        engine,
        batch_wait: float = 500e-6,
        batch_limit: int = 1000,
        metrics=None,
        pipeline_depth: int = None,
        admission: AdmissionConfig = None,
        clock=time.monotonic,
    ):
        self.engine = engine
        self.batch_wait = float(batch_wait)
        self.batch_limit = int(batch_limit)
        self.metrics = metrics
        self.pipeline_depth = resolve_pipeline_depth(pipeline_depth)
        # Overload control plane (docs/overload.md).  The injected clock
        # drives ONLY deadline math (ManualClock in tests); the batch
        # window below stays on real time so a frozen test clock cannot
        # wedge the dispatch thread's timed wait.
        self.admission = (
            admission if admission is not None else AdmissionConfig.from_env()
        )
        self._clock = clock
        self.shed_policy = self.admission.shed_policy
        self.limiter = AimdLimiter(
            self.admission.target_p99_ms, max_limit=self.batch_limit)
        self._queue = AdmissionQueue(
            self.admission.effective_pending_limit(self.batch_limit))
        self.metric_shed_admission = {}  # reason -> shed request count
        self.metric_expired_served = 0  # invariant: stays 0
        self._synced_expired_served = 0
        # Engine counter mirrors already synced into prometheus families
        # (the engine counts in plain ints; deltas flow here per tick).
        self._synced_hits = 0
        self._synced_misses = 0
        self._synced_unexpired = 0
        self._synced_cold_hits = 0
        self._synced_promotions = 0
        self._synced_demotions = 0
        self._synced_ssd_hits = 0
        self._synced_ssd_promotions = 0
        self._synced_ssd_demotions = 0
        self._synced_ssd_compactions = 0
        self._synced_shed = 0
        self._synced_leaky_rows = 0
        self._synced_routed = 0
        self._synced_mesh = {
            "metric_dup_windows": 0, "metric_unique_windows": 0,
            "metric_native_pack_windows": 0, "metric_h2d_uploads": 0,
        }
        self._synced_branch = dict.fromkeys(TICK_BRANCHES, 0)
        self._synced_routed_overflows = 0
        self._cond = sanitize.condition("TickLoop._cond")
        self._pending_count = 0
        self._running = True
        # Reshard admission freeze (docs/resharding.md): level 1 sheds
        # new CLIENT windows with a retriable status while PEER windows
        # keep draining; level 2 (cutover) sheds both.  Queued work is
        # never dropped by a freeze — it drains through _flush as usual.
        self._freeze_level = 0
        # Windows handed to the resolver but not yet delivered; quiesce()
        # waits for this to reach zero (resolve_q.empty() alone races the
        # resolver's in-progress item).
        self._inflight_windows = 0
        self._resolve_q: "queue.Queue" = queue.Queue(
            maxsize=self.pipeline_depth)
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="tick-loop"
        )
        self._resolver = threading.Thread(
            target=self._resolve_loop, daemon=True, name="tick-resolve"
        )
        self._thread.start()
        self._resolver.start()

    def submit(
        self,
        requests: Sequence[RateLimitRequest],
        deadline: float = None,
        klass: int = CLASS_CLIENT,
    ) -> "Future[List[RateLimitResponse]]":
        """Queue a request batch for the next tick.  ``deadline`` is the
        batch's absolute admission deadline on this loop's clock (None =
        never shed); ``klass`` is the admission class (peer reconcile
        traffic outranks client traffic under overload)."""
        return self._enqueue("obj", list(requests), len(requests),
                             deadline, klass)

    def under_pressure(self) -> bool:
        """True while the overload plane is actively backing off —
        the lease tier's cue to answer grants with cheap TTL extension
        instead of full decisions (admission.under_pressure)."""
        return under_pressure(
            self.limiter, self._pending_count,
            self.admission.effective_pending_limit(self.batch_limit),
            self.batch_limit,
        )

    def admission_snapshot(self) -> dict:
        """One consistent view of the admission plane for the control
        plane (autoscaler, /debug/autoscaler): limiter state, queue
        depth, cumulative shed counts, freeze level.  Takes the loop
        condition briefly; not ``@hot_path`` — it runs on the
        controller's sampling cadence, never inside a tick."""
        with self._cond:
            return {
                "limiter": self.limiter.snapshot(),
                "queue": self._queue.snapshot(),
                "pending": self._pending_count,
                "shed": dict(self.metric_shed_admission),
                "frozen": self._freeze_level > 0,
            }

    def submit_columns(self, cols, deadline: float = None,
                       klass: int = CLASS_CLIENT) -> "Future":
        """Queue a columnar batch; the future resolves to the
        ``((5, n) matrix, errors)`` pair — no response objects anywhere
        on the path (the transport fast path; engine must expose
        submit_cols)."""
        return self._enqueue("cols", cols, len(cols), deadline, klass)

    def _enqueue(self, kind: str, payload, n: int, deadline: float = None,
                 klass: int = CLASS_CLIENT) -> Future:
        fut: Future = Future()
        if n == 0:
            fut.set_result(
                [] if kind == "obj" else (_EMPTY_MATRIX, {})
            )
            return fut
        with self._cond:
            if not self._running:
                fut.set_exception(RuntimeError("tick loop is shut down"))
                return fut
            item = QueueItem(kind, payload, n, fut, deadline, klass)
            if flightrec.enabled():
                item.t_enq = time.perf_counter()   # the "queue" overlay
            lvl = self._freeze_level
            if lvl and (lvl >= 2 or klass == CLASS_CLIENT):
                frozen, shed = item, ()
            else:
                frozen = None
                shed = self._queue.push(item)
                self._pending_count = self._queue.requests
                if self.metrics is not None:
                    self.metrics.worker_queue_length.labels(
                        method="GetRateLimits", worker="0"
                    ).set(self._pending_count)
                    self.metrics.admission_queue_depth.set(
                        self._pending_count)
                self._cond.notify()
        if frozen is not None:
            # Answered outside the lock like overflow victims: a frozen
            # window gets the retriable reshard status immediately (it
            # was never queued), so callers retry after the bounded
            # cutover instead of waiting it out.
            self._shed_item(frozen, "reshard")
            return fut
        # Answer overflow victims outside the lock: they are already
        # unlinked from the queue, and shed answers may release arena
        # leases / complete futures with waiting callbacks.
        for victim in shed:
            self._shed_item(victim, "overflow")
        return fut

    @hot_path
    def _run(self) -> None:
        # The flight recorder's "wait": this thread between windows,
        # from the end of one _flush to the pop of the next batch.  It
        # ends before its window is begun, so _flush notes it.
        wait = flightrec.stage("wait", into=None).start()
        while True:
            batch: List[QueueItem] = []
            stopping = False
            with self._cond:
                while self._running and not self._queue:
                    self._cond.wait()
                if not self._running and not self._queue:
                    stopping = True
                else:
                    # Batch window: once something is queued, wait out the
                    # tick (or until the batch fills) to let more requests
                    # coalesce.
                    deadline = time.monotonic() + self.batch_wait
                    while (
                        self._running
                        and self._pending_count < self.batch_limit
                    ):
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cond.wait(timeout=remaining)
                    # Admitted window width: the AIMD limiter narrows it
                    # under measured saturation; shutdown drains at full
                    # width so a throttled loop still closes promptly.
                    # Whatever does not fit stays queued (in priority
                    # order) for the next tick.
                    width = self.batch_limit
                    if self._running and self.limiter.enabled:
                        width = min(width, self.limiter.window_limit)
                    batch = self._queue.pop_window(width)
                    # Written only under _cond; the one unlocked reader is
                    # under_pressure(), a per-grant heuristic that tolerates
                    # one-tick staleness of a GIL-atomic int by design.
                    # guber: allow-g009(advisory queue-depth mirror - the unlocked under_pressure read tolerates one-tick staleness of a GIL-atomic int)
                    self._pending_count = self._queue.requests
                    # Count the window from the moment it leaves the queue:
                    # quiesce must see a batch wedged inside engine dispatch
                    # (it is neither queued nor at the resolver yet, but the
                    # cutover cannot run until it resolves).
                    if batch:
                        self._inflight_windows += 1
            if stopping:
                # The drain/stop sentinel ships OUTSIDE the condition: the
                # resolver handoff queue is bounded, and a full pipeline
                # must park the dispatch thread without wedging every
                # _cond waiter behind it (guberlint G007).
                wait.stop()
                # guber: allow-G001(shutdown-only drain sentinel - runs once at loop exit, never inside a serving tick)
                self._resolve_q.put(None)
                return
            if batch:
                # the recorder's thread clocks, in no stage of the window
                flightrec.read_clocks()
                wait.stop()
                self._flush(batch, wait)
                wait = flightrec.stage("wait", into=None).start()

    @hot_path
    def _flush(self, batch: List[QueueItem], wait=flightrec.OFF) -> None:
        """Dispatch one window.  Object and columnar submissions each
        coalesce into (at most) one engine submission; both ride the same
        resolver handoff and resolve together in one D2H.

        Flight-recorder stages (utils/flightrec.py), consecutive on this
        thread: ``wait`` (the caller's, noted into the window once it is
        begun), ``gather`` to the call into the engine, the engine's own,
        ``handoff`` from its return; beside them the overlays ``queue``
        (pop time less the items' enqueue stamps) and ``cpu``."""
        fr = flightrec.get()
        cpu0 = time.thread_time() if fr is not None else 0.0
        gather = flightrec.stage("gather").start()
        # Deadline-aware admission (docs/overload.md): shed anything
        # already expired BEFORE packing — the device never burns a tick
        # answering an RPC whose caller has given up.  Shed items are
        # answered with a retriable error status, never dropped.
        now = self._clock()
        expired = [it for it in batch if it.expired(now)]
        if expired:
            batch = [it for it in batch if not it.expired(now)]
            for it in expired:
                self._shed_item(it, "expired")
        if not batch:
            gather.stop()
            self._window_done()
            return
        # Flight-recorder window open (docs/observability.md): the engine
        # notes its stages into the active window while we dispatch.
        wid = None
        if fr is not None:
            wid = fr.begin(
                sum(it.n for it in batch), self._resolve_q.qsize())
            fr.note(wid, "wait", wait.seconds)
            stamps = [it.t_enq for it in batch if it.t_enq]
            if stamps and wait.t1:
                fr.note(wid, "queue", wait.t1 - sum(stamps) / len(stamps))
        t0 = time.perf_counter()
        obj_items: List[tuple] = []   # (n, fut)
        reqs: List[RateLimitRequest] = []
        col_parts: List = []
        col_items: List[tuple] = []
        for it in batch:
            # Invariant counter (tests/test_admission.py holds it at
            # 0): an expired item reaching the pack stage means the
            # partition above regressed.  Counted (and exported), never
            # silently served.
            if it.expired(now):
                self.metric_expired_served += it.n
            if it.kind == "cols":
                col_parts.append(it.payload)
                col_items.append((it.n, it.fut))
            else:
                reqs.extend(it.payload)
                obj_items.append((it.n, it.fut))

        # Every engine (single-chip TickEngine AND the sharded
        # MeshTickEngine) speaks the dispatch/resolve split: submissions
        # queue device work and the resolver thread materializes many
        # windows in one D2H.  There is deliberately no synchronous
        # fallback — an engine without submit/submit_cols is a bug.
        subs = []
        cols = None
        if col_parts:
            from gubernator_tpu.ops.reqcols import ReqColumns

            try:
                cols = ReqColumns.concat(col_parts)
            except Exception as e:
                _fail_waiters(col_items, e)
        gather.stop()
        try:
            if reqs:
                try:
                    subs.append(("obj", self.engine.submit(reqs),
                                 obj_items, len(reqs)))
                except Exception as e:
                    _fail_waiters(obj_items, e)
            if cols is not None:
                try:
                    subs.append(("cols", self.engine.submit_cols(cols),
                                 col_items, sum(n for n, _ in col_items)))
                except Exception as e:
                    _fail_waiters(col_items, e)
        finally:
            handoff = flightrec.stage("handoff", into=wid).start()
            # Arena-backed batches (fastwire decode slabs) recycle
            # the moment the engine has packed them — submit_cols
            # copies every column into the device request matrix
            # before returning, so the views are dead here.
            for p in col_parts:
                p.release()
        if fr is not None and wid is not None:
            fr.end_dispatch(wid)
        if not subs:
            handoff.stop()
            if fr is not None and wid is not None:
                fr.note(wid, "cpu", time.thread_time() - cpu0)
                fr.finish(wid)
            self._window_done()
            return
        # Bounded handoff: blocks when pipeline_depth windows are already
        # in flight (device behind), which is exactly the backpressure the
        # dispatch thread should feel.  The in-flight count was taken at
        # pop time in _run; the resolver releases it after the D2H drain.
        # guber: allow-G001(deliberate bounded-pipeline backpressure - blocking here when pipeline_depth windows are in flight IS the flow control)
        self._resolve_q.put((subs, time.perf_counter() - t0, wid))
        # Noted after the handoff, whose wait is the stage: a resolver
        # that seals the window first leaves these two out of that
        # window's histogram sample and slow check, never of the ring.
        handoff.stop()
        if fr is not None and wid is not None:
            fr.note(wid, "cpu", time.thread_time() - cpu0)

    def _window_done(self) -> None:
        """Release one window's in-flight count without a resolver trip
        (the window shed or failed entirely before dispatch)."""
        with self._cond:
            self._inflight_windows = max(0, self._inflight_windows - 1)
            self._cond.notify_all()

    def _resolve_loop(self) -> None:
        while True:
            item = self._resolve_q.get()
            if item is None:
                return
            # Drain whatever else is queued: all drained windows resolve
            # with ONE device-to-host transfer (engine.resolve_ticks) —
            # per-transfer latency is the throughput ceiling when the
            # device is remote, so the resolver never fetches one window
            # at a time when several are in flight.
            items = [item]
            stop = False
            while True:
                try:
                    nxt = self._resolve_q.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    stop = True
                    break
                items.append(nxt)
            fr = flightrec.get()
            flightrec.register_thread("resolver")
            # All drained windows share this one D2H wait; each reports
            # it as its tick time (documented in flightrec).
            with flightrec.stage("tick", into=None) as drain:
                try:
                    from gubernator_tpu.ops.engine import resolve_ticks

                    resolve_ticks([
                        h
                        for subs, _, _ in items
                        for _, sb, _, _ in subs
                        for h in sb.handles()
                    ])
                except Exception:
                    pass  # per-window resolution below surfaces real errors
            if fr is not None:
                for _, _, wid in items:
                    if wid is not None:
                        fr.note(wid, "tick", drain.seconds)
                        fr.note(wid, "tick_cpu", drain.cpu)
            for subs, dispatch_s, wid in items:
                for kind, sb, waiters, n_reqs in subs:
                    # Guarded: an exception escaping this loop would kill
                    # the resolver thread and wedge the whole pipeline
                    # (dispatch eventually blocks on the bounded queue).
                    try:
                        # timed recorder or not: the limiter's sample
                        t1 = time.perf_counter()
                        with flightrec.stage("resolve", into=wid):
                            out = (
                                sb.responses() if kind == "obj"
                                else sb.matrix()
                            )
                        resolve_s = time.perf_counter() - t1
                    except Exception as e:
                        _fail_waiters(waiters, e)
                        continue
                    try:
                        self._deliver_kind(
                            kind, waiters, out, n_reqs,
                            dispatch_s + resolve_s,
                        )
                    except Exception:
                        logging.getLogger("gubernator.tickloop").exception(
                            "tick delivery failed"
                        )
                if fr is not None and wid is not None:
                    fr.finish(wid)
            with self._cond:
                self._inflight_windows = max(
                    0, self._inflight_windows - len(items))
                self._cond.notify_all()
            if stop:
                return

    def _deliver_kind(self, kind, waiters, out, n_reqs, tick_s) -> None:
        if kind == "obj":
            self._deliver(waiters, out, n_reqs, tick_s)
            return
        mat, errors = out
        self._metrics_sync(n_reqs, tick_s)
        off = 0
        for n, fut in waiters:
            errs = {
                i - off: msg for i, msg in errors.items()
                if off <= i < off + n
            } if errors else {}
            _complete(fut, (mat[:, off : off + n], errs))
            off += n

    def _deliver(self, waiters, out, n_reqs: int, tick_s: float) -> None:
        """Complete object waiters' futures + sync metrics.  ``tick_s`` is
        the window's own engine time (dispatch + resolve), NOT wall time
        since flush — under pipelining the latter would include time
        queued behind earlier windows and misreport device health."""
        self._metrics_sync(n_reqs, tick_s)
        off = 0
        for n, fut in waiters:
            _complete(fut, out[off : off + n])
            off += n

    # ------------------------------------------------------------------
    # Reshard admission freeze (docs/resharding.md)
    # ------------------------------------------------------------------
    def freeze(self, shed_peers: bool = False) -> None:
        """Stop admitting new windows into the transition epoch: CLIENT
        submissions answer the retriable reshard status immediately;
        PEER submissions keep draining (they outrank clients and must
        land before the cutover) until ``shed_peers`` escalates the
        freeze for the bounded cutover itself.  Idempotent; never
        downgrades an escalated freeze."""
        with self._cond:
            self._freeze_level = max(
                self._freeze_level, 2 if shed_peers else 1)

    def unfreeze(self) -> None:
        with self._cond:
            self._freeze_level = 0
            self._cond.notify_all()

    @property
    def frozen(self) -> bool:
        return self._freeze_level > 0

    def quiesce(self, timeout: float) -> bool:
        """Wait (bounded) until every admitted window has fully drained:
        nothing queued, nothing mid-dispatch, nothing awaiting the
        resolver.  Returns True when idle was reached — the cutover
        precondition; False means the budget expired with work still in
        flight (the coordinator aborts rather than cutting over under
        traffic)."""
        deadline = time.monotonic() + max(0.0, timeout)
        while True:
            with self._cond:
                idle = (
                    not self._queue
                    and self._pending_count == 0
                    and self._inflight_windows == 0
                    and self._resolve_q.empty()
                )
            if idle:
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.001)

    def _shed_item(self, item: QueueItem, reason: str) -> None:
        """Answer one shed submission (docs/overload.md).  Expired,
        shutdown and reshard sheds answer a retriable per-item error so
        callers know to retry with a fresh budget / against another
        peer / after the cutover; overflow sheds answer the configured
        degradation policy (fail-open UNDER_LIMIT with full remaining,
        fail-closed OVER_LIMIT with zero remaining).  Columnar payloads
        release their arena lease here — a shed batch must not pin a
        decode slab."""
        self.metric_shed_admission[reason] = (
            self.metric_shed_admission.get(reason, 0) + item.n)
        if self.metrics is not None:
            self.metrics.admission_shed.labels(reason=reason).inc(item.n)
        retriable = reason in ("expired", "shutdown", "reshard")
        if reason == "expired":
            msg = SHED_EXPIRED_MSG
        elif reason == "reshard":
            msg = SHED_RESHARD_MSG
        else:
            msg = SHED_SHUTDOWN_MSG
        if item.kind == "obj":
            if retriable:
                out = [RateLimitResponse(error=msg)
                       for _ in range(item.n)]
            else:
                out = [self._policy_response(r) for r in item.payload]
            _complete(item.fut, out)
            return
        cols = item.payload
        try:
            if retriable:
                mat = np.zeros((5, item.n), np.int64)
                errs = {i: msg for i in range(item.n)}
            else:
                mat = self._policy_matrix(cols, item.n)
                errs = {}
        finally:
            cols.release()
        _complete(item.fut, (mat, errs))

    def _policy_response(self, r: RateLimitRequest) -> RateLimitResponse:
        reset = (getattr(r, "created_at", 0) or 0) + (r.duration or 0)
        if self.shed_policy == POLICY_FAIL_CLOSED:
            return RateLimitResponse(
                status=Status.OVER_LIMIT, limit=r.limit,
                remaining=0, reset_time=reset)
        return RateLimitResponse(
            status=Status.UNDER_LIMIT, limit=r.limit,
            remaining=r.limit, reset_time=reset)

    def _policy_matrix(self, cols, n: int) -> np.ndarray:
        """Degradation answers for a shed columnar batch, built from the
        request columns BEFORE the arena lease is recycled (rows: status,
        limit, remaining, reset_time, over_limit)."""
        mat = np.zeros((5, n), np.int64)
        mat[1] = cols.limit
        mat[3] = cols.created_at + cols.duration
        if self.shed_policy == POLICY_FAIL_CLOSED:
            mat[0] = int(Status.OVER_LIMIT)
            mat[4] = 1
        else:
            mat[2] = cols.limit
        return mat

    def _metrics_sync(self, n_reqs: int, tick_s: float) -> None:
        # AIMD feedback (docs/overload.md): every resolved window's own
        # engine time (dispatch + resolve) is one limiter sample.
        self.limiter.record(tick_s * 1000.0)
        if self.metrics is None:
            return
        m = self.metrics
        m.admission_window_limit.set(
            self.limiter.window_limit if self.limiter.enabled
            else self.batch_limit)
        m.admission_queue_depth.set(self._pending_count)
        if self.metric_expired_served > self._synced_expired_served:
            m.admission_expired_served.inc(
                self.metric_expired_served - self._synced_expired_served)
            self._synced_expired_served = self.metric_expired_served
        m.tick_duration.observe(tick_s)
        m.tick_batch_size.observe(n_reqs)
        m.worker_queue_length.labels(
            method="GetRateLimits", worker="0"
        ).set(self._pending_count)
        m.command_counter.labels(
            worker="0", method="GetRateLimits"
        ).inc(n_reqs)
        # Sync engine counter deltas (hit/miss on slot resolution,
        # LRU evictions of unexpired buckets) into the catalog families.
        hits = getattr(self.engine, "metric_hits", 0)
        misses = getattr(self.engine, "metric_misses", 0)
        unexp = getattr(self.engine, "metric_unexpired_evictions", 0)
        if hits > self._synced_hits:
            m.cache_access_count.labels(type="hit").inc(
                hits - self._synced_hits
            )
            self._synced_hits = hits
        if misses > self._synced_misses:
            m.cache_access_count.labels(type="miss").inc(
                misses - self._synced_misses
            )
            self._synced_misses = misses
        if unexp > self._synced_unexpired:
            m.unexpired_evictions.inc(unexp - self._synced_unexpired)
            self._synced_unexpired = unexp
        # Tiering families (docs/tiering.md).  Counters sync as deltas
        # like the cache families above; the occupancy gauges are set
        # directly (they are levels, not flows).
        cold_hits = getattr(self.engine, "metric_cold_hits", 0)
        promos = getattr(self.engine, "metric_promotions", 0)
        shed = getattr(self.engine, "metric_shed_requests", 0)
        cold = getattr(self.engine, "cold", None)
        if cold_hits > self._synced_cold_hits:
            m.cold_hits.inc(cold_hits - self._synced_cold_hits)
            self._synced_cold_hits = cold_hits
        if promos > self._synced_promotions:
            m.cold_promotions.inc(promos - self._synced_promotions)
            self._synced_promotions = promos
        if shed > self._synced_shed:
            m.shed_requests.inc(shed - self._synced_shed)
            self._synced_shed = shed
        leaky = getattr(self.engine, "metric_leaky_rows", 0)
        if leaky > self._synced_leaky_rows:
            m.leaky_rows.inc(leaky - self._synced_leaky_rows)
            self._synced_leaky_rows = leaky
        # The one-chip engine's windows by the dispatch branch that
        # answered them (the sharded engine counts its own two below).
        if hasattr(self.engine, "metric_sequential_ticks"):
            for branch in TICK_BRANCHES:
                value = getattr(self.engine, f"metric_{branch}_ticks")
                if value > self._synced_branch[branch]:
                    m.tick_windows.labels(program=branch).inc(
                        value - self._synced_branch[branch])
                    self._synced_branch[branch] = value
        if cold is not None:
            demos = cold.metric_demotions
            if demos > self._synced_demotions:
                m.cold_demotions.inc(demos - self._synced_demotions)
                self._synced_demotions = demos
            m.cold_size.set(len(cold))
        # SSD tier families: counters as deltas from the slab store's
        # plain-int mirrors; bytes/queue depth are levels, set directly.
        ssd = getattr(self.engine, "ssd", None)
        if ssd is not None:
            ssd_hits = getattr(self.engine, "metric_ssd_hits", 0)
            if ssd_hits > self._synced_ssd_hits:
                m.ssd_hits.inc(ssd_hits - self._synced_ssd_hits)
                self._synced_ssd_hits = ssd_hits
            if ssd.metric_promotions > self._synced_ssd_promotions:
                m.ssd_promotions.inc(
                    ssd.metric_promotions - self._synced_ssd_promotions)
                self._synced_ssd_promotions = ssd.metric_promotions
            if ssd.metric_demotions > self._synced_ssd_demotions:
                m.ssd_demotions.inc(
                    ssd.metric_demotions - self._synced_ssd_demotions)
                self._synced_ssd_demotions = ssd.metric_demotions
            if ssd.metric_compactions > self._synced_ssd_compactions:
                m.ssd_compactions.inc(
                    ssd.metric_compactions - self._synced_ssd_compactions)
                self._synced_ssd_compactions = ssd.metric_compactions
            m.ssd_bytes.set(ssd.bytes_used())
            m.ssd_queue_depth.set(ssd.queue_depth())
        if hasattr(self.engine, "hot_occupancy"):
            m.hot_occupancy.set(self.engine.hot_occupancy())
        if hasattr(self.engine, "h2d_overlap_ratio"):
            m.h2d_overlap_ratio.set(self.engine.h2d_overlap_ratio())
        # Sharded-table routing telemetry (mesh-backed engines only).
        routed = getattr(self.engine, "metric_routed_windows", 0)
        if routed > self._synced_routed:
            m.mesh_routed_windows.inc(routed - self._synced_routed)
            self._synced_routed = routed
            # The mesh's windows by the program that answered them,
            # those the native window pass packed, and its uploads (a
            # one-chip engine routes no window).
            for name, counter in (
                ("metric_dup_windows", m.mesh_dup_windows),
                ("metric_unique_windows", m.mesh_unique_windows),
                ("metric_native_pack_windows", m.mesh_native_pack_windows),
                ("metric_h2d_uploads", m.mesh_h2d_uploads),
            ):
                value = getattr(self.engine, name)
                counter.inc(value - self._synced_mesh[name])
                self._synced_mesh[name] = value
        r_over = getattr(self.engine, "metric_routed_overflows", 0)
        if r_over > self._synced_routed_overflows:
            m.mesh_routed_overflows.inc(
                r_over - self._synced_routed_overflows)
            self._synced_routed_overflows = r_over

    def _drain_resolve_q(self, err: Exception) -> None:
        """Fail every window still queued for resolution.  A drained None
        stop sentinel is re-enqueued: a resolver that was merely slow (not
        dead) must still find it when it loops back to get(), or it would
        block on the empty queue forever."""
        saw_sentinel = False
        while True:
            try:
                item = self._resolve_q.get_nowait()
            except queue.Empty:
                break
            if item is None:
                saw_sentinel = True
                continue
            subs = item[0]
            for _, _, items, _ in subs:
                _fail_waiters(items, err)
        if saw_sentinel:
            self._resolve_q.put(None)

    def close(self) -> None:
        """Shut down, draining the bounded queue deadline-aware: the
        dispatch thread flushes the backlog through ``_flush`` (which
        sheds expired work) before exiting; if it is wedged, everything
        still queued is answered with a retriable shed status instead of
        being abandoned behind a fixed join timeout."""
        with self._cond:
            self._running = False
            self._cond.notify()
        self._thread.join(timeout=5)
        if self._thread.is_alive():
            # Dispatch thread wedged (e.g. blocked on a full resolve queue
            # with a dead resolver): don't hang close() — but don't leave
            # queued waiters hanging forever either; answer everything
            # still pending so callers awaiting wrap_future() return and
            # know to retry elsewhere.
            with self._cond:
                stuck = self._queue.drain()
                self._pending_count = 0
            for item in stuck:
                self._shed_item(item, "shutdown")
            self._drain_resolve_q(
                RuntimeError("tick loop shut down with requests pending"))
            return
        self._resolver.join(timeout=5)
        if self._resolver.is_alive():
            # Resolver wedged (e.g. a D2H that never completes): windows
            # already submitted for resolution would leave their callers
            # awaiting wrap_future forever — fail whatever is still queued.
            self._drain_resolve_q(
                RuntimeError("tick loop shut down with requests pending")
            )
