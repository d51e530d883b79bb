"""EdgePlane: the device-owner side of the shared-memory ingest plane.

Owns the worker processes, their shm segments, and two owner threads:

* the **drain** thread walks every worker's request ring, rebuilds each
  published slab as a zero-copy :class:`ReqColumns` view (key blob
  included — the native slotmap resolves it in place) and submits it to
  the tick loop; the attached :class:`ShmSlabLease` returns the slab to
  the worker when ``TickLoop._flush`` releases after pack, exactly the
  in-process arena timing.  Worker-stamped decode time folds into the
  flight recorder here, so ``/debug/pipeline`` and
  ``stage_duration{stage="decode"}`` show where decode really happened.
* the **supervisor** thread respawns dead workers: unconsumed published
  slabs are shed with the PR 9 retriable-shutdown accounting (never
  silently dropped), the segment generation is bumped so in-flight
  responses from the old life are discarded on arrival, and the ring
  cursors are handed to the fresh process through the control block.

Response fan-out rides the tick loop's future callbacks (resolver and
shed threads both complete futures; the per-worker lock serializes the
slot writes).  Exactly-once holds for ACKED windows: a window either
reaches its worker's response ring once, or is counted shed/dropped.
"""

from __future__ import annotations

import logging
import os
import secrets
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional

import numpy as np

from gubernator_tpu.admission import CLASS_CLIENT, SHED_SHUTDOWN_MSG
from gubernator_tpu.edge import shmring
from gubernator_tpu.edge.shmring import (
    CTRL_GENERATION,
    CTRL_GO,
    CTRL_READY,
    CTRL_REQ_AT,
    CTRL_RESP_AT,
    CTRL_STOP,
    C_DRIVE_DONE,
    N_COUNTERS,
    PUBLISHED,
    RESP_OK,
    RS_STATE,
    EdgeSegment,
    ShmSlabLease,
)
from gubernator_tpu.ops.reqcols import ReqColumns
from gubernator_tpu.utils import flightrec
from gubernator_tpu.utils.hotpath import hot_path
from gubernator_tpu.utils import sanitize

log = logging.getLogger("gubernator.edge")


@dataclass
class EdgeConfig:
    """Shape of the edge plane (GUBER_EDGE_* knobs; docs/edge.md)."""

    workers: int = 0
    slabs: int = 8            # request slabs per worker (GUBER_EDGE_SHM_SLABS)
    ring_depth: int = 16      # response slots per worker (GUBER_EDGE_RING_DEPTH)
    max_batch: int = 1000
    mode: str = "socket"      # "socket" (daemon ingest) | "drive" (tests)
    socket_dir: Optional[str] = None
    drive: dict = field(default_factory=dict)
    timeout_s: float = 30.0

    def __post_init__(self):
        # A live worker bounds its outstanding windows to the response
        # depth; depth >= slabs keeps that bound from throttling below
        # the slab count.
        self.ring_depth = max(int(self.ring_depth), int(self.slabs))


class _WorkerHandle:
    """Owner-side state for one worker process."""

    def __init__(self, wid: int, seg: EdgeSegment):
        self.id = wid
        self.seg = seg
        self.ring = shmring.RequestRing(seg)
        self.resp = shmring.ResponseRing(seg)
        self.generation = 1
        # Reentrant: a tick-loop future can complete inline during
        # submit (shutdown shed), firing _on_done on the drain thread
        # while _drain_once still holds the lock.
        self.lock = sanitize.rlock("_WorkerHandle.lock")
        self.proc = None
        self.restarts = 0
        self.shed_rows = 0
        self.dropped_responses = 0
        self.in_flight = 0
        self.synced = np.zeros(N_COUNTERS, np.float64)
        self.socket_path: Optional[str] = None


class EdgePlane:
    """N worker processes + the owner drain/supervisor (module docstring)."""

    def __init__(self, tick_loop, config: EdgeConfig, metrics=None):
        from gubernator_tpu.transport import fastwire

        if config.workers <= 0:
            raise ValueError("EdgePlane needs workers >= 1; 0 disables the "
                             "plane (the caller must not construct it)")
        if fastwire.load() is None:
            raise RuntimeError(
                "edge plane needs the native wire codec (libguber_wire.so)"
            )
        self.tick_loop = tick_loop
        self.config = config
        self.metrics = metrics
        self.workers: List[_WorkerHandle] = []
        self._threads: List[threading.Thread] = []
        self._closing = False
        self._started = False
        self._token = secrets.token_hex(4)

    # -- lifecycle ------------------------------------------------------
    def start(self) -> None:
        cfg = self.config
        for wid in range(cfg.workers):
            seg = EdgeSegment(
                f"guber_edge_{os.getpid()}_{wid}_{self._token}",
                cfg.max_batch, cfg.slabs, cfg.ring_depth, create=True,
            )
            w = _WorkerHandle(wid, seg)
            if cfg.mode == "socket":
                w.socket_path = os.path.join(
                    cfg.socket_dir or "/tmp",
                    f"guber-edge-{os.getpid()}-{wid}-{self._token}.sock",
                )
            self.workers.append(w)
            self._spawn(w)
        self._started = True
        for name, target in (("edge_drain", self._drain_loop),
                             ("edge_supervisor", self._supervise_loop)):
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        log.info(
            "edge plane up: %d workers, %d slabs x %d rows, mode=%s",
            cfg.workers, cfg.slabs, cfg.max_batch, cfg.mode,
        )

    def _spawn(self, w: _WorkerHandle) -> None:
        import multiprocessing as mp

        cfg = self.config
        options = {"timeout_s": cfg.timeout_s}
        if cfg.mode == "socket":
            options["socket_path"] = w.socket_path
        else:
            drive = dict(cfg.drive)
            drive.setdefault("key_prefix", f"w{w.id}_")
            options["drive"] = drive
        ctx = mp.get_context("spawn")  # the owner holds jax + threads: no fork
        from gubernator_tpu.edge.worker import worker_main

        w.proc = ctx.Process(
            target=worker_main,
            args=(w.seg.shm.name, w.id, cfg.max_batch, cfg.slabs,
                  cfg.ring_depth, cfg.mode, options),
            name=f"guber-edge-w{w.id}",
            daemon=True,
        )
        w.proc.start()

    def close(self, timeout: float = 10.0) -> None:
        """Stop workers, wait out in-flight windows, account every slab,
        then tear down the segments.  Called before TickLoop.close()."""
        if self._closing:
            return
        self._closing = True
        for w in self.workers:
            if hasattr(w.seg, "ctrl"):
                w.seg.ctrl[CTRL_STOP] = 1
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(timeout=max(0.1, deadline - time.monotonic()))
        for w in self.workers:
            p = w.proc
            if p is not None:
                p.join(timeout=max(0.1, min(2.0, deadline - time.monotonic())))
                if p.is_alive():
                    p.terminate()
                    p.join(timeout=1.0)
                if p.is_alive():
                    p.kill()
                    p.join(timeout=1.0)
        # Shed whatever was published but never drained — the retriable
        # shutdown accounting; nothing disappears silently.
        for w in self.workers:
            with w.lock:
                self._shed_unconsumed(w, reason="shutdown")
        # In-flight windows hold zero-copy views into the segments; wait
        # for their futures before unmapping.
        while (time.monotonic() < deadline
               and any(w.in_flight > 0 for w in self.workers)):
            time.sleep(0.005)
        self._sync_metrics()
        for w in self.workers:
            wedged = w.in_flight > 0
            if not wedged:
                w.ring.detach()
                w.resp.detach()
                w.seg.close()
            w.seg.unlink()
            if wedged:
                log.warning(
                    "edge worker %d: %d windows still in flight at close; "
                    "segment left mapped", w.id, w.in_flight,
                )

    # -- drain (owner hot path) -----------------------------------------
    def _drain_loop(self) -> None:
        idle_sleep = 0.0001
        while not self._closing:
            drained = 0
            for w in self.workers:
                with w.lock:
                    drained += self._drain_once(w)
            if drained:
                idle_sleep = 0.0001
            else:
                time.sleep(idle_sleep)
                idle_sleep = min(idle_sleep * 2, 0.002)

    @hot_path
    def _drain_once(self, w: _WorkerHandle) -> int:
        """Pop every published slab of one worker into the tick loop.
        Zero copies: the columns (key blob included) are views into the
        slab; the lease releases it after pack."""
        drained = 0
        seg = w.seg
        while True:
            item = w.ring.pop_published()
            if item is None:
                return drained
            idx, seqno, rows, blob_len, deadline_ns, decode_ns, gen = item
            if gen != w.generation or rows <= 0:
                w.ring.free(idx)  # pre-crash leftovers; supervisor counted
                continue
            ints = seg.req_ints[idx]
            cols = ReqColumns(
                seg.req_blob[idx][:blob_len],
                ints[8, : rows + 1],
                ints[1, :rows], ints[2, :rows], ints[3, :rows],
                ints[4, :rows], ints[5, :rows], ints[7, :rows],
                ints[6, :rows],
                name_len=ints[0, :rows],
                lease=ShmSlabLease(w.ring, idx),
            )
            fr = flightrec.get()
            if fr is not None:
                # The worker stamped decode begin/end around its parse;
                # fold the real decode cost into the window record (and,
                # through the observer, stage_duration{stage="decode"}).
                fr.edge("decode", decode_ns * 1e-9)
            w.in_flight += 1
            fut = self.tick_loop.submit_columns(
                cols, deadline_ns * 1e-9, CLASS_CLIENT
            )
            fut.add_done_callback(
                partial(self._on_done, w, seqno, rows, gen)
            )
            drained += 1

    def _on_done(self, w: _WorkerHandle, seqno: int, rows: int,
                 gen: int, fut) -> None:
        """Tick-loop future → response ring (runs on resolver/shed
        threads).  Stale-generation results — the window was in flight
        when its worker died — are dropped *with accounting*: the
        respawned life must never see them (double-serve)."""
        try:
            mat, errors = fut.result()
        except Exception:
            mat = np.zeros((5, rows), np.int64)
            errors = {i: SHED_SHUTDOWN_MSG for i in range(rows)}
        err_blob, err_count = shmring.encode_errors(errors)
        with w.lock:
            w.in_flight -= 1
            if gen != w.generation or self._closing:
                w.dropped_responses += 1
                return
            ok = w.resp.try_publish(
                seqno, rows, mat, err_blob, err_count, gen, RESP_OK
            )
            if not ok:
                w.dropped_responses += 1

    def _shed_unconsumed(self, w: _WorkerHandle, reason: str) -> int:
        """Count + free every published-but-undrained slab (crash and
        shutdown paths; caller holds w.lock).  Returns rows shed."""
        rows_shed = 0
        windows = 0
        while True:
            item = w.ring.pop_published()
            if item is None:
                break
            idx, _seq, rows, *_ = item
            rows_shed += max(0, rows)
            windows += 1
            w.ring.free(idx)
        if rows_shed and self.metrics is not None:
            # The PR 9 admission path's shed accounting: retriable, never
            # silent (docs/overload.md).
            self.metrics.admission_shed.labels(reason="shutdown").inc(rows_shed)
            self.metrics.edge_shed.labels(
                worker=str(w.id), reason=reason).inc(rows_shed)
        w.shed_rows += rows_shed
        if windows:
            log.warning(
                "edge worker %d: shed %d windows (%d rows), reason=%s",
                w.id, windows, rows_shed, reason,
            )
        return rows_shed

    # -- supervision -----------------------------------------------------
    def _supervise_loop(self) -> None:
        last_sync = 0.0
        while not self._closing:
            for w in self.workers:
                p = w.proc
                if p is not None and not p.is_alive() and not self._closing:
                    self._respawn(w)
            now = time.monotonic()
            if now - last_sync >= 0.25:
                self._sync_metrics()
                last_sync = now
            time.sleep(0.02)

    def _respawn(self, w: _WorkerHandle) -> None:
        """Crash recovery: shed in-flight slabs retriably, bump the
        generation (stale responses drop on arrival), hand the surviving
        cursors to the fresh process."""
        exitcode = w.proc.exitcode
        log.warning("edge worker %d died (exit %s); respawning", w.id, exitcode)
        with w.lock:
            w.generation += 1
            self._shed_unconsumed(w, reason="crash")
            # Unconsumed responses from the old life die with it.
            stale = int((w.seg.resp_hdr[:, RS_STATE] == PUBLISHED).sum())
            if stale:
                w.seg.resp_hdr[:, RS_STATE] = 0
                w.dropped_responses += stale
            ctrl = w.seg.ctrl
            ctrl[CTRL_GENERATION] = w.generation
            ctrl[CTRL_READY] = 0
            ctrl[CTRL_REQ_AT] = w.ring.read_at
            ctrl[CTRL_RESP_AT] = w.resp.write_at
            w.seg.counters[C_DRIVE_DONE] = 0
            w.restarts += 1
        if self.metrics is not None:
            self.metrics.edge_worker_restarts.labels(worker=str(w.id)).inc()
        self._spawn(w)

    # -- telemetry -------------------------------------------------------
    def _sync_metrics(self) -> None:
        """Fold the workers' shm counter blocks into the owner's
        Prometheus families (delta sync; each family carries the
        ``worker`` label so one hot worker is visible as itself)."""
        m = self.metrics
        if m is None:
            return
        C = shmring
        for w in self.workers:
            if not hasattr(w.seg, "counters"):
                continue
            cur = np.array(w.seg.counters)
            d = cur - w.synced
            w.synced = cur
            if (d <= 0).all():
                continue
            lbl = str(w.id)

            def inc(family, i):
                if d[i] > 0:
                    family.labels(worker=lbl).inc(d[i])

            inc(m.edge_decode_seconds, C.C_DECODE_SECONDS)
            inc(m.edge_windows, C.C_WIN_PUBLISHED)
            inc(m.edge_rows, C.C_ROWS_PUBLISHED)
            inc(m.edge_acked_windows, C.C_WIN_ACKED)
            inc(m.edge_backpressure_waits, C.C_BACKPRESSURE_WAITS)
            if d[C.C_SHED_LOCAL] > 0:
                m.edge_shed.labels(worker=lbl, reason="local").inc(
                    d[C.C_SHED_LOCAL]
                )

    # -- introspection ---------------------------------------------------
    def socket_paths(self) -> List[str]:
        return [w.socket_path for w in self.workers if w.socket_path]

    def counters(self, wid: int) -> np.ndarray:
        return np.array(self.workers[wid].seg.counters)

    def totals(self) -> Dict[str, float]:
        """Aggregate worker counters (/debug/state, and the exact-work
        invariants of tests/test_edge.py)."""
        agg = np.zeros(N_COUNTERS, np.float64)
        for w in self.workers:
            if hasattr(w.seg, "counters"):
                agg += np.array(w.seg.counters)
        return {
            "windows_published": float(agg[shmring.C_WIN_PUBLISHED]),
            "rows_published": float(agg[shmring.C_ROWS_PUBLISHED]),
            "hits_published": float(agg[shmring.C_HITS_PUBLISHED]),
            "windows_acked": float(agg[shmring.C_WIN_ACKED]),
            "rows_acked": float(agg[shmring.C_ROWS_ACKED]),
            "hits_acked": float(agg[shmring.C_HITS_ACKED]),
            "err_rows": float(agg[shmring.C_ERR_ROWS]),
            "double_served": float(agg[shmring.C_DOUBLE_SERVED]),
            "decode_seconds": float(agg[shmring.C_DECODE_SECONDS]),
            "backpressure_waits": float(agg[shmring.C_BACKPRESSURE_WAITS]),
            "shed_local": float(agg[shmring.C_SHED_LOCAL]),
            "shed_rows": float(sum(w.shed_rows for w in self.workers)),
            "dropped_responses": float(
                sum(w.dropped_responses for w in self.workers)
            ),
            "restarts": float(sum(w.restarts for w in self.workers)),
            "in_flight": float(sum(w.in_flight for w in self.workers)),
        }

    def debug_state(self) -> dict:
        return {
            "workers": self.config.workers,
            "slabs": self.config.slabs,
            "ring_depth": self.config.ring_depth,
            "mode": self.config.mode,
            "sockets": self.socket_paths(),
            "alive": [
                bool(w.proc is not None and w.proc.is_alive())
                for w in self.workers
            ],
            "generations": [w.generation for w in self.workers],
            "totals": self.totals(),
        }

    # -- drive-mode helpers (tests/test_edge.py, test_chaos.py) ----------
    def wait_ready(self, timeout: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(
                int(w.seg.ctrl[CTRL_READY]) == 1 for w in self.workers
            ):
                return True
            time.sleep(0.005)
        return False

    def go(self) -> None:
        for w in self.workers:
            w.seg.ctrl[CTRL_GO] = 1

    def wait_drive_done(self, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(
                int(w.seg.counters[C_DRIVE_DONE]) == 1 for w in self.workers
            ):
                return True
            time.sleep(0.01)
        return False
