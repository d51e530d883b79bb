"""Elastic live resharding: the n→m transition state machine.

The coordinator sequences a drain-then-cutover protocol
(docs/resharding.md) around the engine's atomic relayout
(:meth:`MeshTickEngine.reshard`):

``FREEZE``
    New CLIENT windows shed-with-retriable at the admission queue
    (:meth:`TickLoop.freeze`); PEER reconcile traffic keeps draining —
    it outranks clients and must land before the cutover.
``DRAIN``
    Bounded quiesce: every admitted window resolves (queue empty,
    nothing mid-dispatch, nothing at the resolver).  A drain that
    misses its budget aborts — the cutover never runs under traffic.
``RELAYOUT``/``CUTOVER``
    Freeze escalates to both classes for the bounded cutover window,
    a ``begin`` record lands in the transition journal, then the
    engine relayouts on-device and swaps layouts atomically (an engine
    failure rolls back to the old layout before raising).
``VERIFY``
    The post-cutover table is audited: every row live at relayout time
    is present exactly once (``reshard_state_loss`` /
    ``reshard_double_served``, both held at zero on a live mesh by
    tests/test_reshard.py::test_reshard_ragged_zipf_round_trip_zero_loss)
    and the routed path agrees with the ring
    (``routing_parity_errors == 0``).

Every failure mode lands in a defined state: peer death surfaces as an
open breaker and aborts before the cutover; a crash mid-cutover leaves
a non-terminal journal record that startup detects (the snapshot store
— never mutated mid-flight — is authoritative); an engine error rolls
back to the old layout and the transition reports ``aborted``.

Engines without a native ``reshard`` (the single-chip
:class:`TickEngine`) get the degenerate identity transition: the full
protocol runs — freeze, drain, journal, breakers, verify — with no
relayout, which is what the chaos suite drives on its existing
clusters without building mesh engines.
"""

from __future__ import annotations

import logging
import threading
from gubernator_tpu.utils import sanitize
import time
from typing import Callable, Optional

log = logging.getLogger("gubernator.reshard")

PHASE_IDLE = "idle"
PHASE_FREEZE = "freeze"
PHASE_DRAIN = "drain"
PHASE_RELAYOUT = "relayout"
PHASE_CUTOVER = "cutover"
PHASE_VERIFY = "verify"
PHASE_COMMITTED = "committed"
PHASE_ABORTED = "aborted"

# Gauge encoding for gubernator_tpu_reshard_phase; terminal phases read
# as idle — the gauge tracks the *running* transition only.
_PHASE_IDS = {
    PHASE_IDLE: 0,
    PHASE_FREEZE: 1,
    PHASE_DRAIN: 2,
    PHASE_RELAYOUT: 3,
    PHASE_CUTOVER: 4,
    PHASE_VERIFY: 5,
    PHASE_COMMITTED: 0,
    PHASE_ABORTED: 0,
}


class ReshardError(RuntimeError):
    """A transition could not start (already running / bad target)."""


# The single source of truth for the concurrent-call outcome: every
# caller — Instance.reshard(), the /debug/reshard 409, the autoscaler's
# reshard_busy veto — consumes this one dict instead of string-matching
# a ReshardError.  The coordinator's non-blocking lock is the only busy
# check anywhere; two callers can never race into a double-freeze.
BUSY_RESULT = {
    "result": "busy",
    "error": "a reshard transition is already running",
}


class ReshardCoordinator:
    """Drives one transition at a time over an engine + tick loop.

    All hooks are optional so the coordinator composes with partial
    stacks (tests, single-chip engines):

    * ``tick_loop`` — freeze/quiesce/unfreeze admission around the
      cutover; without one, the caller owns traffic exclusion.
    * ``transition_log`` — the crash journal
      (:class:`~gubernator_tpu.persistence.TransitionLog`).
    * ``breaker_check`` — callable returning True when the peer plane
      is unsafe (an open breaker mid-transfer); consulted after the
      drain and again immediately before the cutover.
    * ``global_engine`` — a :class:`MeshGlobalEngine` whose reconcile
      cadence is paused for the cutover window (collectives must not
      contend with the relayout dispatch on the same devices).
    * ``federation`` — a :class:`FederationManager` whose envelope
      flushes are paused for FREEZE→CUTOVER and resumed after
      commit/abort: an envelope compacted mid-relayout would snapshot
      half-moved owner state and export it to every remote region.
    * ``metrics`` — the daemon's :class:`Metrics` registry.
    """

    def __init__(
        self,
        engine,
        tick_loop=None,
        transition_log=None,
        breaker_check: Optional[Callable[[], bool]] = None,
        global_engine=None,
        federation=None,
        metrics=None,
        freeze_timeout: float = 5.0,
        verify: bool = True,
    ):
        self.engine = engine
        self.tick_loop = tick_loop
        self.transition_log = transition_log
        self.breaker_check = breaker_check
        self.global_engine = global_engine
        self.federation = federation
        self.metrics = metrics
        self.freeze_timeout = float(freeze_timeout)
        self.verify = bool(verify)
        self._lock = sanitize.lock("ReshardCoordinator._lock")
        self._epoch = 0
        self.phase = PHASE_IDLE
        self.last: dict = {}

    # ------------------------------------------------------------------
    # Introspection (daemon /debug/state)
    # ------------------------------------------------------------------
    def status(self) -> dict:
        return {
            "phase": self.phase,
            "epoch": self._epoch,
            "shards": getattr(self.engine, "n_shards", 1),
            "last": dict(self.last),
        }

    def _set_phase(self, phase: str) -> None:
        self.phase = phase
        if self.metrics is not None:
            self.metrics.reshard_phase.set(_PHASE_IDS[phase])

    def record_interrupted(self, rec) -> None:
        """Surface a non-terminal journal record found at startup (the
        process died mid-transition; the restored snapshot is
        authoritative)."""
        log.warning(
            "interrupted reshard transition detected at startup "
            "(%d -> %d shards, epoch %d); serving from the restored "
            "snapshot on the old layout",
            rec.from_shards, rec.to_shards, rec.epoch,
        )
        if self.metrics is not None:
            self.metrics.reshard_transitions.labels(
                result="interrupted").inc()

    # ------------------------------------------------------------------
    # The transition
    # ------------------------------------------------------------------
    def is_busy(self) -> bool:
        """True while a transition holds the coordinator lock.  Advisory
        only (the lock may flip between check and call) — callers that
        must not block use :meth:`try_reshard`, whose non-blocking
        acquire is the authoritative check."""
        return self._lock.locked()

    def try_reshard(self, new_shards: int) -> dict:
        """Run one n→m transition, or return ``BUSY_RESULT`` (a copy)
        when one is already running — the non-raising entry point the
        autoscaler and admin endpoint share, so neither can double-freeze
        the other.  Still raises :class:`ReshardError` for an invalid
        target; never raises on an *aborted* transition — abort is a
        defined outcome, not an error."""
        new_n = int(new_shards)
        if new_n < 1:
            raise ReshardError(f"target shard count must be >= 1: {new_n}")
        if not self._lock.acquire(blocking=False):
            return dict(BUSY_RESULT)
        try:
            return self._run(new_n)
        finally:
            self._lock.release()

    def reshard(self, new_shards: int) -> dict:
        """Raising wrapper over :meth:`try_reshard` (the original API):
        a concurrent transition surfaces as :class:`ReshardError`."""
        out = self.try_reshard(new_shards)
        if out.get("result") == "busy":
            raise ReshardError(out["error"])
        return out

    def _run(self, new_n: int) -> dict:
        from_n = int(getattr(self.engine, "n_shards", 1))
        self._epoch += 1
        t0 = time.monotonic()
        out = {
            "from_shards": from_n,
            "to_shards": new_n,
            "epoch": self._epoch,
            "state_loss": 0,
            "double_served": 0,
            "parity_errors": 0,
            "live_items": 0,
        }
        if new_n == from_n:
            out.update(outcome="noop", duration_s=0.0)
            self.last = out
            return out
        try:
            # FREEZE: clients shed retriable; peers keep draining first.
            self._set_phase(PHASE_FREEZE)
            if self.tick_loop is not None:
                self.tick_loop.freeze()
            if self.global_engine is not None:
                self.global_engine.pause_reconcile()
            if self.federation is not None:
                # No envelope may be compacted from half-relayouted
                # owner state; resumed in the finally below.
                self.federation.pause()
            # DRAIN: bounded quiesce — cutover never runs under traffic.
            self._set_phase(PHASE_DRAIN)
            if self.tick_loop is not None:
                if not self.tick_loop.quiesce(self.freeze_timeout):
                    return self._abort(out, t0, "drain timeout: in-flight "
                                       "windows did not quiesce")
            if self.breaker_check is not None and self.breaker_check():
                return self._abort(out, t0, "peer breaker open after drain")
            # RELAYOUT/CUTOVER: both classes frozen for the bounded
            # window; journal begin before any state moves.
            self._set_phase(PHASE_RELAYOUT)
            if self.tick_loop is not None:
                self.tick_loop.freeze(shed_peers=True)
            if self.breaker_check is not None and self.breaker_check():
                return self._abort(out, t0, "peer breaker open at cutover")
            self._journal("begin", out)
            self._set_phase(PHASE_CUTOVER)
            try:
                if hasattr(self.engine, "reshard"):
                    info = self.engine.reshard(new_n)
                    out["live_items"] = int(info.get("live_items", 0))
                else:
                    # Degenerate identity transition (single-chip
                    # engine): the protocol runs, no state moves.
                    out["live_items"] = int(self.engine.cache_size())
                    out["degenerate"] = True
            except Exception as e:  # engine rolled back before raising
                self._journal("abort", out)
                return self._abort(out, t0, f"engine relayout failed "
                                   f"(rolled back): {e}")
            # VERIFY: audit the post-cutover table before unfreezing.
            self._set_phase(PHASE_VERIFY)
            if self.verify:
                loss, dup, parity = self._verify(out["live_items"])
                out.update(state_loss=loss, double_served=dup,
                           parity_errors=parity)
                if self.metrics is not None:
                    if loss:
                        self.metrics.reshard_state_loss.inc(loss)
                    if dup:
                        self.metrics.reshard_double_served.inc(dup)
                if loss or dup or parity:
                    log.error(
                        "reshard verify found damage (loss=%d dup=%d "
                        "parity=%d) after %d -> %d; transition committed "
                        "— investigate before the next one",
                        loss, dup, parity, from_n, new_n,
                    )
            self._journal("commit", out)
            return self._finish(out, t0, "committed")
        finally:
            if self.global_engine is not None:
                self.global_engine.resume_reconcile()
            if self.federation is not None:
                self.federation.resume()
            if self.tick_loop is not None:
                self.tick_loop.unfreeze()
            self._set_phase(
                PHASE_COMMITTED if out.get("outcome") == "committed"
                else PHASE_ABORTED if out.get("outcome") == "aborted"
                else PHASE_IDLE
            )

    def _verify(self, expected_live: int) -> tuple:
        """(state_loss, double_served, parity_errors) for the serving
        table: readback every resident row, count keys missing vs. the
        relayout-time live set and keys resident more than once, then
        audit route==owner on the routed path when the engine has one."""
        items = self.engine.export_items()
        keys = [it["key"] for it in items]
        unique = set(keys)
        loss = max(0, int(expected_live) - len(unique))
        dup = len(keys) - len(unique)
        parity = 0
        if unique and hasattr(self.engine, "routing_parity_errors"):
            parity = int(self.engine.routing_parity_errors(sorted(unique)))
        return loss, dup, parity

    def _journal(self, phase: str, out: dict) -> None:
        if self.transition_log is None:
            return
        from gubernator_tpu.persistence.transition import TransitionRecord

        try:
            self.transition_log.append(TransitionRecord(
                phase=phase,
                from_shards=out["from_shards"],
                to_shards=out["to_shards"],
                epoch=self._epoch,
            ))
        except OSError:
            log.warning("transition journal append failed", exc_info=True)

    def _abort(self, out: dict, t0: float, reason: str) -> dict:
        out.update(outcome="aborted", reason=reason)
        log.warning(
            "reshard %d -> %d aborted: %s",
            out["from_shards"], out["to_shards"], reason,
        )
        return self._finish(out, t0, "aborted")

    def _finish(self, out: dict, t0: float, outcome: str) -> dict:
        out["outcome"] = outcome
        out["duration_s"] = time.monotonic() - t0
        if self.metrics is not None:
            self.metrics.reshard_transitions.labels(result=outcome).inc()
            self.metrics.reshard_duration.labels(result=outcome).observe(
                out["duration_s"])
            self.metrics.reshard_shards.set(
                getattr(self.engine, "n_shards", 1))
        self.last = out
        return out
