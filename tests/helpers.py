"""Shared test helpers: a TickEngine wrapper with a controllable clock.

Plays the role of the reference's `clock.Freeze/Advance` (holster clock)
used throughout functional_test.go.
"""

from __future__ import annotations

import asyncio
import contextlib
import faulthandler
import hashlib
import signal
import traceback
from typing import List

import pytest

import numpy as np

from gubernator_tpu.ops import engine as _engine
from gubernator_tpu.ops.engine import TickEngine
from gubernator_tpu.types import (
    Behavior, RateLimitRequest, RateLimitResponse)


def slab_of(m32, now: int) -> np.ndarray:
    """A window's one upload as ``TickEngine._build_cols`` leaves it,
    from a (19, B) REQ32 matrix: the matrix in the slab's REQ32 rows and
    ``now`` stamped in the row behind them (what ``tick32.jitted_tick32``
    and ``jitted_sorted_tick32`` take)."""
    m32 = np.asarray(m32)
    slab = np.zeros((_engine.SLAB_ROWS, m32.shape[1]), np.int32)
    slab[:_engine.REQ32_ROWS] = m32
    _engine.stamp_now(slab[_engine.REQ32_ROWS], now)
    return slab


class Sim:
    """Single-node engine with frozen, manually-advanced time."""

    def __init__(self, capacity: int = 1024, max_batch: int = 64, now: int = 1_700_000_000_000):
        self.engine = TickEngine(capacity=capacity, max_batch=max_batch)
        self.now = now

    def advance(self, ms: int) -> None:
        self.now += ms

    def hit(self, **kw) -> RateLimitResponse:
        return self.batch([RateLimitRequest(**kw)])[0]

    def batch(self, reqs: List[RateLimitRequest]) -> List[RateLimitResponse]:
        return self.engine.process(reqs, now=self.now)


@contextlib.contextmanager
def time_limit(seconds: float, hard_seconds: float = 0):
    """Fail the test that waits in this block past ``seconds``, with the
    stack of where it waited (SIGALRM lands on the main thread, where
    the suite runs its tests and their coroutines).  A wait no signal
    can break, a C call that holds the GIL, ends the process
    ``hard_seconds`` in, if given, with every thread's stack on stderr:
    xdist reports the crash as that test's failure, replaces the worker
    and (``--dist loadfile``) runs the file again from its start.
    Restores whatever timer and handler it found."""

    def expired(signum, frame):
        pytest.fail(
            f"still waiting after {seconds:g} s, at:\n"
            + "".join(traceback.format_stack(frame)), pytrace=False)

    was_handler = signal.signal(signal.SIGALRM, expired)
    was_left, _ = signal.setitimer(signal.ITIMER_REAL, seconds)
    if hard_seconds:
        faulthandler.dump_traceback_later(hard_seconds, exit=True)
    try:
        yield
    finally:
        if hard_seconds:
            faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, was_left)
        signal.signal(signal.SIGALRM, was_handler)


def global_req(name, key, hits=1, limit=1_000_000, duration=3_600_000, **kw):
    return RateLimitRequest(
        name=name, unique_key=key, hits=hits, limit=limit,
        duration=duration, behavior=Behavior.GLOBAL, **kw
    )


async def poll_consumed(daemon, name, key, want, limit=1_000_000,
                        timeout=10.0):
    """Poll a daemon's local GLOBAL state until ``want`` hits landed."""
    client = daemon.client()
    seen = None

    async def poll():
        nonlocal seen
        while True:
            # No deadline on the RPC itself: a first-compile stall on a
            # loaded host must surface as a slow poll, not as a
            # DEADLINE_EXCEEDED crash out of the helper.
            r = (await client.get_rate_limits(
                [global_req(name, key, hits=0, limit=limit)], timeout=None
            ))[0]
            seen = limit - r.remaining
            if seen == want:
                return r
            await asyncio.sleep(0.02)

    try:
        return await asyncio.wait_for(poll(), timeout=timeout)
    except asyncio.TimeoutError:
        raise AssertionError(
            f"{name}_{key}: {seen} hits landed, wanted {want}") from None
    finally:
        await client.close()


def spread_keys(n: int) -> List[str]:
    """``n`` keys that land all over the hash ring.  Keys that differ in
    a last digit only (``k0`` .. ``k63``) hash close together, and with
    the wrong pair of ports every one of them falls to the same daemon:
    a test that looks among them for a key of the other daemon's then
    fails with nothing wrong."""
    return [hashlib.md5(b"%d" % i).hexdigest() for i in range(n)]


async def warm_global_path(cluster, name, owner, *non_owners):
    """GLOBAL traffic end to end with no deadline anywhere, through each
    of ``non_owners`` and then the owner, on a key of its own that
    ``owner`` owns: the local answer, the flush to the owner, the
    owner's tick and its broadcast back each meet their program's first
    trace-and-lower here.  Two windows a daemon: a hit twice over (the
    grouped program) and a hit beside a query (the mixed-duplicate
    program), which is what a flush or a poll landing in one window
    with another forms later, when it pleases.  The traffic a test
    asserts on comes after and races no compile, however busy the
    cores: a peer RPC that times out behind one is retried, and its
    hits are then counted twice."""
    key = next(
        k for k in spread_keys(1000)
        if cluster.find_owning_daemon(name, k) is owner)
    hit, query = global_req(name, key), global_req(name, key, hits=0)
    for d in (*non_owners, owner):
        client = d.client()
        for window in ([hit, hit], [hit, query]):
            out = await client.get_rate_limits(window, timeout=None)
            assert [r.error for r in out] == ["", ""]
        await client.close()
    await poll_consumed(
        owner, name, key, 3 * (len(non_owners) + 1), timeout=None)
    await cluster.wait_for_broadcast(
        cluster.daemons.index(owner), timeout=120)
