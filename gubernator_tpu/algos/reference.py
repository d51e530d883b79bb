"""Scalar Python references — the test oracle.

The zoo functions mirror their vmapped counterparts line for line (same
clamps, same precedence, same integer math).  ``token_bucket`` and
``leaky_bucket`` are the plain reference of the legacy pair, written
from upstream ``algorithms.go`` (``tokenBucket`` / ``tokenBucketNewItem``
/ ``leakyBucket`` / ``leakyBucketNewItem``) in Python ``int`` and
``float``: int64-exact while callers stay in range, and IEEE float64,
every operation rounded, which is what the served tick's leaky path has
to equal bit for bit (ops/b64.py).  Nothing of ``ops/`` is imported.
The parity fuzz drives the real engine and this module with identical
request streams and demands bit-identical responses and exported state.

State is a plain dict of the logical BucketState fields (``None`` for
an absent item); requests are dicts with ``hits``/``limit``/
``duration``/``algorithm``/``behavior``/``burst``/``created_at``.
All arithmetic is on Python ints, which do not wrap — callers keep
parameters inside int64 range (the kernels wrap two's-complement
beyond it, like Go).
"""

from __future__ import annotations

from typing import Optional, Tuple

from gubernator_tpu.types import Algorithm, Behavior, Status

StateDict = dict
RespDict = dict


def _exists(s: Optional[dict], now: int, algorithm: int) -> bool:
    """The shared cache-existence predicate (cf. bucket_transition):
    present, in use, not expired, stored algorithm matches."""
    return (
        s is not None
        and bool(s.get("in_use", True))
        and now <= s["expire_at"]
        and s.get("algorithm", 0) == algorithm
    )


def _base_state(req: dict) -> StateDict:
    """The request-uniform state fields every zoo transition stores."""
    return {
        "algorithm": int(req["algorithm"]),
        "limit": req["limit"],
        "remaining_f": 0.0,
        "duration": req["duration"],
        "updated_at": req["created_at"],
        "burst": req.get("burst", 0),
        "in_use": True,
        "tat": 0,
        "prev_count": 0,
    }


def sliding_window(s: Optional[dict], req: dict, now: int
                   ) -> Tuple[StateDict, RespDict]:
    """Scalar mirror of algos/sliding_window.py (see its docstring)."""
    behavior = req.get("behavior", 0)
    reset_b = bool(behavior & Behavior.RESET_REMAINING)
    drain_b = bool(behavior & Behavior.DRAIN_OVER_LIMIT)
    ex = _exists(s, now, Algorithm.SLIDING_WINDOW) and not reset_b

    t = max(req["created_at"], 0)
    dur = max(req["duration"], 1)
    aligned = t - t % dur

    ws0 = s["created_at"] if ex else aligned
    cur0 = max(s["remaining"], 0) if ex else 0
    prev0 = max(s["prev_count"], 0) if ex else 0

    delta = max(t - ws0, 0)
    k = delta // dur
    if k == 0:
        prev1, cur1, ws1 = prev0, cur0, ws0
    elif k == 1:
        prev1, cur1, ws1 = cur0, 0, aligned
    else:
        prev1, cur1, ws1 = 0, 0, aligned

    frac = min(max(dur - (t - ws1), 0), dur)
    wprev = prev1 * frac // dur
    used = wprev + cur1
    avail = max(req["limit"] - used, 0)

    h = req["hits"]
    admit = h > 0 and h <= avail
    over = h > 0 and not admit
    if admit:
        cur2 = cur1 + h
    elif over and drain_b:
        cur2 = cur1 + avail
    elif h < 0:
        cur2 = max(cur1 + h, 0)
    else:
        cur2 = cur1

    resp_rem = max(req["limit"] - (wprev + cur2), 0)
    status = Status.OVER_LIMIT if (over or (h == 0 and avail == 0)) \
        else Status.UNDER_LIMIT
    touch = h != 0 or not ex
    expire = t + 2 * dur if touch else s["expire_at"]

    new_state = _base_state(req)
    new_state.update(
        remaining=cur2, created_at=ws1, status=int(status),
        expire_at=expire, prev_count=prev1,
    )
    resp = {
        "status": int(status), "limit": req["limit"],
        "remaining": resp_rem, "reset_time": ws1 + dur,
        "over_limit": over,
    }
    return new_state, resp


def gcra(s: Optional[dict], req: dict, now: int
         ) -> Tuple[StateDict, RespDict]:
    """Scalar mirror of algos/gcra.py (see its docstring)."""
    behavior = req.get("behavior", 0)
    reset_b = bool(behavior & Behavior.RESET_REMAINING)
    ex = _exists(s, now, Algorithm.GCRA) and not reset_b

    t = req["created_at"]
    safe_limit = req["limit"] if req["limit"] > 0 else 1
    T = max(req["duration"], 0) // safe_limit
    burst = req.get("burst", 0)
    burst_eff = burst if burst > 0 else req["limit"]
    tau = (burst_eff - 1) * T

    tat0 = s["tat"] if ex else t
    tat1 = max(tat0, t)

    h = req["hits"]
    horizon = t + tau
    conform = tat1 + (h - 1) * T <= horizon
    admit = h > 0 and conform
    over = h > 0 and not conform
    if admit:
        tat2 = tat1 + h * T
    elif h < 0:
        tat2 = max(tat1 + h * T, t)
    else:
        tat2 = tat1

    slack = horizon - tat2
    if slack < 0:
        rem = 0
    elif T == 0:
        rem = burst_eff
    else:
        rem = min(slack // T + 1, burst_eff)
    rem = max(rem, 0)

    status = Status.OVER_LIMIT if (over or (h == 0 and rem == 0)) \
        else Status.UNDER_LIMIT
    touch = h != 0 or not ex
    expire = max(t + req["duration"], tat2) if touch else s["expire_at"]

    new_state = _base_state(req)
    new_state.update(
        remaining=rem,
        created_at=s["created_at"] if ex else t,
        status=int(status), expire_at=expire, tat=tat2,
    )
    resp = {
        "status": int(status), "limit": req["limit"], "remaining": rem,
        "reset_time": max(tat2 - tau, t), "over_limit": over,
    }
    return new_state, resp


def concurrency(s: Optional[dict], req: dict, now: int
                ) -> Tuple[StateDict, RespDict]:
    """Scalar mirror of algos/concurrency.py (see its docstring)."""
    behavior = req.get("behavior", 0)
    reset_b = bool(behavior & Behavior.RESET_REMAINING)
    ex = _exists(s, now, Algorithm.CONCURRENCY) and not reset_b

    t = req["created_at"]
    if ex:
        rem0 = max(s["remaining"] + (req["limit"] - s["limit"]), 0)
    else:
        rem0 = max(req["limit"], 0)

    h = req["hits"]
    admit = h > 0 and h <= rem0
    over = h > 0 and not admit
    if admit:
        rem1 = rem0 - h
    elif h < 0:
        rem1 = max(min(rem0 - h, req["limit"]), 0)
    else:
        rem1 = rem0

    touch = h != 0 or not ex
    expire = t + req["duration"] if touch else s["expire_at"]
    status = Status.OVER_LIMIT if (over or (h == 0 and rem1 == 0)) \
        else Status.UNDER_LIMIT

    new_state = _base_state(req)
    new_state.update(
        remaining=rem1,
        created_at=s["created_at"] if ex else t,
        status=int(status), expire_at=expire,
    )
    resp = {
        "status": int(status), "limit": req["limit"], "remaining": rem1,
        "reset_time": expire, "over_limit": over,
    }
    return new_state, resp


def _live(s: Optional[dict], now: int) -> bool:
    """The cache holds the item: present, in use, not expired (the
    stored algorithm is the caller's to look at)."""
    return (s is not None and bool(s.get("in_use", True))
            and now <= s["expire_at"])


def _legacy_resp(status: int, limit: int, remaining: int, reset_time: int
                 ) -> RespDict:
    return {
        "status": int(status), "limit": limit, "remaining": remaining,
        "reset_time": reset_time,
        "over_limit": status == Status.OVER_LIMIT,
    }


def token_bucket(s: Optional[dict], req: dict, now: int
                 ) -> Tuple[Optional[StateDict], RespDict]:
    """Upstream ``tokenBucket`` (algorithms.go:37-252).  ``now`` is the
    cache's clock (expiry); the arithmetic runs on the request's
    ``created_at``, as upstream's does.  A RESET_REMAINING on a live item
    removes it: the new state is ``None``.  DURATION_IS_GREGORIAN is not
    implemented."""
    behavior = req.get("behavior", 0)
    if behavior & Behavior.DURATION_IS_GREGORIAN:
        raise NotImplementedError("DURATION_IS_GREGORIAN")
    hits, limit, duration = req["hits"], req["limit"], req["duration"]
    t = req["created_at"]
    if _live(s, now) and behavior & Behavior.RESET_REMAINING:
        return None, _legacy_resp(Status.UNDER_LIMIT, limit, limit, 0)
    if not _live(s, now) or s.get("algorithm", 0) != Algorithm.TOKEN_BUCKET:
        # tokenBucketNewItem
        expire = t + duration
        over = hits > limit
        remaining = limit if over else limit - hits
        new = _base_state(req)
        new.update(
            algorithm=int(Algorithm.TOKEN_BUCKET), remaining=remaining,
            created_at=t, updated_at=0, burst=0,
            status=int(Status.UNDER_LIMIT), expire_at=expire)
        return new, _legacy_resp(
            Status.OVER_LIMIT if over else Status.UNDER_LIMIT,
            limit, remaining, expire)

    b = dict(s)
    if b["limit"] != limit:
        b["remaining"] = max(b["remaining"] + limit - b["limit"], 0)
        b["limit"] = limit
    # The answer is drawn up before a changed duration renews the item.
    status, remaining, reset = b["status"], b["remaining"], b["expire_at"]
    if b["duration"] != duration:
        expire = b["created_at"] + duration
        if expire <= t:
            expire = t + duration
            b["created_at"] = t
            b["remaining"] = limit
        b["expire_at"] = expire
        b["duration"] = duration
        reset = expire
    if hits == 0:
        return b, _legacy_resp(status, limit, remaining, reset)
    if remaining == 0 and hits > 0:
        b["status"] = int(Status.OVER_LIMIT)
        return b, _legacy_resp(Status.OVER_LIMIT, limit, remaining, reset)
    if b["remaining"] == hits:
        b["remaining"] = 0
        return b, _legacy_resp(status, limit, 0, reset)
    if hits > b["remaining"]:
        if behavior & Behavior.DRAIN_OVER_LIMIT:
            b["remaining"] = 0
            remaining = 0
        return b, _legacy_resp(Status.OVER_LIMIT, limit, remaining, reset)
    b["remaining"] -= hits
    return b, _legacy_resp(status, limit, b["remaining"], reset)


def leaky_bucket(s: Optional[dict], req: dict, now: int
                 ) -> Tuple[StateDict, RespDict]:
    """Upstream ``leakyBucket`` (algorithms.go:260-493).  ``remaining_f``
    is a Python float: ``float(i)``, ``/``, ``+=``, ``-=`` and ``int()``
    are Go's ``float64(i)``, the IEEE operations and ``int64(f)``, one
    rounding each.  ``limit`` and ``duration`` are positive (upstream
    would divide by zero as well).  DURATION_IS_GREGORIAN is not
    implemented."""
    behavior = req.get("behavior", 0)
    if behavior & Behavior.DURATION_IS_GREGORIAN:
        raise NotImplementedError("DURATION_IS_GREGORIAN")
    hits, limit, duration = req["hits"], req["limit"], req["duration"]
    burst = req.get("burst", 0) or limit
    t = req["created_at"]
    rate = float(duration) / float(limit)
    irate = int(rate)

    if not _live(s, now) or s.get("algorithm", 0) != Algorithm.LEAKY_BUCKET:
        # leakyBucketNewItem
        over = hits > burst
        left = 0 if over else burst - hits
        new = _base_state(req)
        new.update(
            algorithm=int(Algorithm.LEAKY_BUCKET), remaining=0,
            remaining_f=float(left), created_at=t, updated_at=t,
            burst=burst, status=int(Status.UNDER_LIMIT),
            expire_at=t + duration)
        return new, _legacy_resp(
            Status.OVER_LIMIT if over else Status.UNDER_LIMIT,
            limit, left, t + (limit - left) * irate)

    b = dict(s)
    if behavior & Behavior.RESET_REMAINING:
        b["remaining_f"] = float(burst)
    if b["burst"] != burst:
        if burst > int(b["remaining_f"]):
            b["remaining_f"] = float(burst)
        b["burst"] = burst
    b["limit"] = limit
    b["duration"] = duration
    if hits != 0:
        b["expire_at"] = t + duration
    elapsed = t - b["updated_at"]
    leak = float(elapsed) / rate
    if int(leak) > 0:
        b["remaining_f"] += leak
        b["updated_at"] = t
    if int(b["remaining_f"]) > burst:
        b["remaining_f"] = float(burst)
    rem = int(b["remaining_f"])
    reset = t + (limit - rem) * irate
    if rem == 0 and hits > 0:
        return b, _legacy_resp(Status.OVER_LIMIT, limit, rem, reset)
    if rem == hits:
        b["remaining_f"] = 0.0
        return b, _legacy_resp(Status.UNDER_LIMIT, limit, 0,
                               t + limit * irate)
    if hits > rem:
        if behavior & Behavior.DRAIN_OVER_LIMIT:
            b["remaining_f"] = 0.0
            rem = 0
        return b, _legacy_resp(Status.OVER_LIMIT, limit, rem, reset)
    if hits == 0:
        return b, _legacy_resp(Status.UNDER_LIMIT, limit, rem, reset)
    b["remaining_f"] -= float(hits)
    rem = int(b["remaining_f"])
    return b, _legacy_resp(Status.UNDER_LIMIT, limit, rem,
                           t + (limit - rem) * irate)


REFERENCE = {
    Algorithm.TOKEN_BUCKET: token_bucket,
    Algorithm.LEAKY_BUCKET: leaky_bucket,
    Algorithm.SLIDING_WINDOW: sliding_window,
    Algorithm.GCRA: gcra,
    Algorithm.CONCURRENCY: concurrency,
}


def transition(s: Optional[dict], req: dict, now: int
               ) -> Tuple[StateDict, RespDict]:
    """Dispatch on ``req['algorithm']``."""
    return REFERENCE[Algorithm(int(req["algorithm"]))](s, req, now)
