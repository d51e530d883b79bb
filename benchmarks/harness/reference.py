"""The plain reference: Gubernator's token and leaky buckets, written
from upstream ``algorithms.go`` (``tokenBucket``, ``tokenBucketNewItem``,
``leakyBucket``, ``leakyBucketNewItem``) in Python int and float, which
are int64-exact and IEEE float64.  One bucket, one request at a time, in
the order given.  Imports nothing of the program and takes nothing the
program has made.

A bucket is a dict (or None where the key holds nothing):
  algorithm, limit, remaining (token, int), remaining_f (leaky, float),
  duration, created_at, updated_at, burst, status, expire_at
A request is (hits, limit, duration, burst, algorithm, behavior,
created_at).  An answer is (status, limit, remaining, reset_time).

The request's ``created_at`` is the clock, as upstream (the client may
set it; the benchmark's generators do).  DURATION_IS_GREGORIAN is not
implemented: no benchmark traffic sets it.

``control`` names the guarantee to break, for the control that
``correct`` has to fail (PERF.md section 2):
  "lost_hit"   every 64th state-changing hit is answered but not applied
               (an acknowledged hit applied zero times).
"""

from __future__ import annotations

TOKEN, LEAKY = 0, 1
UNDER, OVER = 0, 1
RESET_REMAINING = 8
DRAIN_OVER_LIMIT = 32
GREGORIAN = 4


class Reference:
    def __init__(self, control: str = ""):
        if control not in ("", "lost_hit"):
            raise ValueError(f"unknown control {control!r}")
        self.control = control
        self._applied = 0

    def _lose(self) -> bool:
        """True where the control drops this state-changing hit."""
        if self.control != "lost_hit":
            return False
        self._applied += 1
        return self._applied % 64 == 0

    def apply(self, b, req):
        """(bucket after, answer) for one request on one bucket."""
        hits, limit, duration, burst, algorithm, behavior, now = req
        if behavior & GREGORIAN:
            raise NotImplementedError("DURATION_IS_GREGORIAN")
        if b is not None and now > b["expire_at"]:
            b = None                      # the cache treats expired as a miss
        if algorithm == TOKEN:
            return self._token(b, hits, limit, duration, behavior, now)
        return self._leaky(b, hits, limit, duration, burst, behavior, now)

    # -- tokenBucket ---------------------------------------------------
    def _token(self, b, hits, limit, duration, behavior, now):
        if b is not None and behavior & RESET_REMAINING:
            return None, (UNDER, limit, limit, 0)
        if b is None or b["algorithm"] != TOKEN:
            return self._token_new(hits, limit, duration, now)
        if b["limit"] != limit:
            b["remaining"] = max(b["remaining"] + limit - b["limit"], 0)
            b["limit"] = limit
        status, remaining, reset = b["status"], b["remaining"], b["expire_at"]
        if b["duration"] != duration:
            expire = b["created_at"] + duration
            if expire <= now:
                expire = now + duration
                b["created_at"] = now
                b["remaining"] = limit
            b["expire_at"] = expire
            b["duration"] = duration
            reset = expire
        if hits == 0:
            return b, (status, limit, remaining, reset)
        if remaining == 0 and hits > 0:
            b["status"] = OVER
            return b, (OVER, limit, remaining, reset)
        if b["remaining"] == hits:
            if not self._lose():
                b["remaining"] = 0
            return b, (status, limit, 0, reset)
        if hits > b["remaining"]:
            if behavior & DRAIN_OVER_LIMIT:
                b["remaining"] = 0
                return b, (OVER, limit, 0, reset)
            return b, (OVER, limit, remaining, reset)
        left = b["remaining"] - hits
        if not self._lose():
            b["remaining"] = left
        return b, (status, limit, left, reset)

    @staticmethod
    def _token_new(hits, limit, duration, now):
        expire = now + duration
        b = {"algorithm": TOKEN, "limit": limit, "remaining": limit - hits,
             "remaining_f": 0.0, "duration": duration, "created_at": now,
             "updated_at": 0, "burst": 0, "status": UNDER,
             "expire_at": expire}
        if hits > limit:
            b["remaining"] = limit
            return b, (OVER, limit, limit, expire)
        return b, (UNDER, limit, limit - hits, expire)

    # -- leakyBucket ---------------------------------------------------
    def _leaky(self, b, hits, limit, duration, burst, behavior, now):
        if burst == 0:
            burst = limit
        if b is None or b["algorithm"] != LEAKY:
            return self._leaky_new(hits, limit, duration, burst, now)
        if behavior & RESET_REMAINING:
            b["remaining_f"] = float(burst)
        if b["burst"] != burst:
            if burst > int(b["remaining_f"]):
                b["remaining_f"] = float(burst)
            b["burst"] = burst
        b["limit"] = limit
        b["duration"] = duration
        rate = float(duration) / float(limit)
        if hits != 0:
            b["expire_at"] = now + duration
        elapsed = now - b["updated_at"]
        leak = float(elapsed) / rate
        if int(leak) > 0:
            b["remaining_f"] += leak
            b["updated_at"] = now
        if int(b["remaining_f"]) > burst:
            b["remaining_f"] = float(burst)
        rem = int(b["remaining_f"])
        irate = int(rate)
        reset = now + (limit - rem) * irate
        if rem == 0 and hits > 0:
            return b, (OVER, limit, rem, reset)
        if rem == hits:
            if not self._lose():
                b["remaining_f"] = 0.0
            return b, (UNDER, limit, 0, now + limit * irate)
        if hits > rem:
            if behavior & DRAIN_OVER_LIMIT:
                b["remaining_f"] = 0.0
                return b, (OVER, limit, 0, reset)
            return b, (OVER, limit, rem, reset)
        if hits == 0:
            return b, (UNDER, limit, rem, reset)
        left = b["remaining_f"] - float(hits)
        if not self._lose():
            b["remaining_f"] = left
        return b, (UNDER, limit, int(left), now + (limit - int(left)) * irate)

    @staticmethod
    def _leaky_new(hits, limit, duration, burst, now):
        irate = int(float(duration) / float(limit))
        b = {"algorithm": LEAKY, "limit": limit, "remaining": 0,
             "remaining_f": float(burst - hits), "duration": duration,
             "created_at": now, "updated_at": now, "burst": burst,
             "status": UNDER, "expire_at": now + duration}
        if hits > burst:
            b["remaining_f"] = 0.0
            return b, (OVER, limit, 0, now + limit * irate)
        return b, (UNDER, limit, burst - hits,
                   now + (limit - (burst - hits)) * irate)
