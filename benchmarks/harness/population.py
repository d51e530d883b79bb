"""The key population of a deployment: every bucket's parameters and its
state at fill time are a function of (seed, key id) and of the
configuration file's ``population`` block, nothing else.

Stateless: a splitmix64 hash of the key id picks each field, so the
Loader, the generator children and the reference each compute the rows
they need without holding (or sharing) an 8M-row table.  Imports nothing
of the program.
"""

from __future__ import annotations

import numpy as np

NAME = b"bench"
PREFIX = NAME + b"_k"
DIGITS = 8
KEY_LEN = len(PREFIX) + DIGITS
LEAKY = 1

_U = np.uint64


def mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser over a uint64 array (wraps by design)."""
    with np.errstate(over="ignore"):
        z = x.astype(_U) + _U(0x9E3779B97F4A7C15)
        z = (z ^ (z >> _U(30))) * _U(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> _U(27))) * _U(0x94D049BB133111EB)
        return z ^ (z >> _U(31))


def field(ids: np.ndarray, seed: int, salt: int) -> np.ndarray:
    """One uint64 of hash per key id, independent per (seed, salt)."""
    base = mix(np.asarray([seed & 0xFFFFFFFFFFFFFFFF], _U))
    with np.errstate(over="ignore"):
        base = mix(base + _U(salt))
        return mix(np.asarray(ids).astype(_U) ^ base[0])


def key_blob(ids: np.ndarray) -> tuple:
    """(blob uint8 (n*KEY_LEN,), offsets (n+1,) int64): the hash keys
    ``bench_k00000042`` of the ids, made without a Python loop."""
    ids = np.asarray(ids, np.int64)
    n = len(ids)
    out = np.empty((n, KEY_LEN), np.uint8)
    out[:, : len(PREFIX)] = np.frombuffer(PREFIX, np.uint8)
    x = ids.copy()
    for d in range(DIGITS):
        out[:, KEY_LEN - 1 - d] = 48 + x % 10
        x //= 10
    return out.reshape(-1), np.arange(n + 1, dtype=np.int64) * KEY_LEN


class Population:
    """``spec`` is the configuration file's ``population`` block:
    keys, limit[], duration_ms[]; and, where some buckets are leaky,
    leaky_share and leaky_burst[]."""

    def __init__(self, spec: dict, seed: int):
        self.n = int(spec["keys"])
        self.seed = int(seed)
        self.leaky_share = float(spec.get("leaky_share", 0.0))
        self.limits = np.asarray(spec["limit"], np.int64)
        self.durations = np.asarray(spec["duration_ms"], np.int64)
        self.bursts = np.asarray(spec.get("leaky_burst", [0]), np.int64)

    def _pick(self, ids, salt, choices):
        return choices[(field(ids, self.seed, salt) % _U(len(choices))).astype(np.int64)]

    def params(self, ids: np.ndarray) -> tuple:
        """(algorithm, limit, duration, burst) int64 columns, as a
        client sends them for these keys."""
        ids = np.asarray(ids, np.int64)
        u = (field(ids, self.seed, 1) >> _U(11)).astype(np.float64) / float(1 << 53)
        alg = (u < self.leaky_share).astype(np.int64)
        limit = self._pick(ids, 2, self.limits)
        duration = self._pick(ids, 3, self.durations)
        burst = np.where(alg == LEAKY, self._pick(ids, 4, self.bursts), 0)
        return alg, limit, duration, burst

    def state(self, ids: np.ndarray, t0: int) -> dict:
        """Each key's bucket as the fill leaves it at time ``t0`` (ms):
        a seeded ``remaining`` (token: whole, leaky: in sixteenths), so
        the first served touch of a key proves the restore."""
        ids = np.asarray(ids, np.int64)
        alg, limit, duration, burst = self.params(ids)
        cap = np.where(alg == LEAKY, np.where(burst == 0, limit, burst), limit)
        h = field(ids, self.seed, 5)
        whole = (h % (cap.astype(_U) + _U(1))).astype(np.int64)
        sixteenths = ((h >> _U(40)) % _U(16)).astype(np.float64) / 16.0
        rem_f = np.where(
            alg == LEAKY,
            np.minimum(whole.astype(np.float64) + sixteenths, cap.astype(np.float64)),
            0.0)
        n = len(ids)
        return {
            "algorithm": alg,
            "limit": limit,
            "remaining": np.where(alg == LEAKY, rem_f.astype(np.int64), whole),
            "remaining_f": rem_f,
            "duration": duration,
            "created_at": np.full(n, t0, np.int64),
            "updated_at": np.full(n, t0, np.int64),
            "burst": np.where(alg == LEAKY, cap, 0),
            "status": np.zeros(n, np.int64),
            "expire_at": t0 + duration,
        }
