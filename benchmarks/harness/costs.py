"""What the algorithm needs, whatever implements it: the bytes a rate-limit
decision moves, and the chip's peaks.

A decision reads its bucket's state and writes it back once.  The state
is STATE_WORDS 32-bit words (the ten stored fields of a bucket: seven
int64 and one float64 as two words each, algorithm, status, in_use as
one each, plus the two int64 columns of the later algorithms = 24), fixed
here and not read from the program's table layout: a 512-byte row is
today's implementation, not the need.  The tick does almost no
arithmetic per byte, so it is bound by memory, not by FLOP/s.
"""

from __future__ import annotations

import json
import os

STATE_WORDS = 24
WORD_BYTES = 4


def decision_bytes(decisions: int) -> int:
    """Bytes of HBM traffic ``decisions`` rate-limit decisions need."""
    return int(decisions) * 2 * STATE_WORDS * WORD_BYTES


def peaks(device_kind: str) -> dict:
    """The peaks of a chip by ``device_kind``; an unknown one is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to"
                       " benchmarks/harness/peaks.json with its source")
    return table[device_kind]


def least_seconds(decisions: int, device_kind: str) -> float:
    """The least time the chip could take for them (memory-bound)."""
    return decision_bytes(decisions) / peaks(device_kind)["hbm_bytes_per_s"]
