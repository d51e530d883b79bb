"""The fill: the daemon's start-up Loader (upstream ``Loader.Load``)
handed the seeded population, so 8M keys are resident before the window
without 8,000 served calls.  ``conf.loader`` takes any object with
``load_columns``/``save_columns`` (store.ColumnLoader); this one is kept
with the benchmark so that no later PR changes what is filled.
"""

from __future__ import annotations

import numpy as np

from . import population

# engine.SNAP_FIELDS, spelt out: the benchmark's file states what it
# fills, and a test holds it against the program's tuple.
SNAP_FIELDS = (
    "algorithm", "limit", "remaining", "remaining_f", "duration",
    "created_at", "updated_at", "burst", "status", "expire_at",
)


class SeededLoader:
    def __init__(self, pop: population.Population, t0_ms: int):
        self.pop, self.t0_ms = pop, int(t0_ms)
        self.loaded = 0

    def load_columns(self) -> dict:
        ids = np.arange(self.pop.n, dtype=np.int64)
        blob, offsets = population.key_blob(ids)
        snap = self.pop.state(ids, self.t0_ms)
        snap["key_blob"] = blob
        snap["key_offsets"] = offsets
        self.loaded = self.pop.n
        return snap

    def save_columns(self, snap: dict) -> None:
        """Nothing: a benchmark run keeps no table (else ``close()``
        exports 8M rows)."""
