"""The benchmark's own flight recorder: totals over the window, not
percentiles of a ring.  The program's ``FlightRecorder`` keeps the last
256 windows; this subclass adds every noted second to a per-stage total
before the ring sees it, and counts windows and their rows.  Installed
with ``flightrec.install`` in the traced run only.
"""

from __future__ import annotations

from gubernator_tpu.utils import flightrec


class TotalsRecorder(flightrec.FlightRecorder):
    def __init__(self):
        super().__init__(windows=256)
        self.stage_s = {s: 0.0 for s in flightrec.STAGES}
        self.windows_begun = 0
        self.rows = 0
        self.edge_calls = {"decode": 0, "encode": 0}

    def begin(self, width, depth):
        self.windows_begun += 1
        self.rows += int(width)
        return super().begin(width, depth)

    def note(self, wid, stage, seconds):
        self.stage_s[stage] += seconds
        super().note(wid, stage, seconds)

    def edge(self, stage, seconds):
        self.stage_s[stage] += seconds
        self.edge_calls[stage] += 1
        super().edge(stage, seconds)

    def totals(self) -> dict:
        """A copy of the counts so far (the run reads it at the window's
        start and end and takes the difference)."""
        return {"stage_s": dict(self.stage_s), "windows": self.windows_begun,
                "rows": self.rows, "edge_calls": dict(self.edge_calls)}
