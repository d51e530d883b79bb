"""Serving process, seen from the client: 99th percentile of an open
loop's call latency from the due time, over all calls of the window (what
call_p99_ms is in a closed loop).  Kept per layer there because one stall
of a quarter second inside a ten-second window triples it (PERF.md
section 2).  Nothing to read in a closed loop."""

import numpy as np


def read(ctx):
    lat = ctx["window"]["latency_s"]
    if ctx["mix"]["loop"] != "open" or not len(lat):
        return None
    return float(np.percentile(lat, 99)) * 1e3
