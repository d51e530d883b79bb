"""99th percentile of the samples call_p50_ms takes the median of."""

import numpy as np


def read(ctx):
    lat = ctx["window"]["latency_s"]
    return float(np.percentile(lat, 99)) * 1e3 if len(lat) else None
