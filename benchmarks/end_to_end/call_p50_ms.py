"""Median over all answered calls of the window, client side: from the
sending in a closed loop, from when the call was due in an open one."""

import numpy as np


def read(ctx):
    lat = ctx["window"]["latency_s"]
    return float(np.percentile(lat, 50)) * 1e3 if len(lat) else None
