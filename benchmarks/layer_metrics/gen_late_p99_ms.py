"""Load generator: 99th percentile of sent - due over the window's calls
(in a closed loop a call is due when the lane's last one returned)."""

import numpy as np


def read(ctx):
    late = ctx["window"]["late_s"]
    return float(np.percentile(late, 99)) * 1e3 if len(late) else None
