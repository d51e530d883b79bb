"""Transport edge: CPU microseconds a call of the edge handler itself on
the event loop (V1Servicer.GetRateLimits from its entry to its wait on
the tick, and from its resumption to its return: the deadline's metadata
walk, both codec passes, the submit, the Prometheus updates): the flight
recorder's edge_handler_cpu overlay, per call decoded.  A program
without the overlay reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["edge_calls"]["decode"] or "edge_handler_cpu" not in r["stage_s"]:
        return None
    return r["stage_s"]["edge_handler_cpu"] * 1e6 / r["edge_calls"]["decode"]
