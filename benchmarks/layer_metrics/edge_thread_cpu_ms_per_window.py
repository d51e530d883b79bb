"""Serving process: CPU milliseconds a window of the event-loop thread
that runs the raw-bytes edge (gRPC's Python, decode, encode, the call's
awaits): the flight recorder's edge_thread_cpu overlay, that thread's
CPU clock read once a window by tick-loop, per window begun.  A program
without the overlay reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["windows"] or "edge_thread_cpu" not in r["stage_s"]:
        return None
    return r["stage_s"]["edge_thread_cpu"] * 1e3 / r["windows"]
