"""Resolve: CPU milliseconds a window of the resolver thread
(tick-resolve: the fetch, the un-permute, delivery): the flight
recorder's resolver_cpu overlay, that thread's CPU clock read once a
window by tick-loop, per window begun.  A program without the overlay reports
nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["windows"] or "resolver_cpu" not in r["stage_s"]:
        return None
    return r["stage_s"]["resolver_cpu"] * 1e3 / r["windows"]
