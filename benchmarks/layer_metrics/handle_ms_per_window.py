"""Dispatch: what tick-loop spends a window round the pack and the
upload: the flight recorder's submit_lock (the wait for engine._lock),
handle (submit_columns after h2d to its return: the TickHandle, the
slab's retirement, the counters) and handoff (the parts' release and the
bounded put to the resolver) seconds, per window begun.  A program
without the stages reports nothing."""

STAGES = ("submit_lock", "handle", "handoff")


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["windows"] or any(s not in r["stage_s"] for s in STAGES):
        return None
    return sum(r["stage_s"][s] for s in STAGES) * 1e3 / r["windows"]
