"""Admission + window formation: the flight recorder's wait seconds
(tick-loop between windows, from the end of one flush to the pop of the
next batch: the condition variable and BatchWait) over the window, per
window begun.  A program without the stage reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["windows"] or "wait" not in r["stage_s"]:
        return None
    return r["stage_s"]["wait"] * 1e3 / r["windows"]
