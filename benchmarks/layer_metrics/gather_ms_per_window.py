"""Admission + window formation: the flight recorder's gather seconds
(`_flush` up to the call into the engine: the expiry partition, the item
loop, ReqColumns.concat of the window's calls) over the window, per
window begun.  A program without the stage reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["windows"] or "gather" not in r["stage_s"]:
        return None
    return r["stage_s"]["gather"] * 1e3 / r["windows"]
