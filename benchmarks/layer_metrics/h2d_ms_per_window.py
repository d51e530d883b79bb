"""Dispatch: the flight recorder's h2d seconds over the window, per
window begun."""


def read(ctx):
    r = ctx["recorder"]
    return r["stage_s"]["h2d"] * 1e3 / r["windows"] if r and r["windows"] else None
