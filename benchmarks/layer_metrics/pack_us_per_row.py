"""Host pack: the flight recorder's pack seconds (the lease included)
over the window, per row."""


def read(ctx):
    r = ctx["recorder"]
    return r["stage_s"]["pack"] * 1e6 / r["rows"] if r and r["rows"] else None
