"""Admission + window formation: rows per window begun in the window
(the flight recorder's widths)."""


def read(ctx):
    r = ctx["recorder"]
    return r["rows"] / r["windows"] if r and r["windows"] else None
