"""Serving process, all host layers: process_time() of the daemon's
process over the window, per decision answered in it."""


def read(ctx):
    n = ctx["window"]["decisions"]
    return ctx["cpu_s"] * 1e6 / n if n else None
