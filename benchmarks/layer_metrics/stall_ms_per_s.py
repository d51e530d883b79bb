"""Serving process: milliseconds a second of the window that the whole
process stood still or compiled: the flight recorder's gc overlay
(garbage collections, any generation) and compile overlay (jax trace,
lowering and backend compile of a shape first met while serving).  0 in
a run that met neither; a program without the overlays reports
nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not ctx["wall_s"] or "gc" not in r["stage_s"] \
            or "compile" not in r["stage_s"]:
        return None
    return (r["stage_s"]["gc"] + r["stage_s"]["compile"]) * 1e3 / ctx["wall_s"]
