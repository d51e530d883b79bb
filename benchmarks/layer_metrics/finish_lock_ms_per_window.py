"""Resolve: the flight recorder's finish_lock overlay (the resolver's
wait in TickHandle._finish to take engine._lock, which submit_columns
holds across the next window's pack and dispatch; it lies inside tick,
so inside resolve_ms_per_window), per window begun.  A program without
the overlay reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["windows"] or "finish_lock" not in r["stage_s"]:
        return None
    return r["stage_s"]["finish_lock"] * 1e3 / r["windows"]
