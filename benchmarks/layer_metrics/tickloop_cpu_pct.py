"""Serving process: the share of `_flush` that tick-loop was on the CPU:
the flight recorder's cpu overlay (time.thread_time() across the flush)
over the wall seconds of the flush's stages.  The rest is the thread off
the CPU: waiting for the GIL, a lock or the runtime.  A program without
the overlay, or a window that flushed nothing, reports nothing."""

FLUSH = ("gather", "submit_lock", "route", "pack", "ssd", "h2d", "handle",
         "handoff")


def read(ctx):
    r = ctx["recorder"]
    if not r or "cpu" not in r["stage_s"]:
        return None
    wall = sum(r["stage_s"].get(s, 0.0) for s in FLUSH)
    return 100.0 * r["stage_s"]["cpu"] / wall if wall > 0 else None
