"""Transport edge: the share of the wall the event-loop thread was
neither on the CPU nor idle in its selector's select(): waiting for the
GIL, a lock or a core.  The flight recorder's clock_wall less
edge_thread_cpu and edge_idle, over clock_wall.  select()'s wall holds
the system call's own CPU and the GIL's re-take after a wake-up, so this
is a lower bound of the loop's stalls.  A program without clock_wall or
edge_idle reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or "clock_wall" not in r["stage_s"] or "edge_idle" not in r["stage_s"]:
        return None
    s = r["stage_s"]
    if s["clock_wall"] <= 0:
        return None
    return 100.0 * (s["clock_wall"] - s["edge_thread_cpu"] - s["edge_idle"]) / s["clock_wall"]
