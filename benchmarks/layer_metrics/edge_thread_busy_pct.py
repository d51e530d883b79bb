"""Transport edge: the share of the wall the event-loop thread that runs
the raw-bytes edge was on the CPU: the flight recorder's edge_thread_cpu
(that thread's CPU clock) over its clock_wall (the wall between the same
readings), both read once a window by tick-loop.  A program without
clock_wall reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or "clock_wall" not in r["stage_s"]:
        return None
    s = r["stage_s"]
    return 100.0 * s["edge_thread_cpu"] / s["clock_wall"] if s["clock_wall"] > 0 else None
