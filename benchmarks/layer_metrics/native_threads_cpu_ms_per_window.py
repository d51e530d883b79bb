"""Serving process: CPU milliseconds a window of the threads no Python
thread accounts for (gRPC's C core, the PJRT / TPU runtime, in a traced
run the profiler): the flight recorder's process_cpu less its
tickloop_thread_cpu, edge_thread_cpu and resolver_cpu, all read once a
window by tick-loop, per window begun.  A program without the overlays
reports nothing."""

PYTHON = ("tickloop_thread_cpu", "edge_thread_cpu", "resolver_cpu")


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["windows"] or "process_cpu" not in r["stage_s"]:
        return None
    s = r["stage_s"]
    return (s["process_cpu"] - sum(s[k] for k in PYTHON)) * 1e3 / r["windows"]
