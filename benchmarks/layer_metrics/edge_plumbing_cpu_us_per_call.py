"""Transport edge: CPU microseconds a call of the event-loop thread
outside the edge handler, gRPC aio's per-call work and asyncio's (and
the server interceptors'): the flight recorder's edge_thread_cpu less
its edge_handler_cpu, per call decoded.  A program without the
edge_handler_cpu overlay reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["edge_calls"]["decode"] or "edge_handler_cpu" not in r["stage_s"]:
        return None
    s = r["stage_s"]
    return (s["edge_thread_cpu"] - s["edge_handler_cpu"]) * 1e6 / r["edge_calls"]["decode"]
