"""Transport edge: the flight recorder's decode + encode seconds over the
window, per call decoded."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["edge_calls"]["decode"]:
        return None
    return (r["stage_s"]["decode"] + r["stage_s"]["encode"]) * 1e6 / r["edge_calls"]["decode"]
