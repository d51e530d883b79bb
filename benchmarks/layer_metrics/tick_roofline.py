"""Device programs: the least time the chip could take for the decisions
begun while the trace ran (each reads and writes its bucket's state once:
costs.decision_bytes, memory-bound, over the chip's HBM peak), as a share
of the summed device time of the programs in the trace (all of them:
the window runs nothing but ticks)."""


def read(ctx):
    tr, traced = ctx["trace"], ctx["traced"]
    if not tr or not traced or not traced["rows"]:
        return None
    spent = ctx["xtrace"].program_seconds(tr)
    if spent <= 0:
        return None
    return 100.0 * ctx["costs"].least_seconds(traced["rows"], ctx["device_kind"]) / spent
