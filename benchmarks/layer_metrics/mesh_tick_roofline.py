"""Sharded device programs: the least time one chip could take for its
share of the decisions begun while the trace ran (the rows over the
trace's devices, each read and written once: costs.decision_bytes over
the chip's HBM peak), as a share of the device time of the programs in
the trace, which xtrace.reduce already averages over the devices: each
chip's decisions against its own program time, the psum inside it."""


def read(ctx):
    tr, traced = ctx["trace"], ctx["traced"]
    if not tr or not traced or not traced["rows"]:
        return None
    spent = ctx["xtrace"].program_seconds(tr)
    if spent <= 0:
        return None
    rows = traced["rows"] / tr["devices"]
    return 100.0 * ctx["costs"].least_seconds(rows, ctx["device_kind"]) / spent
