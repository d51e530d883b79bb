"""Sharded device programs of the column layout: the least time one chip
could take for its share of the decisions begun while the trace ran (the
rows over the trace's devices, each read and written once:
costs.decision_bytes over the chip's HBM peak, the need and not the
layout's 93 B a slot), as a share of the device time of the column tick
programs alone, modules named ``jit_mesh_tick_<program>_columns``
(per-chip means, as xtrace.reduce gives them; the psum inside them).
The restore, evict and dead-scan programs are left out.  A trace that
names no such module (the row layout, or a program from before they
were named) reports nothing."""

PREFIX = "jit_mesh_tick_"
SUFFIX = "_columns"


def read(ctx):
    tr, traced = ctx["trace"], ctx["traced"]
    if not tr or not traced or not traced["rows"]:
        return None
    spent = sum(s for n, s in tr["modules"]
                if n.startswith(PREFIX) and n.endswith(SUFFIX))
    if spent <= 0:
        return None
    rows = traced["rows"] / tr["devices"]
    return 100.0 * ctx["costs"].least_seconds(rows, ctx["device_kind"]) / spent
