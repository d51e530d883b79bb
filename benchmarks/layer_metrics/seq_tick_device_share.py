"""Device programs: the device seconds of the sequential tick program
(module jit_tick32_sequential: duplicate windows with no plan, rounds of
gather, up to eight chained units, scatter) as a share of the device time
of all programs in the trace.  The program names its four tick programs
jit_tick32_<branch>; a trace with none of them (a program from before they
were named) reports nothing, one with them and no sequential window 0."""

PREFIX = "jit_tick32_"
SEQUENTIAL = PREFIX + "sequential"


def read(ctx):
    tr = ctx["trace"]
    if not tr or not any(n.startswith(PREFIX) for n, _ in tr["modules"]):
        return None
    spent = ctx["xtrace"].program_seconds(tr)
    if spent <= 0:
        return None
    return 100.0 * sum(s for n, s in tr["modules"] if n == SEQUENTIAL) / spent
