"""Resolve: the flight recorder's tick (the D2H wait; windows resolved in
one drain each report it) + resolve seconds, per window begun."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["windows"]:
        return None
    return (r["stage_s"]["tick"] + r["stage_s"]["resolve"]) * 1e3 / r["windows"]
