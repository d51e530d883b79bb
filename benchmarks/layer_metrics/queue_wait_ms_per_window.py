"""Admission + window formation: the flight recorder's queue overlay
(the mean, over a window's calls, of pop time less enqueue time: how
long a call waited for its window), averaged over the windows begun.  A
program without the overlay reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["windows"] or "queue" not in r["stage_s"]:
        return None
    return r["stage_s"]["queue"] * 1e3 / r["windows"]
