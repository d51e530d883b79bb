"""Admission + window formation: the flight recorder's gather_cpu seconds
(time.thread_time() of tick-loop across the gather stage) per window
begun.  gather_ms_per_window less this is tick-loop off the CPU inside
gather, which makes no blocking call: its wait for the GIL.  Where the
thread clock moves in ticks (10 ms on the chip's host) this is a count
of ticks, right over a run's windows, not in one.  A program without
the overlay reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["windows"] or "gather_cpu" not in r["stage_s"]:
        return None
    return r["stage_s"]["gather_cpu"] * 1e3 / r["windows"]
