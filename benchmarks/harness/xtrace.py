"""From a profiler trace to numbers: device busy and idle, time by
device operation and by program, the longest idle gaps and what the host
was doing in them.

Two steps, so that the arithmetic is checked on a small recorded trace
(benchmarks/tests/data/) without a chip:

  load(path)      .xplane.pb -> {plane: {line: [(name, start_ns, dur_ns)]}}
                  (jax.profiler.ProfileData; nothing else)
  reduce(events)  -> the numbers

On a TPU the device planes are named ``/device:TPU:<n>``; each has a
line of operations (``XLA Ops``) and one of whole programs
(``XLA Modules``).  Host threads are lines of ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_OPS_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return out


def union_ns(starts: np.ndarray, ends: np.ndarray) -> tuple:
    """(covered ns, gaps as (start, end) arrays) of a set of intervals."""
    if len(starts) == 0:
        return 0.0, (np.zeros(0), np.zeros(0))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])   # a gap precedes it
    seg_start = s[new]
    seg_end = np.concatenate([reach[:-1][new[1:]], reach[-1:]])
    return float((seg_end - seg_start).sum()), (seg_end[:-1], seg_start[1:])


def short(name: str) -> str:
    """An operation's own name: the trace gives the whole HLO instruction
    ("%tick.1 = (s32[...]) custom-call(...)"), a program its name and a
    fingerprint ("jit_tick(1161...)")."""
    return name.split(" = ")[0].lstrip("%").split("(")[0][:80]


def _by_name(evs, top):
    by = {}
    for name, _, dur in evs:
        name = short(name)
        by[name] = by.get(name, 0.0) + dur
    return [[n, d / 1e9] for n, d in
            sorted(by.items(), key=lambda kv: -kv[1])[:top]]


def _owner(gs, ge, host):
    """The host event that overlaps the gap most (name, or 'no host event')."""
    names, hs, he = host
    if len(hs) == 0:
        return "no host event"
    over = np.minimum(he, ge) - np.maximum(hs, gs)
    i = int(np.argmax(over))
    return names[i] if over[i] > 0 else "no host event"


def reduce(events: dict, top: int = 10) -> dict:
    """busy_s and window_s averaged over the device planes; seconds by
    device operation and by program; the idle seconds, by what the host
    was doing, of the 200 longest gaps."""
    devices = sorted(p for p in events if p.startswith(DEVICE_PREFIX))
    if not devices:
        raise ValueError("the trace holds no device plane")
    lo = min((s for lines in events.values() for evs in lines.values()
              for _, s, _ in evs), default=0.0)
    hi = max((s + d for lines in events.values() for evs in lines.values()
              for _, s, d in evs), default=0.0)
    host_evs = [e for evs in events.get(HOST_PLANE, {}).values() for e in evs
                if e[2] > 0]
    host = ([e[0] for e in host_evs],
            np.asarray([e[1] for e in host_evs]),
            np.asarray([e[1] + e[2] for e in host_evs]))
    busy, ops, modules, idle_by = [], [], [], {}
    for plane in devices:
        evs = events[plane].get(OPS_LINE, []) + events[plane].get(ASYNC_OPS_LINE, [])
        ops.extend(evs)
        modules.extend(events[plane].get(MODULES_LINE, []))
        starts = np.asarray([e[1] for e in evs])
        ends = starts + np.asarray([e[2] for e in evs])
        covered, (gs, ge) = union_ns(starts, ends)
        busy.append(covered)
        longest = np.argsort(gs - ge)[:200]
        for i in longest:
            who = short(_owner(gs[i], ge[i], host))
            idle_by[who] = idle_by.get(who, 0.0) + float(ge[i] - gs[i])
    n = len(devices)
    return {
        "devices": n,
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / n] for k, v in _by_name(ops, top)],
        "modules": [[k, v / n] for k, v in _by_name(modules, 1 << 30)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]],
    }


def program_seconds(reduced: dict) -> float:
    """Device seconds of all programs in the trace.  The window runs
    nothing but the served path, so every program on the device is a part
    of a tick (today jit_tick, its jit__lambda expansion and a
    convert_element_type); counting them all survives a rename."""
    return sum(s for _, s in reduced["modules"])
