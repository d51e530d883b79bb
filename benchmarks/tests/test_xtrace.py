"""The reduction from a trace to numbers: the interval arithmetic on a
made-up trace whose answers can be worked out by hand, and the whole
reduction on a small excerpt recorded on the chip (TPU v5 lite, a
closed16-zipf window on a 10M-row table, PR 26; tests/data/trace_excerpt.json:
40 ms from the middle of the device's operations, {plane: {line: [[name,
start_ns, dur_ns]]}}, the host's 400 longest events)."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import xtrace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_excerpt.json")


def test_union_of_intervals_and_its_gaps():
    starts = np.asarray([0.0, 5.0, 20.0, 22.0, 50.0])
    ends = np.asarray([10.0, 8.0, 30.0, 25.0, 60.0])
    covered, (gs, ge) = xtrace.union_ns(starts, ends)
    assert covered == 10 + 10 + 10
    assert gs.tolist() == [10.0, 30.0] and ge.tolist() == [20.0, 50.0]
    assert xtrace.union_ns(np.zeros(0), np.zeros(0))[0] == 0.0


def made_up():
    dev = {
        "XLA Modules": [("jit_tick(123)", 1000.0, 3000.0), ("jit__lambda(9)", 6000.0, 1000.0)],
        "XLA Ops": [("%tick.1 = (s32[8]) custom-call(...)", 1000.0, 2000.0),
                    ("%fusion.2 = s32[8] fusion(...)", 2500.0, 1500.0),
                    ("%copy.1 = s32[8] copy(...)", 6000.0, 1000.0)],
        "Async XLA Ops": [("%copy-start = (s32[8]) copy-start(...)", 6500.0, 1500.0)],
    }
    host = {"python3": [("PjitFunction(tick)", 0.0, 900.0), ("np.asarray", 4100.0, 1800.0)],
            "pjrt": [("H2D Dispatch", 9000.0, 1000.0)]}
    return {"/device:TPU:0": dev, "/host:CPU": host, "/host:metadata": {}}


def test_reduce_a_made_up_trace():
    r = xtrace.reduce(made_up())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(10000e-9)        # 0 .. 10000 ns
    assert r["busy_s"] == pytest.approx((3000 + 2000) * 1e-9)   # 1000-4000, 6000-8000
    assert r["device_ops"][0] == ["tick.1", pytest.approx(2000e-9)]
    assert dict(map(tuple, r["modules"])) == {
        "jit_tick": pytest.approx(3000e-9), "jit__lambda": pytest.approx(1000e-9)}
    assert xtrace.program_seconds(r) == pytest.approx(4000e-9)
    # the one gap (4000-6000) belongs to the host event that overlaps it most
    assert r["idle_gaps"] == [["np.asarray", pytest.approx(2000e-9)]]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        xtrace.reduce({"/host:CPU": {"python3": [("x", 0.0, 1.0)]}})


def test_reduce_the_recorded_excerpt():
    with open(DATA) as f:
        events = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                  for p, lines in json.load(f).items()}
    assert "/device:TPU:0" in events and "XLA Ops" in events["/device:TPU:0"]
    r = xtrace.reduce(events)
    assert 0 < r["busy_s"] < r["window_s"] <= 0.0401
    assert r["device_ops"] and all(" = " not in n for n, _ in r["device_ops"])
    assert sum(s for _, s in r["device_ops"]) >= r["busy_s"] * 0.999
    assert r["modules"] and xtrace.program_seconds(r) > 0
    assert r["idle_gaps"]
