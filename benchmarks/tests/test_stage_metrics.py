"""The seven per-layer metrics that read the flight recorder's whole
cycle (PR 38): each reader against a hand-made ctx, nothing where the
program has no such stage (the parent's recorder), and every entry of
BENCHMARK.json's per_layer has its reader file."""

import json
import os

import pytest

from benchmarks import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# One ten-second window of 1,000 windows.  Seconds by stage:
STAGE_S = {
    "decode": 0.5, "encode": 0.3, "tick": 6.0, "resolve": 0.2,
    "wait": 0.4, "gather": 1.5, "submit_lock": 0.1, "route": 0.0,
    "pack": 1.0, "ssd": 0.0, "h2d": 1.4, "handle": 0.7, "handoff": 0.2,
    "lease": 0.05, "queue": 13.0, "finish_lock": 5.0, "cpu": 3.675,
    "compile": 0.05, "gc": 0.25,
}
OLD = ("decode", "route", "lease", "pack", "ssd", "h2d", "tick", "resolve",
       "encode")
EXPECTED = {
    "gather_ms_per_window": 1.5,
    "loop_wait_ms_per_window": 0.4,
    "queue_wait_ms_per_window": 13.0,
    "handle_ms_per_window": 1.0,            # 0.1 + 0.7 + 0.2
    "finish_lock_ms_per_window": 5.0,
    "tickloop_cpu_pct": 75.0,               # 3.675 of the flush's 4.9 s
    "stall_ms_per_s": 30.0,                 # 0.3 s of 10
}


def ctx(stage_s, windows=1000):
    return {"recorder": {"stage_s": dict(stage_s), "windows": windows,
                         "rows": 4000 * windows,
                         "edge_calls": {"decode": 4000, "encode": 4000}},
            "wall_s": 10.0}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_its_stages(name):
    read = run.reader("layer_metrics", name)
    assert read(ctx(STAGE_S)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_nothing_without_the_stage(name):
    """The parent's recorder has the nine old stages only, and an
    untraced run has no recorder: the line leaves the metric out."""
    read = run.reader("layer_metrics", name)
    assert read(ctx({s: STAGE_S[s] for s in OLD})) is None
    assert read({"recorder": None, "wall_s": 10.0}) is None
    if name != "stall_ms_per_s":      # a window in which none was begun
        assert read(ctx(dict.fromkeys(STAGE_S, 0.0), windows=0)) is None


def test_a_run_without_stalls_reads_zero():
    read = run.reader("layer_metrics", "stall_ms_per_s")
    assert read(ctx(dict(STAGE_S, gc=0.0, compile=0.0))) == 0.0


def test_every_per_layer_entry_has_its_reader_and_the_new_ones_are_last():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    for name in names:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py")), name
    new = names[-len(EXPECTED):]
    assert sorted(new) == sorted(EXPECTED)
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"][-len(EXPECTED):]:
        assert m["source"] == "program_span" and "workloads" not in m
        assert m["moves"] in end_to_end
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}


def test_the_stage_names_are_the_programs():
    """What the readers sum is what the program records: the flush's
    stages are flightrec.CYCLE less the wait."""
    from gubernator_tpu.utils import flightrec

    cpu = run.reader("layer_metrics", "tickloop_cpu_pct").__globals__
    handle = run.reader("layer_metrics", "handle_ms_per_window").__globals__
    assert cpu["FLUSH"] == flightrec.CYCLE[1:]
    assert set(handle["STAGES"]) < set(flightrec.CYCLE)
    assert set(STAGE_S) == set(flightrec.STAGES)
