"""The four per-layer metrics that read the flight recorder's CPU clocks
(PR 39): each reader against a hand-made ctx, nothing where the program
has no such overlay (the parent's recorder), and their entries in
BENCHMARK.json's per_layer, looked up by name."""

import json
import os

import pytest

from benchmarks import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# One run of 1,000 windows.  Seconds by stage (those the readers do not
# read are zero):
STAGE_S = {
    "gather": 4.3, "gather_cpu": 0.4,
    "tickloop_thread_cpu": 3.5, "edge_thread_cpu": 2.0, "resolver_cpu": 0.8,
    "process_cpu": 16.3,
}
EXPECTED = {
    "gather_cpu_ms_per_window": 0.4,
    "edge_thread_cpu_ms_per_window": 2.0,
    "resolver_cpu_ms_per_window": 0.8,
    "native_threads_cpu_ms_per_window": 10.0,   # 16.3 - 3.5 - 2.0 - 0.8
}


def ctx(stage_s, windows=1000):
    return {"recorder": {"stage_s": dict(stage_s), "windows": windows,
                         "rows": 4000 * windows,
                         "edge_calls": {"decode": 4000, "encode": 4000}},
            "wall_s": 10.0}


def with_all_stages():
    from gubernator_tpu.utils import flightrec

    return {s: STAGE_S.get(s, 0.0) for s in flightrec.STAGES}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_its_overlays(name):
    read = run.reader("layer_metrics", name)
    assert read(ctx(with_all_stages())) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_nothing_without_the_overlays(name):
    """The parent's recorder has PR 38's stages and none of these: the
    line leaves the metric out; so does an untraced run, and a window
    in which no window was begun."""
    from gubernator_tpu.utils import flightrec

    old = {s: 1.0 for s in flightrec.STAGES
           if s not in flightrec.CLOCKS and not s.endswith("_cpu")}
    read = run.reader("layer_metrics", name)
    assert read(ctx(old)) is None
    assert read({"recorder": None, "wall_s": 10.0}) is None
    assert read(ctx(dict.fromkeys(flightrec.STAGES, 0.0), windows=0)) is None


def test_the_overlays_read_are_the_programs():
    from gubernator_tpu.utils import flightrec

    assert set(STAGE_S) - {"gather"} <= set(flightrec.OVERLAYS)
    native = run.reader("layer_metrics",
                        "native_threads_cpu_ms_per_window").__globals__
    assert set(native["PYTHON"]) == set(flightrec.THREADS.values())


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_entry_is_read_in_every_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    entry = {m["name"]: m for m in per_layer}[name]
    layers = {m["layer"] for m in per_layer if m["name"] not in EXPECTED}
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py"))
    assert "workloads" not in entry and entry["moves"] == "decisions_per_s"
    assert entry["layer"] in layers
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves"}
