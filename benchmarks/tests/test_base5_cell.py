"""``base5-100m-mesh4`` and ``base3-mixed-10m.open-small-zipf``: the files
say what the issue asks of them and load through ``run.py``'s own
lookups; the four-chip cell rehearses on four virtual CPU devices through
the harness as it stands, its table in the column layout; and
``col_tick_roofline`` reads the column tick programs' share of a reduced
trace, and nothing from a trace that names none."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import costs, xtrace
from benchmarks.harness.population import Population

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "base5-100m-mesh4.closed16-zipf"
OPEN = "base3-mixed-10m.open-small-zipf"


def load(name):
    with open(os.path.join(ROOT, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run():
    spec = importlib.util.spec_from_file_location(
        "bench_run_base5", os.path.join(ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_files(run, name):
    """(cell, configs entry, configuration, mix) as ``run.main`` finds them."""
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (cell, entry, run.load_json(os.path.join(run.ROOT, entry["file"])),
            run.load_json(os.path.join(run.HERE, "traffic", cell["traffic"] + ".json")))


def test_the_configuration_is_configs_4_at_its_own_scale(run):
    cell, entry, conf, mix = cell_files(run, CELL)
    mesh = load("benchmarks/configs/base3-mixed-10m-mesh4.json")
    assert entry["reduced"] == [] and conf["reduced"] == {}
    assert "configs[4]" in entry["source"] and "100M keys" in entry["source"]
    assert conf["chips"] == cell["chips"] == 4 and conf["architecture"] is None
    # the columns come from auto, as a deployment gets them
    assert conf["env"] == {"GUBER_CACHE_SIZE": "125000000", "GUBER_TPU_MESH_SHARDS": "4"}
    assert conf["population"] == dict(mesh["population"], keys=100_000_000)
    assert conf["guarantees"] == mesh["guarantees"]
    assert conf["rehearse"] == {"env": {"GUBER_CACHE_SIZE": "40000"}, "keys": 32000}
    assert set(conf["not_carried"]) == {"DRAIN_OVER_LIMIT + RESET_REMAINING",
                                        "multi-region picker"}
    assert set(mesh["assumed"]) <= set(conf["assumed"])
    pop = Population(conf["population"], 2147495011)
    assert pop.n == 100_000_000 and pop.leaky_share == 0.5
    # an 80 % fill: 31,250,000 slots a shard, 93 B a slot in columns
    slots = int(conf["env"]["GUBER_CACHE_SIZE"]) // 4
    assert pop.n / (4 * slots) == 0.8 and slots == 31_250_000
    assert "2.91 GB of columns a chip" in conf["assumed"]["fill"]
    assert mix == load("benchmarks/traffic/closed16-zipf.json")
    for text in (entry["source"], entry["why"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()


def test_the_open_cell_is_open_small_uniform_with_zipf_keys(run):
    cell, _, conf, mix = cell_files(run, OPEN)
    assert conf == load("benchmarks/configs/base3-mixed-10m.json")
    assert cell["chips"] == 1
    uniform = load("benchmarks/traffic/open-small-uniform.json")
    same = set(uniform) - {"name", "what", "keys", "check"}
    assert set(mix) == set(uniform)
    assert {k: mix[k] for k in same} == {k: uniform[k] for k in same}
    assert mix["keys"] == {"dist": "zipfian", "theta": 0.99, "scramble": 7919}
    assert mix["check"] == {"sample_mod": 2, "hot_ranks": 2}
    assert (mix["rate_calls_per_s"], mix["lanes"], mix["items"]) == (520, 256, [1, 10])
    assert 1 <= len(cell["why"]) <= 200 and cell["why"].isprintable()


def test_the_metric_is_appended_for_the_cell_alone():
    bench = load("BENCHMARK.json")
    m = bench["per_layer"][-1]
    assert m == {"name": "col_tick_roofline", "unit": "%", "better": "higher",
                 "source": "device_trace",
                 "layer": "sharded device programs (column layout)",
                 "moves": "decisions_per_s", "workloads": [CELL]}
    assert [w["name"] for w in bench["workloads"][-2:]] == [CELL, OPEN]
    assert bench["configs"][-1]["name"] == "base5-100m-mesh4"


def test_the_cell_rehearses_on_four_virtual_devices():
    """Counts only: a rehearsal ends exit 1 by design, with the
    comparison's own verdict in its last lines.  Untraced, so that it
    shares no trace directory with test_mesh4_cell's traced rehearsal
    (``run.RUN_DIR``, emptied by every traced run) on another worker."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "2147495011", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    out = run.stdout
    assert run.returncode == 1, out[-3000:] + run.stderr[-3000:]
    assert "devices=4 layout=columns" in out and "load_rows=32000" in out
    assert "keys resident after the fill: 32000 of 32000" in out
    assert "the comparison alone would say correct=True" in out, out[-3000:]
    counts = next(ln for ln in out.splitlines() if ln.startswith("rehearsal on cpu"))
    assert '"mismatched_answers": 0' in counts and "'decisions_per_s'" in counts


def test_col_tick_roofline_reads_the_column_programs(run):
    read = run.reader("layer_metrics", "col_tick_roofline")
    kind = "TPU v5 lite"

    def ctx(modules, rows=4_000_000, devices=4):
        tr = modules and {"modules": modules, "devices": devices}
        return {"trace": tr, "traced": {"rows": rows}, "costs": costs,
                "xtrace": xtrace, "device_kind": kind}

    assert read(ctx(None)) is None                      # an untraced run
    # the row layout's programs, or the parent's unnamed ones: nothing
    rows_only = [["jit__tick_ragged", 0.2], ["jit__tick32_ragged", 0.1]]
    assert read(ctx(rows_only)) is None
    assert read(ctx([["jit_mesh_tick_sorted_columns", 0.0]])) is None
    named = [["jit_mesh_tick_sorted_columns", 0.3], ["jit_mesh_tick_unique_columns", 0.1],
             ["jit__restore", 0.5], ["jit_scan", 0.2], ["jit_mesh_tick_sorted", 0.4]]
    want = 100.0 * costs.least_seconds(1_000_000, kind) / 0.4
    assert read(ctx(named)) == pytest.approx(want)
    assert read(ctx(named, rows=0)) is None
    # the excerpt recorded on the chip (PR 26) has no such module
    with open(os.path.join(HERE, "data", "trace_excerpt.json")) as f:
        events = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                  for p, lines in json.load(f).items()}
    reduced = xtrace.reduce(events)
    assert read({"trace": reduced, "traced": {"rows": 1000}, "costs": costs,
                 "xtrace": xtrace, "device_kind": kind}) is None
