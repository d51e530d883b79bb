"""``base2-leaky-1m`` and its two cells: the files say what the issue
asks of them and load through ``run.py``'s own lookups; the generator's
closed windows hold a few repeated keys and too few for a plan (so the
sequential program answers them), at the rehearsal's size and at the
configuration's; a ten-item open call repeats a key one time in
eighty; and ``seq_tick_device_share`` reads the sequential program's
share of a trace's modules, or nothing where no program is named."""

import importlib.util
import json
import os

import numpy as np
import pytest

from benchmarks.harness import traffic, xtrace
from benchmarks.harness.population import LEAKY, Population

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CLOSED = "base2-leaky-1m.closed16-uniform"
OPEN = "base2-leaky-1m.open-small-uniform"


def load(name):
    with open(os.path.join(ROOT, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run():
    spec = importlib.util.spec_from_file_location(
        "bench_run_base2", os.path.join(ROOT, "benchmarks", "run.py"))
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


def test_the_configuration_is_baseline_configs_1(run):
    cell, entry, conf, mix = cell_files(run, CLOSED)
    base1 = load("benchmarks/configs/base1-token-10k.json")
    base3 = load("benchmarks/configs/base3-mixed-10m.json")
    assert entry["reduced"] == [] and conf["reduced"] == {}
    assert conf["chips"] == cell["chips"] == 1 and conf["architecture"] is None
    assert conf["env"] == {"GUBER_CACHE_SIZE": "1250000"}
    assert conf["population"] == dict(
        base1["population"], keys=1_000_000, leaky_share=1.0, leaky_burst=[0, 10, 50])
    assert conf["defaults_kept"] == base3["defaults_kept"]
    assert conf["guarantees"] == base1["guarantees"] + base3["guarantees"][2:]
    assert "no second copy" in conf["what"]
    pop = Population(conf["population"], 7)
    assert (pop.params(np.arange(5000))[0] == LEAKY).all()
    assert pop.n / int(conf["env"]["GUBER_CACHE_SIZE"]) == 0.8     # the fill
    for text in (entry["source"], entry["why"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()


def test_the_cells_are_the_two_mixes_with_uniform_keys(run):
    cell, _, _, mix = cell_files(run, CLOSED)
    zipf = load("benchmarks/traffic/closed16-zipf.json")
    same = set(zipf) - {"name", "what", "keys", "check"}
    assert {k: mix[k] for k in same} == {k: zipf[k] for k in same}
    assert mix["keys"] == {"dist": "uniform"}
    assert mix["check"] == {"sample_mod": 16, "hot_ranks": 0}
    opened, _, conf, omix = cell_files(run, OPEN)
    assert omix == load("benchmarks/traffic/open-small-uniform.json")
    assert (opened["chips"], omix["rate_calls_per_s"], omix["lanes"]) == (1, 520, 256)
    bench = load("BENCHMARK.json")
    m = next(m for m in bench["per_layer"] if m["name"] == "seq_tick_device_share")
    assert m["workloads"] == [CLOSED, OPEN] and m["moves"] == "decisions_per_s"
    assert (m["layer"], m["better"], m["unit"]) == ("device programs", "lower", "%")
    assert callable(run.reader("layer_metrics", "seq_tick_device_share"))
    for text in (opened["why"],):
        assert 1 <= len(text) <= 200 and text.isprintable()


@pytest.mark.parametrize("size", ["rehearse", "full"])
def test_closed_windows_hold_repeats_and_too_few_for_a_plan(run, size):
    """A window is one call each of four lanes (``GUBER_TPU_MAX_BATCH``
    4096, 1,000 items a call; lanes own disjoint keys, so repeats are
    in-call): some followers in every window, and fewer than the one in
    eight at which ``build_group_plan`` / ``build_layer_plan`` plan."""
    _, _, conf, mix = cell_files(run, CLOSED)
    keys = conf["rehearse"]["keys"] if size == "rehearse" else conf["population"]["keys"]
    lanes = [0, 5, 10, 15]
    plans = traffic.plans(mix, keys, 2147485019, 2.0, lanes)
    per_call = []
    for k in range(64):
        ids = np.concatenate([plans[ln][1].call_ids(k) for ln in lanes])
        followers = len(ids) - len(np.unique(ids))
        assert 0 < followers < len(ids) // 8, (k, followers)
        per_call.append(followers / len(lanes))
    if size == "full":
        # 1,000 draws of a lane's 62,500 keys: 1000 * 999 / 2 / 62,500 = 8
        assert 7.0 < np.mean(per_call) < 9.0


def test_a_ten_item_open_call_seldom_repeats_a_key(run):
    """256 lanes leave a lane 3,906 of the 1M keys: 10 * 9 / 2 / 3,906 =
    1.2 % of ten-item calls repeat one; shorter calls fewer, so ~99 % of
    the open cell's windows are the unique program's."""
    _, _, conf, mix = cell_files(run, OPEN)
    keys, lanes, calls = conf["population"]["keys"], [3, 200], 20_000
    assert len(range(lanes[0], keys, int(mix["lanes"]))) in (3906, 3907)
    ids = traffic.lane_keys(mix, keys, 2147485033, lanes, dict.fromkeys(lanes, 10 * calls))
    for ln in lanes:
        assert (ids[ln] % int(mix["lanes"]) == ln).all()
        tens = np.sort(ids[ln].reshape(calls, 10), axis=1)
        share = (tens[:, 1:] == tens[:, :-1]).any(axis=1).mean()
        assert 0.008 < share < 0.016, share


def test_seq_tick_device_share_reads_the_named_programs(run):
    read = run.reader("layer_metrics", "seq_tick_device_share")

    def ctx(modules):
        return {"trace": modules and {"modules": modules}, "xtrace": xtrace}

    assert read(ctx(None)) is None                      # an untraced run
    # a program from before the four were named: nothing to read
    assert read(ctx([["jit_run", 0.5], ["jit_convert_element_type", 0.1]])) is None
    assert read(ctx([["jit_tick32_sequential", 0.0]])) is None     # no device time
    named = [["jit_tick32_sequential", 0.3], ["jit_tick32_unique", 0.1],
             ["jit_tick32_grouped", 0.05], ["jit__dead_mask", 0.05]]
    assert read(ctx(named)) == pytest.approx(60.0)
    assert read(ctx(named[1:])) == 0.0                  # named, none sequential
    # the excerpt recorded on the chip (PR 26) has no such module
    with open(os.path.join(HERE, "data", "trace_excerpt.json")) as f:
        events = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                  for p, lines in json.load(f).items()}
    assert read({"trace": xtrace.reduce(events), "xtrace": xtrace}) is None
