"""``base2-leaky-1m`` on the one-chip engine, held to the benchmark's
plain reference (upstream ``algorithms.go`` in Python int and float; it
imports nothing of the program): a seeded all-leaky population (the
configuration's, small) filled through ``load_columns``, then uniform
windows through ``submit_columns``, as the configuration's two cells
send them.  Windows that repeat a few keys (fewer than one follower in
eight, so neither planner makes a plan) are answered by the sequential
program, ``jit_tick32_sequential``; windows that repeat none by the
unique one, ``jit_tick32_unique``.  Every answer equal, limit 0, which
is what the cells' ``correct`` asks on the chip; the reference one
precision below (leaky arithmetic rounded to float32) does not agree.
And the engine's four branch counters say which program answered each
window and add up to ``metric_h2d_windows``.
"""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import population
from benchmarks.harness.reference import Reference
from benchmarks.tests.test_precision_control import Float32Leaky
from gubernator_tpu.ops.engine import TickEngine
from gubernator_tpu.utils.metrics import TICK_BRANCHES
from tests.test_mesh_reference import (
    B, KEYS, T0, columns, mismatched, replayed, served, snapshot)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs", "base2-leaky-1m.json")) as f:
    SPEC = dict(json.load(f)["population"], keys=KEYS)
SEEDS = (11, 2147483777, 2147489003)
KINDS = {"repeats": "sequential", "unique": "unique"}


# One engine for the module; a history's keys carry its tag, so the
# histories do not meet in the table, which holds all seven fills.  Past
# 16,384 rows both planners are consulted for a window with repeats
# (engine.submit_columns), as in the cell, and both decline.
@pytest.fixture(scope="module")
def engine():
    return TickEngine(capacity=8 * 4096, max_batch=B)


def branch_counts(eng):
    return {b: getattr(eng, f"metric_{b}_ticks") for b in TICK_BRANCHES}


def history(seed, kind, windows=8):
    """(population, [(clock ms, key ids)]): uniform windows of varied
    width with the clock stepping between them, drawn with replacement
    (``repeats``: ~11 repeated keys in 256 draws of 3,000) or without
    (``unique``)."""
    pop = population.Population(SPEC, seed)
    rng = np.random.default_rng([seed, len(kind)])
    out, t = [], T0
    for w in range(windows):
        t += int(rng.choice([1, 700, 40_000]))
        n = B - 23 * (w % 3)
        out.append((t, rng.integers(0, KEYS, n) if kind == "repeats"
                    else rng.choice(KEYS, n, replace=False)))
    return pop, out


def test_the_population_is_the_configurations():
    pop = population.Population(SPEC, 7)
    alg, _, _, burst = pop.params(np.arange(KEYS))
    assert (alg == population.LEAKY).all()
    assert sorted(set(burst.tolist())) == [0, 10, 50]


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("seed", SEEDS)
def test_uniform_leaky_windows_equal_the_reference(engine, seed, kind):
    pop, wins = history(seed, kind)
    tag = 100 + 10 * SEEDS.index(seed) + len(kind)
    for _, ids in wins:
        followers = len(ids) - len(np.unique(ids))
        if kind == "repeats":      # some, and too few for either plan
            assert 0 < followers < len(ids) // 8
        else:
            assert followers == 0
    before, windows0 = branch_counts(engine), engine.metric_h2d_windows
    want = replayed(Reference(), pop, wins)
    got = served(engine, pop, wins, tag)
    assert mismatched(got, want) == 0
    # the branch that answered: every window of the history, no other
    rose = {b: n - before[b] for b, n in branch_counts(engine).items()}
    assert rose == dict(dict.fromkeys(TICK_BRANCHES, 0), **{KINDS[kind]: len(wins)})
    assert engine.metric_h2d_windows - windows0 == len(wins)
    assert sum(branch_counts(engine).values()) == engine.metric_h2d_windows
    # one precision below the one the configuration states: not correct
    lower = mismatched(replayed(Float32Leaky(), pop, wins), want)
    print(f"float32 control, {kind}, seed {seed}: {lower} of"
          f" {sum(len(i) for _, i in wins)} answers differ")
    assert lower > 0


def test_a_hot_group_is_counted_as_grouped(engine):
    """The third counter: a window whose followers are one in eight or
    more gets a plan and ``jit_tick32_grouped``; the four still add up."""
    pop = population.Population(SPEC, 5)
    engine.load_columns(snapshot(pop, np.arange(200), 199), now=T0)
    before = branch_counts(engine)
    ids = np.concatenate([np.full(100, 7), np.arange(100, 200)])
    _, errors = engine.submit_columns(columns(pop, ids, T0 + 9, 199), now=T0).result()
    assert not errors
    rose = {b: n - before[b] for b, n in branch_counts(engine).items()}
    assert rose == dict(dict.fromkeys(TICK_BRANCHES, 0), grouped=1)
    assert sum(branch_counts(engine).values()) == engine.metric_h2d_windows


def test_the_programs_are_named_for_their_branch(engine):
    """What a device trace calls the four programs (module
    ``jit_<name>``): the unique and the sequential program share one
    wrapper and may not share a name."""
    from gubernator_tpu.ops.tick32 import jitted_layered_pipeline

    layered = jitted_layered_pipeline(engine.capacity, engine.layout, 256, 2)
    names = {fn.__name__ for fn in
             (engine._tick32, engine._tick32m, engine._tick, layered)}
    assert names == {f"tick32_{b}" for b in TICK_BRANCHES}
