"""The Loader's snapshot round-trips through the engine's
``load_columns`` at a tiny size, and names the fields the program names."""

import numpy as np

from benchmarks.harness import loader, population

SPEC = {"keys": 3000, "leaky_share": 0.5, "limit": [5, 20, 100, 1000, 1 << 33],
        "duration_ms": [3_600_000, 7_200_000], "leaky_burst": [0, 10, 50]}


def test_fields_are_the_programs():
    from gubernator_tpu.ops import engine

    assert loader.SNAP_FIELDS == engine.SNAP_FIELDS


def test_snapshot_round_trips_through_load_columns():
    import time

    from gubernator_tpu.ops.engine import TickEngine

    t0 = int(time.time() * 1000) + 3_600_000
    ld = loader.SeededLoader(population.Population(SPEC, 11), t0)
    snap = ld.load_columns()
    eng = TickEngine(capacity=4096)
    eng.load_columns(snap)
    assert eng.cache_size() == 3000
    back = eng.export_columns()
    off = back["key_offsets"]
    keys = [bytes(back["key_blob"][off[j]:off[j + 1]]) for j in range(len(off) - 1)]
    ids = np.asarray([int(k[len(population.PREFIX):]) for k in keys])
    assert sorted(ids.tolist()) == list(range(3000))
    want = ld.pop.state(ids, t0)
    leaky = want["algorithm"] == 1
    for f in loader.SNAP_FIELDS:
        got = np.asarray(back[f])
        if f == "remaining":          # a leaky bucket keeps remaining_f only
            assert np.array_equal(got[~leaky], want[f][~leaky]), f
        elif f == "remaining_f":
            assert np.array_equal(got[leaky], want[f][leaky]), f
        else:
            assert np.array_equal(got, want[f]), f
