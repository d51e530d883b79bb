"""Store/Loader persistence hook tests.

Modeled on the reference's store_test.go: TestLoader (:76) proves load-at-
startup / save-at-shutdown; TestStore (:127) proves read-through on miss
and write-through on every mutation.
"""

import pytest

from gubernator_tpu.ops.engine import TickEngine
from gubernator_tpu.store import FileLoader, MockLoader, MockStore
from gubernator_tpu.types import Algorithm, RateLimitRequest, Status

NOW = 1_700_000_000_000


def req(key="k", hits=1, limit=5, duration=60_000, **kw):
    return RateLimitRequest(
        name="store_test", unique_key=key, hits=hits, limit=limit,
        duration=duration, **kw,
    )


def test_store_write_through_and_read_through():
    store = MockStore()
    eng = TickEngine(capacity=256, max_batch=64, store=store)
    out = eng.process([req(hits=2)], now=NOW)[0]
    assert out.remaining == 3
    assert store.called["Get()"] == 1  # miss consults the store
    # Write-through fired with the post-tick state.
    assert store.called["OnChange()"] == 1
    item = store.data["store_test_k"]
    assert item["remaining"] == 3
    assert item["algorithm"] == Algorithm.TOKEN_BUCKET
    assert item["expire_at"] == NOW + 60_000

    # Fresh engine, same store: miss reads through and continues the bucket.
    eng2 = TickEngine(capacity=256, max_batch=64, store=store)
    out = eng2.process([req(hits=1)], now=NOW + 1)[0]
    assert store.called["Get()"] == 2
    assert out.remaining == 2  # 5 - 2 (persisted) - 1

    # Unknown key: store consulted, returns None, new bucket.
    out = eng2.process([req(key="other", hits=1)], now=NOW + 1)[0]
    assert out.remaining == 4
    assert store.called["Get()"] == 3


def test_store_leaky_read_through_preserves_float_remaining():
    store = MockStore()
    eng = TickEngine(capacity=256, max_batch=64, store=store)
    eng.process(
        [req(hits=3, limit=10, duration=10_000,
             algorithm=Algorithm.LEAKY_BUCKET)],
        now=NOW,
    )
    item = store.data["store_test_k"]
    assert item["remaining_f"] == 7.0
    eng2 = TickEngine(capacity=256, max_batch=64, store=store)
    out = eng2.process(
        [req(hits=0, limit=10, duration=10_000,
             algorithm=Algorithm.LEAKY_BUCKET)],
        now=NOW,
    )[0]
    assert out.remaining == 7


def test_loader_roundtrip(tmp_path):
    loader = MockLoader()
    eng = TickEngine(capacity=256, max_batch=64)
    eng.process([req(hits=2), req(key="k2", hits=1, limit=9)], now=NOW)
    loader.save(eng.export_items())
    assert loader.called["Save()"] == 1
    assert len(loader.contents) == 2

    eng2 = TickEngine(capacity=256, max_batch=64)
    eng2.load_items(list(loader.load()), now=NOW)
    out = eng2.process([req(hits=0)], now=NOW)[0]
    assert out.remaining == 3
    out = eng2.process([req(key="k2", hits=0, limit=9)], now=NOW)[0]
    assert out.remaining == 8


def test_file_loader(tmp_path):
    path = str(tmp_path / "snapshot.jsonl")
    loader = FileLoader(path)
    eng = TickEngine(capacity=256, max_batch=64)
    eng.process([req(hits=4)], now=NOW)
    loader.save(eng.export_items())

    eng2 = TickEngine(capacity=256, max_batch=64)
    eng2.load_items(list(loader.load()), now=NOW)
    out = eng2.process([req(hits=0)], now=NOW)[0]
    assert out.remaining == 1


async def test_loader_with_mesh_engine():
    """Loader restore/save must work on the sharded engine too (it crashed
    with AttributeError before MeshTickEngine grew load/export_items)."""
    from gubernator_tpu.service.instance import InstanceConfig, V1Instance

    loader = MockLoader()
    inst = await V1Instance.create(
        InstanceConfig(cache_size=512, tpu_mesh_shards=2, loader=loader)
    )
    try:
        out = await inst.get_rate_limits([req(key="mesh-loader", hits=2)])
        assert out[0].remaining == 3
    finally:
        await inst.close()
    assert loader.called["Save()"] == 1
    assert len(loader.contents) == 1
    assert loader.contents[0]["remaining"] == 3


def test_store_with_mesh_shards_supported():
    """Store write/read-through works on the sharded engine (per-shard
    blocked readback/restore; the round-2 guard that refused this combo
    is gone)."""
    from gubernator_tpu.service.instance import InstanceConfig, _make_engine
    from gubernator_tpu.store import MockStore

    store = MockStore()
    conf = InstanceConfig(store=store, tpu_mesh_shards=2, cache_size=256)
    eng = _make_engine(conf)
    eng.process([req(hits=3)], now=NOW)
    assert store.called["OnChange()"] == 1
    assert store.data["store_test_k"]["remaining"] == 2


def test_loader_drops_expired_items():
    eng = TickEngine(capacity=256, max_batch=64)
    eng.process([req(hits=1, duration=1000)], now=NOW)
    items = eng.export_items()
    eng2 = TickEngine(capacity=256, max_batch=64)
    eng2.load_items(items, now=NOW + 10_000)  # past expire_at
    assert eng2.cache_size() == 0


def test_columnar_snapshot_roundtrip(tmp_path):
    """export_columns/load_columns + ColumnFileLoader: bulk path matches
    the dict path item for item."""
    from gubernator_tpu.ops.engine import TickEngine, items_from_snapshot
    from gubernator_tpu.store import ColumnFileLoader

    eng = TickEngine(capacity=256, max_batch=64)
    eng.process(
        [req(key=f"c{i}", hits=2, limit=9) for i in range(40)]
        + [req(key="leaky", hits=3, limit=8, algorithm=1)],
        now=NOW,
    )
    snap = eng.export_columns()
    items = {it["key"]: it for it in eng.export_items()}
    assert len(items) == 41
    assert {it["key"] for it in items_from_snapshot(snap)} == set(items)

    path = str(tmp_path / "snap.npz")
    loader = ColumnFileLoader(path)
    loader.save_columns(snap)
    back = loader.load_columns()
    eng2 = TickEngine(capacity=256, max_batch=64)
    eng2.load_columns(back, now=NOW + 1)
    out = eng2.process([req(key="c3", hits=0, limit=9)], now=NOW + 1)[0]
    assert out.remaining == 7  # 9 - 2 from before the snapshot
    out = eng2.process([req(key="leaky", hits=0, limit=8, algorithm=1)],
                       now=NOW + 1)[0]
    assert out.remaining == 5

    # Dict-protocol view of the same file agrees.
    assert {it["key"] for it in loader.load()} == set(items)


def test_load_columns_drops_expired_and_dedups(tmp_path):
    from gubernator_tpu.ops.engine import SNAP_FIELDS, TickEngine
    import numpy as np

    eng = TickEngine(capacity=64, max_batch=32)
    keys = [b"store_test_live", b"store_test_dead", b"store_test_live"]  # dup: last wins
    offsets = np.zeros(4, np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    snap = {"key_blob": b"".join(keys), "key_offsets": offsets}
    base = dict(
        algorithm=0, limit=10, remaining=5, remaining_f=0.0,
        duration=60_000, created_at=NOW, updated_at=NOW, burst=10,
        status=0,
    )
    for f in SNAP_FIELDS:
        if f == "expire_at":
            snap[f] = np.asarray([NOW + 60_000, NOW - 1, NOW + 60_000])
        else:
            dt = np.float64 if f == "remaining_f" else np.int64
            snap[f] = np.asarray(
                [base[f], base[f], 3 if f == "remaining" else base[f]], dt
            )
    eng.load_columns(snap, now=NOW)
    assert eng.cache_size() == 1
    out = eng.process([req(key="live", hits=0, limit=10)], now=NOW)[0]
    assert out.remaining == 3  # the LAST duplicate's remaining


@pytest.mark.parametrize("layout", ["columns", "row"])
def test_slim_export_probe_regimes(monkeypatch, layout):
    """The schema-specialized export (engine.export_columns) drops hi
    words a device probe proves redundant; this exercises all three
    per-chunk regimes — hi == sign extension (small values), hi constant
    (epoch-ms columns), hi varying (must transfer) — plus negative
    remainings, the leaky f64 triple, and the multi-chunk path."""
    import numpy as np

    from gubernator_tpu.ops import engine as E

    monkeypatch.setattr(E, "SNAP_CHUNK", 16)  # force several chunks
    eng = E.TickEngine(capacity=256, max_batch=64, table_layout=layout)
    reqs = []
    for i in range(40):
        reqs.append(req(key=f"big{i}", hits=3, limit=(1 << 34) + i,
                        duration=60_000))
    # negative remaining: hits overdraft via DRAIN_OVER_LIMIT
    from gubernator_tpu.types import Behavior

    reqs.append(req(key="drained", hits=9, limit=5,
                    behavior=Behavior.DRAIN_OVER_LIMIT))
    reqs.append(req(key="leaky", hits=3, limit=7, algorithm=1))
    eng.process(reqs, now=NOW)

    snap = eng.export_columns()
    stats = eng.last_export_stats
    assert stats["items"] == 42
    # limits straddle 2^34 (hi word needed) but the epoch-ms columns'
    # hi is constant and the remaining column is sign-extended — the
    # transfer must be well under the full 80 B/slot schema.
    assert 0 < stats["d2h_bytes"] < 42 * 80
    by_key = {it["key"]: it for it in E.items_from_snapshot(snap)}
    # The per-item dict export is the oracle: every field of every item
    # must survive the probe/selection/decoding path bit-for-bit.
    oracle = {it["key"]: it for it in eng.export_items()}
    assert set(by_key) == set(oracle)
    for k, it in oracle.items():
        for f, v in it.items():
            assert by_key[k][f] == v, (k, f, by_key[k][f], v)
    assert by_key["store_test_big7"]["limit"] == (1 << 34) + 7
    assert by_key["store_test_big7"]["remaining"] == (1 << 34) + 7 - 3

    eng2 = E.TickEngine(capacity=256, max_batch=64, table_layout=layout)
    eng2.load_columns(snap, now=NOW + 1)
    out = eng2.process([req(key="big7", hits=0, limit=(1 << 34) + 7)],
                       now=NOW + 1)[0]
    assert out.remaining == (1 << 34) + 7 - 3
