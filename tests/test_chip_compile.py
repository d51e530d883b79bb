"""Compile the served path's device programs for a TPU v5e that is
described, not attached (on-chip-measurement guide, section 2): the
installed Mosaic / XLA:TPU compiler refuses here exactly what it would
refuse on the chip — unaligned lane slices, VMEM overruns, programs that
do not fit HBM — at no chip time.  Nothing runs, so this says nothing
about results; parity lives in the interpret-mode suites.

Everything about the chip is built inside fixtures (never at import:
only one process may load the TPU library, and every xdist worker
imports every test file), the compiles run in the test's own process,
and the persistent compile cache is switched off around them (an entry
written for a described chip cannot be read back without one).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from gubernator_tpu.ops import fusedtick, raggedtick, rowtable
from gubernator_tpu.ops.engine import (
    REQ32_ROWS, SLAB_ROWS, group_upad, grouped_warm_shapes, plan_words)

CAP = 10_000_000        # BASELINE.json config 3: 5.12 GB of rows in HBM
SHARDS = 4
# base3-mixed-10m-mesh4: 12.5M rows (10M keys at an 80 % fill) over the
# four chips, 1.6 GB of rows a shard
LOCAL_CAP = 12_500_000 // SHARDS
B = 4096                # default GUBER_TPU_MAX_BATCH
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return list(topo.devices[:SHARDS])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the kernels to the real Mosaic lowering (the backend here
    is the CPU, so they would pick interpret mode) and keep the compiles
    out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    for mod in (rowtable, fusedtick, raggedtick):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    monkeypatch.setenv("GUBER_TPU_FUSED_TICK", "1")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def named(compiled, program, *kernels) -> bool:
    """A trace tells the tick programs apart by these: the module is
    ``jit_<program>``, each Pallas call carries its ``name=``."""
    text = compiled.as_text()
    return (f"HloModule jit_{program}" in text
            and all(k in text for k in kernels))


def row_state(cap, sharding):
    return rowtable.RowState(
        table=sds((cap + 1, rowtable.ROW_W), I32, sharding))


def test_row_gather_and_scatter(mosaic, one_chip):
    table = sds((CAP + 1, rowtable.ROW_W), I32, one_chip)
    slots = sds((B,), I32, one_chip)
    rows = sds((B, rowtable.ROW_W), I32, one_chip)
    g = jax.jit(rowtable.gather_rows).lower(table, slots).compile()
    s = jax.jit(rowtable.scatter_rows, donate_argnums=(0,)).lower(
        table, slots, rows).compile()
    assert has_kernel(g) and has_kernel(s)


# The engine's entries (TickEngine.submit_columns): ONE program on the
# window's one upload, ``now`` inside it as two int32 words.
def no_int64(compiled) -> bool:
    """``now`` stays a pair: nothing in the program is 64 bits wide,
    which XLA:TPU would emulate."""
    return "s64[" not in compiled.as_text()


def test_fused_tick(mosaic, one_chip):
    """A unique window: the staging slab in, the fused row kernel."""
    from gubernator_tpu.ops.tick32 import jitted_tick32

    fn = jitted_tick32(CAP, "row", fused=True)
    c = fn.lower(
        row_state(CAP, one_chip), sds((SLAB_ROWS, B), I32, one_chip),
    ).compile()
    assert has_kernel(c) and no_int64(c)
    assert named(c, "tick32_unique", "fused_tick32")


# At the default GUBER_TPU_MAX_BATCH the batch widths are 1024 and 4096:
# each at its floor head width, and the widest pair (``_warmup`` compiles
# every ``grouped_warm_shapes`` pair through this entry).
@pytest.mark.parametrize("shape", [(1024, 256), (B, 1024), (B, B)])
def test_fused_merged_tick(mosaic, one_chip, shape):
    """A grouped window: the plan's buffer is cut, the merged kernel
    runs and the members expand, in one program."""
    from gubernator_tpu.ops.tick32 import jitted_merged_pipeline

    b, upad = shape
    assert shape in grouped_warm_shapes((max(1024, B // 4), B), True)
    fn = jitted_merged_pipeline(CAP, "row", fused=True)
    c = fn.lower(
        row_state(CAP, one_chip), sds((plan_words(b, upad),), I32, one_chip),
        b,
    ).compile()
    assert has_kernel(c) and no_int64(c)
    assert named(c, "tick32_grouped", "fused_merged_tick32")


# CAP, and base2-leaky-1m's table (1M keys at an 80 % fill) at both
# batch widths: every window of its closed cell is this program's.
@pytest.mark.parametrize("cap,b", [(CAP, B), (1_250_000, B), (1_250_000, 1024)])
def test_sorted_tick32_on_rows(mosaic, one_chip, cap, b):
    """A sequential window: the slab in, rounds and stack one program."""
    from gubernator_tpu.ops.tick32 import jitted_sorted_tick32

    fn = jitted_sorted_tick32(cap, "row")
    c = fn.lower(
        row_state(cap, one_chip), sds((SLAB_ROWS, b), I32, one_chip),
    ).compile()
    assert has_kernel(c) and no_int64(c)
    assert named(c, "tick32_sequential", "gather_rows", "scatter_rows")


def test_layered_pipeline_at_warmup_shape(mosaic, one_chip):
    """The one layered shape TickEngine._warmup compiles eagerly on a
    TPU: w0 at the narrow width's floor, 2 layers of 512."""
    from gubernator_tpu.ops.tick32 import jitted_layered_pipeline

    w = max(1024, B // 4)
    w0 = group_upad(w)
    fn = jitted_layered_pipeline(CAP, "row", w0, 2, fused=True)
    c = fn.lower(
        row_state(CAP, one_chip),
        sds((REQ32_ROWS, w0), I32, one_chip), sds((w0,), I32, one_chip),
        sds((1, REQ32_ROWS, 512), I32, one_chip),
        sds((1, 512), I32, one_chip),
        sds((SLAB_ROWS, w), I32, one_chip),
        sds((w,), I32, one_chip), sds((w,), I32, one_chip),
    ).compile()
    assert has_kernel(c)
    assert named(c, "tick32_layered", "fused_merged_tick32")


def test_fused_ragged_tick(mosaic, one_chip):
    """One shard's program of the sharded path: a runtime (start, count)
    extent of the flat batch, at an arbitrary lane offset."""
    fn = jax.jit(
        raggedtick.make_fused_ragged_tick_fn(LOCAL_CAP), donate_argnums=(0,))
    scalar = sds((), I32, one_chip)
    c = fn.lower(
        row_state(LOCAL_CAP, one_chip), sds((REQ32_ROWS, B), I32, one_chip),
        scalar, scalar, scalar, sds((), jnp.int64, one_chip),
    ).compile()
    assert has_kernel(c)


def test_sharded_ragged_ticks_on_four_chips(mosaic, four_chips):
    """Both programs MeshTickEngine._warmup compiles when the daemon
    starts with GUBER_TPU_MESH_SHARDS=4: the sorted 32-bit duplicate
    program walked over each shard's extent and the fused ragged kernel,
    on a four-device mesh with the table split 3,125,000 rows a shard
    (``base3-mixed-10m-mesh4``), each a program of (state, slab): the
    window's one upload, ``now`` and the extent offsets in its last row.
    Neither holds a 64-bit float (on a TPU that is float32-pair
    emulation, not IEEE) nor a 64-bit integer."""
    from gubernator_tpu.parallel.mesh_engine import ShardedOps

    mesh = Mesh(np.array(four_chips), ("shard",))
    ops = ShardedOps(mesh, LOCAL_CAP, "row")
    assert ops._fused32
    rep = NamedSharding(mesh, P())
    state = rowtable.RowState(table=sds(
        (SHARDS * (LOCAL_CAP + 1), rowtable.ROW_W), I32,
        ops.state_shardings.table))
    args = (state, sds((SLAB_ROWS, B), I32, rep))
    walker = ops.tick_ragged.lower(*args).compile()
    fused = ops.tick_unique_ragged.lower(*args).compile()
    assert has_kernel(walker) and has_kernel(fused)
    for c in (walker, fused):
        text = c.as_text()
        assert "all-reduce" in text             # the one response psum
        assert "f64[" not in text
        assert no_int64(c) and "u64[" not in text
        # each device holds its quarter of the table, not all of it
        per_dev = c.memory_analysis().argument_size_in_bytes
        assert per_dev < 2 * (LOCAL_CAP + 1) * rowtable.ROW_W * 4


# base5-100m-mesh4: 125M slots (100M keys at an 80 % fill) over the four
# chips, 31,250,000 a shard: 16 GB of rows a chip, so the column layout
# (engine.make_layout_choice)
LOCAL_CAP_100M = 125_000_000 // SHARDS


def column_state(cap, shardings):
    """A stored column table of ``cap`` slots, each column on
    ``shardings`` (a tree like the state's, or one for every column)."""
    from gubernator_tpu.ops.buckets import BucketState

    shape = jax.eval_shape(lambda: BucketState.zeros(cap))
    if not isinstance(shardings, BucketState):
        shardings = jax.tree.map(lambda _: shardings, shape)
    return jax.tree.map(lambda a, s: sds(a.shape, a.dtype, s), shape, shardings)


def test_sharded_column_ticks_and_dead_scan_on_four_chips(mosaic, four_chips):
    """The column layout's two tick programs at 31,250,000 slots a
    shard, each named for its layout, neither with a 64-bit value, each
    device holding about its own shard (93 B a slot: 2.91 GB) and not
    the table; and the reclaimer's dead scan over one shard's own
    columns on its own chip (MeshTickEngine._shard_dead_mask)."""
    from gubernator_tpu.ops.engine import _jitted_dead_scan, make_layout_choice
    from gubernator_tpu.parallel.mesh_engine import ShardedOps

    mesh = Mesh(np.array(four_chips), ("shard",))
    assert make_layout_choice("auto", LOCAL_CAP_100M, four_chips[0], B) == "columns"
    ops = ShardedOps(mesh, LOCAL_CAP_100M, "columns")
    assert not ops._fused32
    state = column_state(SHARDS * LOCAL_CAP_100M, ops.state_shardings)
    shard_bytes = sum(a.dtype.itemsize for a in jax.tree.leaves(state)) * LOCAL_CAP_100M
    assert shard_bytes == 93 * LOCAL_CAP_100M
    args = (state, sds((SLAB_ROWS, B), I32, NamedSharding(mesh, P())))
    for program, name in ((ops.tick_ragged, "sorted"), (ops.tick_unique_ragged, "unique")):
        c = program.lower(*args).compile()
        text = c.as_text()
        assert f"HloModule jit_mesh_tick_{name}_columns" in text
        assert "all-reduce" in text
        assert "f64[" not in text and no_int64(c) and "u64[" not in text
        mem = c.memory_analysis()
        assert mem.argument_size_in_bytes < 2 * shard_bytes
        assert mem.temp_size_in_bytes < shard_bytes
    one = SingleDeviceSharding(four_chips[0])
    own = column_state(LOCAL_CAP_100M, one)
    scan = _jitted_dead_scan().lower(
        own.in_use, *own.expire_at, sds((), jnp.int64, one)).compile()
    assert scan.memory_analysis().argument_size_in_bytes < shard_bytes


def test_global_sparse_reconcile_on_four_chips(mosaic, four_chips):
    """The GLOBAL plane's fused sparse reconcile (x64 XLA, psum
    collectives only) at the engine's sparse defaults: 1M replicated
    slots, 4,096-row envelopes, one node per chip."""
    from gubernator_tpu.ops.buckets import BucketState
    from gubernator_tpu.parallel.global_mesh import (
        ACC_ROWS, AUX_ROWS, make_global_sparse_step_fn)
    from gubernator_tpu.parallel.partition import NodeLayout

    cap = 1 << 20
    mesh = Mesh(np.array(four_chips), ("node",))
    lay = NodeLayout()
    row = lay.shardings(mesh, P("node", None))
    mat = lay.shardings(mesh, lay.mat3())
    state = jax.tree.map(
        lambda a: sds((SHARDS,) + a.shape, a.dtype, row),
        jax.eval_shape(lambda: BucketState.zeros(cap)),
    )
    fn = jax.jit(
        make_global_sparse_step_fn(mesh, cap, SHARDS, 4096),
        donate_argnums=(0, 2),
    )
    c = fn.lower(
        state,
        sds((SHARDS, len(AUX_ROWS), cap), jnp.int64, mat),
        sds((SHARDS, ACC_ROWS, cap), jnp.int64, mat),
        sds((), jnp.int64, NamedSharding(mesh, P())),
    ).compile()
    assert "all-reduce" in c.as_text()
