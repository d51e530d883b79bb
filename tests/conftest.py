"""Test harness config: run on a virtual 8-device CPU mesh.

Mirrors the reference's trick of testing "multi-node" behavior in one
process (cluster/cluster.go): we test multi-chip sharding on virtual CPU
devices. Must run before jax initializes.
"""

import os

# GUBER_TEST_TPU=1 runs the suite against the real device (row-layout
# kernels under the actual Mosaic compiler instead of interpret mode);
# default is the hermetic 8-device CPU mesh.
TEST_TPU = os.environ.get("GUBER_TEST_TPU") == "1"
if not TEST_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# XLA:CPU's fusion emitters compile the interpreted Pallas kernels'
# response store (a concatenate of six deep rows) for 24-30 s a program
# and run it at 0.2 s a batch row; the emitters they replaced take 8 s
# and run it in no time.  The driver's command on one tree, PR 27: 364 s
# with this flag, 434 s without.  XLA:TPU (test_chip_compile) reads no
# ``xla_cpu`` flag.
if "xla_cpu_use_fusion_emitters" not in flags:
    flags += " --xla_cpu_use_fusion_emitters=false"
os.environ["XLA_FLAGS"] = flags.strip()

import jax  # noqa: E402

# A pytest plug-in may have imported jax before this file ran, and then
# the env var above came too late: name the platform in the config too.
# That also makes every test an explicit request for the CPU, which a
# daemon refuses to fall back to silently (instance._make_engine).
if not TEST_TPU:
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


# ---------------------------------------------------------------------------
# Minimal asyncio test support (pytest-asyncio isn't in the image):
# coroutine tests run on the module-scoped `event_loop` fixture when they
# (or their fixtures) request it, else on a fresh loop.
# ---------------------------------------------------------------------------
import asyncio
import gc
import inspect

import grpc.aio
import pytest

from tests.helpers import time_limit

# Hold grpc's asyncio runtime for the life of the process.  When the
# last aio channel or server is collected the library shuts its
# completion queue down and joins the poller thread with no bound, from
# C, wherever the collection ran; where a test has leaked a server with
# an operation still pending the queue never drains and the join never
# returns.  That was the hang in the suite's tail (CHANGES.md, PR 27:
# the stack, taken in ``_boundary_gc``).  With this reference the count
# never reaches zero and nothing is joined; the thread is a daemon.
grpc.aio.init_grpc_aio()


def pytest_configure(config):
    """In the process that starts the xdist workers, before it does.
    Build the native libraries once: ``*.so`` is git-ignored, and in a
    fresh checkout six workers each ran ``make`` on first use, one
    loading the file another was still writing ("file too short": the
    collections then differ and xdist ends the run with nothing run)."""
    if not hasattr(config, "workerinput"):
        from gubernator_tpu import native

        for name in native._SOURCES:
            native.library_path(name)
        # Hand the files out in the order collected (LONGEST_FIRST
        # below): xdist would sort them again, by how many tests each
        # holds, which sends the long files of few tests out last.
        config.option.loadscopereorder = False


# ``--dist loadfile`` gives a worker a whole file, two ahead of need, and
# xdist's own order is by the number of tests in a file: the long files
# of few tests (``test_chip_compile.py``: 8 tests, 240 s) started 170 s
# into the run, and it ended with two workers on them and four idle.
# Longest first, the tail is short files.  Seconds of worker time under
# the driver's ``-n 6`` on the 8-core sandbox, compile cache empty
# (CHANGES.md, PR 27: 348 s of wall in this order); a file not listed
# runs after these, in its usual place.
LONGEST_FIRST = (
    "test_chip_compile",    # 283 (two more sequential shapes, PR 36);
                            # +31 s alone for the column layout at
                            # 31.25M slots a shard (PR 40)
    "test_group_plan",      # 190 (39 before PR 33's one-buffer programs)
    "test_fusedtick",       # 143
    "test_mesh_engine",     # 135
    "test_layered",         # 68
    "test_engine",          # 91
    "test_limit",           # 2: the seventh is handed to the first
                            # worker, behind test_chip_compile
    "test_rowtable",        # 70
    "test_chaos",           # 90
    "test_global_mesh",     # 70
    "test_tiering",         # 54
    "test_merge_fastpath",  # 56
    "test_fuzz_parity",     # 53
    "test_reshard",         # 50
    "test_unit_merge",      # 43
    "test_mesh_reference",  # 41: a four-shard mesh and a one-chip engine
    "test_base2_reference", # 41-56: a 32,768-row engine, three programs
    "test_service",         # 51
    "test_store",           # 50
    "test_fastwire",        # 41
    "test_reqcols",         # 35
)


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    # a stable sort: tests keep their order within a file
    items.sort(key=lambda item: rank.get(item.path.stem, len(rank)))


# The per-test limit: just under four times the slowest test of the
# suite under the driver's ``-n 6`` on the 8-core sandbox (118 s,
# ``test_sharded_ragged_ticks_on_four_chips`` with the other long files
# starting beside it; CHANGES.md, PR 27), because the driver's machine
# has run the suite more than 2.2 times slower than that; twice as long
# for a wait no signal breaks (helpers.time_limit).
LIMIT_S = 450
LIMIT_HARD_S = 900


@pytest.fixture(autouse=True)
def _limit():
    with time_limit(LIMIT_S, LIMIT_HARD_S):
        yield


@pytest.fixture(autouse=True)
def _boundary_gc(_limit):
    """Collect cyclic garbage at test boundaries: grpc.aio servers,
    event loops and executors carry finalizers that join threads, and
    one run by a GC in the middle of a jax trace deadlocks the
    interpreter.  Inside ``_limit``, which names a hang that is left."""
    yield
    gc.collect()


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if not inspect.iscoroutinefunction(fn):
        return None
    kwargs = {
        name: pyfuncitem.funcargs[name]
        for name in pyfuncitem._fixtureinfo.argnames
    }
    loop = pyfuncitem.funcargs.get("event_loop")
    if loop is not None:
        loop.run_until_complete(fn(**kwargs))
    else:
        asyncio.run(fn(**kwargs))
    return True
