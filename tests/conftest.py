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
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

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

import pytest


@pytest.fixture(autouse=True)
def _boundary_gc():
    """Collect cyclic garbage at test boundaries: grpc.aio servers,
    event loops, and executors carry finalizers that join threads, and
    letting a mid-trace allocation-triggered GC run them deadlocks the
    interpreter against jax's tracing machinery (observed ~1 in 4 full
    runs as a fatal hang in the suite tail).  Boundary collection runs
    those finalizers while the loop infrastructure is still intact."""
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
