"""jax bootstrap: x64 mode + the persistent compile cache.

Importing this module configures jax for the whole process; every module
that imports jax MUST import :mod:`gubernator_tpu.jaxinit` first (the
convention that replaced doing this work in the package ``__init__`` —
which made ``import gubernator_tpu`` pull jax into processes that never
touch a device: the container healthcheck probe, config parsing, and the
static-analysis CLI, none of which should pay a multi-second jax import
or require the toolchain at all).

64-bit mode is required: the wire contract is int64 milliseconds /
int64 hits-limits, and leaky-bucket remaining is float64.
"""

from __future__ import annotations

import os

import jax

jax.config.update("jax_enable_x64", True)


# The default cache lives inside the checkout, next to the package: a
# path derived from $HOME, a temp name, a pid or a time moves between
# runs, and a cache directory that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure_compile_cache(environ=None) -> None:
    """Persistent XLA compilation cache, on by default: tick-program
    compiles cost tens of seconds on TPU toolchains and recur on every
    daemon restart otherwise.

    ``JAX_COMPILATION_CACHE_DIR`` is checked first: where it is set,
    that directory is used and nothing here names another.  Otherwise
    ``GUBER_COMPILE_CACHE_DIR=off`` disables, any other value overrides
    the location, and the default is ``DEFAULT_COMPILE_CACHE_DIR``.
    Runs at import AND again from ``setup_daemon_config`` so the knobs
    also work from a ``-config`` file (which loads into the environment
    after import)."""
    env = os.environ if environ is None else environ
    cache_dir = env.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = env.get("GUBER_COMPILE_CACHE_DIR", "")
        if cache_dir.lower() in ("off", "0", "false"):
            jax.config.update("jax_compilation_cache_dir", None)
            return
        cache_dir = cache_dir or DEFAULT_COMPILE_CACHE_DIR
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError:  # unwritable install dir: run uncached
            return
    # jax bound this option at import time; a -config file loads the
    # env var after import, so (re-)apply it explicitly.
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # jax's default floor (1s) only caches the big tick programs; the
    # long tail of sub-second helper compiles (packers, scans, installs)
    # recurs on every process start and dominates single-core cold
    # starts — cache everything.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


configure_compile_cache()
