"""Config-surface tests (the reference's config_test.go analog):
env-first GUBER_* reads, config-file-into-env loading, Go-style duration
parsing, eager validation, and the defaults table of config.go:126-141.
"""

import pytest

from gubernator_tpu.config import (
    DaemonConfig,
    load_config_file,
    parse_duration,
    setup_daemon_config,
)
from gubernator_tpu.ops.engine import make_layout_choice


def conf_from(env, config_file=""):
    return setup_daemon_config(config_file=config_file, environ=env)


def test_defaults_match_reference():
    c = conf_from({})
    b = c.config.behaviors
    # config.go:126-141 defaults
    assert b.batch_timeout == pytest.approx(0.5)
    assert b.batch_wait == pytest.approx(500e-6)
    assert b.batch_limit == 1000
    assert b.global_timeout == pytest.approx(0.5)
    assert b.global_batch_limit == 1000
    assert b.global_sync_wait == pytest.approx(0.1)
    assert c.config.cache_size == 50_000
    assert c.config.replicas == 512
    assert c.config.local_picker_hash == "fnv1"
    assert c.config.tpu_table_layout == "auto"


def test_env_overrides_flow_through():
    c = conf_from({
        "GUBER_GRPC_ADDRESS": "1.2.3.4:81",
        "GUBER_CACHE_SIZE": "1234",
        "GUBER_BATCH_WAIT": "2ms",
        "GUBER_PEER_PICKER_HASH": "fnv1a",
        "GUBER_TPU_TABLE_LAYOUT": "columns",
        "GUBER_TPU_MAX_BATCH": "512",
        "GUBER_DATA_CENTER": "dc-7",
    })
    assert c.grpc_listen_address == "1.2.3.4:81"
    assert c.config.cache_size == 1234
    assert c.config.behaviors.batch_wait == pytest.approx(2e-3)
    assert c.config.local_picker_hash == "fnv1a"
    assert c.config.tpu_table_layout == "columns"
    assert c.config.tpu_max_batch == 512
    assert c.data_center == "dc-7"


def test_config_file_loads_into_env(tmp_path):
    p = tmp_path / "guber.conf"
    p.write_text(
        "# comment line\n"
        "\n"
        "GUBER_CACHE_SIZE=777\n"
        "GUBER_LOG_LEVEL=debug\n"
    )
    env = {"GUBER_CACHE_SIZE": "999"}  # env wins over file (env-first)
    c = conf_from(env, config_file=str(p))
    assert c.log_level == "debug"
    # the file loads INTO the env but a real env var wins
    # (config.go:635-658: set only when unset)
    assert c.config.cache_size == 999


def test_config_file_rejects_garbage(tmp_path):
    p = tmp_path / "bad.conf"
    p.write_text("THIS IS NOT KEY VALUE\n")
    with pytest.raises(ValueError):
        load_config_file(str(p), {})


def test_duration_suffixes():
    assert parse_duration("500ms") == pytest.approx(0.5)
    assert parse_duration("100us") == pytest.approx(100e-6)
    assert parse_duration("30s") == pytest.approx(30.0)
    assert parse_duration("1m") == pytest.approx(60.0)
    assert parse_duration("0.25") == pytest.approx(0.25)


REMOVED_KNOBS = {
    "GUBER_TPU_SORTED32": "0",
    "GUBER_TPU_DMA_RING": "64",
    "GUBER_TPU_DMA_UNROLL": "8",
    "GUBER_MESH_LOCAL_WIDTH": "128",
    "GUBER_MESH_ROUTING": "host",
}


@pytest.mark.parametrize("source", ["environment", "config_file"])
def test_removed_knobs_still_named_change_nothing(source, tmp_path):
    """A deployment whose environment or -config file still names a knob
    that has gone starts exactly as one that does not: a name that is
    only set is never read, so it can neither fail the start nor change
    a field (env_knob refuses an unregistered *read*, not a set)."""
    from gubernator_tpu.config import ENV_REGISTRY

    assert not set(REMOVED_KNOBS) & set(ENV_REGISTRY)
    base = {"GUBER_INSTANCE_ID": "same"}
    if source == "environment":
        got = conf_from({**base, **REMOVED_KNOBS})
    else:
        p = tmp_path / "old.conf"
        p.write_text("".join(f"{k}={v}\n" for k, v in REMOVED_KNOBS.items()))
        got = conf_from(dict(base), config_file=str(p))
    assert got == conf_from(dict(base))


@pytest.mark.parametrize("env", [
    {"GUBER_PEER_PICKER_HASH": "md5"},
    {"GUBER_PEER_PICKER": "consistent-hash"},
    {"GUBER_PEER_DISCOVERY_TYPE": "zookeeper"},
    {"GUBER_CACHE_SIZE": "not-a-number"},
])
def test_eager_validation_rejects(env):
    with pytest.raises(ValueError):
        conf_from(env)


def test_layout_choice_rules():
    import jax

    cpu = jax.devices("cpu")[0]
    # CPU never auto-selects the Pallas row layout
    assert make_layout_choice("auto", 1 << 16, cpu, 4096) == "columns"
    # explicit settings are honored anywhere, bad ones rejected
    assert make_layout_choice("row", 1 << 16, cpu, 4096) == "row"
    assert make_layout_choice("columns", 1 << 16, cpu, 4096) == "columns"
    with pytest.raises(ValueError):
        make_layout_choice("rows", 1 << 16, cpu, 4096)


def test_bg_reclaim_knob(monkeypatch):
    import pytest

    from gubernator_tpu.config import setup_daemon_config

    monkeypatch.setenv("GUBER_TPU_BG_RECLAIM", "off")
    conf = setup_daemon_config()
    assert conf.config.tpu_bg_reclaim == "off"
    monkeypatch.setenv("GUBER_TPU_BG_RECLAIM", "sometimes")
    with pytest.raises(ValueError, match="GUBER_TPU_BG_RECLAIM"):
        setup_daemon_config()


def test_global_mesh_capacity_guard(caplog):
    """Verdict r3 #9: the dense GLOBAL reconcile is O(capacity * nodes)
    per sync interval (global_mesh.py scaling envelope) — the config
    surface warns past the 2^20 soft bound and refuses past 2^24."""
    import logging

    from gubernator_tpu.config import (
        GLOBAL_MESH_CAPACITY_HARD,
        GLOBAL_MESH_CAPACITY_SOFT,
    )

    # in-envelope: silent
    with caplog.at_level(logging.WARNING, logger="gubernator"):
        conf_from({"GUBER_TPU_GLOBAL_MESH_CAPACITY": str(1 << 16)})
    assert "GLOBAL_MESH_CAPACITY" not in caplog.text

    # past the soft bound: warns, still accepted
    with caplog.at_level(logging.WARNING, logger="gubernator"):
        c = conf_from({
            "GUBER_TPU_GLOBAL_MESH_CAPACITY":
                str(GLOBAL_MESH_CAPACITY_SOFT * 2),
        })
    assert c.config.tpu_global_mesh_capacity == GLOBAL_MESH_CAPACITY_SOFT * 2
    assert "GLOBAL_MESH_CAPACITY" in caplog.text

    # past the hard bound: refused
    with pytest.raises(ValueError, match="GLOBAL_MESH_CAPACITY"):
        conf_from({
            "GUBER_TPU_GLOBAL_MESH_CAPACITY":
                str(GLOBAL_MESH_CAPACITY_HARD * 2),
        })

    # the engine constructor enforces the same bound (programmatic use)
    from gubernator_tpu.parallel.global_mesh import MeshGlobalEngine

    with pytest.raises(ValueError, match="GLOBAL_MESH_CAPACITY"):
        MeshGlobalEngine(capacity=GLOBAL_MESH_CAPACITY_HARD * 2)


def test_resilience_env_surface():
    """GUBER_BREAKER_* / GUBER_FORWARD_* / GUBER_REDELIVERY_LIMIT flow into
    ResilienceConfig (docs/resilience.md)."""
    c = conf_from({
        "GUBER_BREAKER_FAILURE_THRESHOLD": "0.25",
        "GUBER_BREAKER_MIN_REQUESTS": "9",
        "GUBER_BREAKER_WINDOW": "5s",
        "GUBER_BREAKER_OPEN_FOR": "250ms",
        "GUBER_BREAKER_OPEN_CAP": "10s",
        "GUBER_FORWARD_MAX_ATTEMPTS": "2",
        "GUBER_FORWARD_BACKOFF_BASE": "1ms",
        "GUBER_REDELIVERY_LIMIT": "123",
    })
    r = c.config.resilience
    assert r.breaker_failure_threshold == pytest.approx(0.25)
    assert r.breaker_min_requests == 9
    assert r.breaker_window == pytest.approx(5.0)
    assert r.breaker_open_for == pytest.approx(0.25)
    assert r.breaker_open_cap == pytest.approx(10.0)
    assert r.forward_max_attempts == 2
    assert r.forward_backoff_base == pytest.approx(0.001)
    assert r.redelivery_limit == 123
    # Defaults: breaker on, no injector.
    assert r.breaker_enabled
    assert c.config.fault_injector is None


def test_snapshot_env_surface():
    """GUBER_SNAPSHOT_* / GUBER_DRAIN_TIMEOUT flow into Config and down
    to InstanceConfig (docs/persistence.md)."""
    from gubernator_tpu.service.instance import InstanceConfig

    c = conf_from({
        "GUBER_SNAPSHOT_DIR": "/tmp/guber-snaps",
        "GUBER_SNAPSHOT_INTERVAL": "250ms",
        "GUBER_SNAPSHOT_DELTAS_PER_BASE": "16",
        "GUBER_DRAIN_TIMEOUT": "3s",
    })
    assert c.config.snapshot_dir == "/tmp/guber-snaps"
    assert c.config.snapshot_interval == pytest.approx(0.25)
    assert c.config.snapshot_deltas_per_base == 16
    assert c.config.drain_timeout == pytest.approx(3.0)
    ic = InstanceConfig.from_config(c.config)
    assert ic.snapshot_dir == "/tmp/guber-snaps"
    assert ic.snapshot_interval == pytest.approx(0.25)
    assert ic.snapshot_deltas_per_base == 16
    assert ic.drain_timeout == pytest.approx(3.0)
    # Default: persistence off.
    assert conf_from({}).config.snapshot_dir == ""


def test_snapshot_env_validation():
    with pytest.raises(ValueError, match="GUBER_SNAPSHOT_INTERVAL"):
        conf_from({"GUBER_SNAPSHOT_INTERVAL": "0"})
    with pytest.raises(ValueError, match="GUBER_SNAPSHOT_DELTAS_PER_BASE"):
        conf_from({"GUBER_SNAPSHOT_DELTAS_PER_BASE": "0"})
    with pytest.raises(ValueError, match="GUBER_DRAIN_TIMEOUT"):
        conf_from({"GUBER_DRAIN_TIMEOUT": "-1s"})


def test_resilience_env_validation():
    with pytest.raises(ValueError, match="GUBER_BREAKER_FAILURE_THRESHOLD"):
        conf_from({"GUBER_BREAKER_FAILURE_THRESHOLD": "1.5"})
    with pytest.raises(ValueError, match="GUBER_REDELIVERY_LIMIT"):
        conf_from({"GUBER_REDELIVERY_LIMIT": "-1"})
    with pytest.raises(ValueError, match="GUBER_FORWARD_MAX_ATTEMPTS"):
        conf_from({"GUBER_FORWARD_MAX_ATTEMPTS": "-2"})


def test_fault_injector_env_surface():
    """GUBER_FAULT_* builds a seeded injector at daemon setup (the chaos
    config hook for staging game-days)."""
    c = conf_from({
        "GUBER_FAULT_PEERS": "10.0.0.1:81,10.0.0.2:81",
        "GUBER_FAULT_ERROR_RATE": "0.5",
        "GUBER_FAULT_DELAY": "5ms",
        "GUBER_FAULT_SEED": "42",
    })
    inj = c.config.fault_injector
    assert inj is not None
    spec = inj.spec_for("10.0.0.1:81")
    assert spec is not None and spec.error_rate == pytest.approx(0.5)
    assert spec.delay == pytest.approx(0.005)
    assert inj.spec_for("10.0.0.3:81") is None
    # Unset → no injector in the hot path.
    assert conf_from({}).config.fault_injector is None


def test_ssd_with_mesh_shards_is_config_error(tmp_path):
    """Satellite robustness fix: SSD tier + sharded mesh engine is a
    hard validation error, not warn+disable — a silently absent third
    tier means the operator sized the deployment around capacity the
    engine never had."""
    env = {
        "GUBER_SSD_DIR": str(tmp_path),
        "GUBER_COLD_CACHE_SIZE": "100",
        "GUBER_TPU_MESH_SHARDS": "2",
    }
    with pytest.raises(ValueError, match="sharded mesh engine"):
        conf_from(env)
    # Either alone is fine.
    env.pop("GUBER_TPU_MESH_SHARDS")
    assert conf_from(env).config.ssd_dir == str(tmp_path)
    assert conf_from(
        {"GUBER_TPU_MESH_SHARDS": "2"}).config.tpu_mesh_shards == 2


def test_ssd_with_mesh_shards_rejected_at_engine_build(tmp_path):
    """The same guard holds for programmatic InstanceConfig use (no
    setup_daemon_config in the path)."""
    from gubernator_tpu.service.instance import InstanceConfig, _make_engine

    conf = InstanceConfig(
        tpu_mesh_shards=2, ssd_dir=str(tmp_path), cold_cache_size=100,
        tpu_platform="cpu",
    )
    with pytest.raises(ValueError, match="sharded mesh engine"):
        _make_engine(conf)


def test_reshard_knobs_defaults_and_overrides():
    c = conf_from({})
    assert c.config.reshard_freeze_timeout == pytest.approx(5.0)
    assert c.config.reshard_verify is True
    c = conf_from({
        "GUBER_RESHARD_FREEZE_TIMEOUT": "500ms",
        "GUBER_RESHARD_VERIFY": "0",
    })
    assert c.config.reshard_freeze_timeout == pytest.approx(0.5)
    assert c.config.reshard_verify is False
    with pytest.raises(ValueError, match="GUBER_RESHARD_FREEZE_TIMEOUT"):
        conf_from({"GUBER_RESHARD_FREEZE_TIMEOUT": "0"})


# ----------------------------------------------------------------------
# Device selection and the compile cache (no fallback that hides the
# device; a cache placed from outside)
# ----------------------------------------------------------------------
def test_engine_refuses_silent_cpu_when_no_platform_named():
    """jax carries on on the CPU when it finds no chip; a daemon nobody
    asked to run there must not.  conftest names ``cpu``, so un-name it
    for the one call."""
    import jax

    from gubernator_tpu.service.instance import InstanceConfig, _make_engine

    named = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(RuntimeError, match="GUBER_TPU_PLATFORM=cpu"):
            _make_engine(InstanceConfig(cache_size=64, tpu_max_batch=16))
    finally:
        jax.config.update("jax_platforms", named)
    # Naming the platform through the knob is an explicit request.
    eng = _make_engine(InstanceConfig(
        cache_size=64, tpu_max_batch=16, tpu_platform="cpu"))
    assert eng.describe()["platform"] == "cpu"
    eng.close()


def test_engine_refuses_fewer_devices_than_mesh_shards():
    import jax

    from gubernator_tpu.service.instance import InstanceConfig, _make_engine

    want = len(jax.devices()) + 1
    with pytest.raises(ValueError, match=f"GUBER_TPU_MESH_SHARDS={want}"):
        _make_engine(InstanceConfig(
            cache_size=64, tpu_max_batch=16, tpu_mesh_shards=want))


@pytest.fixture
def cache_config():
    """configure_compile_cache against a given environment, the process's
    real setting restored afterwards."""
    import jax

    from gubernator_tpu import jaxinit

    was = jax.config.jax_compilation_cache_dir

    def run(env):
        jaxinit.configure_compile_cache(env)
        return jax.config.jax_compilation_cache_dir

    yield run
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_var_wins_over_every_other_knob(
        cache_config, tmp_path):
    outside = str(tmp_path / "placed-from-outside")
    other = tmp_path / "other"
    for knob in ("off", str(other)):
        assert cache_config({
            "JAX_COMPILATION_CACHE_DIR": outside,
            "GUBER_COMPILE_CACHE_DIR": knob,
        }) == outside
    # ...and no other directory was created on its behalf.
    assert not other.exists()


def test_compile_cache_default_is_inside_the_checkout_and_stable(
        cache_config, tmp_path):
    import os

    import gubernator_tpu
    from gubernator_tpu import jaxinit

    repo = os.path.dirname(os.path.dirname(
        os.path.abspath(gubernator_tpu.__file__)))
    first = cache_config({"HOME": str(tmp_path / "a"), "TMPDIR": "/x"})
    second = cache_config({"HOME": str(tmp_path / "b"), "TMPDIR": "/y"})
    assert first == second == jaxinit.DEFAULT_COMPILE_CACHE_DIR
    assert os.path.dirname(first) == repo and os.path.isdir(first)
    assert cache_config({"GUBER_COMPILE_CACHE_DIR": "off"}) is None
