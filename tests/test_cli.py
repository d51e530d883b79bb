"""CLI smoke tests (reference cmd/gubernator/main_test.go:26 pattern):
spawn the daemon binary, wait for "Ready", probe it, shut down cleanly."""

import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest


def _env(**extra):
    env = dict(os.environ)
    # Hermetic: cpu platform, tiny engine.
    env["JAX_PLATFORMS"] = "cpu"
    env.update(
        GUBER_GRPC_ADDRESS="127.0.0.1:19981",
        GUBER_HTTP_ADDRESS="127.0.0.1:19980",
        GUBER_CACHE_SIZE="1024",
        GUBER_TPU_MAX_BATCH="128",
        GUBER_PEER_DISCOVERY_TYPE="none",
    )
    env.update(extra)
    return env


async def test_cli_load_generator_reports_stats(capsys):
    """The load generator drives a live daemon and reports ok/over/err
    counts (reference cmd/gubernator-cli/main.go)."""
    import argparse

    from gubernator_tpu.cmd import cli
    from gubernator_tpu.config import BehaviorConfig, Config, DaemonConfig
    from gubernator_tpu.transport.daemon import spawn_daemon

    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="",
        peer_discovery_type="none",
    )
    conf.config = Config(behaviors=BehaviorConfig(), cache_size=1024)
    d = await spawn_daemon(conf)
    try:
        args = argparse.Namespace(
            address=d.advertise_address,
            limits=20,
            requests=200,
            concurrency=16,
            timeout=5.0,
        )
        # Deterministic key/limit pool: with this seed some buckets have
        # small limits and 200 requests over 20 keys must exhaust them —
        # proving OVER_LIMIT responses are counted as such, not as errors.
        import random

        # The same run first with no deadline: sixteen concurrent
        # callers over twenty keys form windows with duplicates, whose
        # program a daemon this small compiles on first use, and under
        # the generator's 5 s deadline that compile read as errors.
        random.seed(7)
        args.timeout = None
        await cli.run(args)
        capsys.readouterr()
        random.seed(7)
        args.timeout = 5.0
        await cli.run(args)
    finally:
        await d.close()
    out = capsys.readouterr().out
    assert "200 requests" in out
    assert "errors=0" in out
    import re

    over = int(re.search(r"over_limit=(\d+)", out).group(1))
    assert over > 0


def test_healthcheck_exits_2_when_daemon_absent(monkeypatch, capsys):
    from gubernator_tpu.cmd import healthcheck

    monkeypatch.setenv("GUBER_HTTP_ADDRESS", "127.0.0.1:1")  # nothing listens
    assert healthcheck.main() == 2
    assert "healthcheck failed" in capsys.readouterr().err


def test_healthcheck_exits_2_on_unhealthy_body(monkeypatch, capsys):
    import json as _json
    import io
    import urllib.request

    from gubernator_tpu.cmd import healthcheck

    def fake_urlopen(url, timeout=0):
        class R(io.BytesIO):
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

        return R(_json.dumps(
            {"status": "unhealthy", "message": "1 peer error"}
        ).encode())

    monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
    assert healthcheck.main() == 2
    assert "unhealthy" in capsys.readouterr().err


@pytest.mark.slow
def test_cluster_main_boots_six_instances():
    """cluster_main brings up the fixed-port 6-node dev cluster and serves
    on every node (reference cmd/gubernator-cluster/main.go)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "gubernator_tpu.cmd.cluster_main"],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env=_env(),
        text=True,
    )
    try:
        deadline = time.time() + 180
        line = ""
        while time.time() < deadline:
            line = proc.stdout.readline()
            if "Ready" in line:
                break
            assert proc.poll() is None, proc.stderr.read()
        assert "Ready" in line

        # Every instance answers its health endpoint on the fixed ports.
        for port in range(10090, 10096):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/HealthCheck", timeout=5
            ) as resp:
                assert b"healthy" in resp.read()

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


@pytest.mark.slow
def test_daemon_main_boots_and_serves():
    proc = subprocess.Popen(
        [sys.executable, "-m", "gubernator_tpu.cmd.daemon_main"],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env=_env(),
        text=True,
    )
    try:
        # Wait for the readiness marker (compile happens at startup).
        deadline = time.time() + 120
        line = ""
        while time.time() < deadline:
            line = proc.stdout.readline()
            if "Ready" in line:
                break
            assert proc.poll() is None, proc.stderr.read()
        assert "Ready" in line

        with urllib.request.urlopen(
            "http://127.0.0.1:19980/v1/HealthCheck", timeout=5
        ) as resp:
            assert b"healthy" in resp.read()

        # The healthcheck probe binary exits 0 against a healthy daemon.
        probe = subprocess.run(
            [sys.executable, "-m", "gubernator_tpu.cmd.healthcheck"],
            env=_env(GUBER_HTTP_ADDRESS="127.0.0.1:19980"),
            capture_output=True,
            timeout=30,
        )
        assert probe.returncode == 0, probe.stderr

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_compile_cache_configured_at_import(tmp_path):
    """The device bootstrap (gubernator_tpu.jaxinit, imported by every
    jax-using module) enables the persistent XLA compile cache at import:
    daemon restarts must not re-pay tick compiles.  The bare package
    import stays jax-free by design — the probe imports the bootstrap
    the way any device module does.  The rules themselves are pinned
    in-process by tests/test_config.py; this checks a fresh interpreter:
    the default sits inside the checkout whatever $HOME is, and
    JAX_COMPILATION_CACHE_DIR, when set, is the only directory named."""
    probe = [
        sys.executable, "-c",
        "import jax, gubernator_tpu.jaxinit as j;"
        "print(jax.config.jax_compilation_cache_dir"
        " == j.DEFAULT_COMPILE_CACHE_DIR,"
        " jax.config.jax_compilation_cache_dir)",
    ]
    env = _env(HOME=str(tmp_path))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        probe, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    is_default, where = out.stdout.split()
    assert is_default == "True" and str(tmp_path) not in where

    placed = str(tmp_path / "placed")
    out = subprocess.run(
        probe, env=_env(HOME=str(tmp_path), JAX_COMPILATION_CACHE_DIR=placed,
                        GUBER_COMPILE_CACHE_DIR="off"),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["False", placed]
