"""Cooperative quota-lease tests (docs/leases.md).

Everything time-dependent runs on :class:`ManualClock` virtual time —
grant TTLs, expiry syncs, offline-grace extensions — with no wall-clock
sleeps.  Engine-backed tests reuse the shared :class:`tests.helpers.Sim`
width (capacity 1024, max_batch 64), so every jitted program here is
already compiled by the rest of the suite: the file adds no new engine
builds to the tier-1 budget.
"""

from __future__ import annotations

import pytest

from gubernator_tpu.admission import AdmissionConfig, under_pressure
from gubernator_tpu.leases import (
    HAVE_CRYPTO,
    LeaseCache,
    LeaseConfig,
    LeaseManager,
    LeaseSigner,
    LeaseSpec,
    LeaseSync,
    LeaseSyncAck,
    LeaseToken,
    lease_payload,
)
from gubernator_tpu.leases.cache import ADMIT, NEED_LEASE
from gubernator_tpu.resilience import BreakerOpenError
from gubernator_tpu.resilience.clock import ManualClock
from gubernator_tpu.types import RateLimitRequest, Status
from tests.helpers import Sim

NOW_S = 1_700_000_000.0   # seconds twin of Sim's frozen 1.7e12 ms


@pytest.fixture()
def sim():
    return Sim()


def _spec(key, limit=1_000, duration=60_000, want=0, holder=""):
    return LeaseSpec(name="lease_t", key=key, limit=limit,
                     duration=duration, want=want, holder=holder)


def _mgr(sim, clk=None, **cfg):
    cfg.setdefault("ttl_ms", 5_000)
    cfg.setdefault("secret", b"test-secret")
    clk = clk or ManualClock(start=NOW_S)
    return LeaseManager(
        sim.engine, config=LeaseConfig(**cfg),
        signer=LeaseSigner(secret=b"test-secret"), clock=clk,
    ), clk


def _remaining(sim, key, limit=1_000, duration=60_000):
    """hits=0 probe: reads the bucket without consuming."""
    return sim.hit(name="lease_t", unique_key=key, hits=0, limit=limit,
                   duration=duration).remaining


# ----------------------------------------------------------------------
# Signing: both schemes, and graceful degradation without `cryptography`
# ----------------------------------------------------------------------

def test_hmac_sign_verify_and_tamper():
    signer = LeaseSigner(secret=b"k1")
    assert signer.scheme == "hmac-sha256"
    tok = signer.mint("n", "k", 50, 1_700_000_005_000, 1)
    assert signer.verify(tok)
    assert signer.verifier().verify(tok)
    # Any field tamper breaks the signature.
    forged = LeaseToken(tok.name, tok.key, tok.budget + 1, tok.expires_ms,
                        tok.generation, tok.signature)
    assert not signer.verify(forged)
    # A different secret never validates.
    assert not LeaseSigner(secret=b"k2").verify(tok)


def test_force_hmac_is_the_no_cryptography_path():
    # force_hmac mirrors the HAVE_CRYPTO=False degradation (tlsutil's
    # stdlib fallback discipline): self-contained, no external deps.
    signer = LeaseSigner(force_hmac=True)
    assert signer.scheme == "hmac-sha256"
    tok = signer.mint("n", "k", 10, 123, 1)
    assert signer.verifier().verify(tok)


@pytest.mark.skipif(not HAVE_CRYPTO, reason="cryptography not installed")
def test_ed25519_sign_verify_and_tamper():
    signer = LeaseSigner()
    assert signer.scheme == "ed25519"
    tok = signer.mint("n", "k", 50, 1_700_000_005_000, 3)
    assert signer.verify(tok)
    verifier = signer.verifier()  # public material only
    assert verifier.verify(tok)
    forged = LeaseToken(tok.name, tok.key, tok.budget, tok.expires_ms,
                        tok.generation + 1, tok.signature)
    assert not verifier.verify(forged)


def test_payload_field_boundaries_are_unambiguous():
    # Length-prefixed fields: ("a","bc") must never collide with
    # ("ab","c") the way naive concatenation would.
    assert lease_payload("a", "bc", 1, 2, 3) != lease_payload(
        "ab", "c", 1, 2, 3)


# ----------------------------------------------------------------------
# Manager: grants are ordinary charged decisions; syncs reconcile
# ----------------------------------------------------------------------

def test_grant_charges_bucket_and_mirrors_columns(sim):
    mgr, _ = _mgr(sim)
    [tok] = mgr.grant_local([_spec("g1", want=30)], now_ms=sim.now)
    assert tok is not None and tok.budget == 30 and tok.generation == 1
    assert mgr.verifier().verify(tok)
    # The whole slice was charged up front — one ordinary decision.
    assert _remaining(sim, "g1") == 970
    assert mgr.outstanding("lease_t", "g1") == 30
    # Device columns mirror the host record.
    bud, exp, gen = sim.engine.lease_columns([b"lease_t_g1"])
    assert int(bud[0]) == 30
    assert int(exp[0]) == tok.expires_ms
    assert int(gen[0]) == 1


def test_grant_declines_on_hot_bucket(sim):
    mgr, _ = _mgr(sim)
    # Drain the bucket, then ask for a lease: OVER_LIMIT consumes
    # nothing and mints nothing — the client falls back to per-request
    # server decisions (no free budget under contention).
    sim.hit(name="lease_t", unique_key="hot", hits=990, limit=1_000,
            duration=60_000)
    [tok] = mgr.grant_local([_spec("hot", want=30)], now_ms=sim.now)
    assert tok is None
    assert _remaining(sim, "hot") == 10


def test_grant_disabled_declines_everything(sim):
    mgr, _ = _mgr(sim, enabled=False)
    assert mgr.grant_local([_spec("off")], now_ms=sim.now) == [None]
    assert _remaining(sim, "off") == 1_000


def test_sync_credits_unused_budget_back(sim):
    mgr, _ = _mgr(sim)
    [tok] = mgr.grant_local([_spec("cb", want=40)], now_ms=sim.now)
    assert _remaining(sim, "cb") == 960
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="cb", consumed=15,
                   generation=tok.generation, release=True)],
        now_ms=sim.now)
    assert ack.accepted and ack.credited == 25
    # 40 charged at grant, 25 unused credited back: net 15 consumed.
    assert _remaining(sim, "cb") == 985
    assert mgr.outstanding("lease_t", "cb") == 0


def test_sync_excess_is_force_charged_and_counted(sim):
    mgr, _ = _mgr(sim)
    [tok] = mgr.grant_local([_spec("ex", want=10)], now_ms=sim.now)
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="ex", consumed=14,
                   generation=tok.generation, release=True)],
        now_ms=sim.now)
    # 4 beyond the grant: charged to the bucket, surfaced in the ack,
    # and counted as sync loss (the misbehaving-client observable).
    assert ack.charged == 4 and ack.credited == 0
    assert mgr.metric_sync_loss == 4
    assert _remaining(sim, "ex") == 1_000 - 10 - 4


def test_stale_generation_sync_is_rejected(sim):
    mgr, _ = _mgr(sim)
    [tok] = mgr.grant_local([_spec("rv", want=20)], now_ms=sim.now)
    assert mgr.revoke("lease_t", "rv")
    assert mgr.metric_revocations == 1
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="rv", consumed=5,
                   generation=tok.generation, release=True)],
        now_ms=sim.now)
    # Stale generation: reconciled conservatively — no credit-back.
    assert not ack.accepted
    assert ack.generation == tok.generation + 1
    assert ack.credited == 0


def test_config_change_bumps_generation(sim):
    mgr, _ = _mgr(sim)
    [t1] = mgr.grant_local([_spec("cfg", limit=1_000)], now_ms=sim.now)
    [t2] = mgr.grant_local([_spec("cfg", limit=2_000)], now_ms=sim.now)
    assert t2.generation == t1.generation + 1
    assert mgr.metric_revocations == 1


def test_pressure_degrades_grant_to_cheap_extension(sim):
    class _Loop:
        pressured = False

        def under_pressure(self):
            return self.pressured

    mgr, clk = _mgr(sim)
    mgr.tick_loop = _Loop()
    [t1] = mgr.grant_local([_spec("pr", want=25)], now_ms=sim.now)
    before = _remaining(sim, "pr")
    mgr.tick_loop.pressured = True
    clk.advance(2.0)
    [t2] = mgr.grant_local([_spec("pr", want=25)],
                           now_ms=sim.now + 2_000)
    # Under pressure: re-signed TTL extension of the held budget — no
    # decision, no extra charge, no device work.
    assert t2.budget == t1.budget == 25
    assert t2.generation == t1.generation
    assert t2.expires_ms > t1.expires_ms
    assert mgr.verifier().verify(t2)
    assert mgr.metric_renewals == 1
    assert _remaining(sim, "pr") == before


# ----------------------------------------------------------------------
# Per-leaseholder accounting: concurrent holders on one key
# ----------------------------------------------------------------------

def test_release_credits_only_the_syncing_holders_slice(sim):
    mgr, _ = _mgr(sim)
    [ta] = mgr.grant_local([_spec("mh", want=30, holder="A")],
                           now_ms=sim.now)
    [tb] = mgr.grant_local([_spec("mh", want=50, holder="B")],
                           now_ms=sim.now)
    assert ta.budget == 30 and tb.budget == 50
    assert _remaining(sim, "mh") == 1_000 - 80
    # A releases having consumed 10: only A's 20 unused come back.
    # B's 50 are still delegated (its signed token is live) and MUST
    # stay charged, or B's local admissions would over-admit the bucket.
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="mh", consumed=10,
                   generation=ta.generation, release=True, holder="A")],
        now_ms=sim.now)
    assert ack.accepted and ack.credited == 20
    assert _remaining(sim, "mh") == 1_000 - 50 - 10
    assert mgr.outstanding("lease_t", "mh") == 50
    # B's own release reconciles only B's slice.
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="mh", consumed=50,
                   generation=tb.generation, release=True, holder="B")],
        now_ms=sim.now)
    assert ack.accepted and ack.credited == 0
    assert _remaining(sim, "mh") == 1_000 - 60
    assert mgr.outstanding("lease_t", "mh") == 0


def test_pressure_extension_is_per_holder_slice(sim):
    class _Loop:
        def under_pressure(self):
            return True

    mgr, _ = _mgr(sim)
    [ta] = mgr.grant_local([_spec("ph", want=25, holder="A")],
                           now_ms=sim.now)
    [tb] = mgr.grant_local([_spec("ph", want=40, holder="B")],
                           now_ms=sim.now)
    mgr.tick_loop = _Loop()
    # Each renewing holder gets ONLY its own slice re-signed — never the
    # key's pooled outstanding (which would let N clients each admit the
    # whole pool locally).
    [ea] = mgr.grant_local([_spec("ph", want=25, holder="A")],
                           now_ms=sim.now + 1_000)
    [eb] = mgr.grant_local([_spec("ph", want=40, holder="B")],
                           now_ms=sim.now + 1_000)
    assert ea.budget == 25 and eb.budget == 40
    assert mgr.metric_renewals == 2
    assert _remaining(sim, "ph") == 1_000 - 65  # no new charge
    # A holder with nothing held gets a normal (charged) decision even
    # under pressure — never a free extension of someone else's budget.
    [tc] = mgr.grant_local([_spec("ph", want=10, holder="C")],
                           now_ms=sim.now + 1_000)
    assert tc is not None and tc.budget == 10
    assert _remaining(sim, "ph") == 1_000 - 75


def test_two_caches_on_one_key_never_over_admit(sim):
    # budget_fraction=0.5 lets each cache's want=30 through the
    # per-grant cap on a limit-100 bucket.
    mgr, clk = _mgr(sim, budget_fraction=0.5)

    def mk_cache():
        return LeaseCache(
            lambda s: mgr.grant_local(s, now_ms=int(clk() * 1000)),
            lambda s: mgr.sync_local(s, now_ms=int(clk() * 1000)),
            clock=clk, verifier=mgr.verifier(), want_budget=30)

    a, b = mk_cache(), mk_cache()
    assert a.holder_id != b.holder_id
    spec = _spec("mc", limit=100)
    assert a.admit(spec) is True
    assert b.admit(spec) is True
    assert _remaining(sim, "mc", limit=100) == 100 - 60
    # A's shutdown release credits back only A's 29 unused admissions.
    assert a.close(deadline=clk() + 5.0) == 0
    assert mgr.outstanding("lease_t", "mc") == 30
    assert _remaining(sim, "mc", limit=100) == 100 - 30 - 1
    # B self-enforces against its own 30-budget slice, nothing more.
    for _ in range(29):
        assert b.admit(spec) is True
    assert b.metric_local_admits == 30
    assert b.close(deadline=clk() + 5.0) == 0
    assert mgr.outstanding("lease_t", "mc") == 0
    # Joint invariant: bucket reflects exactly the 31 admissions.
    assert _remaining(sim, "mc", limit=100) == 100 - 31


def test_generation_is_monotonic_across_release_and_regrant(sim):
    mgr, _ = _mgr(sim)
    [t1] = mgr.grant_local([_spec("gm", want=20, holder="A")],
                           now_ms=sim.now)
    assert t1.generation == 1
    mgr.sync_local(
        [LeaseSync(name="lease_t", key="gm", consumed=20,
                   generation=1, release=True, holder="A")],
        now_ms=sim.now)
    # The record was popped; a recreated record must NOT restart at
    # generation 1 — a partitioned client still holding a token from
    # the first incarnation has to stay stale forever.
    [t2] = mgr.grant_local([_spec("gm", want=20, holder="B")],
                           now_ms=sim.now)
    assert t2.generation == 2
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="gm", consumed=5,
                   generation=t1.generation, release=True, holder="A")],
        now_ms=sim.now)
    assert not ack.accepted
    assert ack.generation == 2


def test_unknown_holder_sync_is_stale(sim):
    mgr, _ = _mgr(sim)
    [tok] = mgr.grant_local([_spec("uh", want=20, holder="A")],
                            now_ms=sim.now)
    # Right key, right generation, wrong holder: nothing was delegated
    # to B, so its consumption is excess (force-charged), never applied
    # against A's slice.
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="uh", consumed=5,
                   generation=tok.generation, release=True, holder="B")],
        now_ms=sim.now)
    assert not ack.accepted and ack.charged == 5
    assert mgr.outstanding("lease_t", "uh") == 20
    assert _remaining(sim, "uh") == 1_000 - 20 - 5


# ----------------------------------------------------------------------
# Reconcile edge cases: stale configs, shed decisions, unknown keys
# ----------------------------------------------------------------------

def test_stale_generation_excess_charged_with_known_config(sim):
    mgr, _ = _mgr(sim)
    [tok] = mgr.grant_local([_spec("sg", want=20, holder="A")],
                            now_ms=sim.now)
    assert mgr.revoke("lease_t", "sg")
    # The stale sync's excess must be force-charged under the record's
    # REAL config — a limit=0 charge would be treated as a config change
    # by bucket_transition (remaining clamped, limit zeroed) and deny
    # legitimate traffic afterwards.
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="sg", consumed=25,
                   generation=tok.generation, release=True, holder="A")],
        now_ms=sim.now)
    assert not ack.accepted and ack.charged == 25
    assert mgr.metric_sync_loss == 25
    assert _remaining(sim, "sg") == 1_000 - 20 - 25


def test_unknown_key_excess_is_dropped_not_mischarged(sim):
    mgr, _ = _mgr(sim)
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="nokey", consumed=7,
                   generation=3, release=True, holder="A")],
        now_ms=sim.now)
    # No record, no config: charging with an invented limit would
    # corrupt the bucket, so the excess is counted as dropped instead.
    assert not ack.accepted and ack.charged == 0
    assert ack.generation == 4
    assert mgr.metric_sync_loss == 7
    assert mgr.metric_sync_dropped == 7
    assert _remaining(sim, "nokey") == 1_000  # bucket untouched


class _ShedEngine:
    """Engine stub whose every decision is a retriable shed answer."""

    def __init__(self, msg="request shed: tick loop shutting down"):
        self.msg = msg

    def process(self, reqs, now=None):
        from gubernator_tpu.types import RateLimitResponse

        return [RateLimitResponse(error=self.msg) for _ in reqs]


def test_shed_sync_credit_is_counted_not_silent(sim):
    mgr, _ = _mgr(sim)
    [tok] = mgr.grant_local([_spec("sh", want=20, holder="A")],
                            now_ms=sim.now)
    # The release's credit-back decision gets shed: the host record was
    # already reconciled, so the drift (15 credits that never reached
    # the bucket) must at least be counted and logged.
    mgr.engine = _ShedEngine()
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="sh", consumed=5,
                   generation=tok.generation, release=True, holder="A")],
        now_ms=sim.now)
    assert ack.accepted and ack.credited == 15
    assert mgr.metric_sync_dropped == 15
    assert mgr.outstanding("lease_t", "sh") == 0


def test_bounced_force_charge_is_counted(sim):
    mgr, _ = _mgr(sim)
    [tok] = mgr.grant_local([_spec("bf", want=10, holder="A")],
                            now_ms=sim.now)
    # Drain the bucket to the floor, then sync 15 admissions beyond the
    # grant: the force-charge resolves OVER_LIMIT (consumes nothing), so
    # the excess never reached the bucket — counted as dropped.
    sim.hit(name="lease_t", unique_key="bf", hits=990, limit=1_000,
            duration=60_000)
    [ack] = mgr.sync_local(
        [LeaseSync(name="lease_t", key="bf", consumed=25,
                   generation=tok.generation, release=True, holder="A")],
        now_ms=sim.now)
    assert ack.charged == 15
    assert mgr.metric_sync_loss == 15
    assert mgr.metric_sync_dropped == 15
    assert _remaining(sim, "bf") == 0


# ----------------------------------------------------------------------
# Cache lifecycle on virtual time (the docs/leases.md state machine)
# ----------------------------------------------------------------------

def _wired(sim, **cache_kw):
    """Cache wired to the manager's local surfaces, all on one
    ManualClock; returns (cache, mgr, clk, calls) where calls counts
    server round trips (the traffic observable)."""
    mgr, clk = _mgr(sim)
    calls = {"grant": 0, "sync": 0}

    def grant_fn(specs):
        calls["grant"] += 1
        return mgr.grant_local(specs, now_ms=int(clk() * 1000))

    def sync_fn(syncs):
        calls["sync"] += 1
        return mgr.sync_local(syncs, now_ms=int(clk() * 1000))

    cache = LeaseCache(grant_fn, sync_fn, clock=clk,
                       verifier=mgr.verifier(), **cache_kw)
    return cache, mgr, clk, calls


def test_lifecycle_grant_consume_expire_renew(sim):
    cache, mgr, clk, calls = _wired(sim, want_budget=10)
    spec = _spec("lc")
    # Grant: one server round trip delegates a 10-admission slice.
    assert cache.admit(spec) is True
    assert calls == {"grant": 1, "sync": 0}
    # Local consume: nine more admissions, zero server traffic.
    for _ in range(9):
        assert cache.admit(spec) is True
    assert calls == {"grant": 1, "sync": 0}
    assert cache.metric_local_admits == 10
    # Expiry: the next admission syncs consumed counts and renews.
    clk.advance(6.0)  # past the 5s TTL
    assert cache.try_admit(spec) == NEED_LEASE
    assert cache.admit(spec) is True
    assert calls == {"grant": 2, "sync": 1}
    # Renewal charged a fresh slice; the expired lease's budget was
    # fully consumed so nothing was creditable.
    assert _remaining(sim, "lc") == 1_000 - 20
    # Never over-admit: local admissions <= granted budgets, always.
    assert cache.metric_local_admits <= 20


def test_lifecycle_revoke_on_config_change(sim):
    cache, mgr, clk, calls = _wired(sim, want_budget=10)
    assert cache.admit(_spec("rc", limit=1_000)) is True
    # Operator changes the limit: the cached lease's terms are stale.
    changed = _spec("rc", limit=500)
    assert cache.try_admit(changed) == NEED_LEASE
    assert cache.admit(changed) is True
    # The regrant carries a bumped generation (old tokens are dead).
    assert mgr.metric_revocations == 1
    st = cache.stats()
    assert st.details["lease_t_rc"]["generation"] == 2


def test_lifecycle_breaker_open_extends_time_not_budget(sim):
    mgr, clk = _mgr(sim)
    state = {"open": False}

    def grant_fn(specs):
        if state["open"]:
            raise BreakerOpenError("peer down")
        return mgr.grant_local(specs, now_ms=int(clk() * 1000))

    cache = LeaseCache(grant_fn, lambda s: [], clock=clk,
                       verifier=mgr.verifier(), want_budget=10,
                       offline_grace_ms=2_000, max_offline_extensions=2)
    spec = _spec("br")
    assert cache.admit(spec) is True          # holds 10, consumed 1
    state["open"] = True                       # owner unreachable
    clk.advance(6.0)                           # lease TTL expired
    # Offline grace: answered from the held budget, time extended.
    assert cache.admit(spec) is True
    assert cache.metric_offline_extensions == 1
    # Budget is NOT refreshed: burn the remaining 8, then the next
    # admission inside the grace window is a local denial, not a free
    # admission — the invariant holds through any partition length.
    for _ in range(8):
        assert cache.admit(spec) is True
    assert cache.admit(spec) is False
    assert cache.metric_local_admits == 10
    # Extensions are bounded: once spent, the tier answers None and the
    # caller falls back to (failing) server decisions.
    clk.advance(3.0)
    assert cache.admit(spec) is None
    assert cache.extend_offline(spec) is False


def test_close_flushes_unsynced_through_sync_path(sim):
    cache, mgr, clk, calls = _wired(sim, want_budget=10)
    spec = _spec("cl")
    eng = sim.engine
    d0, w0 = eng.metric_lease_dispatches, eng.metric_lease_windows
    for _ in range(4):
        assert cache.admit(spec) is True
    # close() drains via the normal sync path: the release round credits
    # the 6 unused admissions back to the bucket.
    assert cache.close(deadline=clk() + 5.0) == 0
    assert calls["sync"] == 1
    assert cache.metric_sync_lost == 0
    assert _remaining(sim, "cl") == 1_000 - 4
    assert mgr.outstanding("lease_t", "cl") == 0
    # The manager's accounting for the grant and the release round is
    # exactly one column scatter per window, never one per key.
    wins = eng.metric_lease_windows - w0
    assert wins >= 2
    assert eng.metric_lease_dispatches - d0 == wins
    # Idempotent; the cache refuses new admissions once closed.
    assert cache.close() == 0
    with pytest.raises(RuntimeError):
        cache.try_admit(spec)


def test_close_counts_undeliverable_consumption():
    clk = ManualClock(start=NOW_S)

    def sync_fn(syncs):
        raise BreakerOpenError("gone")

    cache = LeaseCache(None, sync_fn, clock=clk)
    tok = LeaseToken("n", "k", 5, int(NOW_S * 1000) + 5_000, 1)
    assert cache.note_grant(LeaseSpec("n", "k", 100, 60_000), tok)
    assert cache.try_admit(LeaseSpec("n", "k", 100, 60_000), 3) == ADMIT
    # Every attempt fails: the drain is bounded and the loss is counted,
    # never silently dropped.
    assert cache.close(deadline=clk() + 1.0, attempts=2) == 3
    assert cache.metric_sync_lost == 3


def test_close_respects_deadline():
    clk = ManualClock(start=NOW_S)
    attempts = {"n": 0}

    def sync_fn(syncs):
        attempts["n"] += 1
        clk.advance(10.0)  # each try burns past the budget
        raise TimeoutError()

    cache = LeaseCache(None, sync_fn, clock=clk)
    tok = LeaseToken("n", "k", 5, int(NOW_S * 1000) + 5_000, 1)
    cache.note_grant(LeaseSpec("n", "k", 100, 60_000), tok)
    cache.try_admit(LeaseSpec("n", "k", 100, 60_000), 2)
    assert cache.close(deadline=clk() + 1.0, attempts=5) == 2
    assert attempts["n"] == 1  # deadline capped the retry loop


# ----------------------------------------------------------------------
# Engine columns: exact-work dispatch accounting + snapshot survival
# ----------------------------------------------------------------------

def test_lease_window_is_one_dispatch_per_window(sim):
    eng = sim.engine
    # Make two keys resident (ordinary decisions install their slots).
    sim.batch([RateLimitRequest(name="w", unique_key=k, hits=1,
                                limit=100, duration=60_000)
               for k in ("a", "b")])
    d0, w0 = eng.metric_lease_dispatches, eng.metric_lease_windows
    applied = eng.lease_window(
        [b"w_a", b"w_b", b"w_missing"], [7, 9, 11],
        [sim.now + 5_000] * 3, [1, 1, 1])
    # Non-resident keys are skipped (host records stay authoritative),
    # but the window is still exactly ONE device dispatch.
    assert applied == 2
    assert eng.metric_lease_dispatches - d0 == 1
    assert eng.metric_lease_windows - w0 == 1
    bud, exp, gen = eng.lease_columns([b"w_a", b"w_b", b"w_missing"])
    assert list(bud) == [7, 9, 0]
    assert list(gen) == [1, 1, 0]
    assert eng.lease_window([], [], [], []) == 0
    assert eng.metric_lease_dispatches - d0 == 1  # empty window is free


def test_lease_columns_survive_snapshot_roundtrip(sim):
    mgr, _ = _mgr(sim)
    [tok] = mgr.grant_local([_spec("snap", want=42)], now_ms=sim.now)
    snap = sim.engine.export_columns()
    for f in ("lease_budget", "lease_expire", "lease_gen"):
        assert f in snap
    fresh = Sim()
    fresh.engine.load_columns(snap, now=fresh.now)
    bud, exp, gen = fresh.engine.lease_columns([b"lease_t_snap"])
    assert int(bud[0]) == 42
    assert int(exp[0]) == tok.expires_ms
    assert int(gen[0]) == tok.generation
    # The bucket charge itself also survived.
    assert fresh.hit(name="lease_t", unique_key="snap", hits=0,
                     limit=1_000, duration=60_000).remaining == 958


def test_old_snapshots_without_lease_columns_still_load(sim):
    sim.hit(name="old", unique_key="x", hits=1, limit=100,
            duration=60_000)
    snap = sim.engine.export_columns()
    legacy = {k: v for k, v in snap.items()
              if not k.startswith("lease_")}
    fresh = Sim()
    fresh.engine.load_columns(legacy, now=fresh.now)
    bud, exp, gen = fresh.engine.lease_columns([b"old_x"])
    assert int(bud[0]) == 0 and int(gen[0]) == 0
    assert fresh.hit(name="old", unique_key="x", hits=0, limit=100,
                     duration=60_000).remaining == 99


# ----------------------------------------------------------------------
# Wire frames (transport/fastwire.py)
# ----------------------------------------------------------------------

def test_fastwire_lease_frames_round_trip():
    from gubernator_tpu.transport import fastwire as fw

    specs = [LeaseSpec("n1", "k1", 100, 60_000, algorithm=1, burst=5,
                       want=25, holder="client-a"),
             LeaseSpec("n2", "k2", 7, 1_000)]
    assert fw.parse_lease_grant_req(
        fw.encode_lease_grant_req(specs)) == specs

    tokens = [LeaseToken("n1", "k1", 25, 1_700_000_005_000, 2,
                         signature=b"\x01" * 64),
              None]
    assert fw.parse_lease_grant_resp(
        fw.encode_lease_grant_resp(tokens)) == tokens

    syncs = [LeaseSync("n1", "k1", 13, 2, release=True,
                       holder="client-a"),
             LeaseSync("n2", "k2", 0, 1)]
    assert fw.parse_lease_sync_req(
        fw.encode_lease_sync_req(syncs)) == syncs

    acks = [LeaseSyncAck(True, 2, credited=12, charged=0),
            LeaseSyncAck(False, 9, charged=3)]
    assert fw.parse_lease_sync_resp(
        fw.encode_lease_sync_resp(acks)) == acks


def test_fastwire_lease_v1_request_frames_still_parse():
    # Pre-holder (v1) request frames carry no holder string; a v2 server
    # must keep parsing them as the shared "" identity so an older
    # client does not break mid-rollout.
    import struct

    from gubernator_tpu.transport import fastwire as fw

    def ps(s):
        b = s.encode()
        return struct.pack("<H", len(b)) + b

    grant_v1 = (b"GLR1" + struct.pack("<I", 1)
                + struct.pack("<qqqqq", 5, 1_000, 0, 0, 2)
                + ps("n") + ps("k"))
    assert fw.parse_lease_grant_req(grant_v1) == [
        LeaseSpec("n", "k", 5, 1_000, want=2)]
    sync_v1 = (b"GSY1" + struct.pack("<I", 1)
               + struct.pack("<qqB", 3, 1, 1) + ps("n") + ps("k"))
    assert fw.parse_lease_sync_req(sync_v1) == [
        LeaseSync("n", "k", 3, 1, release=True)]


def test_fastwire_lease_frames_reject_malformed():
    from gubernator_tpu.transport import fastwire as fw

    good = fw.encode_lease_grant_req([LeaseSpec("n", "k", 1, 1)])
    assert fw.parse_lease_grant_req(b"") is None
    assert fw.parse_lease_grant_req(b"XXXX" + good[4:]) is None
    assert fw.parse_lease_grant_req(good[:-1]) is None          # truncated
    assert fw.parse_lease_grant_req(good + b"\x00") is None     # trailing
    assert fw.parse_lease_sync_resp(good) is None               # wrong frame


# ----------------------------------------------------------------------
# Config knobs and overload wiring
# ----------------------------------------------------------------------

def test_lease_config_env_defaults_and_overrides(monkeypatch):
    for k in ("GUBER_LEASE_ENABLED", "GUBER_LEASE_TTL",
              "GUBER_LEASE_BUDGET_FRACTION", "GUBER_LEASE_MAX_BUDGET",
              "GUBER_LEASE_CREDIT_BACK", "GUBER_LEASE_SECRET"):
        monkeypatch.delenv(k, raising=False)
    cfg = LeaseConfig.from_env()
    assert cfg.enabled and cfg.ttl_ms == 5_000
    assert cfg.budget_fraction == 0.1 and cfg.max_budget == 10_000
    assert cfg.credit_back and cfg.secret == b""
    monkeypatch.setenv("GUBER_LEASE_ENABLED", "0")
    monkeypatch.setenv("GUBER_LEASE_TTL", "30s")
    monkeypatch.setenv("GUBER_LEASE_BUDGET_FRACTION", "0.25")
    monkeypatch.setenv("GUBER_LEASE_MAX_BUDGET", "500")
    monkeypatch.setenv("GUBER_LEASE_CREDIT_BACK", "0")
    monkeypatch.setenv("GUBER_LEASE_SECRET", "s3cret")
    cfg = LeaseConfig.from_env()
    assert not cfg.enabled and cfg.ttl_ms == 30_000
    assert cfg.budget_fraction == 0.25 and cfg.max_budget == 500
    assert not cfg.credit_back and cfg.secret == b"s3cret"


def test_under_pressure_helper():
    class _Lim:
        def __init__(self, enabled, window_limit):
            self.enabled = enabled
            self.window_limit = window_limit

    # AIMD backed off below the full window → pressure.
    assert under_pressure(_Lim(True, 80), 0, 100, 100)
    assert not under_pressure(_Lim(True, 100), 0, 100, 100)
    assert not under_pressure(_Lim(False, 1), 0, 100, 100)
    # Pending queue past half its bound → pressure.
    assert under_pressure(_Lim(False, 0), 50, 100, 100)
    assert not under_pressure(_Lim(False, 0), 49, 100, 100)
    assert under_pressure(None, 50, 100, 100)


def test_tickloop_under_pressure():
    from gubernator_tpu.service.tickloop import TickLoop

    class _StubEngine:
        def submit(self, reqs):
            class _B:
                def result(self):
                    return []
            return _B()

    loop = TickLoop(_StubEngine(), batch_limit=100,
                    admission=AdmissionConfig(target_p99_ms=5.0))
    try:
        assert not loop.under_pressure()
        for _ in range(loop.limiter.adjust_every):
            loop.limiter.record(50.0)  # saturation → window narrows
        assert loop.limiter.window_limit < loop.batch_limit
        assert loop.under_pressure()
    finally:
        loop.close()


# ----------------------------------------------------------------------
# LeaseSession (client.py): the async driver over the same primitives
# ----------------------------------------------------------------------

class _LocalLeaseClient:
    """Stub DaemonClient speaking straight to a local LeaseManager."""

    def __init__(self, mgr, clk, fail=None):
        self.mgr = mgr
        self.clk = clk
        self.fail = fail

    async def lease_grant(self, specs):
        if self.fail is not None:
            raise self.fail
        return self.mgr.grant_local(specs, now_ms=int(self.clk() * 1000))

    async def lease_sync(self, syncs):
        if self.fail is not None:
            raise self.fail
        return self.mgr.sync_local(syncs, now_ms=int(self.clk() * 1000))


async def test_lease_session_admit_and_close(sim):
    from gubernator_tpu.client import LeaseSession

    mgr, clk = _mgr(sim)
    sess = LeaseSession(_LocalLeaseClient(mgr, clk),
                        verifier=mgr.verifier(), want_budget=10,
                        clock=clk)
    spec = _spec("sess")
    for _ in range(10):
        assert await sess.admit(spec) is True
    assert sess.stats().grants == 1
    assert await sess.close(deadline=clk() + 5.0) == 0
    # All 10 were consumed, none creditable: bucket reflects exactly the
    # admitted count.
    assert _remaining(sim, "sess") == 990


async def test_lease_session_offline_extension(sim):
    from gubernator_tpu.client import LeaseSession

    mgr, clk = _mgr(sim)
    client = _LocalLeaseClient(mgr, clk)
    sess = LeaseSession(client, verifier=mgr.verifier(), want_budget=5,
                        clock=clk)
    spec = _spec("soff")
    assert await sess.admit(spec) is True
    client.fail = BreakerOpenError("open")
    clk.advance(6.0)  # TTL expired, owner unreachable
    assert await sess.admit(spec) is True   # grace extension, local
    assert sess.stats().offline_extensions == 1
    # Close can't reach the server either: loss is counted, not hidden.
    lost = await sess.close(deadline=clk() + 1.0)
    assert lost == 2
    assert sess.stats().sync_lost == 2
