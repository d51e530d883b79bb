#!/usr/bin/env python3
"""chip_smoke.py — the served path answers on a TPU v5e, and is right.

One process.  Starts the daemon the way ``gubernator-tpu`` does
(``setup_daemon_config`` + ``spawn_daemon``) from environment knobs only,
drives it over real loopback sockets (gRPC ``DaemonClient`` and the HTTP
gateway), and compares every response item by item against oracles that
share no code with the serving programs: ``algos/reference.py`` (scalar
Python) for sliding-window / GCRA / concurrency, and the x64
``make_tick_fn`` program on this process's host-CPU device for token and
leaky buckets.

    python chip_smoke.py            one chip: 10M-row table, 1M keys loaded
    python chip_smoke.py --mesh     four chips: sharded daemon + GLOBAL mesh
    GUBER_TPU_PLATFORM=cpu python chip_smoke.py --rehearse
                                    tiny sizes, same checks; never a pass

The last line of stdout is one JSON object with ``ok`` and the device as
jax reports it.  Any failed check, mismatch or exception exits non-zero;
without ``--rehearse`` nothing is printed unless the first device is a TPU.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
if not os.path.isdir(os.path.join(HERE, "gubernator_tpu")):
    sys.exit("chip_smoke.py: run from the root of a gubernator-tpu checkout")
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

NAME = "smoke"
KEY_FMT = b"smoke_k%08d"
KEY_LEN = len(KEY_FMT % 0)
BATCH = 1000                 # upstream BatchLimit: items per client call
LANES = 4                    # concurrent calls that form one ~4,000-row window
ZOO_BASE = 2_000_000         # key ids of the sliding/GCRA/concurrency buckets
ZOO_KEYS = 300
HOUR = 3_600_000
# Per-call deadline: a window that meets a shape for the first time waits
# for its compile (seconds on the chip, minutes in a CPU rehearsal).
TIMEOUT = 900.0


class Report:
    """Every check prints; a failed one fails the run at the end (no
    phase is skipped on failure, and none turns into exit 0)."""

    def __init__(self):
        self.failed = []

    def check(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"[{'ok' if ok else 'FAIL'}] {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)


# ----------------------------------------------------------------------
# Traffic: every bucket's parameters are a function of (seed, key id)
# ----------------------------------------------------------------------
class Population:
    def __init__(self, seed: int, n: int):
        from gubernator_tpu.types import Algorithm

        rng = np.random.default_rng(seed)
        self.n = n
        self.alg = (rng.random(n) < 0.5).astype(np.int64)  # token / leaky
        self.limit = rng.choice([5, 20, 100, 1000, 1 << 33], n).astype(np.int64)
        self.duration = rng.choice([HOUR, 2 * HOUR, 24 * HOUR], n).astype(np.int64)
        self.burst = np.where(
            self.alg == 1, rng.choice([0, 10, 50], n), 0).astype(np.int64)
        z = np.arange(ZOO_KEYS)
        self.zoo_alg = np.asarray([
            Algorithm.SLIDING_WINDOW, Algorithm.GCRA, Algorithm.CONCURRENCY,
        ], np.int64)[z % 3]
        self.zoo_limit = rng.choice([3, 10, 50], ZOO_KEYS).astype(np.int64)
        self.zoo_duration = rng.choice([HOUR, 2 * HOUR], ZOO_KEYS).astype(np.int64)

    def params(self, ids: np.ndarray):
        """(algorithm, limit, duration, burst) columns for key ids."""
        zoo = ids >= ZOO_BASE
        p = np.where(zoo, 0, ids)
        z = np.where(zoo, ids - ZOO_BASE, 0)
        return (
            np.where(zoo, self.zoo_alg[z], self.alg[p]),
            np.where(zoo, self.zoo_limit[z], self.limit[p]),
            np.where(zoo, self.zoo_duration[z], self.duration[p]),
            np.where(zoo, 0, self.burst[p]),
        )


def make_cols(ids, hits, pop: Population, behavior, created_at):
    from gubernator_tpu.ops.reqcols import ReqColumns

    n = len(ids)
    alg, limit, duration, burst = pop.params(ids)
    return ReqColumns(
        b"".join([KEY_FMT % i for i in ids.tolist()]),
        np.arange(n + 1, dtype=np.int64) * KEY_LEN,
        hits.astype(np.int64), limit, duration, alg,
        behavior.astype(np.int64), np.full(n, created_at, np.int64), burst,
        name_len=np.full(n, len(NAME), np.int64),
    )


# ----------------------------------------------------------------------
# Oracles
# ----------------------------------------------------------------------
class Oracle:
    """Token/leaky: the x64 ``make_tick_fn`` program, column layout, on
    the host-CPU device (oracle slot == key id).  Zoo algorithms: the
    scalar references, one Python dict of state per key."""

    WIDTH = 8192

    def __init__(self, capacity: int):
        import jax

        from gubernator_tpu.ops.buckets import BucketState
        from gubernator_tpu.ops.engine import make_tick_fn

        self.capacity = capacity
        self.cpu = jax.devices("cpu")[0]
        with jax.default_device(self.cpu):
            self.state = jax.tree.map(
                lambda a: jax.device_put(a, self.cpu),
                BucketState.zeros(capacity))
            self.tick = jax.jit(make_tick_fn(capacity), donate_argnums=(0,))
        self.known = np.zeros(capacity, bool)
        self.zoo = {}

    def apply(self, cols, ids: np.ndarray, now: int) -> np.ndarray:
        """Apply one batch in order; (4, n) status/limit/remaining/reset."""
        out = np.zeros((4, len(ids)), np.int64)
        zoo = ids >= ZOO_BASE
        if (~zoo).any():
            out[:, ~zoo] = self._tick_legacy(cols, ids, np.flatnonzero(~zoo), now)
        for j in np.flatnonzero(zoo):
            out[:, j] = self._zoo_one(cols, int(ids[j]), int(j), now)
        return out

    def _tick_legacy(self, cols, ids, sel, now):
        import jax
        import jax.numpy as jnp

        from gubernator_tpu.ops.engine import REQ_ROW_INDEX as R, REQ_ROWS

        slot = ids[sel]
        # A key's first row in a batch sees what earlier batches left;
        # its later rows see the mapping the first one made (the native
        # slotmap's resolve_batch rule).
        first = np.zeros(len(sel), bool)
        first[np.unique(slot, return_index=True)[1]] = True
        known = self.known[slot] | ~first
        self.known[slot] = True
        res = np.zeros((4, len(sel)), np.int64)
        for s in range(0, len(sel), self.WIDTH):
            ix = sel[s:s + self.WIDTH]
            w = len(ix)
            m = np.zeros((len(REQ_ROWS), self.WIDTH), np.int64)
            m[R["slot"]] = self.capacity
            m[R["slot"], :w] = slot[s:s + w]
            m[R["known"], :w] = known[s:s + w]
            for f in ("hits", "limit", "duration", "algorithm", "behavior",
                      "created_at", "burst"):
                m[R[f], :w] = getattr(cols, f)[ix]
            m[R["valid"], :w] = 1
            with jax.default_device(self.cpu):
                self.state, resp = self.tick(
                    self.state, jax.device_put(m, self.cpu), jnp.int64(now))
            res[:, s:s + w] = np.asarray(resp)[:4, :w]
        return res

    def _zoo_one(self, cols, key_id, j, now):
        from gubernator_tpu.algos import reference

        req = {f: int(getattr(cols, f)[j]) for f in (
            "hits", "limit", "duration", "algorithm", "behavior",
            "created_at", "burst")}
        self.zoo[key_id], r = reference.transition(
            self.zoo.get(key_id), req, now)
        return r["status"], r["limit"], r["remaining"], r["reset_time"]


# ----------------------------------------------------------------------
# Driving the daemon
# ----------------------------------------------------------------------
class Driver:
    def __init__(self, report: Report, pop: Population, oracle: Oracle,
                 client, t_base: int):
        self.report = report
        self.pop = pop
        self.oracle = oracle
        self.client = client
        self.t = t_base     # request created_at: fixed, stepped per round

    async def round(self, lanes) -> tuple:
        """Send the lanes' batches concurrently (one ~window; a key lives
        in exactly one lane, so its order is the oracle's order), then
        replay them through the oracle.  (items, mismatches, errors)."""
        self.t += 1500
        sent = [
            (ids, make_cols(ids, hits, self.pop, beh, self.t))
            for ids, hits, beh in lanes if len(ids)
        ]
        got = await asyncio.gather(*(
            self.client.get_rate_limits_columns(cols, timeout=TIMEOUT)
            for _, cols in sent))
        items = bad = errs = 0
        for (ids, cols), (mat, errors) in zip(sent, got):
            want = self.oracle.apply(cols, ids, self.t)
            items += len(ids)
            errs += len(errors)
            diff = np.flatnonzero((mat != want).any(axis=0))
            bad += len(diff)
            for j in diff[:3]:
                print(f"    mismatch key={int(ids[j])} got={mat[:, j].tolist()}"
                      f" want={want[:, j].tolist()}", flush=True)
        return items, bad, errs

    async def phase(self, name: str, rounds) -> None:
        t0 = time.perf_counter()
        items = bad = errs = 0
        for lanes in rounds:
            i, b, e = await self.round(lanes)
            items, bad, errs = items + i, bad + b, errs + e
        self.report.check(
            name, items > 0 and bad == 0 and errs == 0,
            f"{items} items checked, {bad} mismatches, {errs} error strings,"
            f" {time.perf_counter() - t0:.1f} s",
        )


def split_lanes(ids, hits, beh):
    """Partition rows over LANES concurrent calls by key, order kept."""
    lane = ids % LANES
    return [(ids[lane == k][:BATCH], hits[lane == k][:BATCH],
             beh[lane == k][:BATCH]) for k in range(LANES)]


def load_rounds(n_keys: int, in_flight: int = 8):
    """Every key once, hits=1, in 1,000-item batches (all keys distinct,
    so any number of calls may be in flight)."""
    starts = range(0, n_keys, BATCH)
    for s in range(0, len(starts), in_flight):
        lanes = []
        for a in starts[s:s + in_flight]:
            ids = np.arange(a, min(a + BATCH, n_keys))
            lanes.append((ids, np.ones(len(ids), np.int64),
                          np.zeros(len(ids), np.int64)))
        yield lanes


def unique_rounds(rng, n_keys: int, rounds: int):
    for _ in range(rounds):
        ids = rng.choice(n_keys, LANES * BATCH, replace=False)
        hits = rng.integers(0, 4, len(ids))
        yield split_lanes(ids, hits, np.zeros(len(ids), np.int64))


def zipf_rounds(rng, n_keys: int, rounds: int):
    """Zipf(1.2) over the key population, hits=1 everywhere: duplicate
    groups are uniform, which is what the grouped tick folds."""
    for _ in range(rounds):
        rank = (rng.zipf(1.2, LANES * BATCH) - 1) % n_keys
        ids = (rank * 7919) % n_keys
        n = len(ids)
        yield split_lanes(ids, np.ones(n, np.int64), np.zeros(n, np.int64))


def mixed_rounds(rng, n_keys: int, rounds: int):
    """All five algorithms in one window, duplicates with differing hits,
    RESET_REMAINING and DRAIN_OVER_LIMIT rows: nothing the grouped fold
    accepts, so the layered / sequential programs answer."""
    from gubernator_tpu.types import Behavior

    legacy = rng.choice(n_keys, ZOO_KEYS, replace=False)
    keys = np.concatenate([legacy, ZOO_BASE + np.arange(ZOO_KEYS)])
    for _ in range(rounds):
        n = LANES * BATCH
        ids = rng.choice(keys, n)
        hits = rng.choice([0, 1, 1, 2, 5], n)
        u = rng.random(n)
        beh = np.where(u < 0.1, int(Behavior.RESET_REMAINING),
                       np.where(u < 0.3, int(Behavior.DRAIN_OVER_LIMIT), 0))
        yield split_lanes(ids, hits, beh)


async def race(report: Report, address: str) -> None:
    """64 callers, 8 one-item calls each, one bucket of limit 200, the
    server stamping created_at: exactly 200 of 512 admitted, no errors
    (the shape of test_service_concurrent_clients_exact_accounting)."""
    from gubernator_tpu.transport.daemon import DaemonClient
    from gubernator_tpu.types import RateLimitRequest, Status

    req = RateLimitRequest(
        name=NAME, unique_key="race", hits=1, limit=200, duration=HOUR)

    async def caller():
        c = DaemonClient(address)
        try:
            under = errors = 0
            for _ in range(8):
                r = (await c.get_rate_limits([req], timeout=TIMEOUT))[0]
                errors += r.error != ""
                under += r.error == "" and r.status == Status.UNDER_LIMIT
            return under, errors
        finally:
            await c.close()

    out = await asyncio.gather(*(caller() for _ in range(64)))
    under = sum(u for u, _ in out)
    errors = sum(e for _, e in out)
    report.check("race: 64 callers x 8 one-item calls, limit 200",
                 under == 200 and errors == 0,
                 f"{under} of 512 admitted, {errors} errors")


def fetch(url: str, body: bytes = None) -> bytes:
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.read()


async def http_gateway(report: Report, drv: Driver, http: str) -> None:
    ids = np.arange(drv.pop.n - 8, drv.pop.n)
    hits = np.full(len(ids), 2, np.int64)
    drv.t += 1500
    cols = make_cols(ids, hits, drv.pop, np.zeros(len(ids), np.int64), drv.t)
    body = json.dumps({"requests": [
        {"name": NAME, "unique_key": (KEY_FMT % i).decode()[len(NAME) + 1:],
         "hits": str(int(cols.hits[j])), "limit": str(int(cols.limit[j])),
         "duration": str(int(cols.duration[j])),
         "algorithm": int(cols.algorithm[j]), "burst": str(int(cols.burst[j])),
         "created_at": str(drv.t)}
        for j, i in enumerate(ids.tolist())]}).encode()
    out = json.loads(await asyncio.to_thread(
        fetch, f"http://{http}/v1/GetRateLimits", body))
    want = drv.oracle.apply(cols, ids, drv.t)
    status = {"UNDER_LIMIT": 0, "OVER_LIMIT": 1}
    got = np.asarray([
        [status[r["status"]], int(r["limit"]), int(r["remaining"]),
         int(r["reset_time"])] for r in out["responses"]], np.int64).T
    errs = sum(bool(r.get("error")) for r in out["responses"])
    report.check("http POST /v1/GetRateLimits (JSON)",
                 got.shape == want.shape and (got == want).all() and not errs,
                 f"{len(ids)} items checked, {errs} error strings")
    health = json.loads(await asyncio.to_thread(
        fetch, f"http://{http}/v1/HealthCheck"))
    report.check("http GET /v1/HealthCheck", health.get("status") == "healthy",
                 json.dumps(health))
    text = (await asyncio.to_thread(fetch, f"http://{http}/metrics")).decode()
    wanted = ("gubernator_grpc_request_counts", "gubernator_cache_size",
              "gubernator_tpu_arena_fallbacks")
    report.check("http GET /metrics", all(w in text for w in wanted),
                 f"{len(text.splitlines())} lines")


# ----------------------------------------------------------------------
# Set-up and the two modes
# ----------------------------------------------------------------------
def rebuild_native(report: Report) -> None:
    """``*.so`` is git-ignored: force both libraries to be built from the
    sources in this checkout, and require that they are what is loaded."""
    from gubernator_tpu import native
    from gubernator_tpu.transport import fastwire

    subprocess.run(
        ["make", "-B", "-s", "-C", os.path.join(HERE, "gubernator_tpu", "native")],
        check=True)
    # The loaders refuse a library that is absent or older than its source.
    report.check("native libraries rebuilt and loaded",
                 native.load_library() is not None
                 and fastwire.load() is not None,
                 "slotmap, wire codec")


def print_cache(when: str) -> None:
    """The compile cache's directory and entry count.  Warm-up must add
    nothing on a second run from the same directory; windows that meet a
    new duplicate shape compile lazily and may still add a few."""
    import jax

    d = jax.config.jax_compilation_cache_dir
    n = len(os.listdir(d)) if d and os.path.isdir(d) else 0
    print(f"compile cache: {d} ({n} entries {when})", flush=True)


def bytes_in_use(dev) -> int:
    stats = dev.memory_stats()
    return int(stats["bytes_in_use"]) if stats else 0


async def start_daemon(report: Report, env: dict):
    """The daemon as ``gubernator-tpu`` starts it: environment knobs →
    setup_daemon_config → spawn_daemon (cmd/daemon_main.py)."""
    from gubernator_tpu.config import setup_daemon_config
    from gubernator_tpu.ops.engine import SlotMap
    from gubernator_tpu.transport.daemon import spawn_daemon

    os.environ.update({
        "GUBER_GRPC_ADDRESS": "127.0.0.1:0",
        "GUBER_HTTP_ADDRESS": "127.0.0.1:0",
        "GUBER_PEER_DISCOVERY_TYPE": "none",
        **env,
    })
    daemon = await spawn_daemon(setup_daemon_config())
    eng = daemon.instance.engine
    d = eng.describe()
    print("engine: " + " ".join(f"{k}={v}" for k, v in d.items()), flush=True)
    report.check("device is a TPU", d["platform"] == "tpu", d["device_kind"])
    report.check("table layout row, fused kernel on",
                 d["layout"] == "row" and d["fused"],
                 f"layout={d['layout']} fused={d['fused']}")
    slotmaps = eng.slots if isinstance(eng.slots, list) else [eng.slots]
    report.check("native slotmap in use",
                 not any(isinstance(s, SlotMap) for s in slotmaps))
    return daemon


def print_counters(eng) -> None:
    names = ("_tick_count", "metric_h2d_windows", "metric_h2d_uploads",
             "metric_h2d_overlapped", "metric_native_pack_windows",
             "metric_unique_ticks", "metric_grouped_ticks",
             "metric_sequential_ticks", "metric_layered_ticks",
             "metric_hits", "metric_misses",
             "metric_over_limit", "metric_unexpired_evictions")
    print("engine counters: " + " ".join(
        f"{n.lstrip('_')}={getattr(eng, n)}" for n in names if hasattr(eng, n)),
        flush=True)


async def windows(drv: Driver, rng, n_keys: int, rounds: int, eng) -> None:
    await drv.phase("unique-key window", unique_rounds(rng, n_keys, rounds))
    await drv.phase("zipf(1.2) window", zipf_rounds(rng, n_keys, rounds))
    layered0 = getattr(eng, "metric_layered_ticks", None)
    await drv.phase("mixed five-algorithm window",
                    mixed_rounds(rng, n_keys, rounds))
    if layered0 is not None:
        print(f"layered ticks in the mixed window: "
              f"{eng.metric_layered_ticks - layered0}", flush=True)


def sizes(args) -> tuple:
    """(table rows, keys, rounds per window phase): BASELINE.json config 3
    on the chip; a rehearsal only shrinks them."""
    return (200_000, 20_000, 2) if args.rehearse else (10_000_000, 1_000_000, 8)


async def one_chip(report: Report, args) -> None:
    import jax

    cache, n_keys, rounds = sizes(args)
    print_cache("before")
    daemon = await start_daemon(report, {"GUBER_CACHE_SIZE": str(cache)})
    print_cache("after warm-up")
    try:
        eng = daemon.instance.engine
        dev = jax.devices()[0]
        used = bytes_in_use(dev)
        want = 0.99 * (cache + 1) * 512
        report.check("table resident on the device", used >= want,
                     f"memory_stats bytes_in_use={used}")
        pop = Population(args.seed, n_keys + 8)
        client = daemon.client()
        try:
            drv = Driver(report, pop, Oracle(n_keys + 8), client,
                         int(time.time() * 1000) + HOUR)
            rng = np.random.default_rng(args.seed + 1)
            await drv.phase(f"load {n_keys} distinct keys", load_rounds(n_keys))
            await windows(drv, rng, n_keys, rounds, eng)
            await race(report, daemon.conf.grpc_listen_address)
            await http_gateway(report, drv, daemon.conf.http_listen_address)
        finally:
            await client.close()
        report.check("keys resident in the engine",
                     eng.cache_size() >= n_keys, f"cache_size={eng.cache_size()}")
        print_counters(eng)
        print(f"memory_stats bytes_in_use after: {bytes_in_use(dev)}",
              flush=True)
    finally:
        await daemon.close()
    print_cache("after")


async def mesh(report: Report, args) -> None:
    import jax

    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.types import Behavior, RateLimitRequest

    shards = 4
    cache, n_keys, rounds = sizes(args)
    daemon = await start_daemon(report, {
        "GUBER_CACHE_SIZE": str(cache),
        "GUBER_TPU_MESH_SHARDS": str(shards),
    })
    try:
        eng = daemon.instance.engine
        held = jax.tree.leaves(eng.state)[0].sharding.device_set
        report.check("table sharded over four devices", len(held) == shards,
                     f"{len(held)} devices")
        per = [bytes_in_use(d) for d in jax.devices()[:shards]]
        print(f"memory_stats bytes_in_use per shard: {per}", flush=True)
        quarter = (cache // shards + 1) * 512
        report.check(
            "every shard holds its quarter of the table, none holds it all",
            all(0.99 * quarter <= b < 2 * quarter for b in per),
            f"a quarter is {quarter} bytes")
        pop = Population(args.seed, n_keys)
        client = daemon.client()
        try:
            drv = Driver(report, pop, Oracle(n_keys), client,
                         int(time.time() * 1000) + HOUR)
            await windows(drv, np.random.default_rng(args.seed + 1), n_keys,
                          rounds, eng)
        finally:
            await client.close()
        print_counters(eng)
    finally:
        await daemon.close()

    # The mesh-resident GLOBAL plane: four daemons at the cluster's
    # default table size, one device each, reconciling by collectives.
    hits, limit = [3, 5, 7, 11], 1000
    c = await Cluster.start(
        shards, global_mesh=True,
        behaviors=BehaviorConfig(global_sync_wait=0.05, batch_wait=0.002))
    try:
        gm = c.daemons[0].instance.global_mesh
        held = jax.tree.leaves(gm.state)[0].sharding.device_set
        report.check("GLOBAL replicas on four devices", len(held) == shards,
                     f"{len(held)} devices")

        def g(h):
            return RateLimitRequest(
                name=NAME, unique_key="global", hits=h, limit=limit,
                duration=HOUR, behavior=Behavior.GLOBAL)

        clients = [d.client() for d in c.daemons]
        try:
            errors = 0
            for cl, h in zip(clients, hits):
                errors += (await cl.get_rate_limits([g(h)]))[0].error != ""
            want = limit - sum(hits)
            deadline = time.monotonic() + 60
            seen = []
            while time.monotonic() < deadline:
                seen = [(await cl.get_rate_limits([g(0)]))[0].remaining
                        for cl in clients]
                if seen == [want] * shards:
                    break
                await asyncio.sleep(0.05)
            report.check(
                "GLOBAL hits on every node reconcile to one remaining",
                seen == [want] * shards and errors == 0
                and gm.metric_reconciles > 0,
                f"remaining per node {seen}, want {want};"
                f" {gm.metric_reconciles} reconciles, {errors} errors")
        finally:
            for cl in clients:
                await cl.close()
    finally:
        await c.stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="the four-chip path only: sharded daemon + GLOBAL mesh")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes for a CPU rehearsal; same checks, never ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache)
    import jax

    # A rehearsal names its platform through the daemon's own knob
    # (GUBER_TPU_PLATFORM=cpu), which the daemon applies when it starts;
    # so only the real run may look at the devices before that.
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit(f"chip_smoke.py: no TPU (jax found"
                 f" {jax.devices()[0].platform!r}); --rehearse runs the"
                 " same checks on the CPU, as a failure")

    report = Report()
    rebuild_native(report)
    asyncio.run(mesh(report, args) if args.mesh else one_chip(report, args))
    dev = jax.devices()[0]
    ok = not report.failed
    if report.failed:
        print("failed checks: " + "; ".join(report.failed), flush=True)
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
