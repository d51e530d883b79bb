"""A load generator child: owns some lanes, stays off the chip
(``JAX_PLATFORMS=cpu`` in its environment), draws all its traffic from
the seed before the window, drives the daemon's public entry (gRPC
``GetRateLimits`` on the loopback socket through
``DaemonClient.get_rate_limits_columns``), records (due, sent, done) of
every call and the answers of the sampled keys, and after the window
replays those keys through the plain reference.  The mix's own traffic
runs first as warm-up, until the parent has seen it run steady and names
the window's start.

Talks to its parent in pickled frames over stdin/stdout; anything it
prints goes to stderr.
"""

from __future__ import annotations

import asyncio
import os
import pickle
import struct
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.harness import population, traffic  # noqa: E402
from benchmarks.harness.reference import Reference  # noqa: E402

CALL_TIMEOUT_S = 60.0   # an answer is waited for a minute past its sending


def read_frame(f):
    head = f.read(8)
    if len(head) < 8:
        raise EOFError("peer closed the pipe")
    (n,) = struct.unpack("<Q", head)
    return pickle.loads(f.read(n))


def write_frame(f, obj) -> None:
    raw = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    f.write(struct.pack("<Q", len(raw)))
    f.write(raw)
    f.flush()


class Lane:
    """One lane: its two plans (warm-up, window), its record of every
    call it sent, and which rows of each call the output check replays."""

    def __init__(self, plans, seed, mix, hot):
        self.warm, self.main = plans
        self.lane = self.main.lane
        self.calls = []      # (plan, k, due, sent, done, created_at, state, errors, answers)
        self.note = ""
        self._rows = {}
        for plan in {id(self.warm): self.warm, id(self.main): self.main}.values():
            mask = traffic.sampled(plan.ids, seed, mix["check"], hot)
            self._rows[id(plan)] = [
                np.flatnonzero(mask[plan.bounds[k]: plan.bounds[k + 1]])
                for k in range(len(plan))]

    def rows(self, plan, k):
        return self._rows[id(plan)][k % len(plan)]


def make_cols(ReqColumns, ids, pop, hits, created_at):
    n = len(ids)
    alg, limit, duration, burst = pop.params(ids)
    blob, offsets = population.key_blob(ids)
    return ReqColumns(
        blob.tobytes(), offsets, np.full(n, hits, np.int64), limit, duration,
        alg, np.zeros(n, np.int64), np.full(n, created_at, np.int64), burst,
        name_len=np.full(n, len(population.NAME), np.int64))


class Clock:
    """The phases of a run, in this process's perf_counter: the warm-up
    starts at ``go``; the window's ``start`` and ``end`` are set when the
    parent has seen the warm-up run steady."""

    def __init__(self, go):
        self.go, self.start, self.end = go, float("inf"), float("inf")


async def drive(spec, lanes, pop, clk, progress):
    from gubernator_tpu.ops.reqcols import ReqColumns
    from gubernator_tpu.transport.daemon import DaemonClient

    mix = spec["mix"]
    lead = traffic.CREATED_AT_LEAD_MS
    hits = int(mix["hits"])
    closed = mix["loop"] == "closed"
    client = DaemonClient(spec["address"])
    now = time.perf_counter
    wall_off = time.time() - now()   # created_at is the wall clock in ms

    async def call(ln, plan, k, due, last_created):
        cols = make_cols(
            ReqColumns, plan.call_ids(k), pop, hits,
            max(last_created, int((now() + wall_off) * 1000) + lead))
        created = int(cols.created_at[0])
        sent = now()
        try:
            mat, errors = await client.get_rate_limits_columns(
                cols, timeout=CALL_TIMEOUT_S)
        except Exception as e:  # a shed, refused or timed-out call
            done = now()
            ln.note = ln.note or f"{type(e).__name__}: {str(e)[:200]}"
            ln.calls.append((plan, k, due, sent, done, created, 2, 0, None))
        else:
            done = now()
            rows = ln.rows(plan, k)
            ln.calls.append((plan, k, due, sent, done, created, 1, len(errors),
                             mat[:4, rows].copy() if len(rows) else None))
        progress[0] += 1
        progress[1] = max(progress[1], done - (sent if closed else due))
        return created, done

    async def closed_lane(ln):
        # Lanes join one by one, so that the warm-up forms windows of one
        # call, of two, ... up to the full width, and the program meets
        # (and traces) each window shape before the timed window does.
        await asyncio.sleep(max(
            0.0, clk.go + ln.lane * traffic.RAMP_SECONDS_PER_LANE - now()))
        k, created, done = 0, 0, now()
        while now() < clk.end:
            created, done = await call(ln, ln.main, k, done, created)
            k += 1

    async def open_lane(ln):
        created = 0
        # Warm-up: calls due from ``go`` on, until the window's start is
        # known and reached.
        for k in range(len(ln.warm)):
            due = clk.go + ln.warm.due[k]
            while now() < due < clk.start:
                await asyncio.sleep(max(0.0, min(due - now(), 0.2)))
            if due >= clk.start:
                break
            created, _ = await call(ln, ln.warm, k, due, created)
        while clk.start == float("inf"):      # the warm-up's plan ran out
            await asyncio.sleep(0.05)
        for k in range(len(ln.main)):
            due = clk.start + ln.main.due[k]
            await asyncio.sleep(max(0.0, due - now()))
            created, _ = await call(ln, ln.main, k, due, created)

    await asyncio.sleep(max(0.0, clk.go - now()))
    try:
        await asyncio.gather(*((closed_lane if closed else open_lane)(ln)
                               for ln in lanes))
    finally:
        await client.close()


def check(lanes, pop, spec) -> dict:
    """Replay every sampled key's ordered history through the plain
    reference (or, in a control run, through the reference with one
    guarantee broken: the control in the program's place) and count
    answers that differ.  A failed call is taken as not applied."""
    t0 = time.perf_counter()
    hits = int(spec["mix"]["hits"])
    per_key = {}
    for ln in lanes:
        for plan, k, _, _, _, created, state, _, got in ln.calls:
            if state != 1 or got is None:
                continue
            ids = plan.call_ids(k)[ln.rows(plan, k)]
            for j, key in enumerate(ids.tolist()):
                per_key.setdefault(key, []).append(
                    (created, tuple(got[:, j].tolist())))
    keys = np.fromiter(per_key, np.int64, len(per_key))
    st = pop.state(keys, int(spec["t0_ms"]))
    cols = {f: v.tolist() for f, v in st.items()}
    alg, limit, duration, burst = (v.tolist() for v in pop.params(keys))
    ref = Reference(spec.get("control", ""))
    bad, events, first = 0, 0, []
    for i, key in enumerate(keys.tolist()):
        hist = per_key[key]
        events += len(hist)
        b = {f: cols[f][i] for f in cols}
        for created, got in hist:
            b, want = ref.apply(
                b, (hits, limit[i], duration[i], burst[i], alg[i], 0, created))
            if want != got:
                bad += 1
                if len(first) < 3:
                    first.append(f"key={key} created_at={created} got={got} want={want}")
    return {"keys": len(keys), "events": events, "mismatched": bad, "first": first,
            "replay_s": time.perf_counter() - t0}


async def run(spec, lanes, pop, inp, out):
    """Warm-up, window and the frames between: progress every second
    until the parent names the window's start."""
    go = read_frame(inp)               # {"address", "t_go_wall", "t0_ms", "control"}
    spec.update(go)
    now = time.perf_counter
    clk = Clock(now() + (go["t_go_wall"] - time.time()))
    progress = [0, 0.0]                # calls answered, their longest latency
    loop = asyncio.get_running_loop()
    window = loop.run_in_executor(None, read_frame, inp)
    driving = asyncio.ensure_future(drive(spec, lanes, pop, clk, progress))
    while not window.done():
        await asyncio.wait([window], timeout=1.0)
        if not window.done():
            write_frame(out, {"progress": True, "calls": progress[0],
                              "longest_s": progress[1], "at_s": now() - clk.go})
            progress[0], progress[1] = 0, 0.0
    w = window.result()                # {"t_start_wall"}
    clk.start = now() + (w["t_start_wall"] - time.time())
    clk.end = clk.start + spec["seconds"]
    write_frame(out, {"window_ack": True})
    await driving
    return clk


def main() -> int:
    # Nothing but frames on the pipe: fd 1 becomes stderr for whatever
    # this process or a library under it prints.
    inp = sys.stdin.buffer
    out = os.fdopen(os.dup(1), "wb")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    spec = read_frame(inp)
    t_prep = time.perf_counter()
    mix = spec["mix"]
    pop = population.Population(spec["population"], spec["seed"])
    plans = traffic.plans(mix, pop.n, spec["seed"], spec["seconds"], spec["lanes"])
    hot = traffic.hot_ids(mix, pop.n, int(mix["check"].get("hot_ranks", 0)))
    lanes = [Lane(plans[ln], spec["seed"], mix, hot) for ln in spec["lanes"]]
    # Import the client (and so jax, on the CPU) before the window.
    from gubernator_tpu.transport import fastwire
    if fastwire.load() is None:
        raise RuntimeError("native wire codec unavailable in the generator")
    write_frame(out, {"ready": True, "prep_s": time.perf_counter() - t_prep,
                      "calls_drawn": sum(len(ln.main) for ln in lanes)})
    clk = asyncio.run(run(spec, lanes, pop, inp, out))

    calls = [c for ln in lanes for c in ln.calls]

    def col(i, dtype=np.float64):
        return np.asarray([c[i] for c in calls], dtype)

    result = {
        # seconds from the window's start
        "due": col(2) - clk.start, "sent": col(3) - clk.start,
        "done": col(4) - clk.start, "state": col(6, np.int8),
        "errors": col(7, np.int64),
        "size": np.asarray([len(c[0].call_ids(c[1])) for c in calls], np.int64),
        "warmup_s": clk.start - clk.go,
        "notes": [f"lane {ln.lane}: {ln.note}" for ln in lanes if ln.note],
    }
    write_frame(out, result)
    # The replay runs after the numbers of the window are handed over.
    write_frame(out, check(lanes, pop, spec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
