#!/usr/bin/env python3
"""benchmarks/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The run's process starts the daemon exactly as ``gubernator-tpu`` does
(``setup_daemon_config`` + ``spawn_daemon``; knobs from the configuration
file's ``env``) and holds the chip.  The table is filled through the
daemon's start-up Loader from the seed.  Load comes from generator
children that stay off the chip and drive gRPC ``GetRateLimits`` on the
loopback socket; they run the mix's traffic as warm-up until it is steady (set-up:
every shape the mix meets is compiled or found in the cache), then the
window.  After the window a sample of keys, drawn from the seed, is
replayed through the plain reference and every answer compared.

Everything is found by name from BENCHMARK.json: the configuration's file,
``benchmarks/traffic/<traffic>.json``, and one reader per metric in
``benchmarks/end_to_end/<metric>.py`` or ``benchmarks/layer_metrics/<metric>.py``.

The last line of stdout is the result object.  Without a TPU (or with
fewer chips than the cell asks for) it exits non-zero and prints no
result; where ``correct`` comes out false it prints the result and exits 1.
``--rehearse`` runs the same code on the CPU (at the sizes of the
configuration's ``rehearse`` block, where it has one); it never prints a
metric or ``correct: true``.  ``--control lost_hit`` puts the reference
with that guarantee broken in the program's place: the run has to come
out not correct.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

T_PROCESS = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
RUN_DIR = os.path.join(ROOT, ".bench_run")      # traces; emptied by every run

# The mix's own traffic runs as warm-up (set-up) until, for STEADY_SECONDS,
# every second answered calls and none took STEADY_LATENCY_S: every shape
# the mix meets is then traced.  The traced run traces TRACE_SECONDS from
# the window's middle.
STEADY_SECONDS = 5
STEADY_LATENCY_S = 1.0
TRACE_SECONDS = 3.0


def say(text: str) -> None:
    print(text, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reader(kind: str, name: str):
    """The ``read(ctx)`` of benchmarks/<kind>/<name>.py."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cache_entries() -> int:
    import jax

    d = jax.config.jax_compilation_cache_dir
    return len(os.listdir(d)) if d and os.path.isdir(d) else 0


class Children:
    """The generator children: started before this process touches jax,
    with the CPU named as their platform, so none can reach for the chip."""

    def __init__(self, spec: dict, mix: dict):
        from benchmarks.harness import genclient, traffic

        self.frames = genclient
        n = traffic.GENERATORS
        lanes = int(mix["lanes"])
        env = dict(os.environ, JAX_PLATFORMS="cpu", GUBER_TPU_PLATFORM="cpu")
        self.procs = []
        for c in range(n):
            p = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "harness", "genclient.py")],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, cwd=ROOT)
            self.procs.append(p)
            genclient.write_frame(p.stdin, dict(
                spec, lanes=[ln for ln in range(lanes) if ln % n == c]))

    async def read_all(self) -> list:
        return list(await asyncio.gather(*(
            asyncio.to_thread(self.frames.read_frame, p.stdout) for p in self.procs)))

    def send_all(self, obj) -> None:
        for p in self.procs:
            self.frames.write_frame(p.stdin, obj)

    def stop(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
        for p in self.procs:
            p.wait()
            p.stdin.close()
            p.stdout.close()


async def start_daemon(config: dict, loader):
    from gubernator_tpu.config import setup_daemon_config
    from gubernator_tpu.transport.daemon import spawn_daemon

    os.environ.update({
        "GUBER_GRPC_ADDRESS": "127.0.0.1:0",
        "GUBER_HTTP_ADDRESS": "127.0.0.1:0",
        "GUBER_PEER_DISCOVERY_TYPE": "none",
        **{k: str(v) for k, v in config["env"].items()},
    })
    conf = setup_daemon_config()
    conf.config.loader = loader       # upstream's Loader hook (store.go)
    return await spawn_daemon(conf)


def engine_counters(eng) -> dict:
    names = ("_tick_count", "metric_h2d_windows", "metric_h2d_overlapped",
             "metric_layered_ticks", "metric_hits", "metric_misses",
             "metric_over_limit", "metric_unexpired_evictions")
    return {n.lstrip("_"): int(getattr(eng, n)) for n in names if hasattr(eng, n)}


def snapshot(eng, rec) -> dict:
    """What is read at the window's two ends and differenced."""
    return {"cpu": time.process_time(), "t": time.perf_counter(),
            "cache": cache_entries(), "eng": engine_counters(eng),
            "rec": rec.totals() if rec else None}


async def trace_window(trace_dir: str, t_from: float, seconds: float, rec) -> dict:
    """Trace ``seconds`` of the window.  The recorder's rows at the
    trace's two ends say how many decisions the traced device time
    answered."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    await asyncio.sleep(max(0.0, t_from - time.perf_counter()))
    await asyncio.to_thread(jax.profiler.start_trace, trace_dir,
                            profiler_options=opts)
    t0, rows0 = time.perf_counter(), rec.rows
    await asyncio.sleep(seconds)
    t1, rows1 = time.perf_counter(), rec.rows
    await asyncio.to_thread(jax.profiler.stop_trace)
    return {"host_s": t1 - t0, "rows": rows1 - rows0,
            "stop_s": time.perf_counter() - t1}


async def run_cell(args, config, mix, children, t0_ms, pop) -> dict:
    import jax

    from benchmarks.harness import traffic
    from benchmarks.harness.loader import SeededLoader

    loader = SeededLoader(pop, t0_ms)
    say(f"compile cache: {jax.config.jax_compilation_cache_dir}"
        f" ({cache_entries()} entries before)")
    t = time.perf_counter()
    daemon = await start_daemon(config, loader)
    out = {}
    try:
        eng = daemon.instance.engine
        d = eng.describe()
        say("engine: " + " ".join(f"{k}={v}" for k, v in d.items()))
        say(f"daemon up in {time.perf_counter() - t:.1f} s (warm-up"
            f" {d['warmup_seconds']} s, then the Loader's fill of {loader.loaded} keys)")
        resident = int(eng.cache_size())
        dev = jax.devices()[0]
        say(f"keys resident after the fill: {resident} of {pop.n};"
            f" bytes_in_use {(dev.memory_stats() or {}).get('bytes_in_use')}")
        ready = await children.read_all()
        say("generators ready: " + ", ".join(
            f"{r['calls_drawn']} calls drawn in {r['prep_s']:.1f} s" for r in ready))

        rec = None
        if args.trace:
            from gubernator_tpu.utils import flightrec
            from benchmarks.harness.recorder import TotalsRecorder

            rec = TotalsRecorder()
            flightrec.install(rec)

        # The mix's own traffic as warm-up, until it has run steady (and a
        # closed loop's lanes have all joined).
        children.send_all({"address": daemon.conf.grpc_listen_address,
                           "t_go_wall": time.time() + 0.5, "t0_ms": t0_ms,
                           "control": args.control})
        t_go = time.perf_counter() + 0.5
        need = STEADY_SECONDS
        ramp = (int(mix["lanes"]) * traffic.RAMP_SECONDS_PER_LANE
                if mix["loop"] == "closed" else 0.0)
        steady, seen = 0, []
        while True:
            frames = await children.read_all()
            calls = sum(f["calls"] for f in frames)
            longest = max(f["longest_s"] for f in frames)
            ok = calls > 0 and longest < STEADY_LATENCY_S
            steady = steady + 1 if ok else 0
            seen.append(calls)
            elapsed = time.perf_counter() - t_go
            if (steady >= need and elapsed >= ramp + need) \
                    or elapsed >= traffic.WARMUP_MAX_SECONDS:
                break
        say(f"warm-up traffic: calls answered in each second {seen};"
            f" steady for {steady} s" + ("" if steady >= need else
                                         " (NOT steady: WARMUP_MAX_SECONDS reached)"))
        t_start_wall = time.time() + 1.0
        t_start = time.perf_counter() + 1.0            # the window's start
        t_end = t_start + args.seconds
        children.send_all({"t_start_wall": t_start_wall})
        for p in children.procs:                       # late progress frames, then the ack
            while "window_ack" not in await asyncio.to_thread(
                    children.frames.read_frame, p.stdout):
                pass
        await asyncio.sleep(max(0.0, t_start - time.perf_counter()))
        out["setup_s"] = time.perf_counter() - T_PROCESS
        c0 = snapshot(eng, rec)
        say(f"window opens: set-up {out['setup_s']:.1f} s; compile cache"
            f" {c0['cache']} entries after warm-up")
        traced = None
        if args.trace:
            shutil.rmtree(RUN_DIR, ignore_errors=True)
            os.makedirs(RUN_DIR)
            span = min(TRACE_SECONDS, args.seconds / 2)
            traced = await trace_window(
                RUN_DIR, t_start + (args.seconds - span) / 2, span, rec)
            say(f"traced {traced['host_s']:.2f} s of the window ({traced['rows']} rows"
                f" begun in it); writing the trace took {traced['stop_s']:.1f} s")
        await asyncio.sleep(max(0.0, t_end - time.perf_counter()))
        c1 = snapshot(eng, rec)
        results = await children.read_all()       # stragglers are waited for
        stats = dev.memory_stats() or {}
        out["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        say(f"window closed: compile cache {c1['cache']} entries"
            f" ({c1['cache'] - c0['cache']} added inside the window)")
        say("engine counters over the window: " + " ".join(
            f"{k}={c1['eng'][k] - c0['eng'][k]}" for k in c1["eng"]))
        say(f"memory_stats: bytes_in_use={stats.get('bytes_in_use')}"
            f" peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
        checks = await children.read_all()
        out.update(results=results, checks=checks, c0=c0, c1=c1, traced=traced,
                   resident=resident,
                   device={"platform": dev.platform, "kind": dev.device_kind,
                           "count": len(jax.devices())})
        if rec is not None:
            from gubernator_tpu.utils import flightrec

            flightrec.uninstall()
    finally:
        await daemon.close()
    return out


def window_stats(raw: dict, seconds: float, closed: bool) -> dict:
    """The window's calls, from the children's records (times are seconds
    from each child's own reading of the window's start)."""
    import numpy as np

    def cat(name):
        return np.concatenate([r[name] for r in raw["results"]])

    due, sent, done = cat("due"), cat("sent"), cat("done")
    state, errors, size = cat("state"), cat("errors"), cat("size")
    start = sent if closed else due
    inwin = (start >= 0) & (start < seconds)
    answered = (state == 1) & (errors == 0)
    first = float(np.floor(min(done.min(), 0.0))) if len(done) else 0.0
    return {
        "calls": int(inwin.sum()),
        "calls_all": int(len(sent)),
        "failed_calls": int((inwin & ~answered).sum()),
        "unanswered_calls": int((state != 1).sum()),
        "error_items": int(errors.sum()),
        "latency_s": (done - start)[inwin & (state == 1)],
        # every item answered, without an error string, inside the window
        "decisions": int(size[answered & (done >= 0) & (done < seconds)].sum()),
        "decisions_due": int(size[inwin].sum()),
        "late_s": (sent - due)[inwin],
        "seconds": seconds,
        "last_done_s": float(done.max()) if len(done) else 0.0,
        # decisions answered in each second of the window (warm-up left out)
        "timeline": np.histogram(done[state == 1], weights=size[state == 1],
                                 bins=np.arange(0.0, seconds + 1.0))[0].astype(int).tolist(),
        "warmup_s": max(r["warmup_s"] for r in raw["results"]),
        "per_child_late_ms": [
            [float(np.percentile(((r["sent"] - r["due"])[r["due"] >= 0]) * 1e3, q))
             if (r["due"] >= 0).any() else 0.0 for q in (50, 99, 100)]
            for r in raw["results"]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, same code; never a metric, never correct")
    ap.add_argument("--control", default="", choices=("", "lost_hit"),
                    help="the reference with this guarantee broken stands in the"
                         " program's place: has to come out not correct; the"
                         " driver's runs give none")
    ap.add_argument("--benchmark-json", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another BENCHMARK.json (a rehearsal of files not yet committed)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open loop: offer this rate instead of the mix's (the sweep)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "gubernator_tpu")):
        sys.exit("run.py: no gubernator_tpu/ beside benchmarks/: nothing to measure")
    bench = load_json(args.benchmark_json)
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        sys.exit(f"run.py: no workload {args.workload!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, conf_entry["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if args.rate:
        mix["rate_calls_per_s"] = args.rate
    if args.rehearse:
        small = config.get("rehearse", {})
        config["env"].update(small.get("env", {}))
        config["population"]["keys"] = small.get("keys", config["population"]["keys"])
        os.environ["GUBER_TPU_PLATFORM"] = "cpu"
        if int(cell["chips"]) > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell['chips']}")
    # The compile cache sits at a fixed path inside the checkout unless
    # the machine names one.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))

    from benchmarks.harness import costs, population, traffic, xtrace

    t0_ms = int(time.time() * 1000) + traffic.CREATED_AT_LEAD_MS
    # *.so is git-ignored: build the native libraries (a no-op where they
    # are newer than their sources) before any child races to.
    subprocess.run(["make", "-s", "-C", os.path.join(ROOT, "gubernator_tpu", "native")],
                   check=True)
    children = Children({"mix": mix, "population": config["population"],
                         "seed": args.seed, "seconds": args.seconds}, mix)
    try:
        import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache)
        import jax

        devices = jax.devices()
        if not args.rehearse and devices[0].platform != "tpu":
            sys.exit(f"run.py: no TPU (jax found {devices[0].platform!r});"
                     " --rehearse runs the same code on the CPU, as a failure")
        if len(devices) < int(cell["chips"]):
            sys.exit(f"run.py: the cell asks for {cell['chips']} chips, jax found"
                     f" {len(devices)}")
        from gubernator_tpu import native
        from gubernator_tpu.transport import fastwire

        if native.load_library() is None or fastwire.load() is None:
            sys.exit("run.py: the native slotmap or wire codec did not build")
        pop = population.Population(config["population"], args.seed)
        raw = asyncio.run(run_cell(args, config, mix, children, t0_ms, pop))
    finally:
        children.stop()

    closed = mix["loop"] == "closed"
    win = window_stats(raw, args.seconds, closed)
    say(f"calls in the window: {win['calls']} ({win['calls_all']} with warm-up),"
        f" {win['failed_calls']} failed; decisions answered in it: {win['decisions']}"
        f" of {win['decisions_due']} due; last answer at {win['last_done_s']:.3f} s")
    if len(win["latency_s"]):
        import numpy as np

        say("call latency in the window, p50/p99/max ms: " + "/".join(
            f"{v * 1e3:.3f}" for v in np.percentile(win["latency_s"], (50, 99, 100))))
    say(f"decisions answered in each second of the window (after {win['warmup_s']:.1f} s"
        f" of warm-up traffic): {win['timeline']}")
    say("generator lateness (sent - due) p50/p99/max ms per child: " + "; ".join(
        "/".join(f"{v:.3f}" for v in c) for c in win["per_child_late_ms"]))
    for r in raw["results"]:
        for note in r["notes"]:
            say("generator note: " + note)

    reduced = None
    if args.trace:
        events = xtrace.load(xtrace.find_xplane(RUN_DIR))
        if not args.rehearse:
            reduced = xtrace.reduce(events)
        shutil.rmtree(RUN_DIR, ignore_errors=True)

    # What is compared, each number beside its limit (all exact: limit 0).
    chk = raw["checks"]
    compared = {
        "mismatched_answers": [sum(c["mismatched"] for c in chk), 0],
        "unanswered_calls": [win["unanswered_calls"], 0],
        "error_items": [win["error_items"], 0],
        "keys_not_resident": [pop.n - raw["resident"], 0],
    }
    answers = sum(c["events"] for c in chk)
    say(f"output check: {answers} answers of {sum(c['keys'] for c in chk)} sampled keys"
        f" replayed through the plain reference in"
        f" {max(c['replay_s'] for c in chk):.1f} s (the slowest child)"
        + (f"; CONTROL {args.control}: the reference with that guarantee broken"
           " stands in the program's place" if args.control else ""))
    for c in chk:
        for line in c["first"]:
            say("  mismatch " + line)
    correct = all(v <= lim for v, lim in compared.values()) and answers > 0

    ctx = {
        "window": win, "setup_s": raw["setup_s"], "trace": reduced,
        "traced": raw["traced"], "device_kind": raw["device"]["kind"],
        "cpu_s": raw["c1"]["cpu"] - raw["c0"]["cpu"],
        "wall_s": raw["c1"]["t"] - raw["c0"]["t"],
        "recorder": None, "costs": costs, "xtrace": xtrace, "mix": mix,
        "engine": {k: raw["c1"]["eng"][k] - raw["c0"]["eng"][k] for k in raw["c1"]["eng"]},
    }
    if raw["c0"]["rec"]:
        a, b = raw["c0"]["rec"], raw["c1"]["rec"]
        ctx["recorder"] = {
            "stage_s": {s: b["stage_s"][s] - a["stage_s"][s] for s in b["stage_s"]},
            "windows": b["windows"] - a["windows"], "rows": b["rows"] - a["rows"],
            "edge_calls": {s: b["edge_calls"][s] - a["edge_calls"][s]
                           for s in b["edge_calls"]},
        }
        say("flight recorder totals over the window: " + json.dumps(ctx["recorder"]))

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = reader("layer_metrics" if args.trace else "end_to_end", m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    if args.rehearse:
        say("rehearsal on " + raw["device"]["platform"] + ": counts only; "
            + json.dumps({k: v[0] for k, v in compared.items()})
            + f" answers={answers} metrics_read={sorted(metrics)}")
        say(f"rehearsal: the comparison alone would say correct={bool(correct)}")
        say(json.dumps({"correct": False, "attempted": win["calls"],
                        "failed": win["failed_calls"], "metrics": {},
                        "device": raw["device"]}))
        return 1

    device = dict(raw["device"], memory_peak_bytes=raw["memory_peak_bytes"])
    line = {"correct": bool(correct), "attempted": win["calls"],
            "failed": win["failed_calls"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    compared["answers_compared"] = [answers, ">=1"]
    line["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"compared {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
    say(json.dumps(line))
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
