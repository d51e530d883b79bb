"""guberlint (gubernator_tpu/analysis): rule fixtures, suppression and
baseline mechanics, and the repo-wide zero-findings gate.

Deliberately jax-free: the linter is pure stdlib and these tests import
only ``gubernator_tpu.analysis`` (the package root imports no jax — a
subprocess test below pins that property so it can't regress silently).
Everything here is AST walking over tiny fixture projects; the whole
file runs in a couple of seconds.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from gubernator_tpu.analysis import (
    RULES,
    load_baseline,
    load_project,
    run_project,
    write_baseline,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------
# Fixture projects
# ----------------------------------------------------------------------
MINI_CONFIG = """\
ENV_REGISTRY = {
    "GUBER_GOOD_KNOB": "a registered knob",
    "GUBER_OTHER_KNOB": "another registered knob",
}
"""

MINI_CONF = "# GUBER_GOOD_KNOB=1\n# GUBER_OTHER_KNOB=2\n"


def make_project(tmp_path, files, config=MINI_CONFIG, conf=MINI_CONF,
                 prometheus=None, metrics=None):
    """Write a minimal lintable project: pkg/config.py + example.conf
    boilerplate plus the given {relpath: source} fixture files."""
    pkg = tmp_path / "pkg"
    (pkg / "utils").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "utils" / "__init__.py").write_text("")
    (pkg / "config.py").write_text(config)
    (tmp_path / "example.conf").write_text(conf)
    (tmp_path / "docs").mkdir()
    if prometheus is not None:
        (tmp_path / "docs" / "prometheus.md").write_text(prometheus)
    if metrics is not None:
        (pkg / "utils" / "metrics.py").write_text(metrics)
    for rel, src in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return load_project(str(tmp_path), "pkg")


def findings(tmp_path, files, rule, **kw):
    proj = make_project(tmp_path, files, **kw)
    return [f for f in run_project(proj, rule_ids=[rule]).findings]


# ----------------------------------------------------------------------
# G001 — hot-path device sync
# ----------------------------------------------------------------------
G001_POS = """\
from pkg.utils.hotpath import hot_path
import numpy as np
import jax

@hot_path
def dispatch(self, state, resp):
    a = np.asarray(resp)          # D2H
    b = resp.item()               # D2H
    jax.device_get(resp)          # D2H
    state.block_until_ready()     # sync
    c = float(resp)               # scalar materialization
    return a, b, c
"""


def test_g001_flags_sync_primitives_in_hot_path(tmp_path):
    out = findings(tmp_path, {"mod.py": G001_POS}, "G001")
    assert len(out) == 5
    assert {f.rule for f in out} == {"G001"}
    msgs = " ".join(f.message for f in out)
    for tok in ("np.asarray", ".item()", "jax.device_get",
                "block_until_ready", "float()"):
        assert tok in msgs


def test_g001_flags_blocking_file_syscalls_in_hot_path(tmp_path):
    src = """\
    import os
    import mmap
    from pkg.utils.hotpath import hot_path

    @hot_path
    def take_batch(self, path):
        f = open(path, "rb")              # storage stall
        fd = os.open(path, os.O_RDONLY)   # storage stall
        os.fsync(fd)                      # storage stall
        m = mmap.mmap(fd, 0)              # storage stall
        return f, m

    def writer_loop(path):
        return open(path, "ab")           # unmarked: fine
    """
    out = findings(tmp_path, {"mod.py": src}, "G001")
    assert len(out) == 4
    msgs = " ".join(f.message for f in out)
    for tok in ("open()", "os.open()", "os.fsync()", "mmap.mmap()"):
        assert tok in msgs
    assert "blocking syscall" in out[0].message


def test_g001_ignores_unmarked_and_nested_and_jnp(tmp_path):
    src = """\
    import numpy as np
    import jax.numpy as jnp
    from pkg.utils.hotpath import hot_path

    def cold(resp):
        return np.asarray(resp)      # unmarked: fine

    @hot_path
    def dispatch(state, m):
        state = tick(state, jnp.asarray(m))   # H2D: fine

        def finish():                 # deferred callback: not checked
            return np.asarray(state)

        return state, finish
    """
    assert findings(tmp_path, {"mod.py": src}, "G001") == []


def test_g001_suppression_with_reason(tmp_path):
    src = """\
    import numpy as np
    from pkg.utils.hotpath import hot_path

    @hot_path
    def dispatch(sel):
        # guber: allow-G001(sel is host numpy)
        return np.asarray(sel)
    """
    proj = make_project(tmp_path, {"mod.py": src})
    res = run_project(proj, rule_ids=["G001"])
    assert res.findings == []
    assert res.suppressed == 1


def test_g001_empty_reason_does_not_suppress(tmp_path):
    src = """\
    import numpy as np
    from pkg.utils.hotpath import hot_path

    @hot_path
    def dispatch(sel):
        return np.asarray(sel)  # guber: allow-G001()
    """
    res = run_project(make_project(tmp_path, {"mod.py": src}),
                      rule_ids=["G001"])
    assert len(res.findings) == 1 and res.suppressed == 0


# ----------------------------------------------------------------------
# G002 — blocking under lock / blocking in async
# ----------------------------------------------------------------------
def test_g002_await_under_threading_lock(tmp_path):
    src = """\
    import asyncio

    class W:
        async def flush(self):
            with self._write_lock:
                await asyncio.sleep(1)
    """
    out = findings(tmp_path, {"mod.py": src}, "G002")
    assert len(out) == 1 and "held lock" in out[0].message


def test_g002_blocking_calls_in_async(tmp_path):
    src = """\
    import os
    import time

    async def loop(self):
        time.sleep(0.1)
        os.fsync(3)
        f = open("/tmp/x")
    """
    out = findings(tmp_path, {"mod.py": src}, "G002")
    assert len(out) == 3
    msgs = " ".join(f.message for f in out)
    assert "time.sleep" in msgs and "os.fsync" in msgs and "open" in msgs


def test_g002_negative_cases(tmp_path):
    src = """\
    import asyncio
    import time

    def sync_writer(self):
        with self._write_lock:
            time.sleep(0.1)       # sync fn: allowed (runs in executor)

    async def good(self):
        async with self._alock:   # asyncio lock: fine to await under
            await asyncio.sleep(0)
        await asyncio.get_running_loop().run_in_executor(
            None, self.flush)     # blocking work via executor

        def thunk():
            open("/tmp/x")        # nested sync def: runs elsewhere
        return thunk
    """
    assert findings(tmp_path, {"mod.py": src}, "G002") == []


# ----------------------------------------------------------------------
# G003 — fire-and-forget tasks
# ----------------------------------------------------------------------
def test_g003_flags_discarded_handles(tmp_path):
    src = """\
    import asyncio

    def spawn(loop, coro):
        asyncio.create_task(coro())
        asyncio.ensure_future(coro())
        loop.create_task(coro())
        _ = asyncio.create_task(coro())
    """
    out = findings(tmp_path, {"mod.py": src}, "G003")
    assert len(out) == 4
    assert all("fire-and-forget" in f.message for f in out)


def test_g003_negative_cases(tmp_path):
    src = """\
    import asyncio

    async def ok(loop, coro, tasks):
        t = asyncio.create_task(coro())
        tasks.add(t)
        t.add_done_callback(tasks.discard)
        await asyncio.ensure_future(coro())
        return asyncio.ensure_future(coro())
    """
    assert findings(tmp_path, {"mod.py": src}, "G003") == []


# ----------------------------------------------------------------------
# G004 — env discipline
# ----------------------------------------------------------------------
def test_g004_direct_environ_read_outside_config(tmp_path):
    src = """\
    import os
    A = os.environ.get("GUBER_GOOD_KNOB")
    B = os.getenv("GUBER_OTHER_KNOB", "4")
    C = os.environ["GUBER_GOOD_KNOB"]
    """
    out = findings(tmp_path, {"mod.py": src}, "G004")
    assert len(out) == 3
    assert all("bypasses the config registry" in f.message for f in out)


def test_g004_unregistered_name_and_conf_sync(tmp_path):
    src = 'KNOB = "GUBER_NOT_REGISTERED"\n'
    conf = "# GUBER_GOOD_KNOB=1\n# GUBER_STALE_DOC=1\n"
    out = findings(tmp_path, {"mod.py": src}, "G004", conf=conf)
    msgs = " | ".join(f.message for f in out)
    assert "GUBER_NOT_REGISTERED" in msgs       # mentioned, unregistered
    assert "GUBER_OTHER_KNOB is registered but not documented" in msgs
    assert "GUBER_STALE_DOC" in msgs            # documented, unregistered
    assert len(out) == 3


def test_g004_env_writes_and_prefix_families_ok(tmp_path):
    src = """\
    import os
    os.environ["GUBER_GOOD_KNOB"] = "1"     # write: allowed
    DOC = "set any GUBER_FAULT_ knob"        # prefix mention: allowed
    """
    assert findings(tmp_path, {"mod.py": src}, "G004") == []


def test_g004_missing_registry_is_a_finding(tmp_path):
    out = findings(tmp_path, {"mod.py": "X = 1\n"}, "G004",
                   config="OTHER = 1\n")
    assert len(out) == 1 and "ENV_REGISTRY" in out[0].message


# ----------------------------------------------------------------------
# G005 — metric registry sync
# ----------------------------------------------------------------------
METRICS_SRC = """\
from prometheus_client import Counter, Gauge

class M:
    def __init__(self, reg):
        self.a = Counter("gubernator_alpha", "doc", registry=reg)
        self.b = Gauge("gubernator_beta", "doc", registry=reg)
"""

PROM_DOC = """\
# Metrics

| Metric | Type |
| ------ | ---- |
| `gubernator_alpha` | Counter |
| `gubernator_beta` | Gauge |

Prose may cite `gubernator_alpha_total` without a finding.
"""


def test_g005_in_sync(tmp_path):
    assert findings(tmp_path, {}, "G005", metrics=METRICS_SRC,
                    prometheus=PROM_DOC) == []


def test_g005_both_directions_and_duplicates(tmp_path):
    metrics = METRICS_SRC + """\

def extra(reg):
    from prometheus_client import Counter
    return (Counter("gubernator_alpha", "dup", registry=reg),
            Counter("gubernator_undocumented", "doc", registry=reg))
"""
    doc = PROM_DOC + "| `gubernator_ghost` | Counter |\n"
    out = findings(tmp_path, {}, "G005", metrics=metrics, prometheus=doc)
    msgs = " ".join(f.message for f in out)
    assert "duplicate metric family gubernator_alpha" in msgs
    assert "gubernator_undocumented" in msgs
    assert "gubernator_ghost" in msgs
    assert len(out) == 3


# ----------------------------------------------------------------------
# G006 — trace purity
# ----------------------------------------------------------------------
def test_g006_impure_calls_and_branches(tmp_path):
    src = """\
    import os
    import time
    import jax

    @jax.jit
    def decorated(x):
        t = time.time()
        if x > 0:
            x = x + 1
        return x + t

    def by_name(state, n):
        d = os.environ.get("GUBER_GOOD_KNOB")
        return state

    f = jax.jit(by_name, donate_argnums=(0,))
    g = jax.jit(lambda rows: rows + time.monotonic())
    """
    out = findings(tmp_path, {"mod.py": src}, "G006")
    msgs = " ".join(f.message for f in out)
    assert "time.time()" in msgs
    assert "Python-level branch" in msgs
    assert "os.environ" in msgs
    assert "time.monotonic()" in msgs
    assert len(out) == 4


def test_g006_static_metadata_branches_ok(tmp_path):
    src = """\
    import time
    import jax

    @jax.jit
    def ok(x, w):
        if x.shape[0] > 2:
            pass
        if w is None:
            pass
        if len(x.shape) == 2:
            pass
        return x

    def untraced(x):
        return time.time()       # never jitted: fine
    """
    assert findings(tmp_path, {"mod.py": src}, "G006") == []


def test_g006_shard_map_and_partial(tmp_path):
    src = """\
    import functools
    import jax
    from jax.experimental.shard_map import shard_map

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state):
        print("tracing")
        return state

    def body(x):
        import random
        return x * random.random()

    s = shard_map(body, mesh=None, in_specs=None, out_specs=None)
    """
    out = findings(tmp_path, {"mod.py": src}, "G006")
    msgs = " ".join(f.message for f in out)
    assert "print()" in msgs and "random.random()" in msgs
    assert len(out) == 2


def test_g006_sees_ragged_tick_wrappers():
    """The sharded engine's ragged tick wrappers are traced through
    shard_map by NAME — pin that G006's traced-function discovery still
    sees them (renaming or inlining them would silently drop the
    trace-purity guard from the serving path's hottest programs)."""
    import ast

    from gubernator_tpu.analysis.rules import _traced_functions

    path = os.path.join(
        REPO_ROOT, "gubernator_tpu", "parallel", "mesh_engine.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    traced = {
        fn.name for fn, _ in _traced_functions(tree)
        if hasattr(fn, "name")
    }
    assert {"_tick_ragged", "_tick32_ragged"} <= traced


# ----------------------------------------------------------------------
# Suppression + baseline mechanics
# ----------------------------------------------------------------------
def test_suppression_line_above_and_wrong_rule(tmp_path):
    src = """\
    import asyncio

    def f(coro):
        # guber: allow-G003(intentional detach, probe result unused)
        asyncio.create_task(coro())
        # guber: allow-G001(wrong rule id)
        asyncio.create_task(coro())
    """
    res = run_project(make_project(tmp_path, {"mod.py": src}),
                      rule_ids=["G003"])
    assert len(res.findings) == 1
    assert res.suppressed == 1


def test_suppression_in_string_literal_does_not_count(tmp_path):
    src = '''\
    import asyncio

    DOC = "# guber: allow-G003(not a comment)"
    def f(coro):
        asyncio.create_task(coro())
    '''
    res = run_project(make_project(tmp_path, {"mod.py": src}),
                      rule_ids=["G003"])
    assert len(res.findings) == 1


def test_baseline_roundtrip_and_line_drift(tmp_path):
    src = "import asyncio\n\ndef f(c):\n    asyncio.create_task(c())\n"
    proj = make_project(tmp_path, {"mod.py": src})
    res = run_project(proj, rule_ids=["G003"])
    assert len(res.findings) == 1
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(bl_path, proj, res.findings)
    data = json.load(open(bl_path))
    assert data["findings"][0]["rule"] == "G003"
    assert "reason" in data["findings"][0]

    # Same code → baselined out.
    res2 = run_project(proj, load_baseline(bl_path), rule_ids=["G003"])
    assert res2.findings == [] and res2.baselined == 1

    # Lines shift above the finding → fingerprint still matches.
    shifted = "import asyncio\n\nX = 1\nY = 2\n\ndef f(c):\n" \
              "    asyncio.create_task(c())\n"
    proj3 = make_project(tmp_path / "v2", {"mod.py": shifted})
    res3 = run_project(proj3, load_baseline(bl_path), rule_ids=["G003"])
    assert res3.findings == [] and res3.baselined == 1

    # A DIFFERENT offending line is not covered by the old entry.
    other = "import asyncio\n\ndef f(c):\n    asyncio.ensure_future(c())\n"
    proj4 = make_project(tmp_path / "v3", {"mod.py": other})
    res4 = run_project(proj4, load_baseline(bl_path), rule_ids=["G003"])
    assert len(res4.findings) == 1


def test_baseline_count_caps_repeated_findings(tmp_path):
    one = "import asyncio\n\ndef f(c):\n    asyncio.create_task(c())\n"
    two = ("import asyncio\n\ndef f(c):\n    asyncio.create_task(c())\n"
           "\ndef g(c):\n    asyncio.create_task(c())\n")
    proj1 = make_project(tmp_path, {"mod.py": one})
    res1 = run_project(proj1, rule_ids=["G003"])
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(bl_path, proj1, res1.findings)
    # The second copy of the same offending line is NOT grandfathered.
    proj2 = make_project(tmp_path / "v2", {"mod.py": two})
    res2 = run_project(proj2, load_baseline(bl_path), rule_ids=["G003"])
    assert len(res2.findings) == 1 and res2.baselined == 1


# ----------------------------------------------------------------------
# The real repo: the permanent gate
# ----------------------------------------------------------------------
def test_repo_has_zero_unsuppressed_findings():
    proj = load_project(REPO_ROOT, "gubernator_tpu")
    assert len(proj.files) > 50  # sanity: the walk found the package
    baseline = load_baseline(
        os.path.join(REPO_ROOT, ".guberlint-baseline.json"))
    res = run_project(proj, baseline)
    assert res.findings == [], "\n" + "\n".join(
        f.render() for f in res.findings)


def test_repo_hot_path_markers_present():
    """G001 only guards what's marked — pin the serving-path coverage so
    removing a decorator (which would silently disable the rule there)
    fails loudly."""
    proj = load_project(REPO_ROOT, "gubernator_tpu")
    expected = {
        # lease_window is the quota-lease column scatter (docs/leases.md
        # — distinct from _lease_matrix's staging-slab lease): one
        # batched dispatch per grant/sync window on the serving path.
        # pack_wide_rows/pack_cols_req32/join_i32_pair are the host-side
        # column packers the call graph proves reachable from submit —
        # transitive G001 guards their bodies, so they carry the marker.
        "gubernator_tpu/ops/engine.py": [
            "_build_cols", "_build_cols_numpy", "_lease_matrix",
            "_promote_misses",
            "submit_columns", "submit_cols", "submit", "lease_window",
            "pack_wide_rows", "pack_cols_req32", "join_i32_pair"],
        # The sharded serving path: resolve + the ragged flat dispatch
        # (the ONE serving format) run per serving window.
        # _dispatch_relayout/_cutover are the reshard transition's
        # bounded window (docs/resharding.md): every serving window is
        # frozen behind them, so G001 keeps them sync- and I/O-free.
        "gubernator_tpu/parallel/mesh_engine.py": [
            "submit_columns", "submit_cols", "submit",
            "_gregorian_cols", "_resolve_columns",
            "_resolve_columns_locked", "_account_misses",
            "_dispatch_ragged",
            "_dispatch_relayout", "_cutover"],
        "gubernator_tpu/service/tickloop.py": ["_run", "_flush"],
        # Overload control plane (docs/overload.md): queue admission,
        # window pops, and limiter feedback all run per serving window.
        "gubernator_tpu/admission/queue.py": ["push", "pop_window"],
        "gubernator_tpu/admission/limiter.py": ["record"],
        # Zero-copy ingest edge: the wire decode/encode and the arena
        # lease (plus its bounded-fallback accounting) run once per
        # serving window too.
        "gubernator_tpu/ops/reqcols.py": ["lease", "try_fallback"],
        "gubernator_tpu/transport/fastwire.py": ["parse_req",
                                                 "encode_resp"],
        # Telemetry plane (docs/observability.md): the flight recorder's
        # record path runs inside every instrumented serving window.
        "gubernator_tpu/utils/flightrec.py": ["begin", "note", "finish"],
        # Multi-process edge (docs/edge.md): the SPSC slab handoff and
        # the owner's drain both run once per published window — G001's
        # sync/file-syscall arms must keep them lock- and I/O-free.
        "gubernator_tpu/edge/shmring.py": ["publish", "pop_published"],
        "gubernator_tpu/edge/plane.py": ["_drain_once"],
        # SSD tier (docs/tiering.md): demote staging and the miss-path
        # batched lookup run on the dispatch thread — the file-syscall
        # arm of G001 keeps slab I/O on the background writer.
        "gubernator_tpu/tiering/ssd.py": ["put_columns", "take_batch"],
        # Algorithm zoo (docs/algorithms.md): the N-way policy fold and
        # each per-lane transition run inside every device tick — G001
        # keeps them sync-free, G006 keeps them retrace-free.
        "gubernator_tpu/algos/table.py": ["zoo_transitions"],
        "gubernator_tpu/algos/sliding_window.py": ["transition"],
        "gubernator_tpu/algos/gcra.py": ["transition"],
        "gubernator_tpu/algos/concurrency.py": ["transition"],
        # The branchless zoo mask runs inside submit's packing path.
        "gubernator_tpu/algos/__init__.py": ["invalid_algorithm_mask"],
    }
    for path, names in expected.items():
        text = proj.by_path[path].text
        for name in names:
            assert (
                f"@hot_path\n    def {name}(" in text
                or f"@hot_path\ndef {name}(" in text
            ), f"{path}: {name} lost its @hot_path marker"


def test_all_ten_rules_registered():
    assert sorted(RULES) == ["G001", "G002", "G003", "G004", "G005",
                             "G006", "G007", "G008", "G009", "G010"]
    for r in RULES.values():
        assert r.title and r.description and r.fix_hint


# ----------------------------------------------------------------------
# CLI + the no-jax property
# ----------------------------------------------------------------------
def test_cli_exits_zero_on_repo_and_imports_no_jax():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from gubernator_tpu.analysis.__main__ import main\n"
         "rc = main(['--root', sys.argv[1]])\n"
         "assert 'jax' not in sys.modules, 'linter imported jax'\n"
         "sys.exit(rc)\n",
         REPO_ROOT],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stdout + out.stderr


def test_cli_exits_nonzero_on_injected_finding(tmp_path):
    make_project(tmp_path, {
        "bad.py": "import asyncio\n\ndef f(c):\n"
                  "    asyncio.create_task(c())\n"
    })
    out = subprocess.run(
        [sys.executable, "-m", "gubernator_tpu.analysis",
         "--root", str(tmp_path), "--package", "pkg"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
    )
    assert out.returncode == 1, out.stdout + out.stderr
    assert "G003" in out.stdout


@pytest.mark.parametrize("rule", ["G001", "G002", "G003", "G004",
                                  "G005", "G006", "G007", "G008",
                                  "G009", "G010"])
def test_each_rule_fixture_fails_the_cli(tmp_path, rule):
    """Acceptance: injecting any rule's positive fixture into a clean
    project makes the CLI exit nonzero."""
    fixture = {
        "G001": G001_POS,
        "G002": "async def f(self):\n    import time\n"
                "    time.sleep(1)\n",
        "G003": "import asyncio\n\ndef f(c):\n"
                "    asyncio.create_task(c())\n",
        "G004": "import os\nX = os.environ.get('GUBER_GOOD_KNOB')\n",
        "G005": None,
        "G006": "import jax, time\n\n@jax.jit\ndef f(x):\n"
                "    return x + time.time()\n",
        "G007": "import threading, time\n\nclass S:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "    def f(self):\n"
                "        with self._lock:\n"
                "            time.sleep(1)\n",
        "G008": "import threading\n\nclass P:\n"
                "    def __init__(self):\n"
                "        self._lock1 = threading.Lock()\n"
                "        self._lock2 = threading.Lock()\n"
                "    def ab(self):\n"
                "        with self._lock1:\n"
                "            with self._lock2:\n"
                "                pass\n"
                "    def ba(self):\n"
                "        with self._lock2:\n"
                "            with self._lock1:\n"
                "                pass\n",
        "G009": "import threading\n\nclass C:\n"
                "    def __init__(self):\n"
                "        self.n = 0\n"
                "        self._t = threading.Thread(target=self._run)\n"
                "    def _run(self):\n"
                "        self.n += 1\n"
                "    def read(self):\n"
                "        return self.n\n",
        "G010": "class Req:\n    deadline: float = 0.0\n\n\n"
                "def spawn_supervised(factory):\n    return factory\n\n\n"
                "class M:\n"
                "    def __init__(self):\n"
                "        self._q = {}\n"
                "        spawn_supervised(self._loop)\n"
                "    async def _loop(self):\n"
                "        self._q.clear()\n"
                "    def put(self, r: Req):\n"
                "        self._q[0] = r\n",
    }[rule]
    files = {"bad.py": fixture} if fixture else {}
    kw = {}
    if rule == "G005":
        kw = {"metrics": METRICS_SRC,
              "prometheus": PROM_DOC + "| `gubernator_ghost` | C |\n"}
    proj = make_project(tmp_path, files, **kw)
    res = run_project(proj, rule_ids=[rule])
    assert res.findings, f"{rule} fixture produced no findings"
