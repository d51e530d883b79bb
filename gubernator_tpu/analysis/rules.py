"""The core guberlint rules (G001-G006), each grounded in a bug class
this repo has already shipped and hand-fixed at least once.  The
concurrency rules (G007-G010) live in analysis/concurrency.py.

All rules are pure AST walks — no imports of the inspected modules.
Since guberlint v2, G001 and G002 are *transitive*: the package call
graph (analysis/callgraph.py) propagates @hot_path and async-context
taint through resolved callees, so a primitive hidden one call deep in
a helper flags at the call site.  Where static truth is unreachable (is
this ``asarray`` argument a device buffer or host numpy?) the rules err
toward flagging inside an explicitly marked scope and let the author
answer with a reason-carrying ``# guber: allow-…`` comment; an
invariant you have to argue for in writing is the point.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from gubernator_tpu.analysis.core import Finding, Project, Rule, register
from gubernator_tpu.analysis.callgraph import (
    CallGraph,
    FuncInfo,
    decorator_names,
    first_primitive,
    iter_stmts_skip_nested,
)
from gubernator_tpu.analysis.concurrency import (
    blocking_call_label,
    line_allowed,
)

# ----------------------------------------------------------------------
# Shared AST helpers
# ----------------------------------------------------------------------


def qual_name(node: ast.AST) -> str:
    """Dotted name of a Name/Attribute chain ('' when not a plain chain):
    ``os.environ.get`` → "os.environ.get"."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def walk_skip_nested(body: Iterable[ast.stmt]) -> Iterable[ast.AST]:
    """Walk statements without descending into nested function/lambda
    bodies: nested defs run at some other time, under some other
    discipline (a resolver callback, an executor thunk) — and every
    function gets its own visit from the enclosing rule's loop anyway."""
    stack: List[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


# ----------------------------------------------------------------------
# G001 — device sync primitive in a @hot_path function
# ----------------------------------------------------------------------
# The per-tick serving path (dispatch threads: TickLoop._run/_flush,
# TickEngine submit/_build_cols, the mesh twin) must queue device work
# and NEVER materialize it — per-request D2H is the exact regression the
# fused-tick architecture exists to avoid (BASELINE.md; the one-dispatch
# tests of tier-1 hold the counts, this rule gates the source).
# Functions opt in with @hot_path (gubernator_tpu/utils/hotpath.py);
# the decorator is the documented contract, the rule is its enforcement.

_G001_CALLS = {
    "jax.device_get": "jax.device_get",
    "jax.block_until_ready": "jax.block_until_ready",
}
_G001_ASARRAY_BASES = {"np", "numpy", "onp"}
# Blocking file syscalls are the SSD-tier twin of a device sync: a
# per-tick open()/fsync()/mmap() stalls the dispatch thread on storage
# latency instead of PCIe.  Slab I/O belongs on the background writer
# (SsdStore._writer_loop) or in non-hot helpers (_map_slab).
_G001_FILE_CALLS = {"open", "os.open", "os.fsync", "mmap.mmap"}


def _g001_match(node: ast.Call, q: str,
                canonical: str) -> Optional[Tuple[str, bool]]:
    """(label, is_blocking_syscall) when this call is a G001 primitive:
    a device sync, or a thread-blocking syscall (file I/O, sleep,
    socket send/recv, blocking queue put/get, subprocess)."""
    if q in _G001_CALLS:
        return q, False
    if q in _G001_FILE_CALLS or canonical in _G001_FILE_CALLS:
        return f"{q or canonical}()", True
    if q.split(".")[-1] == "block_until_ready":
        return (q or ".block_until_ready()"), False
    if (
        q.split(".")[-1] in ("asarray", "array")
        and q.split(".")[0] in _G001_ASARRAY_BASES
    ):
        return q, False
    if (
        isinstance(node.func, ast.Attribute)
        and node.func.attr == "item"
        and not node.args
    ):
        return ".item()", False
    if (
        isinstance(node.func, ast.Name)
        and node.func.id in ("float", "bool")
        and len(node.args) == 1
        and not isinstance(node.args[0], ast.Constant)
    ):
        return f"{node.func.id}()", False
    # Blocking-syscall family (socket send/recv, blocking Queue.put/get,
    # subprocess, sleep): the edge drain path's gap — any of these on a
    # dispatch thread is a per-tick stall exactly like an fsync.
    label = blocking_call_label(node, q.split(".") if q else [], canonical)
    if label is not None:
        return label, True
    return None


def _is_hot(fi: FuncInfo) -> bool:
    return "hot_path" in decorator_names(fi.node)


def _g001(project: Project) -> Iterable[Finding]:
    hint = ("queue the device work and materialize it on the resolver "
            "side (TickHandle.result / resolve_ticks), or move this off "
            "the per-tick path")
    io_hint = ("blocking syscalls belong on the SSD tier's background "
               "writer (SsdStore._writer_loop) or in a non-hot helper, "
               "never inline on the dispatch thread")
    cg = CallGraph.of(project)
    memo: Dict[str, object] = {}

    def direct(fi: FuncInfo) -> List[Tuple[int, str]]:
        """Primitive sites in one body, minus inline-allowed ones (a
        G001 allow at the primitive line covers every transitive
        caller)."""
        hits: List[Tuple[int, str]] = []
        for node in iter_stmts_skip_nested(fi.node.body):
            if not isinstance(node, ast.Call):
                continue
            q = qual_name(node.func)
            m = _g001_match(node, q, cg.canonical(node.func, fi))
            if m is not None and not line_allowed(fi.sf, node.lineno,
                                                  "G001"):
                hits.append((node.lineno, m[0]))
        return hits

    def skip(fi: FuncInfo) -> bool:
        # Hot-marked callees get their own direct visit; async callees
        # aren't *run* by a sync call expression.
        return _is_hot(fi) or fi.is_async

    for qname in sorted(cg.functions):
        fi = cg.functions[qname]
        if not _is_hot(fi):
            continue
        for node in iter_stmts_skip_nested(fi.node.body):
            if not isinstance(node, ast.Call):
                continue
            q = qual_name(node.func)
            m = _g001_match(node, q, cg.canonical(node.func, fi))
            if m is not None:
                bad, blocking = m
                if blocking:
                    yield Finding(
                        "G001", fi.sf.path, node.lineno,
                        f"blocking syscall {bad} inside @hot_path "
                        f"function '{fi.name}' — a per-tick stall on "
                        "the dispatch thread", io_hint,
                    )
                else:
                    yield Finding(
                        "G001", fi.sf.path, node.lineno,
                        f"device-sync primitive {bad} inside @hot_path "
                        f"function '{fi.name}' — a per-tick host/device "
                        "round trip", hint,
                    )
                continue
            # Transitive: taint propagates through resolved callees, so
            # a primitive one call deep in an unmarked helper flags at
            # this call site.
            r = cg.resolve_expr(node.func, fi)
            callee: Optional[FuncInfo] = None
            if r is not None and r[0] == "func":
                callee = r[1]
            elif r is not None and r[0] == "class":
                callee = cg.class_method(r[1], "__init__")
            if callee is None or callee.qname == fi.qname or skip(callee):
                continue
            sub = first_primitive(cg, callee, direct, memo, skip)
            if sub is not None:
                yield Finding(
                    "G001", fi.sf.path, node.lineno,
                    f"@hot_path function '{fi.name}' reaches "
                    f"{sub.describe()} — the helper runs on the "
                    "dispatch thread and stalls it exactly like an "
                    "inline sync",
                    "mark the helper @hot_path and fix it, or move the "
                    "primitive off the per-tick path (an allow-comment "
                    "at the primitive's own line covers all callers)",
                )


register(Rule(
    "G001", "hot-path device sync / blocking syscall",
    "np.asarray / .item() / float()/bool() / block_until_ready / "
    "jax.device_get, or a thread-blocking syscall (open / os.fsync / "
    "mmap.mmap / time.sleep / socket send-recv / blocking Queue "
    "put-get / subprocess), inside — or transitively reachable from — "
    "a @hot_path serving function.",
    "Dispatch, don't materialize: syncs belong on the resolver side, "
    "blocking I/O on the SSD tier's background writer.",
    _g001,
))


# ----------------------------------------------------------------------
# G002 — blocking under a held lock / blocking in async
# ----------------------------------------------------------------------
_LOCKISH = re.compile(r"(^|_)(lock|cond|mutex|sem)[a-z0-9]*$", re.I)
_G002_BLOCKING = {"time.sleep", "os.fsync", "os.fdatasync"}


def _lockish_ctx(expr: ast.AST) -> bool:
    """Heuristic: the with-item looks like a threading lock/condition —
    terminal name segment lock/cond/mutex-ish, or a direct
    threading.Lock()/RLock()/Condition() call."""
    if isinstance(expr, ast.Call):
        q = qual_name(expr.func)
        if q.split(".")[-1] in ("Lock", "RLock", "Condition", "Semaphore",
                                "BoundedSemaphore"):
            return True
        expr = expr.func
    q = qual_name(expr)
    return bool(q) and bool(_LOCKISH.search(q.split(".")[-1]))


def _g002_blocking_q(q: str, canonical: str) -> bool:
    return (
        q in _G002_BLOCKING or canonical in _G002_BLOCKING
        or q in ("open", "io.open") or canonical in ("open", "io.open")
    )


def _g002(project: Project) -> Iterable[Finding]:
    cg = CallGraph.of(project)
    memo: Dict[str, object] = {}

    def direct(fi: FuncInfo) -> List[Tuple[int, str]]:
        hits: List[Tuple[int, str]] = []
        for node in iter_stmts_skip_nested(fi.node.body):
            if not isinstance(node, ast.Call):
                continue
            q = qual_name(node.func)
            if _g002_blocking_q(q, cg.canonical(node.func, fi)) and \
                    not line_allowed(fi.sf, node.lineno, "G002"):
                hits.append((node.lineno, q or "(call)"))
        return hits

    def skip(fi: FuncInfo) -> bool:
        return fi.is_async  # awaited callees carry their own async taint

    for sf in project.files:
        if sf.tree is None:
            continue
        for fn in functions(sf.tree):
            # (a) await while holding a (threading) lock: the event loop
            # parks this coroutine with the lock held; every thread that
            # then touches the lock — the tick loop, the reclaimer —
            # deadlocks behind a suspended coroutine.
            if isinstance(fn, ast.AsyncFunctionDef):
                for node in walk_skip_nested(fn.body):
                    if not isinstance(node, ast.With):
                        continue
                    if not any(
                        _lockish_ctx(it.context_expr) for it in node.items
                    ):
                        continue
                    for inner in walk_skip_nested(node.body):
                        if isinstance(inner, ast.Await):
                            yield Finding(
                                "G002", sf.path, inner.lineno,
                                f"await inside a held lock in "
                                f"'{fn.name}' — the coroutine parks "
                                "with the lock held and wedges every "
                                "thread behind it",
                                "release the lock before awaiting, or "
                                "make the critical section synchronous "
                                "and run it in an executor",
                            )
                # (b) blocking sync calls on the event loop: fsync and
                # friends stall EVERY coroutine (ticks, health probes,
                # peer RPCs) for the duration.  Transitive since v2: a
                # sync helper that opens/sleeps/fsyncs taints its async
                # callers through the call graph.
                for node in walk_skip_nested(fn.body):
                    if not isinstance(node, ast.Call):
                        continue
                    q = qual_name(node.func)
                    scope = cg.func_of(fn)
                    canonical = (cg.canonical(node.func, scope)
                                 if scope is not None else "")
                    if _g002_blocking_q(q, canonical):
                        yield Finding(
                            "G002", sf.path, node.lineno,
                            f"blocking call {q or '(call)'}() inside "
                            f"async def '{fn.name}' stalls the event "
                            "loop",
                            "await loop.run_in_executor(None, fn) or "
                            "asyncio.to_thread(fn) — see "
                            "persistence/writer.py",
                        )
                        continue
                    if scope is None:
                        continue
                    r = cg.resolve_expr(node.func, scope)
                    callee: Optional[FuncInfo] = None
                    if r is not None and r[0] == "func":
                        callee = r[1]
                    if callee is None or callee.is_async or \
                            callee.qname == scope.qname:
                        continue
                    sub = first_primitive(cg, callee, direct, memo, skip)
                    if sub is not None:
                        yield Finding(
                            "G002", sf.path, node.lineno,
                            f"async def '{fn.name}' reaches blocking "
                            f"{sub.describe()} — the helper runs on "
                            "the event loop and stalls every "
                            "coroutine",
                            "run the sync helper in an executor "
                            "(asyncio.to_thread), or move the blocking "
                            "primitive out of it",
                        )


register(Rule(
    "G002", "blocking under lock / blocking in async",
    "await while a threading lock is held, or time.sleep/os.fsync/raw "
    "file IO directly inside an async def.",
    "Blocking work belongs in an executor; locks release before awaits.",
    _g002,
))


# ----------------------------------------------------------------------
# G003 — fire-and-forget asyncio tasks
# ----------------------------------------------------------------------
_SPAWN_TAILS = ("create_task", "ensure_future")


def _g003(project: Project) -> Iterable[Finding]:
    hint = ("keep the handle: store it in a tracked set with an "
            "add_done_callback that logs exceptions (the "
            "V1Instance._peer_shutdown_tasks pattern), await it, or use "
            "resilience.spawn_supervised for loops")
    for sf in project.files:
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            call: Optional[ast.Call] = None
            if isinstance(node, ast.Expr) and isinstance(node.value,
                                                         ast.Call):
                call = node.value
            elif (
                isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and all(
                    isinstance(t, ast.Name) and t.id == "_"
                    for t in node.targets
                )
            ):
                call = node.value
            if call is None:
                continue
            q = qual_name(call.func)
            if q.split(".")[-1] not in _SPAWN_TAILS:
                continue
            yield Finding(
                "G003", sf.path, call.lineno,
                f"fire-and-forget task: {q}(...) discards its handle — "
                "the task can be GC'd mid-flight and its exception is "
                "silently swallowed", hint,
            )


register(Rule(
    "G003", "fire-and-forget tasks",
    "asyncio.create_task/ensure_future whose handle is discarded "
    "(bare statement or assigned to _).",
    "Track the task and log its exceptions on completion.",
    _g003,
))


# ----------------------------------------------------------------------
# G004 — GUBER_* env discipline
# ----------------------------------------------------------------------
_ENV_NAME = re.compile(r"^GUBER_[A-Z0-9]+(?:_[A-Z0-9]+)*$")


def _registry_names(project: Project) -> Optional[Set[str]]:
    """Keys of the ENV_REGISTRY dict literal in config.py (the single
    source of truth for the supported env surface)."""
    sf = project.by_path.get(project.config_path)
    if sf is None or sf.tree is None:
        return None
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        else:
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == "ENV_REGISTRY"
            for t in targets
        ):
            continue
        if isinstance(node.value, ast.Dict):
            return {
                s for k in node.value.keys
                if (s := str_const(k)) is not None
            }
    return None


def _env_read_literal(call: ast.Call) -> Optional[str]:
    """GUBER_* literal read directly from the process environment:
    os.environ.get("X") / os.getenv("X")."""
    q = qual_name(call.func)
    if q in ("os.environ.get", "os.getenv", "getenv") and call.args:
        s = str_const(call.args[0])
        if s and _ENV_NAME.match(s):
            return s
    return None


def _g004(project: Project) -> Iterable[Finding]:
    registry = _registry_names(project)
    if registry is None:
        yield Finding(
            "G004", project.config_path, 1,
            "config.py must define the ENV_REGISTRY dict literal — the "
            "single source of truth for the GUBER_* env surface",
            "declare ENV_REGISTRY: Dict[str, str] = {\"GUBER_…\": "
            "\"description\", …}",
        )
        return

    # (a) ad-hoc process-env reads outside config.py.  The registry's
    # typed accessors (env_knob / EnvReader) exist so every knob is
    # registered, validated, and documented in one place.
    for sf in project.files:
        if sf.tree is None or sf.path == project.config_path:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call):
                name = _env_read_literal(node)
                if name:
                    yield Finding(
                        "G004", sf.path, node.lineno,
                        f"direct os.environ read of {name} bypasses the "
                        "config registry",
                        "use gubernator_tpu.config.env_knob(name, "
                        "default, parse=…) — registered, validated, "
                        "documented",
                    )
            elif (
                isinstance(node, ast.Subscript)
                and isinstance(node.ctx, ast.Load)
                and qual_name(node.value) == "os.environ"
            ):
                s = str_const(node.slice)
                if s and _ENV_NAME.match(s):
                    yield Finding(
                        "G004", sf.path, node.lineno,
                        f"direct os.environ[{s!r}] read bypasses the "
                        "config registry",
                        "use gubernator_tpu.config.env_knob",
                    )

    # (b) every GUBER_* name mentioned in code must be registered —
    # names ending in '_' are prefix-family mentions (GUBER_FAULT_*) and
    # don't count.
    for sf in project.files:
        if sf.tree is None:
            continue
        seen_lines: Set[Tuple[str, int]] = set()
        for node in ast.walk(sf.tree):
            s = str_const(node)
            if not s or not _ENV_NAME.match(s) or s in registry:
                continue
            key = (s, node.lineno)
            if key in seen_lines:
                continue
            seen_lines.add(key)
            yield Finding(
                "G004", sf.path, node.lineno,
                f"unregistered env var name {s} — not a key of "
                "config.ENV_REGISTRY",
                "register it (name → one-line description) in "
                "config.ENV_REGISTRY and document it in example.conf",
            )

    # (c/d) registry ↔ example.conf, both directions.
    conf_text = project.read_text(project.example_conf_path)
    if conf_text is None:
        yield Finding(
            "G004", project.example_conf_path, 1,
            "example.conf is missing — every registered knob must be "
            "documented there",
            "restore example.conf",
        )
        return
    conf_names = {
        m for m in re.findall(r"GUBER_[A-Z0-9_]+", conf_text)
        if _ENV_NAME.match(m)
    }
    sf = project.by_path[project.config_path]
    reg_line = 1
    for i, ln in enumerate(sf.lines, 1):
        if "ENV_REGISTRY" in ln:
            reg_line = i
            break
    for name in sorted(registry - conf_names):
        yield Finding(
            "G004", project.config_path, reg_line,
            f"{name} is registered but not documented in example.conf",
            "add a commented example entry to example.conf",
        )
    for name in sorted(conf_names - registry):
        yield Finding(
            "G004", project.example_conf_path, 1,
            f"{name} appears in example.conf but is not registered in "
            "config.ENV_REGISTRY",
            "register it or remove the stale documentation",
        )


register(Rule(
    "G004", "env discipline",
    "Every GUBER_* env var is registered in config.ENV_REGISTRY, read "
    "through it, and documented in example.conf.",
    "One registry; no ad-hoc os.environ reads.",
    _g004,
))


# ----------------------------------------------------------------------
# G005 — metric catalog ↔ docs/prometheus.md sync
# ----------------------------------------------------------------------
_METRIC_CTORS = {"Counter", "Gauge", "Summary", "Histogram"}
_METRIC_NAME = re.compile(r"^gubernator[a-z0-9_]*$")


def _g005(project: Project) -> Iterable[Finding]:
    sf = project.by_path.get(project.metrics_path)
    if sf is None or sf.tree is None:
        return
    code_names: Dict[str, int] = {}
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        if qual_name(node.func).split(".")[-1] not in _METRIC_CTORS:
            continue
        if not node.args:
            continue
        name = str_const(node.args[0])
        if not name or not _METRIC_NAME.match(name):
            continue
        if name in code_names:
            yield Finding(
                "G005", sf.path, node.lineno,
                f"duplicate metric family {name} (first defined on "
                f"line {code_names[name]})",
                "one family per name; reuse the existing attribute",
            )
            continue
        code_names[name] = node.lineno
    doc_text = project.read_text(project.prometheus_doc_path)
    if doc_text is None:
        yield Finding(
            "G005", project.prometheus_doc_path, 1,
            "docs/prometheus.md is missing — the metric catalog must be "
            "documented",
            "restore docs/prometheus.md",
        )
        return
    doc_names: Dict[str, int] = {}
    for i, ln in enumerate(doc_text.splitlines(), 1):
        if not ln.lstrip().startswith("|"):
            continue  # only catalog table rows count; prose may cite
            # derived series like _count/_sum
        for m in re.finditer(r"`(gubernator[a-z0-9_]*)`", ln):
            doc_names.setdefault(m.group(1), i)
    for name in sorted(set(code_names) - set(doc_names)):
        yield Finding(
            "G005", sf.path, code_names[name],
            f"metric {name} is registered in code but missing from "
            "docs/prometheus.md",
            "add a table row to docs/prometheus.md",
        )
    for name in sorted(set(doc_names) - set(code_names)):
        yield Finding(
            "G005", project.prometheus_doc_path, doc_names[name],
            f"metric {name} is documented but not registered in "
            f"{project.metrics_path}",
            "remove the stale row or register the family",
        )


register(Rule(
    "G005", "metric registry sync",
    "Prometheus family names in utils/metrics.py and docs/prometheus.md "
    "must match exactly, both directions, with no duplicates.",
    "The docs table IS the catalog; keep it generated from the code.",
    _g005,
))


# ----------------------------------------------------------------------
# G006 — trace purity inside jit / shard_map functions
# ----------------------------------------------------------------------
_G006_IMPURE = {
    "time.time", "time.monotonic", "time.perf_counter", "time.time_ns",
    "datetime.now", "datetime.utcnow", "datetime.datetime.now",
    "datetime.datetime.utcnow", "os.getenv", "print",
}
_G006_IMPURE_PREFIX = ("random.", "np.random.", "numpy.random.")
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "sharding", "at"}


def _traced_functions(tree: ast.AST):
    """(function node, reason) for every function we can statically see
    being traced: decorated with @jit/@jax.jit (directly or via
    partial), or passed by name/lambda to jit()/shard_map()."""
    defs: Dict[str, List[ast.AST]] = {}
    for fn in functions(tree):
        defs.setdefault(fn.name, []).append(fn)

    def is_jit_name(node: ast.AST) -> bool:
        q = qual_name(node)
        return q in ("jit", "jax.jit", "pjit", "jax.pjit", "shard_map",
                     "jax.experimental.shard_map.shard_map")

    traced: List[Tuple[ast.AST, str]] = []
    for fn in functions(tree):
        for d in fn.decorator_list:
            if is_jit_name(d):
                traced.append((fn, qual_name(d)))
            elif isinstance(d, ast.Call):
                if is_jit_name(d.func):
                    traced.append((fn, qual_name(d.func)))
                elif (
                    qual_name(d.func).split(".")[-1] == "partial"
                    and d.args and is_jit_name(d.args[0])
                ):
                    traced.append((fn, qual_name(d.args[0])))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not is_jit_name(node.func):
            continue
        if not node.args:
            continue
        target = node.args[0]
        if isinstance(target, ast.Lambda):
            traced.append((target, qual_name(node.func)))
        elif isinstance(target, ast.Name):
            for fn in defs.get(target.id, []):
                traced.append((fn, qual_name(node.func)))
    return traced


def _value_dependent_param_use(test: ast.AST, params: Set[str]) -> bool:
    """True when the expression reads a traced parameter's VALUE (vs its
    static metadata: .shape/.dtype/len()/isinstance()/is-None)."""

    def visit(node: ast.AST) -> bool:
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return visit(node.value)
        if isinstance(node, ast.Call):
            q = qual_name(node.func)
            if q in ("len", "isinstance", "type", "id"):
                return False
            return any(visit(c) for c in ast.iter_child_nodes(node))
        if isinstance(node, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
        ):
            return False
        if isinstance(node, ast.Name):
            return node.id in params
        return any(visit(c) for c in ast.iter_child_nodes(node))

    return visit(test)


def _g006(project: Project) -> Iterable[Finding]:
    for sf in project.files:
        if sf.tree is None:
            continue
        seen: Set[Tuple[int, str]] = set()
        for fn, how in _traced_functions(sf.tree):
            if isinstance(fn, ast.Lambda):
                body: List[ast.AST] = [fn.body]
                name = "<lambda>"
                args = fn.args
            else:
                body = list(fn.body)
                name = fn.name
                args = fn.args
            params = {
                a.arg for a in (
                    args.posonlyargs + args.args + args.kwonlyargs
                )
            } - {"self", "cls"}
            # Traced bodies include nested defs: fori_loop/scan bodies
            # trace right along with their parent.
            stack = list(body)
            nodes: List[ast.AST] = []
            while stack:
                n = stack.pop()
                nodes.append(n)
                stack.extend(ast.iter_child_nodes(n))
            for node in nodes:
                if isinstance(node, ast.Call):
                    q = qual_name(node.func)
                    if q in _G006_IMPURE or any(
                        q.startswith(p) for p in _G006_IMPURE_PREFIX
                    ):
                        key = (node.lineno, q)
                        if key in seen:
                            continue
                        seen.add(key)
                        yield Finding(
                            "G006", sf.path, node.lineno,
                            f"impure call {q}() inside {how}-traced "
                            f"function '{name}' — evaluated once at "
                            "trace time, then frozen into the compiled "
                            "program",
                            "hoist it to the host caller and pass the "
                            "value in as an argument",
                        )
                elif (
                    isinstance(node, (ast.Attribute, ast.Subscript))
                    and qual_name(
                        node.value if isinstance(node, ast.Subscript)
                        else node
                    ) in ("os.environ",)
                ):
                    key = (node.lineno, "os.environ")
                    if key in seen:
                        continue
                    seen.add(key)
                    yield Finding(
                        "G006", sf.path, node.lineno,
                        f"os.environ access inside {how}-traced "
                        f"function '{name}' — read at trace time and "
                        "frozen",
                        "resolve the knob outside the traced function",
                    )
                elif isinstance(node, (ast.If, ast.While)):
                    if _value_dependent_param_use(node.test, params):
                        key = (node.lineno, "branch")
                        if key in seen:
                            continue
                        seen.add(key)
                        yield Finding(
                            "G006", sf.path, node.lineno,
                            f"Python-level branch on a traced value in "
                            f"{how}-traced function '{name}' — this "
                            "either fails to trace or silently "
                            "specializes on one concrete value",
                            "use jnp.where / jax.lax.cond / "
                            "jax.lax.select on device values",
                        )


register(Rule(
    "G006", "trace purity",
    "No time.time()/os.environ/random/print or Python-level branching "
    "on traced values inside functions passed to jit/shard_map.",
    "Traced functions see abstract values; host state must be an input.",
    _g006,
))
