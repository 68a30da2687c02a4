"""The framework lint rules (see package docstring for the contract).

Each rule is a function ``(FileContext) -> [LintFinding]`` registered
under its id; ids double as the allowlist-marker names
(``# lint: host-sync-ok``). Rules use only stdlib ``ast`` — the lint
must run in any environment, including ones where jax cannot import.
"""
from __future__ import annotations

import ast
import os
from typing import List, Optional, Set

from . import FileContext, LintFinding, rule

# ---------------------------------------------------------------- config

# Modules on the per-step hot path: one stray eager host read here is a
# pipeline stall under traffic. Anything else may sync freely.
HOST_SYNC_HOT_PATHS = frozenset({
    "paddle_tpu/jit/api.py",
    "paddle_tpu/distributed/fleet/train_step.py",
    "paddle_tpu/io/device_prefetch.py",
    "paddle_tpu/generation/api.py",
    "paddle_tpu/generation/kv_cache.py",
    "paddle_tpu/generation/paged_cache.py",
    "paddle_tpu/generation/attention.py",
    "paddle_tpu/generation/speculative.py",
    "paddle_tpu/hapi/model.py",
    "paddle_tpu/serving/engine.py",
    "paddle_tpu/serving/router.py",
})

# Files allowed to name metrics freely (the schema itself + the
# registry implementation and its re-export).
METRIC_NAME_EXEMPT = frozenset({
    "paddle_tpu/core/monitor.py",
    "paddle_tpu/core/metrics.py",
    "paddle_tpu/profiler/metrics.py",
})

_FAULT_INJECTION_MODULE = "paddle_tpu.utils.fault_injection"


def _dotted(node: ast.AST) -> str:
    """'np.random.randn' for an Attribute/Name chain, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


# ------------------------------------------------------------- host-sync

@rule("host-sync")
def check_host_sync(ctx: FileContext) -> List[LintFinding]:
    """Eager device->host reads in hot-path modules: ``.numpy()``,
    ``.item()``, ``float(tensor)``, ``np.asarray(tensor)``, and
    ``bool(<call>)`` (the ``bool(jnp.all(done))`` polling spelling)
    each block the dispatch queue. Deliberate sync points (the async
    loop's bounded loss fetch, generate()'s end-of-call transfer, the
    every-K-steps eos poll) carry ``# lint: host-sync-ok`` with a
    reason. Known limitation: ``bool(x)``/``int(x)`` on a BARE name
    can't be told apart from config coercion without type info, so
    only call/attribute arguments are flagged — reviewers should still
    eyeball truthiness tests of device arrays."""
    if ctx.relpath not in HOST_SYNC_HOT_PATHS:
        return []
    findings = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        label = None
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in ("numpy", "item") and not node.args:
            label = f".{node.func.attr}()"
        elif isinstance(node.func, ast.Name) and node.func.id == "float" \
                and len(node.args) == 1 \
                and not isinstance(node.args[0], ast.Constant):
            label = "float(...)"
        elif isinstance(node.func, ast.Name) and node.func.id == "bool" \
                and len(node.args) == 1 \
                and isinstance(node.args[0], (ast.Call, ast.Attribute)):
            label = "bool(...)"
        elif _dotted(node.func) in ("np.asarray", "numpy.asarray"):
            label = "np.asarray(...)"
        if label is None or ctx.allowed(node, "host-sync"):
            continue
        findings.append(LintFinding(
            ctx.relpath, node.lineno, node.col_offset, "host-sync",
            f"{label} in a hot-path module forces a host sync; move it "
            "off the per-step path or mark the line "
            "'# lint: host-sync-ok (reason)' if it is a deliberate "
            "sync point"))
    return findings


# ------------------------------------------------------------ jit-random

def _jitted_function_names(tree: ast.Module) -> Set[str]:
    """Names of functions that get jitted in this module: decorated
    with jit/to_static (any dotted spelling), or passed by name to a
    ``jax.jit(...)`` / ``jit(...)`` / ``to_static(...)`` call."""
    jit_entries = {"jit", "to_static"}
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                dotted = _dotted(target)
                if dotted.split(".")[-1] in jit_entries:
                    names.add(node.name)
                # functools.partial(jax.jit, ...) decorators
                if isinstance(dec, ast.Call) and dec.args and \
                        _dotted(dec.args[0]).split(".")[-1] in jit_entries:
                    names.add(node.name)
        elif isinstance(node, ast.Call):
            if _dotted(node.func).split(".")[-1] in jit_entries and \
                    node.args and isinstance(node.args[0], ast.Name):
                names.add(node.args[0].id)
    return names


@rule("jit-random")
def check_jit_randomness(ctx: FileContext) -> List[LintFinding]:
    """``np.random.*`` / stdlib ``random.*`` inside a function that
    gets jitted: the draw happens ONCE at trace time and is baked into
    the program as a constant — every execution replays it. Use
    ``jax.random`` with an explicit key (or draw outside the jitted
    function and pass the result in)."""
    jitted = _jitted_function_names(ctx.tree)
    if not jitted:
        return []
    findings = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or node.name not in jitted:
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            dotted = _dotted(sub.func)
            if not (dotted.startswith("np.random.")
                    or dotted.startswith("numpy.random.")
                    or dotted.startswith("random.")):
                continue
            if ctx.allowed(sub, "jit-random"):
                continue
            findings.append(LintFinding(
                ctx.relpath, sub.lineno, sub.col_offset, "jit-random",
                f"{dotted}() inside jitted function "
                f"'{node.name}' is drawn once at trace time and baked "
                "into the program; use jax.random with an explicit "
                "key"))
    return findings


# ----------------------------------------------------------- bare-except

@rule("bare-except")
def check_bare_except(ctx: FileContext) -> List[LintFinding]:
    """``except:`` that neither re-raises nor records through
    ``monitor.record_swallowed``: a silently swallowed error is how
    fault-tolerance bugs hide (PR 3 added the recorder precisely so
    deliberate swallows stay observable)."""
    findings = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler) or node.type is not None:
            continue
        ok = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Raise):
                ok = True
            elif isinstance(sub, ast.Call) and \
                    _dotted(sub.func).endswith("record_swallowed"):
                ok = True
        if ok or ctx.allowed(node, "bare-except"):
            continue
        findings.append(LintFinding(
            ctx.relpath, node.lineno, node.col_offset, "bare-except",
            "bare 'except:' without re-raise or "
            "monitor.record_swallowed(...): swallow observably (catch "
            "a concrete exception type, or record the swallow)"))
    return findings


# ----------------------------------------------------------- metric-name

_DECLARED_METRICS_CACHE: Optional[Set[str]] = None


def _declared_metrics() -> Set[str]:
    """The DECLARED_METRICS literal parsed out of core/monitor.py (AST
    only — the lint never imports the framework)."""
    global _DECLARED_METRICS_CACHE
    if _DECLARED_METRICS_CACHE is not None:
        return _DECLARED_METRICS_CACHE
    from . import repo_root  # lazy: repo_root is defined after the
    #                          rules module is imported by __init__
    monitor_path = os.path.join(repo_root(), "paddle_tpu", "core",
                                "monitor.py")
    declared: Set[str] = set()
    try:
        with open(monitor_path, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "DECLARED_METRICS"
                    for t in node.targets):
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Constant) and \
                            isinstance(sub.value, str):
                        declared.add(sub.value)
    except OSError:
        pass
    _DECLARED_METRICS_CACHE = declared
    return declared


@rule("metric-name")
def check_metric_names(ctx: FileContext) -> List[LintFinding]:
    """Literal metric names passed to ``metrics.counter/gauge/
    histogram`` in the framework must be declared in
    ``core/monitor.DECLARED_METRICS``: an undeclared name is either a
    typo (the real counter stays 0 forever) or schema drift nobody can
    dashboard against."""
    if not ctx.relpath.startswith("paddle_tpu/") \
            or ctx.relpath in METRIC_NAME_EXEMPT or ctx.is_test_file:
        return []
    declared = _declared_metrics()
    if not declared:
        return []  # monitor.py unreadable: never cascade bogus findings
    findings = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("counter", "gauge", "histogram")
                and _dotted(node.func.value).split(".")[-1] == "metrics"):
            continue
        if not (node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue  # dynamic names are the recorders' business
        name = node.args[0].value
        if name in declared or ctx.allowed(node, "metric-name"):
            continue
        findings.append(LintFinding(
            ctx.relpath, node.lineno, node.col_offset, "metric-name",
            f"metric {name!r} is not declared in "
            "core/monitor.DECLARED_METRICS; declare it there (with a "
            "docstring entry) or fix the typo"))
    return findings


# ------------------------------------------------------------- event-name

# the module that declares the event schema (and implements the ring):
# free to name events as it likes
_EVENT_NAME_EXEMPT = frozenset({"paddle_tpu/core/flight_recorder.py"})

# (DECLARED_EVENTS, DECLARED_SPANS) as parsed
_DECLARED_EVENTS_CACHE: Optional[tuple] = None


def _declared_events() -> tuple:
    """The DECLARED_EVENTS set literal and the keys of the
    DECLARED_SPANS table, parsed out of core/flight_recorder.py (AST
    only, the _declared_metrics precedent)."""
    global _DECLARED_EVENTS_CACHE
    if _DECLARED_EVENTS_CACHE is not None:
        return _DECLARED_EVENTS_CACHE
    from . import repo_root
    fr_path = os.path.join(repo_root(), "paddle_tpu", "core",
                           "flight_recorder.py")
    events: Set[str] = set()
    spans: Set[str] = set()
    try:
        with open(fr_path, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            names = {t.id for t in node.targets
                     if isinstance(t, ast.Name)}
            if "DECLARED_EVENTS" in names:
                events.update(
                    sub.value for sub in ast.walk(node.value)
                    if isinstance(sub, ast.Constant)
                    and isinstance(sub.value, str))
            elif "DECLARED_SPANS" in names and \
                    isinstance(node.value, ast.Dict):
                spans.update(
                    k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str))
    except OSError:
        pass
    _DECLARED_EVENTS_CACHE = (events, spans)
    return _DECLARED_EVENTS_CACHE


@rule("event-name")
def check_event_names(ctx: FileContext) -> List[LintFinding]:
    """Literal event names passed to ``flight_recorder.record(...)``
    in the framework must be declared in
    ``core/flight_recorder.DECLARED_EVENTS``, and literal span names
    passed to ``flight_recorder.span / record_span(...)`` or
    ``Request.stage_span(...)`` in its ``DECLARED_SPANS`` table: an
    undeclared name is a stream no post-mortem tooling (and no
    benchmark reader) greps for and no docs/events.md row explains
    (the DECLARED_METRICS contract, applied to the black box). The
    sampled per-request segments (``Request.span``, an f-string name)
    and dynamic ``record(kind_var)`` names are the recorders'
    business, same as metric-name."""
    if not ctx.relpath.startswith("paddle_tpu/") \
            or ctx.relpath in _EVENT_NAME_EXEMPT or ctx.is_test_file:
        return []
    events, spans = _declared_events()
    if not events:
        return []  # flight_recorder.py unreadable: no bogus cascade
    findings = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        attr = node.func.attr
        on_recorder = _dotted(node.func.value).split(".")[-1] \
            .lstrip("_") == "flight_recorder"
        if attr == "record" and on_recorder:
            declared, table = events, "DECLARED_EVENTS"
        elif attr == "stage_span" or (
                on_recorder and attr in ("span", "record_span")):
            declared, table = spans, "DECLARED_SPANS"
        else:
            continue
        if not (node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        name = node.args[0].value
        if name in declared or ctx.allowed(node, "event-name"):
            continue
        kind = "event" if table == "DECLARED_EVENTS" else "span"
        findings.append(LintFinding(
            ctx.relpath, node.lineno, node.col_offset, "event-name",
            f"flight-recorder {kind} {name!r} is not declared in "
            f"core/flight_recorder.{table}; declare it there (with "
            "its one-line description) or fix the typo"))
    return findings


# ------------------------------------------------------------ dead-metric

_RECORDED_NAMES_CACHE = None  # (literals: Set[str], patterns: List[regex])


def _recording_calls(tree: ast.Module):
    """(literal names, f-string regexes) from every ``metrics.counter/
    gauge/histogram(...)`` first argument in one module. F-string names
    (``f"{target}.compile"``) become anchored regexes with ``.+`` at
    each formatted field, so dynamically-prefixed recordings still
    count as live."""
    import re
    literals: Set[str] = set()
    patterns = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("counter", "gauge", "histogram")
                and _dotted(node.func.value).split(".")[-1] == "metrics"):
            continue
        if not node.args:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            literals.add(arg.value)
        elif isinstance(arg, ast.JoinedStr):
            parts = []
            for v in arg.values:
                if isinstance(v, ast.Constant):
                    parts.append(re.escape(str(v.value)))
                else:
                    parts.append(".+")
            patterns.append(re.compile("^" + "".join(parts) + "$"))
    return literals, patterns


def _recorded_names():
    """Every metric name recorded anywhere under paddle_tpu/ (scanned
    once per process, stdlib ast only)."""
    global _RECORDED_NAMES_CACHE
    if _RECORDED_NAMES_CACHE is not None:
        return _RECORDED_NAMES_CACHE
    from . import repo_root
    literals: Set[str] = set()
    patterns: list = []
    pkg = os.path.join(repo_root(), "paddle_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames
                       if d != "__pycache__" and not d.startswith(".")]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            try:
                with open(os.path.join(dirpath, fn), "r",
                          encoding="utf-8") as f:
                    tree = ast.parse(f.read())
            except (OSError, SyntaxError):
                continue
            lit, pat = _recording_calls(tree)
            literals |= lit
            patterns += pat
    _RECORDED_NAMES_CACHE = (literals, patterns)
    return _RECORDED_NAMES_CACHE


@rule("dead-metric")
def check_dead_metrics(ctx: FileContext) -> List[LintFinding]:
    """Every name in ``DECLARED_METRICS`` must be RECORDED somewhere
    under ``paddle_tpu/`` (a ``metrics.counter/gauge/histogram`` call,
    literal or f-string first arg — the same AST machinery as
    ``metric-name``, pointed the other way). A declared-but-never-
    recorded name is schema rot: dashboards and docs promise a series
    that will sit at zero forever. Fires on the module that declares
    the schema (``DECLARED_METRICS`` assignment in a paddle_tpu core
    module), so the finding lands on the stale declaration line."""
    if not ctx.relpath.startswith("paddle_tpu/core/") \
            or ctx.is_test_file:
        return []
    declared_nodes = []  # (name, lineno, col)
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "DECLARED_METRICS"
                for t in node.targets):
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Constant) and \
                        isinstance(sub.value, str):
                    declared_nodes.append(
                        (sub.value, sub.lineno, sub.col_offset))
    if not declared_nodes:
        return []
    literals, patterns = _recorded_names()
    # the declaring module's own recorders count too (snippet tests
    # lint a synthetic monitor.py that is not under the real package)
    own_lit, own_pat = _recording_calls(ctx.tree)
    literals = literals | own_lit
    patterns = patterns + own_pat
    findings = []
    for name, line, col in declared_nodes:
        if name in literals or any(p.match(name) for p in patterns):
            continue
        node = ast.Constant(value=name)
        node.lineno, node.col_offset, node.end_lineno = line, col, line
        if ctx.allowed(node, "dead-metric"):
            continue
        findings.append(LintFinding(
            ctx.relpath, line, col, "dead-metric",
            f"metric {name!r} is declared in DECLARED_METRICS but never "
            "recorded anywhere under paddle_tpu/ (no metrics.counter/"
            "gauge/histogram call names it); wire a recorder or drop "
            "the declaration"))
    return findings


# ------------------------------------------------------ compile-cache-dir

# the one module allowed to touch jax's process-global compile-cache
# config (owns the set-once + conflict-warning semantics)
_COMPILE_CACHE_OWNER = "paddle_tpu/jit/compile_cache.py"


@rule("compile-cache-dir")
def check_compile_cache_dir(ctx: FileContext) -> List[LintFinding]:
    """Direct ``jax.config.update("jax_compilation_cache_dir", ...)``
    outside ``jit/compile_cache.py``: the jax cache dir is
    process-global state — a stray update silently re-points (or races)
    every other subsystem's cache, the predictor global-hijack bug
    class. Call ``paddle_tpu.jit.enable_compile_cache(dir)`` instead;
    it owns the set-once/warn-on-conflict semantics."""
    if ctx.relpath == _COMPILE_CACHE_OWNER:
        return []
    findings = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and _dotted(node.func).endswith("config.update")
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value == "jax_compilation_cache_dir"):
            continue
        if ctx.allowed(node, "compile-cache-dir"):
            continue
        findings.append(LintFinding(
            ctx.relpath, node.lineno, node.col_offset,
            "compile-cache-dir",
            "direct jax.config.update('jax_compilation_cache_dir', ...) "
            "re-points process-global state under every other "
            "subsystem; use paddle_tpu.jit.enable_compile_cache(dir) "
            "(jit/compile_cache.py owns the set-once semantics)"))
    return findings


# ------------------------------------------------------- lock-discipline

# Shared mutable state that MUST be written under a lock: the scheduler
# thread, the telemetry HTTP thread, and Future.result() pumps all
# touch these concurrently (the PR-12 telemetry-thread race class).
# relpath -> {class name -> protected attribute names}. Writes are
# legal (a) lexically inside a ``with self.<...lock...>:`` block, (b)
# in ``__init__`` (single-threaded construction), or (c) on a line /
# in a method whose def line carries ``# lint: lock-discipline-ok
# (reason)`` — the "caller holds the lock" helpers.
LOCK_DISCIPLINE = {
    "paddle_tpu/generation/paged_cache.py": {
        "PageAllocator": frozenset({
            "_free", "_ref", "_prefix", "_page_key"}),
    },
    "paddle_tpu/serving/engine.py": {
        "ServingEngine": frozenset({
            "_queue", "_slots", "_slot_used"}),
    },
    "paddle_tpu/serving/router.py": {
        "FleetRouter": frozenset({
            "_replicas", "_stats"}),
    },
}

# deque/list/dict/OrderedDict methods that mutate their receiver
_MUTATOR_METHODS = frozenset({
    "append", "appendleft", "extend", "extendleft", "insert", "remove",
    "pop", "popleft", "popitem", "clear", "update", "setdefault",
    "move_to_end", "sort", "reverse", "add", "discard",
})


def _protected_attr(node: ast.AST, attrs) -> Optional[str]:
    """The protected ``self.X`` attribute a node writes/mutates, if
    any: plain/aug/subscript assignment targets and mutator-method
    calls on ``self.X``."""
    def self_attr(n):
        if isinstance(n, ast.Attribute) and \
                isinstance(n.value, ast.Name) and n.value.id == "self" \
                and n.attr in attrs:
            return n.attr
        return None

    if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
        targets = [node.target] if isinstance(node, ast.AugAssign) \
            else node.targets
        for t in targets:
            for el in ast.walk(t):
                if isinstance(el, ast.Subscript):
                    hit = self_attr(el.value)
                    if hit:
                        return hit
                hit = self_attr(el)
                if hit:
                    return hit
    elif isinstance(node, ast.Call) and \
            isinstance(node.func, ast.Attribute) and \
            node.func.attr in _MUTATOR_METHODS:
        recv = node.func.value
        if isinstance(recv, ast.Subscript):
            recv = recv.value
        return self_attr(recv)
    return None


def _lock_with_items(with_node: ast.With) -> bool:
    """True when the with-statement enters ``self.<something lock>``
    (``self._lock``, ``self._qlock``, ``self._pump_lock``, including
    ``.acquire()``-less RLock reentry)."""
    for item in with_node.items:
        expr = item.context_expr
        if isinstance(expr, ast.Call):
            expr = expr.func
        for n in ast.walk(expr):
            if isinstance(n, ast.Attribute) and "lock" in n.attr \
                    and isinstance(n.value, ast.Name) \
                    and n.value.id == "self":
                return True
    return False


def _def_line_marked(ctx: FileContext, fn: ast.AST, rule_name: str) -> bool:
    """Marker on the method's def line (or a decorator line): the
    whole body is exempt — the 'caller holds self._lock' helpers."""
    token = f"lint: {rule_name}-ok"
    lines = [fn.lineno] + [d.lineno for d in
                           getattr(fn, "decorator_list", [])]
    return any(token in ctx.lines[ln - 1] for ln in lines
               if 0 < ln <= len(ctx.lines))


@rule("lock-discipline")
def check_lock_discipline(ctx: FileContext) -> List[LintFinding]:
    """Writes to the allocator free-list/refcount maps and the engine
    queue/slot tables outside a ``with self._lock``-style block: the
    statically-catchable form of the PR-12 telemetry-thread race (an
    HTTP scrape iterating ``self._free`` mid-mutation). Helpers whose
    caller holds the lock mark their def line ``# lint:
    lock-discipline-ok (caller holds self._lock)``."""
    scopes = LOCK_DISCIPLINE.get(ctx.relpath)
    if not scopes:
        return []
    findings = []
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef) or cls.name not in scopes:
            continue
        attrs = scopes[cls.name]
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            if fn.name == "__init__" or \
                    _def_line_marked(ctx, fn, "lock-discipline"):
                continue

            def walk_fn(node, locked):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.Lambda)):
                        continue  # nested defs run elsewhere
                    child_locked = locked or (
                        isinstance(child, ast.With)
                        and _lock_with_items(child))
                    if not child_locked:
                        hit = _protected_attr(child, attrs)
                        if hit and not ctx.allowed(
                                child, "lock-discipline"):
                            findings.append(LintFinding(
                                ctx.relpath, child.lineno,
                                child.col_offset, "lock-discipline",
                                f"write to self.{hit} outside a 'with "
                                "self._lock' block: another thread "
                                "(telemetry scrape, Future.result "
                                "pump) can observe it mid-mutation; "
                                "take the lock, or mark the line/def "
                                "'# lint: lock-discipline-ok (reason)'"
                                " if the caller holds it"))
                    walk_fn(child, child_locked)

            walk_fn(fn, False)
    return findings


# ---------------------------------------------------------- chaos-marker

def _has_chaos_marker(nodes: List[ast.AST]) -> bool:
    """True if any node in the chain (module, class, function) carries
    a pytest chaos marker: module-level ``pytestmark = ...chaos...`` or
    a ``@pytest.mark.chaos`` decorator."""
    for node in nodes:
        if isinstance(node, ast.Module):
            for stmt in node.body:
                if isinstance(stmt, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == "pytestmark"
                        for t in stmt.targets):
                    if any(isinstance(s, ast.Attribute) and s.attr == "chaos"
                           for s in ast.walk(stmt.value)):
                        return True
        else:
            for dec in getattr(node, "decorator_list", []):
                if any(isinstance(s, ast.Attribute) and s.attr == "chaos"
                       for s in ast.walk(dec)):
                    return True
    return False


@rule("chaos-marker")
def check_chaos_marker(ctx: FileContext) -> List[LintFinding]:
    """Tests importing ``paddle_tpu.utils.fault_injection`` must carry
    the ``chaos`` marker — module-level ``pytestmark`` or a decorator
    on the enclosing test/class — so ``pytest -m chaos`` runs the whole
    chaos tier and ``-m 'not chaos'`` really excludes it. This promotes
    the conftest collection guard (module-level imports only) to lint,
    which also sees function-level imports."""
    if not ctx.is_test_file or "conftest" in os.path.basename(ctx.relpath):
        return []
    findings = []

    def _imports_fi(node) -> bool:
        if isinstance(node, ast.Import):
            return any(a.name.startswith(_FAULT_INJECTION_MODULE)
                       for a in node.names)
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.startswith(_FAULT_INJECTION_MODULE):
                return True
            return mod == "paddle_tpu.utils" and any(
                a.name == "fault_injection" for a in node.names)
        return False

    def _walk(node, chain):
        for child in ast.iter_child_nodes(node):
            if _imports_fi(child):
                if not _has_chaos_marker(chain) and \
                        not ctx.allowed(child, "chaos-marker"):
                    findings.append(LintFinding(
                        ctx.relpath, child.lineno, child.col_offset,
                        "chaos-marker",
                        "imports paddle_tpu.utils.fault_injection "
                        "without a chaos marker on the module "
                        "(pytestmark), class, or test: add "
                        "@pytest.mark.chaos"))
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                _walk(child, chain + [child])
            else:
                _walk(child, chain)

    _walk(ctx.tree, [ctx.tree])
    return findings
