"""Framework-lint coverage (tools/lint): each rule caught on a minimal
bad snippet and silent on the corresponding good one, the allowlist
markers, and — the tier-1 gate — ``python -m tools.lint paddle_tpu
tests`` exiting 0 on the shipped tree."""
import os
import subprocess
import sys
import textwrap

import pytest

from tools.lint import lint_file, lint_paths, RULES

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_snippet(tmp_path, source, relpath):
    """Lint `source` as if it lived at `relpath` in the repo."""
    p = tmp_path / os.path.basename(relpath)
    p.write_text(textwrap.dedent(source))
    return lint_file(str(p), relpath)


def _rules_of(findings):
    return sorted({f.rule for f in findings})


class TestHostSyncRule:
    HOT = "paddle_tpu/generation/api.py"

    def test_flags_numpy_float_asarray(self, tmp_path):
        found = _lint_snippet(tmp_path, """
            import numpy as np
            def step(t):
                a = t.numpy()
                b = float(t)
                c = np.asarray(t)
                return a, b, c
            """, self.HOT)
        assert _rules_of(found) == ["host-sync"]
        assert len(found) == 3
        assert [f.line for f in found] == [4, 5, 6]

    def test_cold_module_and_markers_pass(self, tmp_path):
        src = """
            import numpy as np
            def step(t):
                a = t.numpy()  # lint: host-sync-ok (deliberate)
                b = np.asarray(t)  # lint: host-sync-ok (end-of-call)
                c = float(1.5)
                d = jnp.asarray(t)
                return a, b, c, d
            """
        assert not _lint_snippet(tmp_path, src, self.HOT)
        # same calls, unmarked, in a non-hot-path module: fine
        bad = """
            import numpy as np
            def helper(t):
                return np.asarray(t.numpy())
            """
        assert not _lint_snippet(tmp_path, bad,
                                 "paddle_tpu/vision/ops.py")


class TestJitRandomRule:
    def test_flags_np_random_in_jitted_fn(self, tmp_path):
        found = _lint_snippet(tmp_path, """
            import jax
            import numpy as np

            @jax.jit
            def decorated(x):
                return x + np.random.randn(4)

            def by_reference(x):
                noise = np.random.normal(size=4)
                return x + noise

            jitted = jax.jit(by_reference)

            def eager(x):
                return x + np.random.randn(4)  # never jitted: fine
            """, "paddle_tpu/nn/whatever.py")
        assert _rules_of(found) == ["jit-random"]
        assert len(found) == 2
        assert {f.line for f in found} == {7, 10}

    def test_stdlib_random_and_to_static(self, tmp_path):
        found = _lint_snippet(tmp_path, """
            import random
            from paddle_tpu.jit import to_static

            @to_static
            def f(x):
                return x * random.random()
            """, "paddle_tpu/nn/whatever.py")
        assert len(found) == 1 and found[0].rule == "jit-random"


class TestBareExceptRule:
    def test_flags_silent_swallow(self, tmp_path):
        found = _lint_snippet(tmp_path, """
            def f():
                try:
                    risky()
                except:
                    pass
            """, "paddle_tpu/utils/x.py")
        assert _rules_of(found) == ["bare-except"]

    def test_recorded_or_reraised_pass(self, tmp_path):
        src = """
            from paddle_tpu.core import monitor
            def f():
                try:
                    risky()
                except:
                    monitor.record_swallowed("f", Exception("x"))
                try:
                    risky()
                except:
                    raise
            """
        assert not _lint_snippet(tmp_path, src, "paddle_tpu/utils/x.py")


class TestMetricNameRule:
    def test_flags_undeclared_literal(self, tmp_path):
        found = _lint_snippet(tmp_path, """
            from ..core import metrics
            def f():
                metrics.counter("totally.undeclared").inc()
                metrics.gauge("comm.bytes").set(1)  # declared: fine
                metrics.counter(name_var).inc()     # dynamic: fine
            """, "paddle_tpu/nn/whatever.py")
        assert _rules_of(found) == ["metric-name"]
        assert len(found) == 1 and "totally.undeclared" in found[0].message

    def test_tests_and_monitor_exempt(self, tmp_path):
        src = """
            from paddle_tpu.profiler import metrics
            metrics.counter("t.anything.goes").inc()
            """
        assert not _lint_snippet(tmp_path, src, "tests/test_whatever.py")
        assert not _lint_snippet(tmp_path, src,
                                 "paddle_tpu/core/monitor.py")


class TestEventNameRule:
    """Flight-recorder event names in the framework must come from
    core/flight_recorder.DECLARED_EVENTS (the metric-name contract
    applied to the black box)."""

    def test_flags_undeclared_literal(self, tmp_path):
        found = _lint_snippet(tmp_path, """
            from ..core import flight_recorder
            def f(kind):
                flight_recorder.record("serve.typo_event", req=1)
                flight_recorder.record("serve.submit", req=1)  # declared
                flight_recorder.record(kind, req=1)   # dynamic: fine
                flight_recorder.record_span(f"req{kind}.decode", 0, 1)
            """, "paddle_tpu/serving/whatever.py")
        assert _rules_of(found) == ["event-name"]
        assert len(found) == 1 and "serve.typo_event" in found[0].message

    def test_flags_undeclared_span_literal(self, tmp_path):
        """Literal span names are held to DECLARED_SPANS through every
        way of opening one."""
        found = _lint_snippet(tmp_path, """
            from ..core import flight_recorder
            from ..core import flight_recorder as _flight_recorder
            def f(req, name):
                with flight_recorder.span("setup.typo"):
                    pass
                with flight_recorder.span("serve.step"):      # declared
                    with _flight_recorder.span("serve.stepp"):
                        pass
                flight_recorder.record_span("serve.sync", 0, 1)  # declared
                flight_recorder.record_span("serve.snyc", 0, 1)
                req.stage_span("serve.queue_wiat", 0, 1)
                req.stage_span("serve.prefill", 0, 1)          # declared
                with flight_recorder.span(name):     # dynamic: fine
                    pass
            """, "paddle_tpu/serving/whatever.py")
        assert _rules_of(found) == ["event-name"] and len(found) == 4
        assert sorted(f.message.split("'")[1] for f in found) == [
            "serve.queue_wiat", "serve.snyc", "serve.stepp",
            "setup.typo"]
        assert all("DECLARED_SPANS" in f.message for f in found)

    def test_exemptions_and_marker(self, tmp_path):
        src = """
            from . import flight_recorder
            flight_recorder.record("anything.at.all")
            """
        # the declaring module and tests name events freely
        assert not _lint_snippet(tmp_path, src,
                                 "paddle_tpu/core/flight_recorder.py")
        assert not _lint_snippet(tmp_path, src, "tests/test_x.py")
        marked = """
            from ..core import flight_recorder
            flight_recorder.record("x.y")  # lint: event-name-ok (test hook)
            """
        assert not _lint_snippet(tmp_path, marked,
                                 "paddle_tpu/nn/whatever.py")


class TestDeadMetricRule:
    """The metric-name rule pointed the other way: a DECLARED name no
    ``metrics.counter/gauge/histogram`` call under paddle_tpu/ ever
    records is schema rot."""

    MONITOR = "paddle_tpu/core/monitor.py"

    def test_flags_declared_but_never_recorded(self, tmp_path):
        found = _lint_snippet(tmp_path, """
            DECLARED_METRICS = frozenset({
                "serve.requests",
                "zombie.metric.nobody.records",
            })
            """, self.MONITOR)
        assert _rules_of(found) == ["dead-metric"]
        assert len(found) == 1
        assert "zombie.metric.nobody.records" in found[0].message
        # the finding anchors on the stale declaration's line
        assert found[0].line == 4

    def test_recorded_names_pass(self, tmp_path):
        # "serve.requests" is recorded by the real tree; "jit.compile"
        # only via an f-string (f"{target}.compile") — both live.
        # "snippet.local" is recorded by this very module's own call.
        src = """
            from . import metrics
            DECLARED_METRICS = frozenset({
                "serve.requests",
                "jit.compile",
                "snippet.local",
            })
            def record_local():
                metrics.counter("snippet.local").inc()
            """
        assert not _lint_snippet(tmp_path, src, self.MONITOR)

    def test_marker_and_scope(self, tmp_path):
        src = """
            DECLARED_METRICS = frozenset({
                "zombie.allowed",  # lint: dead-metric-ok (wired next PR)
            })
            """
        assert not _lint_snippet(tmp_path, src, self.MONITOR)
        # the rule only fires on the schema-declaring core module
        bad = """
            DECLARED_METRICS = frozenset({"zombie.elsewhere"})
            """
        assert not _lint_snippet(tmp_path, bad,
                                 "paddle_tpu/vision/ops.py")
        assert not _lint_snippet(tmp_path, bad, "tests/test_x.py")


class TestCompileCacheDirRule:
    def test_flags_direct_config_update(self, tmp_path):
        found = _lint_snippet(tmp_path, """
            import jax
            def setup(path):
                jax.config.update("jax_compilation_cache_dir", path)
                jax.config.update("jax_default_matmul_precision",
                                  "highest")   # other keys: fine
            """, "paddle_tpu/inference/predictor.py")
        assert _rules_of(found) == ["compile-cache-dir"]
        assert len(found) == 1 and found[0].line == 4
        assert "enable_compile_cache" in found[0].message

    def test_owner_module_and_marker_pass(self, tmp_path):
        src = """
            import jax
            def setup(path):
                jax.config.update("jax_compilation_cache_dir", path)
            """
        # the owning module sets it freely
        assert not _lint_snippet(tmp_path, src,
                                 "paddle_tpu/jit/compile_cache.py")
        # ...everyone else needs the marker
        marked = """
            import jax
            def restore(prev):
                jax.config.update("jax_compilation_cache_dir", prev)  # lint: compile-cache-dir-ok (test restore)
            """
        assert not _lint_snippet(tmp_path, marked,
                                 "tests/test_whatever.py")
        # and tests/benches are NOT exempt without one
        assert _lint_snippet(tmp_path, src, "tests/test_whatever.py")
        assert _lint_snippet(tmp_path, src, "bench.py")


class TestLockDisciplineRule:
    ALLOC = "paddle_tpu/generation/paged_cache.py"
    ENGINE = "paddle_tpu/serving/engine.py"

    BAD_ALLOC = """
        import threading

        class PageAllocator:
            def __init__(self):
                self._lock = threading.Lock()
                self._free = [1, 2, 3]
                self._ref = {}

            def free_row(self, pages):
                for p in pages:
                    n = self._ref.get(p, 0) - 1
                    if n <= 0:
                        self._ref.pop(p, None)
                        self._free.append(p)

            def forget(self, key):
                del self._page_key[key]
        """

    def test_flags_unlocked_allocator_writes(self, tmp_path):
        found = _lint_snippet(tmp_path, self.BAD_ALLOC, self.ALLOC)
        assert _rules_of(found) == ["lock-discipline"]
        # _ref.pop + _free.append + the del-statement mutation form
        assert len(found) == 3
        # __init__ construction is exempt (no second thread exists yet)
        assert all(f.line > 10 for f in found)

    def test_locked_writes_and_markers_pass(self, tmp_path):
        src = """
            import threading

            class PageAllocator:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._free = []
                    self._ref = {}

                def free_row(self, pages):
                    with self._lock:
                        for p in pages:
                            self._free.append(p)
                            self._ref.pop(p, None)

                def _maybe_release(self, page):  # lint: lock-discipline-ok (caller holds self._lock)
                    self._free.append(page)

                def reads_are_free(self):
                    return len(self._free)
            """
        assert not _lint_snippet(tmp_path, src, self.ALLOC)
        # same writes in a module OUTSIDE the scoped set: fine
        assert not _lint_snippet(tmp_path, self.BAD_ALLOC,
                                 "paddle_tpu/vision/ops.py")

    def test_flags_engine_slot_and_queue_writes(self, tmp_path):
        src = """
            import threading

            class ServingEngine:
                def __init__(self):
                    self._qlock = threading.Lock()
                    self._pump_lock = threading.RLock()
                    self._queue = []
                    self._slots = [None] * 4

                def submit(self, req):
                    self._queue.append(req)

                def finish(self, slot):
                    self._slots[slot] = None

                def locked_ok(self, req, slot):
                    with self._qlock:
                        self._queue.append(req)
                    with self._pump_lock:
                        self._slots[slot] = req
            """
        found = _lint_snippet(tmp_path, src, self.ENGINE)
        assert _rules_of(found) == ["lock-discipline"]
        assert len(found) == 2
        assert {f.line for f in found} == {12, 15}

    def test_line_marker_escapes_with_reason(self, tmp_path):
        src = """
            import threading

            class ServingEngine:
                def __init__(self):
                    self._pump_lock = threading.RLock()
                    self._slots = [None] * 4

                def _evict(self, slot):
                    self._slots[slot] = None  # lint: lock-discipline-ok (caller holds pump lock)
            """
        assert not _lint_snippet(tmp_path, src, self.ENGINE)


class TestChaosMarkerRule:
    def test_flags_unmarked_import(self, tmp_path):
        found = _lint_snippet(tmp_path, """
            from paddle_tpu.utils import fault_injection

            def test_kill():
                fault_injection.poison_batch(None)
            """, "tests/test_whatever.py")
        assert _rules_of(found) == ["chaos-marker"]

    def test_module_class_and_function_markers_pass(self, tmp_path):
        src = """
            import pytest
            pytestmark = pytest.mark.chaos
            from paddle_tpu.utils import fault_injection
            """
        assert not _lint_snippet(tmp_path, src, "tests/test_a.py")
        src = """
            import pytest

            @pytest.mark.chaos
            def test_kill():
                from paddle_tpu.utils import fault_injection as fi
                fi.poison_batch(None)
            """
        assert not _lint_snippet(tmp_path, src, "tests/test_b.py")
        # non-test files import the harness freely (it's the library)
        src = "from paddle_tpu.utils import fault_injection\n"
        assert not _lint_snippet(tmp_path, src,
                                 "paddle_tpu/utils/__init__.py")


class TestEngine:
    def test_all_rules_registered(self):
        assert set(RULES) == {"host-sync", "jit-random", "bare-except",
                              "metric-name", "chaos-marker",
                              "compile-cache-dir", "dead-metric",
                              "event-name", "lock-discipline"}

    def test_syntax_error_is_reported_not_raised(self, tmp_path):
        found = _lint_snippet(tmp_path, "def broken(:\n",
                              "paddle_tpu/x.py")
        assert found and found[0].rule == "syntax"

    def test_nonexistent_path_fails_not_clean(self, tmp_path):
        """A typo'd path must FAIL (exit 2), never read as a clean
        pass — CI with `tools.lint paddel_tpu` must go red."""
        with pytest.raises(FileNotFoundError, match="does not exist"):
            lint_paths(["definitely_not_a_dir_xyz"])
        proc = subprocess.run(
            [sys.executable, "-m", "tools.lint", "paddel_tpu"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 2
        assert "error:" in proc.stderr

    def test_lint_paths_walks_directories(self, tmp_path):
        pkg = tmp_path / "paddle_tpu" / "sub"
        pkg.mkdir(parents=True)
        (pkg / "mod.py").write_text(
            "try:\n    pass\nexcept:\n    pass\n")
        found = lint_paths(["paddle_tpu"], root=str(tmp_path))
        assert len(found) == 1 and found[0].rule == "bare-except"
        assert found[0].path == "paddle_tpu/sub/mod.py"


class TestTreeIsClean:
    def test_shipped_tree_lints_clean(self):
        """THE tier-1 lint gate: the exact command CI runs must exit 0
        on the shipped tree — any new violation fails here with the
        offending findings in the assertion message."""
        proc = subprocess.run(
            [sys.executable, "-m", "tools.lint", "paddle_tpu", "tests"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, \
            f"framework lint found violations:\n{proc.stdout}"

    def test_cli_rules_listing(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.lint", "--rules"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0
        for rule_name in RULES:
            assert rule_name in proc.stdout

    def test_cli_nonzero_on_finding(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("try:\n    pass\nexcept:\n    pass\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.lint", str(bad)],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 1
        assert "bare-except" in proc.stdout

    def test_cli_from_foreign_cwd_still_scopes_rules(self, tmp_path):
        """Relative paths resolve against the REPO root, not the cwd:
        invoked from a neutral directory (the verify-skill workflow),
        the lint must still walk the real tree — a bad cwd reads as
        '0 file(s)', never as a vacuous clean pass — and the
        repo-relative paths that scope host-sync/metric-name must
        survive absolute-path invocation too."""
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + \
            env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.lint", "paddle_tpu", "tests"],
            cwd=str(tmp_path), capture_output=True, text=True,
            timeout=120, env=env)
        assert proc.returncode == 0, proc.stdout
        n_files = int(proc.stderr.split("file(s)")[0].strip())
        assert n_files > 100  # the walk matched the real tree

    def test_path_scoped_rules_apply_under_absolute_invocation(self):
        """A hot-path file addressed ABSOLUTELY must still resolve to
        its repo-relative identity (the host-sync scoping bug class:
        relpath-vs-cwd silently disabling scoped rules)."""
        from tools.lint import lint_paths
        target = os.path.join(REPO_ROOT, "paddle_tpu", "hapi",
                              "model.py")
        stats = {}
        findings = lint_paths([target], stats=stats)
        assert stats["files"] == 1
        # the shipped file is clean — but ONLY because its deliberate
        # sync points carry markers; strip the markers in a shadow copy
        # at the same relpath under a mirrored root to prove the rule
        # actually fires on this path
        import tempfile
        with tempfile.TemporaryDirectory() as td:
            shadow = os.path.join(td, "paddle_tpu", "hapi")
            os.makedirs(shadow)
            with open(target) as f:
                src = f.read().replace("# lint: host-sync-ok", "#")
            with open(os.path.join(shadow, "model.py"), "w") as f:
                f.write(src)
            hits = lint_paths(["paddle_tpu"], root=td)
            assert any(f.rule == "host-sync" for f in hits)
        assert findings == []
