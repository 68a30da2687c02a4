"""A scheduler iteration accounts for itself (ISSUE 36). On a tiny
engine: an iteration whose poll read sleeps leaves one ``serve.stall``
naming the span, the sleeping frame and the threads that were at work,
and one dump; every garbage collection inside an iteration is on the
step's ``gc_ms`` and a full one is a ``host.gc`` child; the new children
of ``serve.step`` (``serve.plan``, ``serve.dispatch{program}``,
``serve.telemetry``) hold no blocking read and leave
``sched_host_ms_per_step.serve``'s sum where it was; with the recorder
off there is no watcher and no ``gc`` callback."""
import ast
import gc
import importlib.util
import os
import threading
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flight_recorder as fr
from paddle_tpu.serving import RequestParams

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
SLEEP_S = 0.4


@pytest.fixture(autouse=True)
def _fresh_recorder(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)
    yield
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)


def _engine(**kw):
    from paddle_tpu.inference import Config
    from paddle_tpu.models.gpt import gpt
    from paddle_tpu.serving import ServingEngine
    paddle.seed(0)
    m = gpt("test-tiny")
    m.eval()
    spec = [paddle.to_tensor(np.zeros((2, 12), np.int32))]
    gen = dict(max_new_tokens=8, prefill_buckets=(8, 16), max_batch=2)
    gen.update(kw.pop("generation", {}))
    cfg = Config().from_layer(m, spec).enable_generation(**gen)
    if "serving" in kw:
        cfg = cfg.enable_serving(**kw.pop("serving"))
    return ServingEngine(cfg, **kw)


PROMPTS = [np.arange(1, 1 + n, dtype=np.int32) for n in (5, 12, 3, 9, 7)]
BUDGETS = [8, 3, 1, 6, 8]


def _drain(eng, prompts=PROMPTS, budgets=BUDGETS, **params):
    handles = [eng.submit(p, RequestParams(max_new_tokens=b, **params))
               for p, b in zip(prompts, budgets)]
    while eng.busy:
        eng.step()
    return handles


def _spans():
    return fr.spans_between(0, 2 ** 62)


def _stalls(wait_s=0.0):
    """The ``serve.stall`` events, once the watcher (awake every 50 ms)
    has had ``wait_s`` to report them."""
    deadline = time.monotonic() + wait_s
    while True:
        found = [f for _, kind, f in fr.events() if kind == "serve.stall"]
        if found or time.monotonic() >= deadline:
            return found
        time.sleep(0.02)


def _dumps(tmp_path):
    return sorted(p.name for p in tmp_path.glob("*serve_stall*.json"))


class _SlowRead:
    """Stands in for ``jax.device_get``: the first poll read after
    ``arm()`` sleeps ``SLEEP_S`` in ``slow_poll_read``."""

    def __init__(self, monkeypatch):
        import jax
        self._get = jax.device_get
        self.armed = False
        self.slept_ms = None
        monkeypatch.setattr(jax, "device_get", self)

    def arm(self):
        self.armed = True

    def slow_poll_read(self):
        t0 = time.monotonic()
        time.sleep(SLEEP_S)
        self.slept_ms = (time.monotonic() - t0) * 1e3

    def __call__(self, x):
        if self.armed and hasattr(x, "finished"):
            self.armed = False
            self.slow_poll_read()
        return self._get(x)


# ------------------------------------------------------------ (a) stalls

def test_a_slept_poll_read_leaves_one_stall_and_one_dump(
        monkeypatch, tmp_path):
    slow = _SlowRead(monkeypatch)
    eng = _engine(poll_every=2)
    try:
        fr.clear()
        _drain(eng)
        assert _stalls(0.2) == [] and _dumps(tmp_path) == []
        slow.arm()
        _drain(eng)
        (stall,) = _stalls(2.0)
        time.sleep(0.15)                   # no second report follows
        assert len(_stalls()) == 1
    finally:
        eng.shutdown()
    assert stall["span"] == "serve.sync" and stall["site"] == "poll"
    assert {"steps_queued", "ahead", "gc_ms", "thread"} <= set(stall)
    assert SLEEP_S * 1e3 - 1 <= stall["ms"] <= slow.slept_ms + 50
    assert stall["samples"] >= 1
    # blocked, not computing, and the watcher was not held with it
    assert stall["late_ms"] < 100 and stall["thread_cpu_ms"] < 100
    assert stall["top"].endswith(" slow_poll_read")
    assert __file__.rstrip("c") in stall["top"]
    assert "slow_poll_read < " in stall["stack"]
    assert stall["gc_ms"] == 0.0
    # the stretch is the poll read's own span, and the reader of the
    # spans' stamps finds it there too
    (read,) = [s for s in _spans() if s.name == "serve.sync"
               and s.end_ns - s.start_ns > SLEEP_S * 1e9 - 1e6]
    assert read.fields["site"] == "poll"
    assert abs((read.end_ns - read.start_ns) / 1e6 - stall["ms"]) < 5
    # one dump, on the spot, holding the event
    deadline = time.monotonic() + 2.0
    while not _dumps(tmp_path) and time.monotonic() < deadline:
        time.sleep(0.02)
    (dump,) = _dumps(tmp_path)
    assert "serve.stall" in (tmp_path / dump).read_text()


def test_a_stall_under_the_interpreter_lock_says_so():
    """One C call that never lets the interpreter go holds the watcher
    too: it sleeps through the stall (``late_ms`` near ``ms``), and the
    thread's own CPU clock says it was computing, not blocked. The
    slept read above is the other case: the watcher on time, no CPU."""
    t0 = time.perf_counter()
    sum(range(2_000_000))
    n = int(2_000_000 * 0.5 / (time.perf_counter() - t0))
    for _ in range(3):                 # the watcher sees the thread at work
        with fr.span("serve.step"):
            time.sleep(0.06)
    with fr.span("serve.step"):
        with fr.span("serve.dispatch", program="step"):
            sum(range(n))
    (stall,) = _stalls(2.0)
    assert stall["span"] == "serve.dispatch" and stall["program"] == "step"
    assert stall["ms"] > 250
    assert stall["late_ms"] > 0.5 * stall["ms"]
    assert stall["thread_cpu_ms"] > 0.5 * stall["ms"]
    assert stall["samples"] <= 2


def test_a_silence_between_iterations_is_no_stall():
    """``jax.profiler.start_trace`` holds the harness's thread for
    seconds between iterations, and a loop waits for arrivals there: no
    ``serve.step`` is open, so nothing is reported."""
    with fr.span("serve.step"):
        pass
    time.sleep(0.35)
    with fr.span("serve.step"):
        pass
    with fr.span("setup.warmup"):          # not a scheduler iteration
        time.sleep(0.35)
    assert _stalls(0.2) == []


# ----------------------------------------------------- (b) other threads

def test_a_spinning_thread_is_named_and_a_parked_one_is_not(monkeypatch):
    slow = _SlowRead(monkeypatch)
    eng = _engine(poll_every=2)
    stop, parked = [], threading.Event()

    def spin_in_python():
        n = 0
        while not stop:
            n += 1

    threads = [threading.Thread(target=spin_in_python, name="spinner"),
               threading.Thread(target=parked.wait, name="parked")]
    try:
        fr.clear()
        for t in threads:
            t.start()
        slow.arm()
        _drain(eng)
        (stall,) = _stalls(2.0)
    finally:
        stop.append(True)
        parked.set()
        for t in threads:
            t.join(5.0)
        eng.shutdown()
    assert not any(t.is_alive() for t in threads)
    assert stall["top"].endswith(" slow_poll_read")
    others = dict(o.split(": ", 1) for o in stall["others"].split("; "))
    assert "spinner" in others and "parked" not in others
    assert others["spinner"].endswith(" spin_in_python")


# ------------------------------------------------ (c) garbage collection

def test_collections_inside_an_iteration_are_on_its_record():
    eng = _engine(poll_every=2)
    land = eng._land

    def land_after_collecting(admitted):
        gc.collect()
        t = threading.Thread(target=gc.collect, name="collector")
        t.start()
        t.join(5.0)
        return land(admitted)

    try:
        fr.clear()
        eng._land = land_after_collecting
        eng.submit(PROMPTS[0], RequestParams(max_new_tokens=2))
        eng.step()
        eng._land = land
        while eng.busy:
            eng.step()
    finally:
        eng.shutdown()
    time.sleep(0.1)     # a span the callback could not record at once
    spans = _spans()
    steps = [s for s in spans if s.name == "serve.step"]
    assert all("gc_ms" in s.fields for s in steps)
    first = steps[0]
    full = [s for s in spans if s.name == "host.gc"
            and s.fields["gen"] == 2
            and first.start_ns <= s.start_ns <= first.end_ns]
    mine = [s for s in full if s.fields["thread"] != "collector"]
    theirs = [s for s in full if s.fields["thread"] == "collector"]
    # (the collector may add one of its own: the heap has just grown)
    assert mine and theirs
    assert any(s.parent == first.id for s in mine)
    assert all(s.parent is None for s in theirs)
    covered = sum(s.end_ns - s.start_ns for s in full) / 1e6
    assert first.fields["gc_ms"] >= covered > 0
    # an iteration without a collection says so: the field is there
    assert any(s.fields["gc_ms"] == 0.0 for s in steps[1:])


# ------------------------------- (d) the new children moved nothing

ENGINES = [
    pytest.param({}, id="dense"),
    pytest.param({"serving": dict(paged=True, kv_page_size=8)},
                 id="paged"),
    pytest.param({"generation": dict(prefill_buckets=(16,)),
                  "serving": dict(prefill_chunk_tokens=4)},
                 id="chunked"),
]


def _reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def bench_modules():
    import sys
    before = set(sys.modules)
    sys.path.insert(0, BENCH)
    try:
        yield
    finally:
        sys.path.remove(BENCH)
        for name in set(sys.modules) - before:
            if (getattr(sys.modules[name], "__file__", "") or "") \
                    .startswith(BENCH):
                del sys.modules[name]


@pytest.mark.parametrize("kw", ENGINES)
def test_host_time_is_the_same_sum_split_finer(kw, bench_modules):
    """What ``sched_host_ms_per_step.serve`` adds up (the self time of
    everything under ``serve.step`` but ``serve.sync``) is the
    iterations' time less their blocking reads, to the microsecond:
    finer children only lengthen its split. Every program call happens
    under a ``serve.dispatch`` that names it, and no blocking read lies
    under a span that is host time by name."""
    eng = _engine(**kw, poll_every=2)
    called = []

    class Checked:
        def __init__(self, key, exe):
            self.key, self.exe = key, exe

        def __call__(self, *args):
            inner = fr._tls.st.span
            called.append((self.key[0], inner.name,
                           inner.fields.get("program")))
            return self.exe(*args)

    try:
        for key in eng._programs:
            eng._exes[key] = Checked(key, eng._compiled(key))
        t_proc = fr.now_ns()
        fr.clear()
        _drain(eng)
        # a deadline eviction: the free program
        h = eng.submit(PROMPTS[0], RequestParams(max_new_tokens=8,
                                                 deadline_s=0.05))
        eng.step()
        time.sleep(0.06)
        while eng.busy:
            eng.step()
        assert h.status.value == "cancelled"
        t_close = fr.now_ns()
    finally:
        eng.shutdown()
    assert called and all(
        span == "serve.dispatch"
        and program == ("step" if key.endswith("step") else key)
        for key, span, program in called), called
    if "serving" in kw and "prefill_chunk_tokens" in kw["serving"]:
        # (a chunked admission past its deadline is aborted before its
        # lane is installed: nothing to free)
        want = {"admit", "step", "chunk", "chunk_final"}
    else:
        want = {"prefill", "admit", "step", "free"}
    assert want <= {key for key, _, _ in called}
    spans = _spans()
    by_id = {s.id: s for s in spans}
    host_only = {"serve.telemetry", "serve.plan", "serve.dispatch"}
    for s in spans:
        if s.name == "serve.sync":
            up = s
            while up.parent is not None:
                up = by_id[up.parent]
                assert up.name not in host_only, (s, up)
            assert up.name == "serve.step"
    names = {s.name for s in spans}
    assert {"serve.telemetry", "serve.dispatch"} <= names
    assert ("serve.plan" in names) == ("serving" in kw
                                       and kw["serving"].get("paged", False))
    run = types.SimpleNamespace(
        t_proc=t_proc * 1e-9, setup_s=0.0,
        window_s=(t_close - t_proc) * 1e-9)
    steps = [s for s in spans if s.name == "serve.step"]
    decodes = sum(s.fields["decode"] for s in steps)
    got = _reader("sched_host_ms_per_step.serve").read(run) * decodes
    syncs = [s for s in spans if s.name == "serve.sync"]
    want_ms = (sum(s.end_ns - s.start_ns for s in steps)
               - sum(s.end_ns - s.start_ns for s in syncs)) / 1e6
    assert got == pytest.approx(want_ms, abs=1e-3)


def test_every_exe_call_in_the_engine_is_inside_a_dispatch_span():
    """The source's side of it: a call of a bare executable (``exe(...)``)
    in ``serving/engine.py`` stands inside ``with
    flight_recorder.span("serve.dispatch", program=...)``."""
    path = os.path.join(REPO, "paddle_tpu", "serving", "engine.py")
    with open(path) as f:
        tree = ast.parse(f.read())

    def is_dispatch(item):
        c = item.context_expr
        return (isinstance(c, ast.Call) and c.args
                and isinstance(c.args[0], ast.Constant)
                and c.args[0].value == "serve.dispatch"
                and any(k.arg == "program" for k in c.keywords))

    calls = []

    def walk(node, inside):
        if isinstance(node, ast.With) and any(map(is_dispatch,
                                                  node.items)):
            inside = True
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "exe":
            calls.append((node.lineno, inside))
        for child in ast.iter_child_nodes(node):
            walk(child, inside)

    walk(tree, False)
    assert len(calls) >= 2 and all(inside for _, inside in calls), calls


# -------------------------------------------------- (f) the recorder off

def test_recorder_off_runs_no_watcher_and_registers_no_callback():
    def watchers():
        return [t for t in threading.enumerate()
                if t.name == "flight-recorder-watch"]

    with fr.span("serve.step"):
        pass
    assert len(watchers()) == 1 and fr._on_gc in gc.callbacks
    with fr.span("serve.step"):            # one watcher, however many
        pass
    assert len(watchers()) == 1 and gc.callbacks.count(fr._on_gc) == 1
    fr.disable()
    deadline = time.monotonic() + 2.0
    while watchers() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert watchers() == [] and fr._on_gc not in gc.callbacks
    assert fr.span("serve.step") is fr.span("serve.plan")   # the no-op
    eng = _engine(poll_every=2)
    try:
        _drain(eng)
    finally:
        eng.shutdown()
    assert watchers() == [] and fr._on_gc not in gc.callbacks
    assert fr.gc_ns() >= 0


def test_the_environment_switch_is_read_as_off(monkeypatch):
    for raw in ("0", "off"):
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", raw)
        assert fr._env_capacity()[0] is False
