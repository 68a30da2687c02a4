"""The benchmark's ``program_span`` readers (ISSUE 26), under tier-1: the
yardstick's rehearsal cells run end to end on the CPU with ``--trace 1``
and print every new per-layer metric that lists the cell's kind; a reader
handed a recorder that dropped events inside the window, or a program
whose recorder predates the spans, returns ``None`` and does not raise.

The three rehearsal runs are subprocesses of 10-20 s each on the CPU (a
count or a correctness result only: no device metric comes from them).
The spec they run is built here from ``benchmarks/rehearsal.json`` plus
the ``program_span`` entries of ``BENCHMARK.json`` mapped onto the
rehearsal cell of the same kind, so the test follows ``BENCHMARK.json``.
"""
import importlib.util
import json
import os
import subprocess
import sys
import time
import types

import pytest

from paddle_tpu.core import flight_recorder as fr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
KIND = {"gpt2s-train": "rehearsal-train",
        "gpt3l8-offline": "rehearsal-closed",
        "gpt3l8-chat": "rehearsal-open"}


def rehearsed(metric):
    """The rehearsal cells of ``rehearsal.json`` that stand for a
    metric's cells; a cell of another family (its rehearsal is
    ``benchmarks/rehearsal_sdar.json``, driven by
    ``benchmarks/tests/test_correct_sdar.py``) has none here."""
    return [KIND[w] for w in metric["workloads"] if w in KIND]


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _span_metrics():
    return [m for m in _load(REPO, "BENCHMARK.json")["per_layer"]
            if m["source"] == "program_span"
            and m["name"] != "queue_wait_p95_ms.serve"]   # the harness's


SPAN_METRICS = _span_metrics()
# ISSUE 36; the last three read spans and fields that ISSUE adds
ITERATION_METRICS = ["longest_silence_ms.serve",
                     "host_gc_ms_per_step.serve",
                     "telemetry_ms_per_step.serve", "admit_host_ms.serve"]


@pytest.fixture(scope="module")
def spec_path(tmp_path_factory):
    spec = _load(BENCH, "rehearsal.json")
    have = {m["name"] for m in spec["per_layer"]}
    for m in SPAN_METRICS:
        assert m["name"] not in have
        spec["per_layer"].append(
            dict(m, workloads=rehearsed(m)))
    path = tmp_path_factory.mktemp("spec") / "rehearsal-spans.json"
    path.write_text(json.dumps(spec))
    return str(path)


def test_nine_new_readers_are_declared():
    # ISSUE 26's nine, ISSUE 31's sync_covered_share.serve and ISSUE
    # 36's four (the iteration's own account)
    assert len(SPAN_METRICS) == 9 + 1 + 4
    assert {m["name"] for m in SPAN_METRICS} >= set(ITERATION_METRICS)
    for m in SPAN_METRICS:
        assert os.path.exists(
            os.path.join(BENCH, "metrics", m["name"] + ".py")), m["name"]
        assert m["layer"] in ("scheduler", "entry points", "programs")


@pytest.mark.parametrize("cell", sorted(KIND.values()))
def test_rehearsal_cell_prints_its_span_metrics(cell, spec_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_FLIGHT_RECORDER", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--spec",
         spec_path, "--workload", cell, "--seed", "2147484026",
         "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    want = [m for m in SPAN_METRICS if cell in rehearsed(m)]
    assert want
    got = line["metrics"]
    for m in want:
        assert m["name"] in got, (m["name"], proc.stderr[-3000:])
        v = got[m["name"]]
        assert v["unit"] == m["unit"] and v["value"] >= 0, m["name"]
    assert "not read" not in proc.stderr      # nothing was dropped
    if cell == "rehearsal-closed":
        # the program's polls and the harness's lane counters agree
        assert abs(got["poll_lane_occupancy.serve"]["value"]
                   - got["decode_batch_occupancy.serve"]["value"]) < 0.5
    if cell == "rehearsal-open":
        # from outside the wait also holds the generator's lateness
        assert got["sched_queue_wait_p95_ms.serve"]["value"] \
            <= got["queue_wait_p95_ms.serve"]["value"] + 1.0


# ------------------------------------------------- readers, in process

@pytest.fixture
def bench_modules():
    """``spans`` and ``common`` importable by name, as under run.py;
    taken out of ``sys.modules`` again afterwards."""
    before = set(sys.modules)
    sys.path.insert(0, BENCH)
    try:
        yield
    finally:
        sys.path.remove(BENCH)
        for name in set(sys.modules) - before:
            if getattr(sys.modules[name], "__file__", "") and \
                    sys.modules[name].__file__.startswith(BENCH):
                del sys.modules[name]


def _reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(t_proc_ns, t_open_ns, t_close_ns):
    return types.SimpleNamespace(
        t_proc=t_proc_ns * 1e-9, setup_s=(t_open_ns - t_proc_ns) * 1e-9,
        window_s=(t_close_ns - t_open_ns) * 1e-9,
        cfg={"serve": {"generation": {"max_batch": 2}}},
        program_median_ms=lambda which: None)


def _traffic(ahead=(1, 0), accounted=True):
    """A set-up and a window's worth of spans, by hand. ``ahead``: what
    the poll's and the row's ``serve.sync`` carry of it (None: the field
    is not there, as on a program before ISSUE 31). ``accounted``: the
    iteration splits its host time as since ISSUE 36 (a plan and an
    admission with its two dispatches, ``program`` on every dispatch,
    the poll's telemetry, ``gc_ms`` on the step); False: the tree of
    the program before it."""
    def field(value):
        return {} if value is None else {"ahead": value}

    def program(name):
        return {"program": name} if accounted else {}
    with fr.span("setup.engine_init"):
        with fr.span("jit.program", label="serving.step") as sp:
            sp.set(source="compile", lower_s=0.0, bytes=0)
    with fr.span("train.step"):
        pass
    t_open = fr.now_ns()
    for i in range(8):
        with fr.span("serve.step") as step:
            t = fr.now_ns()
            fr.record_span("serve.queue_wait", t, t + 10, req=i)
            fr.record_span("serve.prefill", t + 10, t + 30, req=i)
            if accounted:
                with fr.span("serve.plan", req=i, pages=2, shared=0):
                    pass
            with fr.span("serve.admit", req=i, slot=0, bucket=8, prompt=5):
                if accounted:
                    with fr.span("serve.dispatch", program="prefill"):
                        pass
                    with fr.span("serve.dispatch", program="admit"):
                        pass
            with fr.span("serve.dispatch", **program("step")):
                pass
            with fr.span("serve.poll") as poll:
                with fr.span("serve.sync", site="poll", steps_queued=1,
                             **field(ahead[0])):
                    pass
                if i % 2:
                    with fr.span("serve.sync", site="row", steps_queued=0,
                                 **field(ahead[1])):
                        pass
                if accounted:
                    with fr.span("serve.telemetry"):
                        pass
                poll.set(steps=1, emitted=2, admitted=1)
            step.set(decode=1, **({"gc_ms": 0.0} if accounted else {}))
        with fr.span("train.step"):
            pass
    return t_open


@pytest.fixture
def recorder():
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)
    yield
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)


NAMES = [m["name"] for m in SPAN_METRICS]


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_a_whole_window(name, recorder, bench_modules):
    t_proc = fr.now_ns()
    t_open = _traffic()
    value = _reader(name).read(_run(t_proc, t_open, fr.now_ns()))
    assert value is not None and value >= 0
    if name == "poll_lane_occupancy.serve":
        assert value == pytest.approx(100.0 * (2 - 1) / (1 * 2))
    if name == "sync_covered_share.serve":
        # 8 poll reads with a step behind them, 4 row reads with none
        assert value == pytest.approx(100.0 * 8 / 12)


@pytest.mark.parametrize("ahead,want", [((1, 1), 100.0), ((0, 0), 0.0),
                                        ((None, None), None),
                                        ((2, None), 100.0 * 8 / 12)])
def test_sync_covered_share_reads_ahead(ahead, want, recorder,
                                        bench_modules, capsys):
    """Every site counts; a read with nothing behind it is uncovered
    whatever it says or leaves unsaid; a program whose ``serve.sync``
    carries no ``ahead`` at all (the parent, with this file laid over
    it) gives nothing and does not raise."""
    t_proc = fr.now_ns()
    t_open = _traffic(ahead)
    got = _reader("sync_covered_share.serve").read(
        _run(t_proc, t_open, fr.now_ns()))
    assert got == (want if want is None else pytest.approx(want))
    err = capsys.readouterr().err
    assert ("by site: poll" in err) == (want is not None)


@pytest.mark.parametrize("name", ITERATION_METRICS)
def test_iteration_reader_on_a_tree_without_its_spans(
        name, recorder, bench_modules):
    """The benchmark files are laid over the parent's checkout too: the
    three readers of what ISSUE 36 adds give nothing on a program whose
    iterations carry no ``gc_ms``, ``serve.telemetry`` or
    ``serve.dispatch{program=prefill}`` (0 would read as a cost of
    nothing); the longest silence comes from the spans' own stamps and
    reads on both."""
    t_proc = fr.now_ns()
    t_open = _traffic(accounted=False)
    value = _reader(name).read(_run(t_proc, t_open, fr.now_ns()))
    if name == "longest_silence_ms.serve":
        assert value is not None and 0 <= value < 1e3
    else:
        assert value is None


def test_longest_silence_names_the_span_and_the_stall(
        recorder, bench_modules, capsys):
    t_proc = fr.now_ns()
    t_open = _traffic()
    with fr.span("serve.step") as step:
        with fr.span("serve.poll"):
            with fr.span("serve.sync", site="poll", steps_queued=4,
                         ahead=1) as sync:
                time.sleep(0.03)
        step.set(decode=1, gc_ms=0.0)
    fr.record("serve.stall", ms=30.0, span="serve.sync", site="poll",
              gc_ms=0.0, samples=1, top="engine.py:1 read", others="")
    # (stamped where the stretch ended, as the watcher stamps it)
    fr.recorder()._buf[-1] = (sync.end_ns,) + fr.recorder()._buf[-1][1:]
    value = _reader("longest_silence_ms.serve").read(
        _run(t_proc, t_open, fr.now_ns()))
    assert value == pytest.approx((sync.end_ns - sync.start_ns) / 1e6)
    err = capsys.readouterr().err
    assert "inside serve.sync {'site': 'poll'" in err
    assert "serve.stall: ms=30.0" in err and "top='engine.py:1 read'" in err


@pytest.mark.parametrize("name", NAMES)
def test_reader_refuses_a_cut_window(name, recorder, bench_modules):
    """The ring holds less than the run recorded: something inside the
    interval went, so the reader gives nothing rather than a number
    computed from what was left."""
    fr.configure(capacity=16)
    t_proc = fr.now_ns()
    t_open = _traffic()
    assert fr.dropped_since(t_open) > 0
    assert _reader(name).read(_run(t_proc, t_open, fr.now_ns())) is None


@pytest.mark.parametrize("how", ["off", "before_the_spans"])
def test_reader_gives_nothing_without_the_recorder(
        how, recorder, bench_modules, monkeypatch):
    """The benchmark files are laid over the parent's checkout too: a
    program whose recorder has no ``dropped_since`` (or one switched
    off) yields ``None`` from every reader, and no error."""
    t_proc = fr.now_ns()
    t_open = _traffic()
    if how == "off":
        fr.disable()
    else:
        monkeypatch.delattr(fr, "dropped_since")
    run = _run(t_proc, t_open, fr.now_ns())
    assert [_reader(n).read(run) for n in NAMES] == [None] * len(NAMES)
