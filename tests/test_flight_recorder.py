"""Flight recorder coverage (ISSUE 10): the bounded event ring, span
storage, Perfetto/plaintext dumps, auto-dump rate limiting, the wiring
into retraces and the profiler export — and the chaos-tier acceptance
scenarios: a Watchdog timeout and a SIGTERM mid-``serve_forever`` each
leave a dump containing the stalled/in-flight request's spans."""
import glob
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flight_recorder as fr
from paddle_tpu.core import monitor


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Every test starts with an empty, enabled ring and leaves the
    process defaults behind (capacity reset also clears the auto-dump
    rate-limit state, so scenarios don't starve each other)."""
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)
    yield
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)


# ----------------------------------------------------------------- ring


class TestRing:
    def test_record_and_read(self):
        fr.record("test.alpha", a=1)
        fr.record("test.beta")
        evs = fr.events()
        kinds = [k for _, k, _ in evs]
        assert kinds == ["test.alpha", "test.beta"]
        assert evs[0][2] == {"a": 1}
        assert evs[1][2] is None
        assert evs[0][0] <= evs[1][0]  # ns timestamps, monotonic

    def test_ring_bound_evicts_oldest(self):
        r = fr.configure(capacity=8)
        for i in range(20):
            fr.record("test.n", i=i)
        evs = r.events()
        assert len(evs) == 8
        assert [e[2]["i"] for e in evs] == list(range(12, 20))
        assert r._dropped == 12

    def test_disabled_records_nothing(self):
        fr.disable()
        fr.record("test.off", x=1)
        fr.record_span("test.span", 0, 1)
        assert fr.events() == []
        fr.enable()
        fr.record("test.on")
        assert len(fr.events()) == 1

    def test_spans_between(self):
        t0 = fr.now_ns()
        fr.record_span("req1.decode", t0, t0 + 1000, trace_id="x.1",
                       tid=1001, tokens=3)
        fr.record("test.point")  # point events never surface as spans
        fr.record_span("early", t0 - 5000, t0 - 4000)
        spans = fr.spans_between(t0 - 100, t0 + 2000)
        assert [tuple(s) for s in spans] == [
            ("req1.decode", t0, t0 + 1000, 1001, spans[0].id, None,
             "x.1", {"tokens": 3})]

    def test_span_helper_links_parent_and_child(self):
        with fr.span("serve.step", queued=2) as outer:
            with fr.span("serve.poll") as inner:
                inner.set(emitted=3)
            explicit = fr.record_span("serve.sync", outer.start_ns,
                                      outer.start_ns + 5,
                                      parent=outer.id, site="poll")
        poll, sync, step = fr.spans_between(0, 2 ** 62)  # end order
        assert (step.name, step.parent, step.fields) == \
            ("serve.step", None, {"queued": 2})
        assert (poll.name, poll.parent, poll.fields) == \
            ("serve.poll", step.id, {"emitted": 3})
        assert (sync.id, sync.parent, sync.fields) == \
            (explicit, step.id, {"site": "poll"})
        assert len({poll.id, sync.id, step.id}) == 3
        assert step.start_ns <= poll.start_ns <= poll.end_ns \
            <= step.end_ns == outer.end_ns

    def test_span_parent_is_per_thread(self):
        import threading
        seen = {}

        def other():
            with fr.span("serve.step") as sp:
                seen["parent"] = sp.parent
        with fr.span("setup.warmup"):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert seen["parent"] is None

    def test_span_closes_on_error_and_restores_parent(self):
        with pytest.raises(ValueError):
            with fr.span("serve.step"):
                with fr.span("serve.admit"):
                    raise ValueError("boom")
        with fr.span("serve.step") as after:
            pass
        assert after.parent is None        # the thread's parent is back
        assert [s.name for s in fr.spans_between(0, 2 ** 62)] == \
            ["serve.admit", "serve.step", "serve.step"]

    def test_span_records_through_record_span(self):
        """``span()`` draws its id at the start (children name it) and
        lands through ``record_span`` with that id."""
        with fr.span("serve.step", queued=1) as outer:
            with fr.span("serve.poll") as inner:
                pass
            outer.set(decode=1)
        free = fr.record_span("serve.sync", 0, 1, site="stats")
        by_id = {s.id: s for s in fr.spans_between(0, 2 ** 62)}
        assert len({outer.id, inner.id, free}) == 3
        assert by_id[inner.id].parent == outer.id
        assert by_id[outer.id].fields == {"queued": 1, "decode": 1}
        assert by_id[free].fields == {"site": "stats"}

    def test_disabled_span_stamps_nothing(self):
        fr.disable()
        with fr.span("serve.step", a=1) as sp:
            sp.set(b=2)
        assert (sp.id, sp.start_ns, sp.end_ns) == (None, 0, 0)
        assert fr.record_span("serve.sync", 0, 1) is None
        fr.enable()
        assert fr.events() == []

    @pytest.mark.parametrize("cut", [False, True])
    def test_dropped_since_tells_a_cut_window(self, cut):
        """A reader can tell a whole window from one the ring bound cut
        into."""
        fr.configure(capacity=8)
        t0 = fr.now_ns()
        for i in range(6):
            fr.record("test.before", i=i)
        t_open = fr.now_ns()
        for i in range(12 if cut else 6):
            with fr.span("serve.step"):
                pass
        assert fr.dropped_since(t0) > 0        # the early ones went
        assert (fr.dropped_since(t_open) > 0) == cut
        fr.clear()
        assert fr.dropped_since(0) == 0

    def test_one_clock(self):
        """The recorder's clock is the requests' and the harness's
        (CLOCK_MONOTONIC), and agrees with perf_counter, which the
        profiler's host spans use, to 1 ms."""
        a, b, c = fr.now_ns(), time.perf_counter_ns(), fr.now_ns()
        assert a <= c and abs((a + c) // 2 - b) < 1_000_000
        assert abs(fr.now_ns() * 1e-9 - time.monotonic()) < 1e-3
        fr.record("test.stamp")
        assert abs(fr.events()[-1][0] - fr.now_ns()) < 1e9

    def test_default_capacity_holds_a_benchmark_window(self):
        # ISSUE 36: a whole run of the busiest cell on the chip left
        # 37,382 events (nemotron3n-l13-offline; gpt3l8-chat 27,035,
        # gpt3l8-offline 30,328): x 4
        assert fr.DEFAULT_CAPACITY == 262144 >= 4 * 37382
        assert fr.capacity() == fr.DEFAULT_CAPACITY

    def test_env_capacity_parse(self, monkeypatch):
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", "off")
        assert fr._env_capacity() == (False, fr.DEFAULT_CAPACITY)
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", "0")
        assert fr._env_capacity()[0] is False
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", "128")
        assert fr._env_capacity() == (True, 128)
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", "bogus")
        assert fr._env_capacity() == (True, fr.DEFAULT_CAPACITY)


# ---------------------------------------------------------------- dumps


class TestDumps:
    def test_dump_writes_perfetto_and_tail(self, tmp_path):
        t = fr.now_ns()
        fr.record("test.kind", a=1)
        fr.record_span("req7.prefill", t, t + 500000, trace_id="p.7",
                       tid=1007)
        path = fr.dump(str(tmp_path / "d"), reason="unit")
        assert path.endswith(".json")
        with open(path) as f:
            d = json.load(f)
        assert d["metadata"]["reason"] == "unit"
        names = {e["name"] for e in d["traceEvents"]}
        assert {"test.kind", "req7.prefill"} <= names
        span = next(e for e in d["traceEvents"]
                    if e["name"] == "req7.prefill")
        assert span["ph"] == "X" and span["dur"] == pytest.approx(500.0)
        assert span["args"]["trace"] == "p.7"
        inst = next(e for e in d["traceEvents"]
                    if e["name"] == "test.kind")
        assert inst["ph"] == "i" and inst["args"] == {"a": 1}
        txt = (tmp_path / "d.txt").read_text()
        assert "reason: unit" in txt
        assert "test.kind a=1" in txt
        assert "span req7.prefill" in txt

    def test_auto_dump_rate_limit_and_counter(self, tmp_path,
                                              monkeypatch):
        from paddle_tpu.profiler import metrics
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
        metrics.enable()
        try:
            fr.record("test.crash")
            p1 = fr.auto_dump("unitreason")
            p2 = fr.auto_dump("unitreason")       # inside min interval
            p3 = fr.auto_dump("unitreason2")      # different reason: ok
            assert p1 is not None and os.path.exists(p1)
            assert p2 is None
            assert p3 is not None
            snap = metrics.snapshot()
            assert snap["flightrecorder.dumps{reason=unitreason}"][
                "value"] == 1
            assert snap["flightrecorder.dumps{reason=unitreason2}"][
                "value"] == 1
        finally:
            metrics.disable()

    def test_auto_dump_writes_the_newest_events_only(self, tmp_path,
                                                     monkeypatch):
        """The crash path serialises AUTO_DUMP_EVENTS, not the whole
        262,144-event ring; a dump on demand writes everything."""
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
        monkeypatch.setattr(fr, "AUTO_DUMP_EVENTS", 8)
        for i in range(20):
            fr.record("test.kind", i=i)
        with open(fr.auto_dump("bounded")) as f:
            d = json.load(f)
        got = [e["args"]["i"] for e in d["traceEvents"]
               if e["name"] == "test.kind"]
        assert got == list(range(12, 20))
        assert d["metadata"]["events"] == 20
        with open(fr.dump(str(tmp_path / "all"))) as f:
            assert len(json.load(f)["traceEvents"]) == 21
        assert [e[2]["i"] for e in fr.events(3)] == [17, 18, 19]
        assert len(fr.events(100)) == 20

    def test_auto_dump_cap(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
        r = fr.recorder()
        r._auto_dumps = fr.MAX_AUTO_DUMPS
        assert fr.auto_dump("capped") is None

    def test_disabled_auto_dump_noop(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
        fr.disable()
        assert fr.auto_dump("off") is None
        assert not list(tmp_path.iterdir())

    def test_dump_identity_and_clock_metadata(self, tmp_path,
                                              monkeypatch):
        """ISSUE-15: dumps carry (rank, restart_count, pid) in the
        default FILENAME (N processes share one dump dir without
        clobbering) and the clock mapping (anchors + fleet offset) in
        the metadata (what tools/trace_merge aligns on)."""
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
        monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
        monkeypatch.setenv("PADDLE_RESTART_COUNT", "2")
        fr.enable()
        fr.set_clock_offset_ns(12345)
        try:
            fr.record("checkpoint.commit", step=1)
            path = fr.dump(reason="unit")
            name = os.path.basename(path)
            assert name.startswith(
                f"flightrecorder_unit_r3i2_p{os.getpid()}_")
            with open(path) as f:
                d = json.load(f)
            md = d["metadata"]
            assert md["rank"] == 3 and md["restart_count"] == 2
            assert md["clock_offset_ns"] == 12345
            assert isinstance(md["anchor_wall_ns"], int)
            assert isinstance(md["anchor_perf_ns"], int)
            proc = next(e for e in d["traceEvents"]
                        if e["name"] == "process_name")
            assert proc["args"]["name"].startswith("rank3.2 ")
            assert "rank: 3, incarnation: 2" in \
                open(path[:-5] + ".txt").read()
        finally:
            fr.set_clock_offset_ns(0)


class TestEventSchema:
    def test_event_doc_covers_declared_events(self):
        assert set(fr.EVENT_DOC) == set(fr.DECLARED_EVENTS)
        for name, desc in fr.EVENT_DOC.items():
            assert desc and "\n" not in desc, name

    def test_declared_spans_one_line_each(self):
        assert set(fr.DECLARED_SPANS) >= {
            "serve.step", "serve.admit", "serve.sync", "serve.dispatch",
            "serve.poll", "serve.queue_wait", "serve.prefill",
            "setup.engine_init", "setup.state", "setup.cache_alloc",
            "setup.warmup", "jit.program", "train.step"}
        for name, desc in fr.DECLARED_SPANS.items():
            assert desc and "\n" not in desc and "|" not in desc, name

    def test_generated_events_doc_is_fresh(self):
        """Tier-1 drift gate: docs/events.md must match what
        tools.metrics_doc renders from the live event schema."""
        from tools.metrics_doc import events_doc_path, render_events
        with open(events_doc_path(), "r", encoding="utf-8") as f:
            committed = f.read()
        assert committed == render_events(), (
            "docs/events.md is stale — regenerate with "
            "`python -m tools.metrics_doc`")


# --------------------------------------------------------------- wiring


class TestWiring:
    def test_retrace_lands_in_recorder_without_monitor(self):
        """jit compiles reach the black box even when the metrics
        registry was never enabled — the post-mortem contract."""
        from paddle_tpu.profiler import metrics
        assert not metrics.is_enabled()

        def _total():
            snap = metrics.snapshot().get("jit.compile.total")
            return snap["value"] if snap else 0

        import paddle_tpu.jit as jit
        before = _total()  # registry history survives disable by design

        @jit.to_static
        def f(x):
            return x * 2

        f(paddle.to_tensor(np.ones((3,), np.float32)))
        compiles = [e for e in fr.events() if e[1] == "jit.compile"]
        assert compiles and compiles[0][2]["cause"] == "first"
        # and the (disabled) metrics registry stayed untouched
        assert _total() == before

    def test_profiler_export_includes_recorder_spans(self, tmp_path):
        """Spans recorded while a Profiler records join its Perfetto
        JSON — sampled request traces and RecordEvent spans share one
        timeline."""
        from paddle_tpu import profiler as P
        prof = P.Profiler(trace_dir=str(tmp_path))
        prof.start()
        t = fr.now_ns()
        fr.record_span("req3.decode", t, t + 100000, trace_id="z.3",
                       tid=1003)
        with P.RecordEvent("host_work"):
            pass
        prof.stop()
        out = tmp_path / "trace.json"
        prof.result.export_chrome_tracing(str(out))
        names = {e["name"] for e in
                 json.load(open(out))["traceEvents"]}
        assert "req3.decode" in names
        assert "host_work" in names

    def test_spans_lie_on_the_device_traces_host_plane(self, tmp_path):
        """Each span the helper opens is a jax.profiler.TraceAnnotation:
        while a device trace is being taken it lands on the trace's
        host plane, in the trace's clock."""
        import glob as _glob
        import jax
        jax.profiler.start_trace(str(tmp_path))
        try:
            with fr.span("serve.step"):
                with fr.span("serve.sync", site="poll"):
                    jax.numpy.ones((8,)).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        (path,) = _glob.glob(str(
            tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        found = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
                 for plane in data.planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name in ("serve.step", "serve.sync")}
        assert set(found) == {"serve.step", "serve.sync"}
        (s0, s1), (c0, c1) = found["serve.step"], found["serve.sync"]
        assert s0 <= c0 <= c1 <= s1

    def test_fit_crash_dumps(self, tmp_path, monkeypatch):
        """An uncaught exception inside Model.fit leaves a fit_crash
        dump with the last dispatched steps in it."""
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
        from paddle_tpu import nn, optimizer
        from paddle_tpu.hapi import Model
        from paddle_tpu.hapi.callbacks import Callback

        class Bomb(Callback):
            def on_train_batch_end(self, step, logs=None):
                if step >= 1:
                    raise RuntimeError("injected trainer bug")

        paddle.seed(0)
        net = nn.Linear(4, 2)
        m = Model(net)
        m.prepare(optimizer=optimizer.SGD(learning_rate=0.1,
                                          parameters=net.parameters()),
                  loss=lambda out, lbl: (out ** 2).mean())
        data = [(np.ones((2, 4), np.float32),
                 np.zeros((2,), np.int64)) for _ in range(4)]
        monkeypatch.setenv("PADDLE_ASYNC_STEPS", "0")
        with pytest.raises(RuntimeError, match="injected trainer bug"):
            m.fit(data, epochs=1, verbose=0, callbacks=[Bomb()])
        dumps = glob.glob(str(tmp_path / "flightrecorder_fit_crash_*"
                              ".json"))
        assert len(dumps) == 1
        d = json.load(open(dumps[0]))
        names = [e["name"] for e in d["traceEvents"]]
        assert "train.step_begin" in names
        assert "fit.crash" in names


# ---------------------------------------------------------------- chaos
# The acceptance scenarios: each failure mode leaves a dump from which
# the in-flight request's trace can be read back.


def _tiny_engine(**kw):
    from paddle_tpu.inference import Config
    from paddle_tpu.models.gpt import gpt
    from paddle_tpu.serving import ServingEngine
    paddle.seed(0)
    m = gpt("test-tiny")
    m.eval()
    spec = [paddle.to_tensor(np.zeros((2, 12), np.int32))]
    cfg = (Config().from_layer(m, spec)
           .enable_generation(max_new_tokens=8, prefill_buckets=(16,),
                              max_batch=2))
    return ServingEngine(cfg, trace_sample=1, **kw)


def _req_spans(dump_path):
    """{(span name, request id)} of the dump's request spans, and the
    dump."""
    d = json.load(open(dump_path))
    return {(e["name"], e["args"]["req"]) for e in d["traceEvents"]
            if e["ph"] == "X" and "req" in e.get("args", {})}, d


@pytest.mark.chaos
def test_watchdog_timeout_dumps_inflight_request_spans(tmp_path,
                                                       monkeypatch):
    """A Watchdog expiry while a request is mid-decode produces a dump
    whose trace holds that request's queue-wait/prefill spans — the
    post-mortem shows what the wedged replica was serving."""
    from paddle_tpu.distributed.resilience import (Watchdog,
                                                   WatchdogTimeout)
    monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
    eng = _tiny_engine(poll_every=4)
    h = eng.submit(np.arange(1, 9, dtype=np.int32))
    eng.step()                        # admit: queue_wait+prefill spans
    assert h.status.value == "running"
    with pytest.raises(WatchdogTimeout):
        with Watchdog(timeout=0.2, label="test.stall",
                      dump_stacks=False):
            t0 = time.monotonic()
            while time.monotonic() - t0 < 10:   # stalled host loop
                pass
    dumps = glob.glob(str(tmp_path / "flightrecorder_watchdog_*.json"))
    assert len(dumps) == 1
    spans, d = _req_spans(dumps[0])
    assert ("serve.queue_wait", h.id) in spans
    assert ("serve.prefill", h.id) in spans
    assert any(e["name"] == "watchdog.timeout"
               and e["args"]["label"] == "test.stall"
               for e in d["traceEvents"])
    eng.drain()


@pytest.mark.chaos
def test_sigterm_mid_serve_dumps_inflight_request_spans(tmp_path,
                                                        monkeypatch):
    """SIGTERM mid-serve_forever: the preemption dump (written BEFORE
    the drain) carries the spans of the requests that were decoding
    when the signal landed, plus the drain's own begin/end events in a
    follow-up read of the ring."""
    import signal
    from paddle_tpu.distributed.resilience import GracefulShutdown
    from paddle_tpu.utils.fault_injection import KillAfter
    monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
    eng = _tiny_engine(poll_every=2, drain_timeout_s=60.0)
    rng = np.random.RandomState(1)
    traffic = [rng.randint(0, 512, 4 + i).astype(np.int32)
               for i in range(4)]
    killer = KillAfter(4, signal.SIGTERM)
    with GracefulShutdown(exit_on_save=False):
        handles = eng.serve_forever(iter(traffic),
                                    on_step=lambda e: killer.step())
    assert killer.fired
    assert all(h.status.terminal for h in handles)
    dumps = glob.glob(str(tmp_path /
                          "flightrecorder_preemption_*.json"))
    assert len(dumps) == 1
    spans, d = _req_spans(dumps[0])
    names = [e["name"] for e in d["traceEvents"]]
    assert "serve.preempted" in names
    # the dump happens before the drain, so at least one admitted
    # request's spans are already in the ring
    admitted = [h for h in handles if h.admitted_at is not None]
    assert admitted
    assert any(("serve.prefill", h.id) in spans for h in admitted)
    # the ring (post-drain) holds the drain bracket too
    kinds = [k for _, k, _ in fr.events()]
    assert "serve.drain_begin" in kinds and "serve.drain_end" in kinds
