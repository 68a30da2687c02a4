"""Observability overhead gate: a disabled RecordEvent span plus a
disabled counter increment must stay under 5 µs/op on CPU, so
instrumentation creep can never silently slow the hot path. Runs in
tier-1 (deliberately NOT marked slow); the budget is ~50x the measured
cost on a warm CPython, so scheduler noise doesn't flake it."""
import time

import pytest

from paddle_tpu.core import flight_recorder as _fr
from paddle_tpu.core import monitor
from paddle_tpu.profiler import RecordEvent, metrics

BUDGET_US = 5.0
N = 20000


def _measure() -> float:
    c = metrics.counter("gate.disabled")
    t0 = time.perf_counter()
    for _ in range(N):
        with RecordEvent("gate_span"):
            c.inc()
    return (time.perf_counter() - t0) / N * 1e6  # µs/op


def test_disabled_instrumentation_under_budget():
    metrics.disable()
    assert not monitor.enabled
    _measure()  # warm up allocator + bytecode caches
    best = min(_measure() for _ in range(3))
    assert best < BUDGET_US, (
        f"disabled RecordEvent+counter costs {best:.2f}µs/op "
        f"(budget {BUDGET_US}µs) — instrumentation crept into the "
        f"disabled hot path")
    assert metrics.counter("gate.disabled").value == 0  # truly off


# --------------------------------------------------- step pipeline layer
# The async step pipeline must be free when OFF: a lag-0 fetcher
# (PADDLE_ASYNC_STEPS=0, the fully synchronous mode) and an idempotent
# re-placement of an already-resident batch may add <10 µs of host work
# per train step, or the "optimization" taxes every non-pipelined user.

PIPELINE_BUDGET_US = 10.0
N_STEPS = 5000


def _measure_fetcher() -> float:
    from paddle_tpu.hapi.model import AsyncScalarFetcher
    f = AsyncScalarFetcher(lag=0)
    t0 = time.perf_counter()
    for i in range(N_STEPS):
        for _ in f.push(i, 0.5):
            pass
    f.drain()
    return (time.perf_counter() - t0) / N_STEPS * 1e6


def test_async_fetcher_disabled_under_budget():
    metrics.disable()
    _measure_fetcher()  # warm up
    best = min(_measure_fetcher() for _ in range(3))
    assert best < PIPELINE_BUDGET_US, (
        f"lag-0 AsyncScalarFetcher costs {best:.2f}µs/step "
        f"(budget {PIPELINE_BUDGET_US}µs)")


def _measure_place(batch) -> float:
    from paddle_tpu.io.device_prefetch import place_batch
    t0 = time.perf_counter()
    for _ in range(N_STEPS):
        place_batch(batch)  # every leaf already resident: all skips
    return (time.perf_counter() - t0) / N_STEPS * 1e6


def test_idempotent_placement_under_budget():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.io.device_prefetch import place_batch
    metrics.disable()
    x = paddle.to_tensor(np.zeros((4, 8), np.float32))
    y = paddle.to_tensor(np.zeros((4,), np.int64))
    batch = (x, y)
    out = place_batch(batch)  # warm up; also prove it is a pass-through
    assert out[0] is x and out[1] is y
    best = min(_measure_place(batch) for _ in range(3))
    assert best < PIPELINE_BUDGET_US, (
        f"idempotent place_batch costs {best:.2f}µs/step "
        f"(budget {PIPELINE_BUDGET_US}µs) — the skip path regrew "
        f"per-step transfers or tree walks")


# ---------------------------------------------------- telemetry layer
# The flight recorder promises a SUB-MICROSECOND disabled path (it sits
# on per-step, per-collective and per-request call sites), and the
# per-request tracing helper must be free for the 7-in-8 unsampled
# requests. Budgets are ~5-10x the measured warm-CPython cost.

RECORDER_BUDGET_US = 1.0


def _measure_recorder() -> float:
    from paddle_tpu.core import flight_recorder as fr
    t0 = time.perf_counter()
    for _ in range(N):
        fr.record("gate.off", step=1)
    return (time.perf_counter() - t0) / N * 1e6


def test_flight_recorder_disabled_under_budget():
    from paddle_tpu.core import flight_recorder as fr
    was = fr.is_enabled()
    fr.disable()
    try:
        n0 = len(fr.events())
        _measure_recorder()  # warm up
        best = min(_measure_recorder() for _ in range(3))
        assert len(fr.events()) == n0  # truly off
    finally:
        fr.configure(on=was)
    assert best < RECORDER_BUDGET_US, (
        f"disabled flight_recorder.record costs {best:.2f}µs/op "
        f"(budget {RECORDER_BUDGET_US}µs) — the disabled path must "
        "stay a bool check")


def _measure_untraced_span(req) -> float:
    t0 = time.perf_counter()
    for _ in range(N):
        req.span("decode", 0, 1, tokens=1)
    return (time.perf_counter() - t0) / N * 1e6


def test_request_tracing_off_under_budget():
    import numpy as np
    from paddle_tpu.serving.request import Request, RequestParams
    req = Request(np.arange(4, dtype=np.int32), RequestParams(), 4,
                  None)
    assert not req.traced  # the engine samples 1-in-N; default is off
    _measure_untraced_span(req)  # warm up
    best = min(_measure_untraced_span(req) for _ in range(3))
    assert best < RECORDER_BUDGET_US, (
        f"untraced Request.span costs {best:.2f}µs/op "
        f"(budget {RECORDER_BUDGET_US}µs) — tracing-off must stay one "
        "attribute check")


# ------------------------------------------------- fleet/goodput layer
# The fleet plane publishes from a background thread — there is no
# per-step hook at all — so the only per-step cost its OFF path may
# add is the goodput ledger's ambient charge with no ledger active:
# one truthiness check (the ISSUE-15 <10µs/step publish-loop gate).


def _measure_ambient_goodput() -> float:
    from paddle_tpu.core import goodput
    t0 = time.perf_counter()
    for _ in range(N_STEPS):
        goodput.charge("checkpoint", 0.001)
        with goodput.timed("compute"):
            pass
    return (time.perf_counter() - t0) / N_STEPS * 1e6


def test_ambient_goodput_disabled_under_budget():
    from paddle_tpu.core import goodput
    assert goodput.active() is None  # nothing on the ambient stack
    _measure_ambient_goodput()  # warm up
    best = min(_measure_ambient_goodput() for _ in range(3))
    assert best < PIPELINE_BUDGET_US, (
        f"ambient goodput charge with no active ledger costs "
        f"{best:.2f}µs/step (budget {PIPELINE_BUDGET_US}µs) — the "
        "fleet/goodput off path must stay a truthiness check")


# ----------------------------------------------------- SLO watchtower
# slo.tick() sits inside the serving poll loop and the fit loop's step
# section. Its not-due path must stay one clock read + compare (ring
# not due) and its registry-off path one bool check, or the watchtower
# taxes every step it is supposed to be observing.


def _measure_maybe_sample(ring) -> float:
    t0 = time.perf_counter()
    for _ in range(N):
        ring.maybe_sample()
    return (time.perf_counter() - t0) / N * 1e6


def test_timeseries_not_due_under_budget():
    from paddle_tpu.core import timeseries
    metrics.disable()
    ring = timeseries.TimeSeriesRing(period_s=3600.0, retention=4)
    ring.sample()  # arms _next_due an hour out: every call is not-due
    _measure_maybe_sample(ring)  # warm up
    best = min(_measure_maybe_sample(ring) for _ in range(3))
    assert len(ring) == 1  # truly not due
    assert best < BUDGET_US, (
        f"not-due TimeSeriesRing.maybe_sample costs {best:.2f}µs/op "
        f"(budget {BUDGET_US}µs) — the record path must stay a clock "
        "read + compare")


def _measure_slo_tick() -> float:
    from paddle_tpu.core import slo
    t0 = time.perf_counter()
    for _ in range(N):
        slo.tick()
    return (time.perf_counter() - t0) / N * 1e6


def test_slo_tick_disabled_under_budget():
    from paddle_tpu.core import slo
    metrics.disable()
    assert not monitor.enabled
    assert slo.tick() is False  # registry off: nothing evaluated
    _measure_slo_tick()  # warm up
    best = min(_measure_slo_tick() for _ in range(3))
    assert best < BUDGET_US, (
        f"registry-off slo.tick costs {best:.2f}µs/op "
        f"(budget {BUDGET_US}µs) — the off path must stay a bool "
        "check")


# ------------------------------------------------- spans (ISSUE 26)
# The recorder is ON by default and in every benchmark run, and the
# scheduler opens spans inside every iteration. Off, a span is the bool
# check, held to the absolute 1 µs of the gates above. On, what the
# recorder adds is measured on the real calls, with and without it:
# ``engine.step()`` of a live engine whose decode program is swapped for
# one that hands its lanes back unchanged (the host side of a steady
# decode iteration, polls included, with no device work to drown it),
# the tracing of one admission through the engine's own ``_dequeued`` /
# ``_sync`` / ``_first_token``, and ``TrainStep`` / ``DistributedTrainStep``
# calls with the jitted step swapped the same way. Thread CPU time, the
# least of many short batches, on less off. Measured here: 6.5 µs an
# iteration (2.75 records; 3.5 since a full engine's poll dispatches a
# step ahead of its read; 4.0 since ISSUE 36 split the poll's telemetry
# and the copies' dispatch out), 8.7 µs an admission, 3.8-4.5 µs a
# TrainStep call and 2.5-2.8 µs a DistributedTrainStep call; under eight
# busy processes on the eight cores the same to within 0.3 µs. ISSUE 36
# put a compare and a store of the boundary's stamp in every span's open
# and close (the stall watcher reads it) and the collector on the record:
# a span alone (``serve.plan``, ``serve.telemetry``) and a ``gc``
# callback pair are held below.

STEP_BUDGET_US = 25.0            # added per engine.step(), and per admission
# ISSUE 26 asks for 5 µs and a call reads 2.5-4.5; the gate holds it
# to twice that, since a threshold a tenth above a reading would flake
TRAIN_STEP_BUDGET_US = 10.0


def _cpu_us(fn, n: int = 50, batches: int = 100) -> float:
    """µs of this thread's CPU time per call of ``fn``: the least of
    ``batches`` batches of ``n`` (a batch that kept its core)."""
    best = float("inf")
    for _ in range(batches):
        t0 = time.thread_time()
        for _ in range(n):
            fn()
        best = min(best, (time.thread_time() - t0) / n * 1e6)
    return best


def _on_less_off(fn) -> float:
    """What the recorder adds to one call of ``fn``, in µs."""
    was = _fr.is_enabled()
    try:
        cost = {}
        for on in (True, False) * 4:
            _fr.configure(capacity=_fr.DEFAULT_CAPACITY, on=on)
            fn()  # warm up
            cost[on] = min(cost.get(on, float("inf")), _cpu_us(fn))
            if on:
                assert _fr.events()  # truly on
        return cost[True] - cost[False]
    finally:
        _fr.configure(capacity=_fr.DEFAULT_CAPACITY, on=was)


def _span_with_set():
    with _fr.span("serve.sync", site="poll", steps_queued=3) as sp:
        sp.set(emitted=1)


def test_span_off_under_budget():
    was = _fr.is_enabled()
    _fr.disable()
    try:
        _cpu_us(_span_with_set, 2000, 1)  # warm up
        best = _cpu_us(_span_with_set, 2000, 30)
        assert _fr.events() == [] or was  # truly off
    finally:
        _fr.configure(on=was)
    assert best < RECORDER_BUDGET_US, (
        f"flight_recorder.span with the recorder off costs {best:.2f}"
        f"µs (budget {RECORDER_BUDGET_US}µs) — the off path must stay "
        "the bool check")


class _Frozen:
    """Stands in for a jitted step: hands back what it was given (no
    device work, nothing donated), with the jit cache's size reader."""

    def __init__(self, out):
        self._out = out

    def __call__(self, *args):
        return self._out(*args)

    def _cache_size(self):
        return 1


def _tiny_engine():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config
    from paddle_tpu.models.gpt import gpt
    from paddle_tpu.serving import RequestParams, ServingEngine
    paddle.seed(0)
    m = gpt("test-tiny")
    m.eval()
    spec = [paddle.to_tensor(np.zeros((2, 12), np.int32))]
    cfg = (Config().from_layer(m, spec)
           .enable_generation(max_new_tokens=64, prefill_buckets=(16,),
                              max_batch=2))
    # the default poll cadence and sampling, as a benchmark run has them
    eng = ServingEngine(cfg)
    handles = [eng.submit(np.arange(1, 9, dtype=np.int32) + i,
                          RequestParams(max_new_tokens=64))
               for i in range(2)]
    for _ in range(2 * eng.poll_every):
        eng.step()
    assert all(h.status.value == "running" for h in handles)
    return eng, handles


def test_engine_step_spans_under_budget():
    """Added host time per ``engine.step()`` in steady decode, recorder
    on less off: ``serve.step`` and ``serve.dispatch{program=step}``
    every iteration, ``serve.poll`` with its ``serve.sync``, its
    ``serve.dispatch{program=poll_view}`` and its two ``serve.telemetry``
    (the window's charge before the completions, the gauges after them)
    every ``poll_every``-th, the ``set()`` calls and the sampled
    request's decode segment."""
    eng, _ = _tiny_engine()
    try:
        # both lanes live for ever
        eng._exes[("step",)] = _Frozen(lambda state, *carried: carried)
        _fr.configure(capacity=_fr.DEFAULT_CAPACITY, on=True)
        # both slots hold a request, so a poll dispatches the next step
        # ahead of its read: poll_every steps take poll_every - 1 = 3
        # iterations, which record 3 steps, 4 decode dispatches, and the
        # poll's 5 (poll, sync, the copies' dispatch, two telemetry):
        # 12 / 3 = 4.0, and a decode segment a poll for each sampled
        # lane (two at most: 14 / 3)
        polls0 = eng.stats["polls"]
        iters = 4 * (eng.poll_every - 1)
        for _ in range(iters):
            eng.step()
        names = [f["name"] for _, kind, f in _fr.events() if kind == "span"]
        per_step = len(_fr.events()) / iters
        assert eng.stats["polls"] - polls0 == 4
        assert 4.0 <= per_step <= 4.7, per_step
        assert names.count("serve.telemetry") == 2 * 4
        added = _on_less_off(eng.step)
    finally:
        eng.shutdown()
    assert added < STEP_BUDGET_US, (
        f"the recorder adds {added:.1f}µs to one engine.step() "
        f"(budget {STEP_BUDGET_US}µs)")


def _plan_span():
    with _fr.span("serve.plan", req=7) as sp:
        sp.set(pages=3, shared=0)


def _telemetry_span():
    with _fr.span("serve.telemetry"):
        pass


@pytest.mark.parametrize("one_span", [_plan_span, _telemetry_span],
                         ids=["serve.plan", "serve.telemetry"])
def test_one_span_under_budget(one_span):
    """One span of those ISSUE 36 adds to an iteration, on less off: the
    two stamps, the compare and store of the thread's last boundary at
    each, the annotation, the locked append."""
    _fr.configure(capacity=_fr.DEFAULT_CAPACITY, on=True)
    with _fr.span("serve.step") as step:
        one_span()
        # the boundary store: the last stamp is the child's close
        (child,) = [f for _, kind, f in _fr.events() if kind == "span"]
        assert _fr._tls.st.stamp == child["end_ns"]
    assert _fr._tls.st.stamp == step.end_ns
    added = _on_less_off(one_span)
    assert added < TRAIN_STEP_BUDGET_US, (
        f"one span costs {added:.1f}µs on less off (budget "
        f"{TRAIN_STEP_BUDGET_US}µs)")


def test_gc_callback_pair_under_budget():
    """What the recorder's ``gc.callbacks`` entry adds to every
    collection, young ones included (hundreds a second): two stamps and
    a sum; only a long or a full one is made a span."""
    info = {"generation": 0, "collected": 0, "uncollectable": 0}

    def pair():
        _fr._on_gc("start", info)
        _fr._on_gc("stop", info)

    _fr.configure(capacity=_fr.DEFAULT_CAPACITY, on=True)
    total0 = _fr.gc_ns()
    _cpu_us(pair, 2000, 1)  # warm up
    best = _cpu_us(pair, 2000, 30)
    assert _fr.gc_ns() > total0            # every collection is summed
    assert not [f for _, kind, f in _fr.events() if kind == "span"]
    assert best < BUDGET_US, (
        f"a gc callback pair costs {best:.2f}µs (budget {BUDGET_US}µs)")


def test_admission_spans_under_budget():
    """What one admission's tracing adds (about one iteration in eight
    of the busiest benchmark cell has one): its ``serve.admit``, the
    queue wait's end, the prefill's ``serve.sync`` and the first
    token's stamp, through the engine's own methods."""
    eng, (req, _) = _tiny_engine()

    def admission():
        with _fr.span("serve.admit", req=req.id, slot=0, bucket=16,
                      prompt=8) as sp:
            t0 = eng._dequeued(req, sp, 16)
            _, t1 = eng._sync("prefill", int)
            eng._first_token(req, t0, t1, 16)

    try:
        added = _on_less_off(admission)
    finally:
        eng.shutdown()
    assert added < STEP_BUDGET_US, (
        f"the recorder adds {added:.1f}µs to one admission (budget "
        f"{STEP_BUDGET_US}µs)")


def _train_step(kind):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    model = nn.Linear(4, 2)
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())

    def loss_fn(o, y):
        return ((o - y) ** 2).mean()
    x = paddle.to_tensor(np.ones((8, 4), np.float32))
    y = paddle.to_tensor(np.zeros((8, 2), np.float32))
    if kind == "TrainStep":
        return paddle.jit.TrainStep(model, opt, loss_fn), (x, y)
    from paddle_tpu.distributed import fleet
    fleet.init(strategy=fleet.DistributedStrategy(
        hybrid_configs={"dp_degree": 8}))
    step = fleet.DistributedTrainStep(
        fleet.distributed_model(model), fleet.distributed_optimizer(opt),
        loss_fn)
    return step, (x, y)


@pytest.mark.parametrize("kind", ["TrainStep", "DistributedTrainStep"])
def test_train_step_span_under_budget(kind):
    """Added host time per train-step call, recorder on less off: the
    ``train.step`` span and the jit cache's size and the persistent
    cache's hit count read around the dispatch."""
    import paddle_tpu.distributed as dist
    try:
        step, batch = _train_step(kind)
        loss = step(*batch)._data           # compiles
        step._jitted = _Frozen(
            lambda params, opt_state, *rest: (loss, params, opt_state))
        if kind == "DistributedTrainStep":
            # placing the batch on the mesh is a millisecond of host
            # time in which 3 µs cannot be seen
            placed = step._prepare(batch)
            step._prepare = lambda batch: placed
        _fr.configure(capacity=_fr.DEFAULT_CAPACITY, on=True)
        for _ in range(50):
            step(*batch)
        assert [e[2]["name"] for e in _fr.events()] == ["train.step"] * 50
        added = _on_less_off(lambda: step(*batch))
    finally:
        dist.set_hybrid_communicate_group(None)
    assert added < TRAIN_STEP_BUDGET_US, (
        f"the recorder adds {added:.1f}µs to one {kind} call (budget "
        f"{TRAIN_STEP_BUDGET_US}µs)")
