"""Continuous-batching serving engine tests (ISSUE 8).

Covers: the steady-state invariant (ragged multi-request traffic replay
with mid-decode arrivals completes with zero new-shape retraces, every
request bitwise-equal to a sequential Predictor.generate() reference
under greedy decoding, and slot reuse actually exercised), admission
control (queue bound, deadlines — queued and in-flight), eos slot
freeing, the serve.* SLA metrics family + MetricsCallback surfacing,
the tier-1 audit gate on the slot-decode program, the bf16 precision
path, thread mode, and the chaos graceful-shutdown drain.
"""
import dataclasses
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import Config, PrecisionType, create_predictor
from paddle_tpu.models.gpt import gpt
from paddle_tpu.serving import (QueueFull, RequestFailed, RequestParams,
                                RequestStatus, ServingEngine)

import jax.numpy as jnp


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(0)
    m = gpt("test-tiny")
    m.eval()
    return m


def _spec():
    return [paddle.to_tensor(np.zeros((2, 12), np.int32))]


def _config(m, *, max_new=8, buckets=(16,), max_batch=2, eos=None,
            **serving_kw):
    cfg = (Config().from_layer(m, _spec())
           .enable_generation(max_new_tokens=max_new,
                              prefill_buckets=buckets,
                              max_batch=max_batch, eos_token_id=eos))
    if serving_kw:
        cfg.enable_serving(**serving_kw)
    return cfg


@pytest.fixture(scope="module")
def engine(tiny_gpt):
    """Shared 2-slot engine with two prompt buckets (reused across the
    steady-state, inline-pump, and metrics tests — all of which leave
    it drained of traffic but serviceable)."""
    return ServingEngine(_config(tiny_gpt, max_new=8, buckets=(16, 32),
                                 max_batch=2), poll_every=2)


def _counter(name):
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot().get(name)
    return int(snap["value"]) if snap else 0


# ----------------------------------------------------------- validation


def test_engine_requires_layer_and_generation(tiny_gpt):
    with pytest.raises(ValueError, match="live layer"):
        ServingEngine(Config())
    with pytest.raises(ValueError, match="enable_generation"):
        ServingEngine(Config().from_layer(tiny_gpt, _spec()))
    with pytest.raises(ValueError, match="no prefill bucket"):
        # test-tiny max_position_embeddings=128: bucket 512 never fits
        ServingEngine(_config(tiny_gpt, buckets=(512,)), warmup=False)
    eng = ServingEngine(_config(tiny_gpt, max_new=4, buckets=(16,),
                                max_batch=1), warmup=False)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([])
    with pytest.raises(ValueError, match="largest compiled"):
        eng.submit(list(range(17)))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], RequestParams(max_new_tokens=9))


# --------------------------------------------- the steady-state invariant


def test_steady_state_ragged_traffic(tiny_gpt, engine):
    """THE acceptance gate: ragged prompts and budgets, arrivals
    mid-decode, zero retraces after warmup, bitwise parity with the
    sequential Predictor, and a request admitted into a freed slot."""
    from paddle_tpu.core import monitor
    rng = np.random.RandomState(0)
    lens = (5, 12, 20, 7, 3)
    budgets = (8, 3, 6, 5, 8)
    prompts = [rng.randint(0, 512, n).astype(np.int32) for n in lens]
    reused0 = engine.stats["slots_reused"]

    monitor.enable()
    try:
        ns0 = _counter("jit.compile{cause=new_shape}")
        tot0 = _counter("jit.compile.total")
        handles = [engine.submit(p, RequestParams(max_new_tokens=b))
                   for p, b in zip(prompts[:2], budgets[:2])]
        for _ in range(3):          # both slots now mid-decode
            engine.step()
        handles += [engine.submit(p, RequestParams(max_new_tokens=b))
                    for p, b in zip(prompts[2:], budgets[2:])]
        while engine.busy:
            engine.step()
        # steady-state no-retrace invariant: nothing compiled under
        # traffic (every dispatch hit a warm executable)
        assert _counter("jit.compile{cause=new_shape}") - ns0 == 0
        assert _counter("jit.compile.total") - tot0 == 0
    finally:
        monitor.disable()

    assert all(h.status is RequestStatus.COMPLETED for h in handles)
    # slot reuse actually exercised: 5 requests through 2 slots
    assert engine.stats["slots_reused"] - reused0 >= 3

    # bitwise parity with the sequential one-request-at-a-time reference
    pred = create_predictor(_config(tiny_gpt, max_new=8,
                                    buckets=(16, 32), max_batch=1))
    for p, b, h in zip(prompts, budgets, handles):
        ref = pred.generate([p], max_new_tokens=b)[0]
        np.testing.assert_array_equal(h.result(), ref)


def test_result_pumps_inline(engine):
    """submit(); result() makes progress without any pump thread."""
    h = engine.submit(np.arange(1, 9, dtype=np.int32),
                      RequestParams(max_new_tokens=4))
    out = h.result(timeout=60)
    assert out.shape == (4,) and h.status is RequestStatus.COMPLETED
    assert h.ttft is not None and h.ttft >= 0.0


# ---------------------------------------------------- admission control


def test_queue_bound_rejects(tiny_gpt):
    eng = ServingEngine(_config(tiny_gpt, max_new=8, buckets=(16,),
                                max_batch=1, max_queue=1), poll_every=1)
    running = eng.submit([1, 2, 3])
    eng.step()                       # admitted into the only slot
    queued = eng.submit([4, 5])      # fills the queue (depth bound 1)
    with pytest.raises(QueueFull):
        eng.submit([6, 7])
    assert eng.stats["rejected"] == 1
    assert running.result(timeout=60).size == 8
    assert queued.result(timeout=60).size == 8

    # deadline on a QUEUED request: expired before a slot freed
    blocker = eng.submit([1, 2, 3])
    eng.step()                       # admit it (queue has room again)
    late = eng.submit([4, 5], RequestParams(deadline_s=0.0))
    while not late.done():
        eng.step()
    assert late.status is RequestStatus.CANCELLED
    assert late.detail == "deadline"
    with pytest.raises(RequestFailed, match="deadline"):
        late.result(timeout=5)
    assert blocker.result(timeout=60).size == 8

    # deadline on an IN-FLIGHT request: evicted mid-decode, slot freed,
    # partial tokens kept. The deadline is expired EXPLICITLY after
    # admission (a wall-clock deadline_s raced the admission step on a
    # loaded machine)
    slow = eng.submit([1, 2, 3], RequestParams(deadline_s=60.0))
    eng.step()                       # admit
    assert slow.status is RequestStatus.RUNNING
    slow.deadline = time.monotonic() - 1e-3
    while not slow.done():
        eng.step()
    assert slow.status is RequestStatus.CANCELLED
    assert slow.detail == "deadline"
    assert all(s is None for s in eng._slots)
    nxt = eng.submit([9, 9])         # the evicted slot is reusable
    assert nxt.result(timeout=60).size == 8


def test_rejection_reason_dense(tiny_gpt):
    """ISSUE-19 satellite: a queue-bound rejection carries the
    STRUCTURED health reason on both the QueueFull and the
    already-terminal handle — the router's re-route classifier reads
    it, so it must distinguish lanes from pool memory from capacity."""
    # both decode lanes busy -> queue_full:no_free_slots
    eng = ServingEngine(_config(tiny_gpt, max_new=8, buckets=(16,),
                                max_batch=1, max_queue=1), poll_every=1)
    running = eng.submit([1, 2, 3])
    eng.step()
    queued = eng.submit([4, 5])
    with pytest.raises(QueueFull) as ei:
        eng.submit([6, 7])
    assert ei.value.reason == "queue_full:no_free_slots"
    handle = ei.value.request
    assert handle is not None and handle.done()
    assert handle.status is RequestStatus.REJECTED
    assert handle.detail == "queue_full:no_free_slots"
    with pytest.raises(RequestFailed, match="no_free_slots"):
        handle.result(timeout=1)
    assert running.result(timeout=60).size == 8
    assert queued.result(timeout=60).size == 8
    eng.shutdown()

    # queue at bound with lanes still free -> bare queue_full
    eng2 = ServingEngine(_config(tiny_gpt, max_new=8, buckets=(16,),
                                 max_batch=2, max_queue=1), poll_every=1)
    first = eng2.submit([1, 2, 3])    # queued, no step yet
    with pytest.raises(QueueFull) as ei2:
        eng2.submit([4, 5])
    assert ei2.value.reason == "queue_full"
    assert ei2.value.request.detail == "queue_full"
    assert first.result(timeout=60).size == 8
    eng2.shutdown()


def test_rejection_reason_paged(tiny_gpt):
    """Paged twin: a queue blocked on POOL MEMORY stamps its rejections
    queue_full:no_free_pages (the retryable-pressure signal, distinct
    from the dense lane bound)."""
    eng = ServingEngine(_config(tiny_gpt, max_batch=2, paged=True,
                                kv_page_size=16, kv_pages=3,
                                max_queue=1), poll_every=1)
    a = eng.submit(np.arange(1, 16, dtype=np.int32))   # 2 pages
    eng.step()                                         # admit a
    b = eng.submit(np.arange(2, 17, dtype=np.int32))   # blocked on pages
    eng.step()                                         # marks _page_blocked
    assert eng.health()["queue_blocked_on"] == "pages"
    with pytest.raises(QueueFull) as ei:
        eng.submit(np.arange(3, 10, dtype=np.int32))
    assert ei.value.reason == "queue_full:no_free_pages"
    assert ei.value.request.status is RequestStatus.REJECTED
    assert ei.value.request.detail == "queue_full:no_free_pages"
    while eng.busy:
        eng.step()
    assert a.status is RequestStatus.COMPLETED
    assert b.status is RequestStatus.COMPLETED
    eng._alloc.assert_conserved()
    eng.shutdown()


def test_eos_frees_slot_and_trims(tiny_gpt):
    """A row finishing on eos ends early; its result is trimmed before
    the eos, matching the Predictor's contract."""
    prompt = np.arange(1, 7, dtype=np.int32)
    pred = create_predictor(_config(tiny_gpt, max_new=8, buckets=(16,),
                                    max_batch=1))
    ref = pred.generate([prompt])[0]          # no eos configured
    eos = int(ref[3])                         # greedy token at step 3
    eng = ServingEngine(_config(tiny_gpt, max_new=8, buckets=(16,),
                                max_batch=1, eos=eos), poll_every=1)
    h = eng.submit(prompt)
    out = h.result(timeout=60)
    first = int(np.nonzero(ref == eos)[0][0])  # eos may repeat earlier
    np.testing.assert_array_equal(out, ref[:first])
    assert h.n_emitted == first + 1            # the eos itself emitted


# ------------------------------------------------------------ SLA metrics


def test_serve_metrics_family(tiny_gpt, engine):
    from paddle_tpu.core import monitor
    from paddle_tpu.profiler import metrics
    monitor.enable()
    try:
        c0 = _counter("serve.requests{status=completed}")
        hs = [engine.submit(np.arange(1, 5 + i, dtype=np.int32),
                            RequestParams(max_new_tokens=6))
              for i in range(4)]
        while engine.busy:
            engine.step()
        for h in hs:
            h.result(timeout=60)
        snap = metrics.snapshot()
        assert _counter("serve.requests{status=completed}") - c0 == 4
        assert snap["serve.ttft"]["count"] >= 4
        assert snap["serve.token_latency"]["count"] >= 1
        assert snap["serve.slot_occupancy"]["peak"] > 0
        assert "serve.queue_depth" in snap
        ttft = metrics.histogram("serve.ttft")
        p50, p95 = ttft.percentile(50), ttft.percentile(95)
        assert 0 < p50 <= p95

        # MetricsCallback surfaces both capacity gauges in its summary
        from paddle_tpu.hapi.callbacks import MetricsCallback
        cb = MetricsCallback(verbose=0)
        cb.on_train_begin()
        cb.on_epoch_begin(0)
        logs = {}
        cb.on_epoch_end(0, logs)
        assert "slot_occupancy" in logs
        assert "cache_occupancy" in logs
        cb.on_train_end()
    finally:
        monitor.disable()


def test_serve_forever_without_iterator_serves_through_idle(tiny_gpt):
    """serve_forever(None) really serves forever: it pumps submit()
    traffic from other threads THROUGH idle gaps (it must not return at
    the first idle instant) until shutdown — and the idle gap is not
    attributed to serve.token_latency."""
    import threading
    from paddle_tpu.core import monitor
    from paddle_tpu.profiler import metrics
    eng = ServingEngine(_config(tiny_gpt, max_new=6, buckets=(16,),
                                max_batch=1), poll_every=2)
    monitor.enable()
    try:
        server = threading.Thread(target=eng.serve_forever, daemon=True)
        server.start()
        h1 = eng.submit([1, 2, 3])
        assert h1.result(timeout=60).size == 6
        time.sleep(0.25)                  # engine idle, loop must survive
        assert server.is_alive()
        h2 = eng.submit([4, 5])           # traffic after the gap
        assert h2.result(timeout=60).size == 6
        eng.shutdown()
        server.join(timeout=30)
        assert not server.is_alive()
        # the 0.25s idle gap must not leak into per-token latency
        lat = metrics.histogram("serve.token_latency")
        assert lat.percentile(99) < 0.2
    finally:
        monitor.disable()


# ------------------------------------------------------- tier-1 audit gate


def test_serving_audit_gate(tiny_gpt):
    """Flagship gate: zero analysis ERRORs across every program the
    scheduler dispatches, and full donation coverage on the slot-decode
    and admit programs — the KV cache and token buffers must stay
    donated (in-place) across scheduler steps."""
    eng = ServingEngine(_config(tiny_gpt, max_new=8, buckets=(16, 32),
                                max_batch=2), warmup=False)
    reports = eng.audit()
    assert set(reports) == {("prefill", 16), ("prefill", 32), "decode",
                            "admit", "free", "poll_view"}
    for rep in reports.values():
        rep.raise_on_error()
    assert not reports["decode"].by_check("host_sync")
    assert reports["decode"].donation_coverage == 1.0
    assert reports["admit"].donation_coverage == 1.0


def test_audit_gate_not_vacuous(tiny_gpt):
    """Seeded regression: a host callback smuggled into the decode
    path must fail the gate."""
    import jax
    from paddle_tpu.analysis import AuditError
    eng = ServingEngine(_config(tiny_gpt, max_new=4, buckets=(16,),
                                max_batch=1), warmup=False)
    step = eng._programs[("step",)]

    def poisoned(*args):
        cache, lanes, key = step.fn(*args)
        leak = jax.pure_callback(
            lambda t: np.asarray(t), jax.ShapeDtypeStruct((1,), jnp.int32),
            lanes.tok)
        return cache, lanes._replace(tok=lanes.tok + leak * 0), key

    eng._programs[("step",)] = dataclasses.replace(step, fn=poisoned)
    with pytest.raises(AuditError):
        eng.audit()["decode"].raise_on_error()


def test_engine_forces_eval_at_trace_points():
    """A shared layer flipped to train mode by a fit() loop must not
    leak train-mode tracing into the served programs: deferred
    warmup(), lazy compiles, and audit() all force eval first (the
    GenerationSession._ensure_eval contract — a train-mode trace bakes
    active dropout in, or closes over extra RNG inputs and breaks the
    compiled call signature)."""
    paddle.seed(0)
    m = gpt("test-tiny", dropout=0.5)
    eng = ServingEngine(_config(m, max_new=4, buckets=(16,),
                                max_batch=1), warmup=False)
    m.train()                         # what every fit() batch does
    eng.audit()["decode"].raise_on_error()
    assert not m.training
    m.train()
    out = eng.submit([1, 2, 3]).result(timeout=60)  # lazy compile here
    assert out.size == 4 and not m.training


# -------------------------------------------------------- precision paths


def test_bf16_precision_path(tiny_gpt):
    """The engine serves the bf16 cast the Predictor audits: cast
    params, bf16 activations, bf16 KV cache — and still completes."""
    cfg = (Config().from_layer(tiny_gpt, _spec())
           .enable_tpu(precision=PrecisionType.Bfloat16)
           .enable_generation(max_new_tokens=4, prefill_buckets=(16,),
                              max_batch=1))
    eng = ServingEngine(cfg)
    assert eng._cache.dtype == jnp.bfloat16
    assert all(v.dtype == jnp.bfloat16 for v in eng._state
               if jnp.issubdtype(v.dtype, jnp.floating))
    out = eng.submit(np.arange(1, 7, dtype=np.int32)).result(timeout=60)
    assert out.shape == (4,)
    # the module-scope model must stay fp32 (the cast is serving-side)
    assert all(
        jnp.issubdtype(t._data.dtype, jnp.floating) is False
        or t._data.dtype == jnp.float32
        for t in tiny_gpt.state_dict().values())


def test_int8_weight_only_path(tiny_gpt):
    """int8 weight-only serving: quantized Linear weights + in-trace
    dequant, engine end-to-end."""
    cfg = (Config().from_layer(tiny_gpt, _spec())
           .enable_tpu(precision=PrecisionType.Int8)
           .enable_generation(max_new_tokens=4, prefill_buckets=(16,),
                              max_batch=1))
    eng = ServingEngine(cfg)
    assert eng._sp.scales              # something actually quantized
    assert any(v.dtype == jnp.int8 for v in eng._state)
    out = eng.submit(np.arange(1, 7, dtype=np.int32)).result(timeout=60)
    assert out.shape == (4,)


# ----------------------------------------------------------- thread mode


def test_thread_mode_and_shutdown(tiny_gpt):
    eng = ServingEngine(_config(tiny_gpt, max_new=4, buckets=(16,),
                                max_batch=1), poll_every=1)
    eng.start()
    try:
        hs = [eng.submit(np.arange(1, 4 + i, dtype=np.int32))
              for i in range(3)]
        outs = [h.result(timeout=60) for h in hs]
        assert all(o.size == 4 for o in outs)
    finally:
        eng.shutdown()
    assert eng._thread is None
    with pytest.raises(RuntimeError, match="shut down"):
        eng.submit([1, 2])
    eng.shutdown()                    # idempotent


def test_drain_completes_rows_finished_since_last_poll(tiny_gpt):
    """A row whose decode finished between the last cadence poll and
    the drain cutoff must drain as COMPLETED, not CANCELLED: drain runs
    one final poll before declaring stragglers."""
    eng = ServingEngine(_config(tiny_gpt, max_new=8, buckets=(16,),
                                max_batch=1, drain_timeout_s=0.0),
                        poll_every=4)
    h = eng.submit([1, 2, 3], RequestParams(max_new_tokens=2))
    eng.step()   # admit + 1 decode step: budget reached, but the poll
    #              cadence (4) has not come around yet
    eng.drain()  # zero drain window: only the final poll can save it
    assert h.status is RequestStatus.COMPLETED
    assert h.result().size == 2


def test_admission_failure_never_hangs_the_handle(tiny_gpt):
    """A request popped from the queue whose admission raises (device
    error mid-prefill) must still reach a terminal status — its Future
    can never hang — and the engine keeps serving later requests."""
    eng = ServingEngine(_config(tiny_gpt, max_new=4, buckets=(16,),
                                max_batch=1), poll_every=1)
    orig = eng._exe_prefill
    calls = {"n": 0}

    def flaky(bucket):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected device failure")
        return orig(bucket)

    eng._exe_prefill = flaky
    doomed = eng.submit([1, 2, 3])
    ok = eng.submit([4, 5])
    eng.step()
    assert doomed.done()
    assert doomed.status is RequestStatus.CANCELLED
    assert "admission error" in doomed.detail
    with pytest.raises(RequestFailed, match="injected device failure"):
        doomed.result(timeout=5)
    assert ok.result(timeout=60).size == 4   # engine kept serving


def test_drain_with_no_traffic_is_clean(tiny_gpt):
    eng = ServingEngine(_config(tiny_gpt, max_new=4, buckets=(16,),
                                max_batch=1), warmup=False)
    eng.drain()
    with pytest.raises(RuntimeError, match="shut down"):
        eng.submit([1, 2])


# ----------------------------------------------------------------- chaos


@pytest.mark.chaos
def test_graceful_shutdown_drains_serving(tiny_gpt):
    """SIGTERM mid-serve_forever: in-flight requests drain to a
    terminal status (here: complete within the drain window), queued
    requests get a clean rejection, nothing hangs, and the engine
    accepts no new work afterwards."""
    import signal
    from paddle_tpu.distributed.resilience import GracefulShutdown
    from paddle_tpu.utils.fault_injection import KillAfter

    eng = ServingEngine(_config(tiny_gpt, max_new=8, buckets=(16,),
                                max_batch=2, max_queue=8,
                                drain_timeout_s=60.0), poll_every=2)
    rng = np.random.RandomState(1)
    traffic = [rng.randint(0, 512, 4 + i).astype(np.int32)
               for i in range(5)]
    killer = KillAfter(4, signal.SIGTERM)
    with GracefulShutdown(exit_on_save=False) as gs:
        handles = eng.serve_forever(
            iter(traffic), on_step=lambda e: killer.step())
        assert gs.preempted
    assert killer.fired
    assert len(handles) == 5
    assert all(h.done() for h in handles), "a request hung"
    assert all(h.status.terminal for h in handles)
    completed = [h for h in handles
                 if h.status is RequestStatus.COMPLETED]
    rejected = [h for h in handles
                if h.status is RequestStatus.REJECTED]
    assert completed and all(h.tokens.size == 8 for h in completed)
    assert rejected and all(h.detail == "shutdown" for h in rejected)
    with pytest.raises(RuntimeError, match="shut down"):
        eng.submit(traffic[0])


@pytest.mark.chaos
def test_drain_timeout_cancels_stragglers(tiny_gpt):
    """A drain window too short for the in-flight budget cancels the
    stragglers with a shutdown status instead of hanging."""
    eng = ServingEngine(_config(tiny_gpt, max_new=8, buckets=(16,),
                                max_batch=1, drain_timeout_s=0.0),
                        poll_every=1)
    h = eng.submit([1, 2, 3])
    eng.step()                       # admitted, 7 tokens to go
    eng.drain()
    assert h.done()
    assert h.status is RequestStatus.CANCELLED
    assert h.detail == "shutdown"
    assert h.tokens is not None and 1 <= h.tokens.size < 8
