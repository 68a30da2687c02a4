"""Chunked prefill (ISSUE 20): page-aligned prefill chunks interleaved
with decode.

Covers: the knob's validation surface (alignment, ring safety, env
parsing), the correctness gate (chunked admissions bitwise-equal to the
sequential Predictor reference — dense, paged, and int8-quant, with
arrivals mid-decode), the steady-state invariant extended to the chunk
programs (zero compiles under chunked traffic after warmup), the
PENDING_PREFILL slot state and its health/readiness surface
(pending_prefill_tokens / prefill_chunks_queued), mid-prefill rollback
(deadline expiry and drain release the committed pages — free-list
conserved), the audit/memory-plan extension (donation coverage 1.0 on
the chunk pair + span install), the serve.prefill.* metrics +
serve.prefill_chunk flight events, and the chunk attention kernel's
parity against the naive reference (XLA dispatch path and the Pallas
q-tiled kernel in interpret mode, wide and int8). The chaos-tier
SIGTERM-mid-prefill test and the TTFT head-of-line gate live at the
bottom (chaos / slow markers).
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.models.gpt import gpt
from paddle_tpu.serving import RequestParams, RequestStatus, ServingEngine

import jax.numpy as jnp


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(0)
    m = gpt("test-tiny")
    m.eval()
    return m


def _spec():
    return [paddle.to_tensor(np.zeros((2, 12), np.int32))]


def _config(m, *, max_new=8, buckets=(16, 32), max_batch=2, eos=None,
            kv_dtype=None, **serving_kw):
    cfg = (Config().from_layer(m, _spec())
           .enable_generation(max_new_tokens=max_new,
                              prefill_buckets=buckets,
                              max_batch=max_batch, eos_token_id=eos,
                              kv_cache_dtype=kv_dtype))
    cfg.enable_serving(**serving_kw)
    return cfg


def _counter(name):
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot().get(name)
    return int(snap["value"]) if snap else 0


@pytest.fixture(scope="module")
def reference(tiny_gpt):
    pred = create_predictor(
        Config().from_layer(tiny_gpt, _spec())
        .enable_generation(max_new_tokens=8, prefill_buckets=(16, 32),
                           max_batch=1))
    return lambda p, b=8: pred.generate([p], max_new_tokens=b)[0]


def _prompts(seed=0):
    """The adversarial mix: two chunk-worthy long prompts among
    shorts."""
    rng = np.random.RandomState(seed)
    lens = (5, 24, 12, 20, 7)
    return [rng.randint(0, 512, n).astype(np.int32) for n in lens]


# ----------------------------------------------------------- validation


def test_chunk_knob_validation(tiny_gpt):
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        ServingEngine(_config(tiny_gpt, prefill_chunk_tokens=0),
                      warmup=False)
    # paged: chunks must be page-aligned (span installs never straddle)
    with pytest.raises(ValueError, match="multiple"):
        ServingEngine(_config(tiny_gpt, paged=True, kv_page_size=8,
                              prefill_chunk_tokens=12), warmup=False)
    # ring safety: the final chunk is right-padded to a multiple of C;
    # ceil(32/24)*24 = 48 > cache_max_len 40 would wrap the ring onto
    # the row's own prefix
    with pytest.raises(ValueError, match="cache"):
        ServingEngine(_config(tiny_gpt, cache_max_len=40,
                              prefill_chunk_tokens=24), warmup=False)
    # a cap at/above the largest bucket disables chunking (inline
    # prefill already covers every admissible prompt)
    eng = ServingEngine(_config(tiny_gpt, prefill_chunk_tokens=32),
                        warmup=False)
    assert not eng._chunk_enabled


def test_chunk_env_knob(tiny_gpt, monkeypatch):
    monkeypatch.setenv("PADDLE_PREFILL_CHUNK_TOKENS", "16")
    eng = ServingEngine(_config(tiny_gpt), warmup=False)
    assert eng.prefill_chunk_tokens == 16 and eng._chunk_enabled
    # garbage env falls back (recorded, not raised) — the constructor
    # must never die on a deploy-environment typo
    monkeypatch.setenv("PADDLE_PREFILL_CHUNK_TOKENS", "lots")
    eng = ServingEngine(_config(tiny_gpt), warmup=False)
    assert eng.prefill_chunk_tokens is None


# -------------------------------------------- the correctness invariant


def test_chunked_dense_matches_sequential(tiny_gpt, reference):
    """THE gate: long prompts admitted in chunks while short requests
    decode, zero compiles after warmup, every completion bitwise-equal
    to the sequential Predictor."""
    from paddle_tpu.core import monitor
    eng = ServingEngine(_config(tiny_gpt, prefill_chunk_tokens=8,
                                max_queue=8), poll_every=2)
    prompts = _prompts()
    monitor.enable()
    try:
        ns0 = _counter("jit.compile{cause=new_shape}")
        tot0 = _counter("jit.compile.total")
        handles = [eng.submit(prompts[0])]     # short: decoding first
        for _ in range(3):
            eng.step()
        handles += [eng.submit(p) for p in prompts[1:]]
        while eng.busy:
            eng.step()
        assert _counter("jit.compile{cause=new_shape}") - ns0 == 0
        assert _counter("jit.compile.total") - tot0 == 0
    finally:
        monitor.disable()
    assert all(h.status is RequestStatus.COMPLETED for h in handles)
    for h, p in zip(handles, prompts):
        np.testing.assert_array_equal(h.tokens, reference(p))
    eng.shutdown()


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_chunked_paged_matches_sequential(tiny_gpt, reference, kv_dtype):
    """Chunked admission over the paged pool (span installs + final
    page-table commit), wide and int8-quant: bitwise parity with the
    matching sequential reference, pool conserved after traffic."""
    eng = ServingEngine(_config(tiny_gpt, paged=True, kv_page_size=8,
                                prefill_chunk_tokens=8, max_queue=8,
                                kv_cache_dtype=kv_dtype), poll_every=2)
    if kv_dtype is None:
        ref = reference
    else:
        pred = create_predictor(
            Config().from_layer(tiny_gpt, _spec())
            .enable_generation(max_new_tokens=8,
                               prefill_buckets=(16, 32), max_batch=1,
                               kv_cache_dtype="int8"))
        ref = lambda p: pred.generate([p], max_new_tokens=8)[0]  # noqa
    prompts = _prompts(seed=1)
    handles = [eng.submit(prompts[0])]
    for _ in range(2):
        eng.step()
    handles += [eng.submit(p) for p in prompts[1:]]
    while eng.busy:
        eng.step()
    assert all(h.status is RequestStatus.COMPLETED for h in handles)
    for h, p in zip(handles, prompts):
        np.testing.assert_array_equal(h.tokens, ref(p))
    assert eng._alloc.used_pages() == 0
    eng._alloc.assert_conserved()
    eng.shutdown()


# ------------------------------------- PENDING_PREFILL state + health


def test_pending_prefill_never_decoded(tiny_gpt):
    """Mid-chunking the slot holds PENDING_PREFILL: no tokens emitted,
    the health/readiness surface reports the backlog, and the final
    chunk flips it RUNNING with the first token."""
    eng = ServingEngine(_config(tiny_gpt, prefill_chunk_tokens=8),
                        poll_every=1)
    long_p = np.arange(1, 25, dtype=np.int32)          # 24 -> 3 chunks
    h = eng.submit(long_p)
    eng.step()                                  # chunk 0 dispatched
    assert h.status is RequestStatus.PENDING_PREFILL
    assert h.n_emitted == 0 and h.first_token_at is None
    health = eng.health()
    assert health["prefill_chunks_queued"] >= 1
    assert health["pending_prefill_tokens"] >= 8
    while eng.busy:
        eng.step()
    assert h.status is RequestStatus.COMPLETED
    assert h.tokens.size == 8
    health = eng.health()
    assert health["prefill_chunks_queued"] == 0
    assert health["pending_prefill_tokens"] == 0
    eng.shutdown()


def test_chunked_admission_interleaves_and_serializes(tiny_gpt):
    """While one long prompt chunks, later short arrivals are still
    admitted into FREE slots (the interleave: decode traffic keeps
    flowing) — but a second chunk-worthy prompt parks at the queue head
    until the first finishes (ONE side cache, strict FIFO — the
    scheduler never interleaves two chunked prefills)."""
    eng = ServingEngine(_config(tiny_gpt, prefill_chunk_tokens=8,
                                max_batch=2, max_queue=8), poll_every=1)
    long_a = np.arange(1, 25, dtype=np.int32)
    long_b = np.arange(2, 26, dtype=np.int32)
    short = np.array([3, 1, 4], np.int32)
    ha = eng.submit(long_a)
    eng.step()
    assert ha.status is RequestStatus.PENDING_PREFILL
    hs = eng.submit(short)
    hb = eng.submit(long_b)
    eng.step()
    # the short took the free slot mid-chunking; the second long is
    # parked (chunking busy) with its pages uncommitted
    assert hs.status in (RequestStatus.RUNNING, RequestStatus.COMPLETED)
    assert hb.status is RequestStatus.QUEUED
    while eng.busy:
        eng.step()
    assert all(h.status is RequestStatus.COMPLETED
               for h in (ha, hb, hs))
    eng.shutdown()


# -------------------------------------------------- mid-prefill rollback


def test_deadline_mid_prefill_releases_pages(tiny_gpt):
    eng = ServingEngine(_config(tiny_gpt, paged=True, kv_page_size=8,
                                prefill_chunk_tokens=8), poll_every=1)
    h = eng.submit(np.arange(1, 25, dtype=np.int32),
                   RequestParams(deadline_s=0.05))
    eng.step()                                  # chunking underway
    assert h.status is RequestStatus.PENDING_PREFILL
    held = eng._alloc.used_pages()
    assert held > 0                             # pages committed
    time.sleep(0.08)
    eng.step()                                  # deadline check fires
    assert h.done() and h.status is RequestStatus.CANCELLED
    assert h.detail == "deadline"
    assert eng._alloc.used_pages() == 0
    eng._alloc.assert_conserved()
    # the slot is reusable: a fresh request completes
    h2 = eng.submit(np.array([1, 2, 3], np.int32))
    while eng.busy:
        eng.step()
    assert h2.status is RequestStatus.COMPLETED
    eng._alloc.assert_conserved()
    eng.shutdown()


def test_drain_mid_prefill_terminal_and_conserved(tiny_gpt):
    eng = ServingEngine(_config(tiny_gpt, paged=True, kv_page_size=8,
                                prefill_chunk_tokens=8,
                                drain_timeout_s=30.0), poll_every=1)
    h = eng.submit(np.arange(1, 25, dtype=np.int32))
    eng.step()
    assert h.status is RequestStatus.PENDING_PREFILL
    eng.drain()
    assert h.done() and h.status is RequestStatus.CANCELLED
    assert h.detail == "shutdown"
    assert eng._alloc.used_pages() == 0
    eng._alloc.assert_conserved()


# ------------------------------------------- audit / memory-plan / docs


def test_audit_chunk_programs_donate_fully(tiny_gpt):
    eng = ServingEngine(_config(tiny_gpt, paged=True, kv_page_size=8,
                                prefill_chunk_tokens=8), warmup=False)
    rs = eng.audit()
    for key in (("chunk", 8), ("chunk_final", 8), ("install_span",)):
        rep = rs[key]
        rep.raise_on_error()
        assert rep.donation_coverage == 1.0, key


def test_memory_plan_covers_chunk_program(tiny_gpt):
    eng = ServingEngine(_config(tiny_gpt, prefill_chunk_tokens=8),
                        warmup=False)
    mp = eng.memory_plan()
    assert mp["chunk_peak_bytes"] > 0
    assert mp["predicted_peak_bytes"] >= mp["kv_cache_bytes"]


# --------------------------------------- metrics + flight-recorder trail


def test_chunk_metrics_and_flight_events(tiny_gpt):
    from paddle_tpu.core import flight_recorder, monitor
    eng = ServingEngine(_config(tiny_gpt, prefill_chunk_tokens=8,
                                trace_sample=1), poll_every=1)
    monitor.enable()
    try:
        c0 = _counter("serve.prefill.chunks")
        t0 = _counter("serve.prefill.chunk_tokens")
        h = eng.submit(np.arange(1, 25, dtype=np.int32))   # 3 chunks
        while eng.busy:
            eng.step()
        assert h.status is RequestStatus.COMPLETED
        assert _counter("serve.prefill.chunks") - c0 == 3
        assert _counter("serve.prefill.chunk_tokens") - t0 == 24
        from paddle_tpu.profiler import metrics as _m
        assert "serve.prefill.interleave_ratio" in _m.snapshot()
    finally:
        monitor.disable()
    evs = [f for _, k, f in flight_recorder.events()
           if k == "serve.prefill_chunk" and f.get("req") == h.id]
    assert [e["chunk"] for e in evs] == [0, 1, 2]
    assert sum(e["tokens"] for e in evs) == 24
    assert evs[-1]["remaining"] == 0
    # the traced request carries per-chunk spans (the preemption-dump
    # evidence the chaos test asserts end to end)
    spans = [s for s in flight_recorder.spans_between(0, 2 ** 62)
             if s.name == f"req{h.id}.prefill_chunk"]
    assert len(spans) == 3
    eng.shutdown()


# ----------------------------------------------- chunk attention kernel


def _naive_decode(q, kc, vc, kv_len):
    b, sq, h, d = q.shape
    t = kc.shape[1]
    scale = 1.0 / np.sqrt(d)
    out = np.zeros_like(q, dtype=np.float32)
    for bi in range(b):
        for hi in range(h):
            s = (q[bi, :, hi] @ kc[bi, :, hi].T) * scale
            for i in range(sq):
                lim = kv_len[bi] - sq + i
                mask = np.arange(t) <= lim
                e = np.exp(s[i] - s[i][mask].max()) * mask
                out[bi, i, hi] = (e / e.sum()) @ vc[bi, :, hi]
    return out


@pytest.mark.parametrize("sq", [5, 16, 24])
def test_flash_attention_chunk_parity(sq):
    """The public chunk entry (XLA dispatch on CPU) against the naive
    causal-window reference — q_len past the decode kernel's 8-row
    cap."""
    from paddle_tpu.kernels.flash_attention import flash_attention_chunk
    rng = np.random.RandomState(1)
    b, h, d, t = 2, 4, 64, 256
    kv = np.array([sq + 3, 250], np.int32)
    q = rng.randn(b, sq, h, d).astype(np.float32)
    kc = rng.randn(b, t, h, d).astype(np.float32)
    vc = rng.randn(b, t, h, d).astype(np.float32)
    out = np.asarray(flash_attention_chunk(q, kc, vc, kv))
    np.testing.assert_allclose(out, _naive_decode(q, kc, vc, kv),
                               rtol=2e-5, atol=2e-5)


def test_chunk_pallas_interpret_parity():
    """The q-tiled Pallas kernel itself (interpret mode): per-tile
    causal window shift, GQA head mapping, k-block skipping — including
    a padded tail tile (sq 20 pads to 24, tile rows overhang)."""
    from paddle_tpu.kernels.flash_attention import _chunk_pallas
    rng = np.random.RandomState(2)
    b, hq, hk, d, t, sq = 2, 4, 2, 64, 256, 20
    group = hq // hk
    kv = np.array([sq + 5, 250], np.int32)
    q = rng.randn(b, sq, hq, d).astype(np.float32)
    kc = rng.randn(b, t, hk, d).astype(np.float32)
    vc = rng.randn(b, t, hk, d).astype(np.float32)
    qt = jnp.swapaxes(jnp.asarray(q), 1, 2).reshape(b * hq, sq, d)
    kt = jnp.swapaxes(jnp.asarray(kc), 1, 2).reshape(b * hk, t, d)
    vt = jnp.swapaxes(jnp.asarray(vc), 1, 2).reshape(b * hk, t, d)
    out = _chunk_pallas(qt, kt, vt, jnp.repeat(jnp.asarray(kv), hk),
                        1.0 / np.sqrt(d), block_k=128, group=group)
    out = np.asarray(jnp.swapaxes(out.reshape(b, hq, sq, d), 1, 2))
    ref = _naive_decode(q, np.repeat(kc, group, 2),
                        np.repeat(vc, group, 2), kv)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_chunk_pallas_int8_interpret_parity():
    """Fused int8 dequant through the q-tiled kernel (interpret mode)
    against the XLA fused-dequant fallback."""
    from paddle_tpu.kernels.flash_attention import (_chunk_pallas,
                                                    _decode_xla)
    rng = np.random.RandomState(3)
    B, T, D, sq = 2, 128, 64, 12
    k8 = rng.randint(-127, 128, (B, T, D)).astype(np.int8)
    v8 = rng.randint(-127, 128, (B, T, D)).astype(np.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (B, T))
                     .astype(np.float32)).astype(jnp.bfloat16)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (B, T))
                     .astype(np.float32)).astype(jnp.bfloat16)
    q = rng.randn(B, sq, D).astype(np.float32)
    kv_len = jnp.asarray(np.array([sq + 25, 100], np.int32))
    args = (jnp.asarray(q), jnp.asarray(k8), jnp.asarray(v8), kv_len,
            float(D ** -0.5))
    ref = _decode_xla(*args, ks=ks, vs=vs)
    out = _chunk_pallas(*args, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_cached_attention_dispatches_chunk_past_decode_cap():
    """The dense decode branch routes q_len > MAX_DECODE_QLEN to the
    chunk kernel instead of dying on the decode kernel's row cap."""
    from paddle_tpu.generation.attention import cached_attention
    from paddle_tpu.generation.kv_cache import KVCache
    rng = np.random.RandomState(4)
    b, h, d, t, sq = 1, 2, 64, 64, 12
    cache = KVCache.create(1, b, t, h, d)
    q = paddle.to_tensor(rng.randn(b, sq, h, d).astype(np.float32))
    k = paddle.to_tensor(rng.randn(b, sq, h, d).astype(np.float32))
    v = paddle.to_tensor(rng.randn(b, sq, h, d).astype(np.float32))
    out, cache = cached_attention(q, k, v, cache, 0, decode=True,
                                  causal=True)
    ref = _naive_decode(q.numpy(), np.asarray(cache.k[0]),
                        np.asarray(cache.v[0]),
                        np.full((b,), sq, np.int32))
    np.testing.assert_allclose(out.numpy(), ref, rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------- chaos


@pytest.mark.chaos
def test_sigterm_mid_chunked_prefill(tiny_gpt, tmp_path, monkeypatch):
    """SIGTERM landing while a chunked prefill is in flight under live
    Poisson traffic: every handle reaches a terminal status, the
    mid-prefill request's committed pages are released (free-list
    conserved), and the preemption dump carries the partial per-chunk
    spans — the post-mortem shows exactly how far the prompt got."""
    import glob
    import json
    import os
    import signal
    import threading
    from paddle_tpu.core import flight_recorder
    from paddle_tpu.distributed.resilience import GracefulShutdown

    monkeypatch.setenv("PADDLE_FLIGHT_RECORDER_DIR", str(tmp_path))
    eng = ServingEngine(_config(tiny_gpt, paged=True, kv_page_size=8,
                                prefill_chunk_tokens=8, max_queue=16,
                                trace_sample=1, drain_timeout_s=0.0),
                        poll_every=1)
    rng = np.random.RandomState(7)
    h_long = eng.submit(np.arange(1, 25, dtype=np.int32))
    shorts = []

    def feeder():
        for i in range(6):
            time.sleep(float(rng.exponential(0.004)))
            shorts.append(eng.submit(
                rng.randint(0, 512, 3 + i % 5).astype(np.int32)))

    th = threading.Thread(target=feeder, daemon=True)
    th.start()
    # step until the long prompt is mid-chunking (>= 1 chunk landed,
    # not yet admitted)
    for _ in range(200):
        eng.step()
        st = eng._chunking
        if h_long.status is RequestStatus.PENDING_PREFILL \
                and st is not None and st["next"] >= 1:
            break
    assert h_long.status is RequestStatus.PENDING_PREFILL
    th.join()
    # clear the per-reason rate limit + cap so THIS dump isn't swallowed
    # by earlier chaos tests' dumps
    flight_recorder._recorder._last_auto.pop("preemption", None)
    flight_recorder._recorder._auto_dumps = 0
    with GracefulShutdown(store=None, exit_on_save=False) as gs:
        os.kill(os.getpid(), signal.SIGTERM)
        assert gs.check(step=1)          # preemption dump, no exit
        eng.drain()                      # drain window 0: cancel all
    assert h_long.done() and h_long.status is RequestStatus.CANCELLED
    assert all(h.done() and h.status.terminal for h in shorts)
    assert eng._alloc.used_pages() == 0
    eng._alloc.assert_conserved()
    dumps = glob.glob(str(tmp_path / "flightrecorder_preemption_*.json"))
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        doc = json.load(f)
    chunk_spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"
                   and e.get("name") == f"req{h_long.id}.prefill_chunk"]
    assert 1 <= len(chunk_spans) < 3     # partial: killed mid-prefill
    assert doc["metadata"]["reason"] == "preemption"


@pytest.mark.slow
def test_short_request_ttft_head_of_line_gate():
    """The ISSUE-20 acceptance gate (slow tier): the `bench.py serve
    --adversarial` row — short-request Poisson traffic with periodic
    long-prompt injections, inline vs chunked at equal HBM — must show
    chunked short-request TTFT p99 >= 3x better (vs_baseline >= 1.0)
    with zero compiles under traffic in both passes. Runs the bench
    function itself so the gate and the published row can't diverge."""
    import jax

    from bench import bench_serve_adversarial
    row = bench_serve_adversarial(jax.devices()[0],
                                  jax.default_backend() == "tpu")
    assert row["vs_baseline"] >= 1.0, row["metric"]
    for mode in ("inline", "chunked"):
        assert row[mode]["counters"]["jit.compile.total"] == 0, mode
    assert row["chunked"]["prefill_chunks"] > 0
