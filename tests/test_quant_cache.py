"""Quantized KV cache + int4 weight path (ISSUE 13).

Covers: the quantize_kv unit contract (round trip, structurally-zero
saturation), QuantKVCache/QuantPagedKVCache protocol + verbatim
install parity, THE parity gates (bounded decode logit error AND
greedy eos-position parity vs the full-width cache on test-tiny), the
int8 engine bitwise-vs-sequential gate with zero post-warmup
retraces, int8 pages x shared-prefix COW (scales privatize with the
page), speculative ngram windows over the int8 cache (accept rate
within tolerance of full width), int4 pack/unpack round-trip units +
the int4-weight serving path, the dtype.quant_escape detector (fires
on unsanctioned widening, silent on the fused dequant sites), the
audit gates over every quantized program (zero ERRORs, donation 1.0),
the serve.cache.kv_dtype / gen.cache.quant.* metrics, the health()
capacity-in-tokens fields, and the PADDLE_KV_CACHE_DTYPE env knob.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.generation.kv_cache import (KVCache, QuantKVCache,
                                            quantize_kv,
                                            resolve_cache_dtype)
from paddle_tpu.generation.paged_cache import (PagedKVCache,
                                               QuantPagedKVCache)
from paddle_tpu.inference import Config
from paddle_tpu.inference.config import PrecisionType
from paddle_tpu.models.gpt import gpt
from paddle_tpu.serving import RequestParams, RequestStatus, ServingEngine


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(0)
    m = gpt("test-tiny")
    m.eval()
    return m


def _spec():
    return [paddle.to_tensor(np.zeros((2, 12), np.int32))]


def _config(m, *, max_new=8, buckets=(16,), max_batch=2, eos=None,
            speculative=None, kv_cache_dtype="int8", **serving_kw):
    cfg = (Config().from_layer(m, _spec())
           .enable_generation(max_new_tokens=max_new,
                              prefill_buckets=buckets,
                              max_batch=max_batch, eos_token_id=eos,
                              speculative=speculative,
                              kv_cache_dtype=kv_cache_dtype))
    cfg.enable_serving(**serving_kw)
    return cfg


@pytest.fixture(scope="module")
def int8_engine(tiny_gpt):
    """Shared dense int8-cache engine."""
    return ServingEngine(_config(tiny_gpt), poll_every=2)


@pytest.fixture(scope="module")
def int8_paged_engine(tiny_gpt):
    """Shared paged int8-cache engine (page 16)."""
    return ServingEngine(_config(tiny_gpt, buckets=(16, 32), paged=True,
                                 kv_page_size=16), poll_every=2)


@pytest.fixture(scope="module")
def int8_reference(tiny_gpt):
    """Sequential batch-1 int8-cache reference at the engines' bucket
    and cache geometry (the PR-8 gate shape: engine rows must be
    bitwise this)."""
    from paddle_tpu.generation.api import GenerationSession, generate
    sess = GenerationSession(tiny_gpt, cache_dtype="int8")

    def ref(prompt, budget, cache_len):
        bucket = 16 if prompt.size <= 16 else 32
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :prompt.size] = prompt
        out = generate(tiny_gpt, ids, budget,
                       prompt_len=np.array([prompt.size], np.int32),
                       cache_max_len=cache_len, session=sess)
        return np.asarray(out._data)[0]

    return ref


def _counter(name):
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot().get(name)
    return int(snap["value"]) if snap else 0


# ----------------------------------------------------------- cache unit


def test_quantize_kv_roundtrip_no_saturation():
    """Per-(token, head) absmax scales: dequant error bounded by half a
    step of the token's own absmax, and the saturation counter is
    structurally zero under round-to-nearest bf16 scales (the
    worst-case ratio 127 * (1 + 2^-9) < 127.5) — exactly what the
    gen.cache.quant.scale_clips guardrail asserts in production."""
    rng = np.random.RandomState(0)
    x = (rng.randn(3, 5, 2, 16) * rng.lognormal(0, 2, (3, 5, 2, 1))) \
        .astype(np.float32)
    q, s, clips = quantize_kv(jnp.asarray(x))
    assert q.dtype == jnp.int8 and s.dtype == jnp.bfloat16
    assert int(clips) == 0
    deq = np.asarray(q.astype(jnp.float32) * s.astype(jnp.float32)[..., None])
    absmax = np.abs(x).max(-1, keepdims=True)
    # half an int8 step of the token absmax + the bf16 scale rounding
    assert (np.abs(deq - x) <= absmax * (0.5 / 127 + 2 ** -8) + 1e-6).all()


def test_quant_cache_update_protocol():
    """QuantKVCache speaks the ring-cache protocol: scatter writes at
    kv_len quantize in place, scales land beside the values, and
    reset_rows/with_kv_len/copy_row_from preserve the quantized class
    (a wide cache must never silently reappear mid-stream)."""
    rng = np.random.RandomState(1)
    c = KVCache.create(2, 2, 8, 2, 4, cache_dtype="int8")
    assert isinstance(c, QuantKVCache) and c.cache_dtype == "int8"
    k = rng.randn(2, 3, 2, 4).astype(np.float32)
    v = rng.randn(2, 3, 2, 4).astype(np.float32)
    c = c.update(0, jnp.asarray(k), jnp.asarray(v), c.kv_len)
    deq = np.asarray(c.k[0].astype(jnp.float32)) * \
        np.asarray(c.k_scale[0].astype(jnp.float32))[..., None]
    np.testing.assert_allclose(deq[:, :3], k, atol=2e-2, rtol=2e-2)
    c2 = c.with_kv_len(3).reset_rows(np.array([1]))
    assert isinstance(c2, QuantKVCache)
    assert np.asarray(c2.kv_len).tolist() == [3, 0]
    # row copy is verbatim: int8 values + scales bitwise
    dst = KVCache.create(2, 2, 8, 2, 4, cache_dtype="int8")
    dst = dst.copy_row_from(c2, 0, 1)
    np.testing.assert_array_equal(np.asarray(dst.k[:, 1]),
                                  np.asarray(c2.k[:, 0]))
    np.testing.assert_array_equal(np.asarray(dst.k_scale[:, 1]),
                                  np.asarray(c2.k_scale[:, 0]))


def test_quant_paged_install_bitwise():
    """install_row routes a batch-1 quant row's int8 values AND scales
    through the page table verbatim (no requantization), and a
    subsequent paged update quantizes the SAME bits the dense update
    would — the cache-level facts that make engine admissions
    bitwise-reproducible (the engine tests below close the loop
    end-to-end)."""
    rng = np.random.RandomState(0)
    L, T, H, D, ps = 2, 64, 4, 16, 16
    row = KVCache.create(L, 1, T, H, D, cache_dtype="int8")
    for layer in range(L):
        row = row.update(layer, jnp.asarray(
            rng.randn(1, 10, H, D).astype(np.float32)), jnp.asarray(
            rng.randn(1, 10, H, D).astype(np.float32)), row.kv_len)
    row = row.with_kv_len(10)
    paged = PagedKVCache.create(L, 2, 16, ps, T // ps, H, D,
                                cache_dtype="int8")
    assert isinstance(paged, QuantPagedKVCache)
    table = jnp.asarray(np.array([1, 2, 3, 4], np.int32))
    paged = paged.install_row(row, 0, table, 0)
    tb = np.asarray(table)

    def rows(pool):  # [L, n_pages, H, page, ..] -> the row's [L, T, H, ..]
        return np.swapaxes(np.asarray(pool)[:, tb], 2, 3).reshape(
            (L, T, H) + pool.shape[4:])

    kp, sp = rows(paged.k), rows(paged.k_scale)
    np.testing.assert_array_equal(kp[:, :10], np.asarray(row.k)[:, 0, :10])
    np.testing.assert_array_equal(sp[:, :10],
                                  np.asarray(row.k_scale)[:, 0, :10])
    # the next decode write quantizes identical bits through the table
    k1 = rng.randn(1, 1, H, D).astype(np.float32)
    v1 = rng.randn(1, 1, H, D).astype(np.float32)
    drow = row.update(0, jnp.asarray(k1), jnp.asarray(v1), row.kv_len)
    prow = paged.with_kv_len(jnp.asarray(np.array([10, 0], np.int32)))
    prow = prow.update(0, jnp.asarray(np.concatenate([k1, k1])),
                       jnp.asarray(np.concatenate([v1, v1])),
                       prow.kv_len)
    kq, sq = rows(prow.k), rows(prow.k_scale)
    np.testing.assert_array_equal(kq[0, 10], np.asarray(drow.k)[0, 0, 10])
    np.testing.assert_array_equal(sq[0, 10],
                                  np.asarray(drow.k_scale)[0, 0, 10])


def test_quant_decode_kernel_interpret_parity():
    """The Pallas int8 decode kernel (interpret mode) against the XLA
    fused-dequant fallback — same scale-on-score-columns structure, so
    they agree to float tolerance (the TPU-vs-CPU parity contract the
    wide kernel already carries)."""
    from paddle_tpu.kernels.flash_attention import (_decode_pallas,
                                                    _decode_xla)
    rng = np.random.RandomState(2)
    B, T, D, sq = 2, 128, 64, 2
    k8 = rng.randint(-127, 128, (B, T, D)).astype(np.int8)
    v8 = rng.randint(-127, 128, (B, T, D)).astype(np.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (B, T))
                     .astype(np.float32)).astype(jnp.bfloat16)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (B, T))
                     .astype(np.float32)).astype(jnp.bfloat16)
    q = rng.randn(B, sq, D).astype(np.float32)
    kv_len = jnp.asarray(np.array([37, 100], np.int32))
    args = (jnp.asarray(q), jnp.asarray(k8), jnp.asarray(v8), kv_len,
            float(D ** -0.5))
    ref = _decode_xla(*args, ks=ks, vs=vs)
    out = _decode_pallas(*args, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


# --------------------------------------------- THE parity gates (tier-1)


def test_int8_logit_error_bounded(tiny_gpt):
    """Decode logits over the int8 cache stay within a calibrated
    bound of the full-width cache (measured ~3e-4 on test-tiny; gate
    at 10x headroom relative to the logit scale)."""
    ids = np.random.RandomState(0).randint(0, 512, (1, 24)) \
        .astype(np.int32)
    plen = Tensor(np.full((1,), 24, np.int32))
    _, cw = tiny_gpt.forward(Tensor(ids), use_cache=True,
                             prompt_len=plen, cache_max_len=128)
    _, cq = tiny_gpt.forward(Tensor(ids), use_cache=True,
                             prompt_len=plen, cache_max_len=128,
                             cache_dtype="int8")
    tok = Tensor(np.array([[3]], np.int32))
    lw, _ = tiny_gpt.forward(tok, cache=cw)
    lq, _ = tiny_gpt.forward(tok, cache=cq)
    a, b = np.asarray(lw._data), np.asarray(lq._data)
    assert np.abs(a - b).max() <= 0.01 * max(1.0, np.abs(a).max())


def test_int8_greedy_eos_position_parity(tiny_gpt):
    """Greedy generation over the int8 cache stops at the SAME eos
    position as the full-width cache on test-tiny (the PR-pattern
    parity gate: the quantization error must not move the argmax at
    any step before eos)."""
    ids = np.random.RandomState(5).randint(0, 512, (2, 20)) \
        .astype(np.int32)
    wide = np.asarray(tiny_gpt.generate(ids, max_new_tokens=16)._data)
    # pick the token the wide stream emits mid-sequence as eos, so the
    # parity test exercises a REAL stop
    row = 0
    eos = int(wide[row, 4])
    w = np.asarray(tiny_gpt.generate(
        ids, max_new_tokens=16, eos_token_id=eos)._data)
    q = np.asarray(tiny_gpt.generate(
        ids, max_new_tokens=16, eos_token_id=eos,
        kv_cache_dtype="int8")._data)
    w_eos = np.argmax(w[row] == eos)
    q_eos = np.argmax(q[row] == eos)
    assert (eos in w[row]) and (eos in q[row])
    assert w_eos == q_eos
    np.testing.assert_array_equal(w[row][:w_eos], q[row][:q_eos])
    # the other row's full streams must agree token-for-token up to
    # ITS first eos too (positions after a row's eos hold padding)
    other = 1 - row
    w_cut = np.argmax(w[other] == eos) if eos in w[other] else 16
    q_cut = np.argmax(q[other] == eos) if eos in q[other] else 16
    assert w_cut == q_cut
    np.testing.assert_array_equal(w[other][:w_cut], q[other][:q_cut])


def test_int8_engine_bitwise_and_zero_retrace(tiny_gpt, int8_engine,
                                              int8_reference):
    """The PR-8 gate shape under int8: ragged traffic through the
    dense int8 engine with mid-decode arrivals — zero post-warmup
    compiles AND every request bitwise-equal to the sequential int8
    session (prefill quantizes once, the admit copies int8+scales
    verbatim, decode quantizes per row independently)."""
    from paddle_tpu.core import monitor
    engine = int8_engine
    rng = np.random.RandomState(0)
    lens = (5, 12, 14, 7, 3)
    budgets = (8, 3, 6, 5, 8)
    prompts = [rng.randint(0, 512, n).astype(np.int32) for n in lens]
    monitor.enable()
    try:
        ns0 = _counter("jit.compile{cause=new_shape}")
        tot0 = _counter("jit.compile.total")
        handles = [engine.submit(p, RequestParams(max_new_tokens=b))
                   for p, b in zip(prompts[:2], budgets[:2])]
        for _ in range(3):
            engine.step()
        handles += [engine.submit(p, RequestParams(max_new_tokens=b))
                    for p, b in zip(prompts[2:], budgets[2:])]
        while engine.busy:
            engine.step()
        assert _counter("jit.compile{cause=new_shape}") - ns0 == 0
        assert _counter("jit.compile.total") - tot0 == 0
        # the structural invariant: absmax scales never saturate
        assert _counter("gen.cache.quant.scale_clips") == 0
    finally:
        monitor.disable()
    assert all(h.status is RequestStatus.COMPLETED for h in handles)
    for p, b, h in zip(prompts, budgets, handles):
        np.testing.assert_array_equal(
            h.result(), int8_reference(p, b, engine.max_len)[:b])


def test_int8_pages_cow_scales_privatize(tiny_gpt, int8_paged_engine,
                                         int8_reference):
    """int8 pages x shared-prefix COW: two identical 20-token prompts
    (20 % 16 != 0) — the second references the first's full page and
    privatizes the partial tail, VALUES AND SCALES together (the
    scales live in the page), so both decode bitwise-equal to the
    sequential int8 reference."""
    engine = int8_paged_engine
    stats0 = dict(engine._alloc.stats)
    prompt = np.random.RandomState(3).randint(0, 512, 20) \
        .astype(np.int32)
    h1 = engine.submit(prompt, RequestParams(max_new_tokens=6))
    while engine.busy:
        engine.step()
    h2 = engine.submit(prompt.copy(), RequestParams(max_new_tokens=8))
    while engine.busy:
        engine.step()
    s = engine._alloc.stats
    assert s["prefix_hits"] - stats0["prefix_hits"] == 1
    assert s["cow_copies"] - stats0["cow_copies"] == 1
    np.testing.assert_array_equal(
        h1.result(), int8_reference(prompt, 6, engine.max_len)[:6])
    np.testing.assert_array_equal(
        h2.result(), int8_reference(prompt, 8, engine.max_len)[:8])
    engine._alloc.assert_conserved()


def test_int8_speculative_accept_rate(tiny_gpt):
    """Speculative ngram windows over the int8 cache: greedy output
    matches the sequential int8 stream bitwise, and the accept rate
    stays within tolerance of the full-width run (quantization must
    not break the drafter's repetition hits)."""
    from paddle_tpu.core import monitor
    motif = np.random.RandomState(7).randint(0, 512, 8)
    ids = np.tile(motif, 8)[None, :48].astype(np.int32)

    def accept_rate(kv_dtype):
        monitor.enable()
        try:
            p0 = _counter("gen.spec.proposed")
            a0 = _counter("gen.spec.accepted")
            out = tiny_gpt.generate(ids, max_new_tokens=16,
                                    speculative="ngram",
                                    kv_cache_dtype=kv_dtype)
            dp = _counter("gen.spec.proposed") - p0
            da = _counter("gen.spec.accepted") - a0
        finally:
            monitor.disable()
        return np.asarray(out._data)[0], (da / dp if dp else 0.0)

    seq = np.asarray(tiny_gpt.generate(
        ids, max_new_tokens=16, kv_cache_dtype="int8")._data)[0]
    out_q, rate_q = accept_rate("int8")
    _, rate_w = accept_rate(None)
    np.testing.assert_array_equal(out_q, seq)   # greedy bitwise gate
    assert abs(rate_q - rate_w) <= 0.15


# ----------------------------------------------------- int4 weight path


def test_int4_pack_unpack_roundtrip():
    """Two-nibbles-per-byte packing round-trips exactly for the int4
    value range, even and odd row counts (the pad row slices off)."""
    from paddle_tpu.inference.precision import pack_int4, unpack_int4
    rng = np.random.RandomState(0)
    for rows in (6, 7):
        q = rng.randint(-7, 8, (rows, 5)).astype(np.int8)
        packed = pack_int4(jnp.asarray(q))
        assert packed.shape == ((rows + 1) // 2, 5)
        assert packed.dtype == jnp.int8
        np.testing.assert_array_equal(
            np.asarray(unpack_int4(packed, rows)), q)


def test_int4_weight_serving(tiny_gpt):
    """precision Int8 + weight_bits=4: Linear weights pack two values
    per stored byte with per-channel scales, materialize reconstructs
    them in-trace, and the served engine still decodes correctly
    (finite outputs, zero post-warmup compiles, audit clean at
    donation 1.0)."""
    from paddle_tpu.core import monitor
    from paddle_tpu.inference.precision import serving_params
    cfg = _config(tiny_gpt, kv_cache_dtype="int8", weight_bits=4)
    cfg.precision = PrecisionType.Int8
    sp = serving_params(tiny_gpt, cfg)
    assert sp.int4, "no Linear weight took the int4 path"
    for n, rows in sp.int4.items():
        i = sp.names.index(n)
        assert sp.vals[i].shape[0] == (rows + 1) // 2
    # dequant error bounded by the per-channel int4 step
    n = next(iter(sp.int4))
    i = sp.names.index(n)
    w = tiny_gpt.state_dict()[n]._data
    deq = np.asarray(sp.materialize(list(sp.vals))[i], np.float32)
    step = np.asarray(sp.scales[n], np.float32)  # absmax/7 per channel
    assert (np.abs(deq - np.asarray(w)) <= step * 0.75 + 1e-6).all()

    engine = ServingEngine(cfg, poll_every=2)
    monitor.enable()
    try:
        tot0 = _counter("jit.compile.total")
        h = engine.submit(np.arange(1, 9, dtype=np.int32),
                          RequestParams(max_new_tokens=6))
        while engine.busy:
            engine.step()
        assert _counter("jit.compile.total") - tot0 == 0
    finally:
        monitor.disable()
    assert h.status is RequestStatus.COMPLETED and len(h.result()) == 6
    reports = engine.audit()
    assert all(not r.errors for r in reports.values())
    assert reports["decode"].donation_coverage == 1.0
    engine.shutdown()


# -------------------------------------------------- analysis satellite


def test_quant_escape_detector():
    """dtype.quant_escape: an int8 buffer widened to float in
    UNSANCTIONED code fires a WARNING naming the site; registering the
    site silences it; the sanctioned fused-dequant paths never fire
    (asserted on a real quantized decode program below)."""
    from paddle_tpu.analysis import audit, register_dequant_site
    from paddle_tpu.analysis.detectors import QUANT_DEQUANT_SITES

    def escape(x8, w):
        return jnp.dot(x8.astype(jnp.float32), w)

    rep = audit(escape, jax.ShapeDtypeStruct((8, 8), jnp.int8),
                jax.ShapeDtypeStruct((8, 8), jnp.float32))
    qe = [f for f in rep.findings if f.check == "dtype.quant_escape"]
    assert len(qe) == 1 and "widens a quantized" in qe[0].message
    assert qe[0].severity.name == "WARNING"   # gate stays zero-ERROR
    # registering this test file as a dequant site silences it
    register_dequant_site("test_quant_cache.py")
    try:
        rep2 = audit(escape, jax.ShapeDtypeStruct((8, 8), jnp.int8),
                     jax.ShapeDtypeStruct((8, 8), jnp.float32))
        assert not [f for f in rep2.findings
                    if f.check == "dtype.quant_escape"]
    finally:
        QUANT_DEQUANT_SITES.discard("test_quant_cache.py")


def test_quant_audit_gates(int8_paged_engine):
    """The tier-1 audit gate over every int8-cache program (paged
    prefill/decode/admit/free): zero ERRORs, donation 1.0 on decode,
    and ZERO quant_escape findings — the int8 pools and scale sidecars
    are sanctioned storage, their only widening is the fused kernel
    dequant."""
    reports = int8_paged_engine.audit()
    for key, r in reports.items():
        assert not r.errors, f"{key}: {r.errors}"
        assert not [f for f in r.findings
                    if f.check == "dtype.quant_escape"], key
    assert reports["decode"].donation_coverage == 1.0
    assert reports["admit"].donation_coverage == 1.0


# ------------------------------------------------- health + metrics


def test_health_capacity_tokens(int8_engine, int8_paged_engine):
    """health() reports effective cache capacity in TOKENS (the PR-12
    remainder): slots x max_len dense, pool pages x page size paged —
    the number already reflects the cache dtype because an int8 pool
    at equal HBM is configured with ~2x the pages."""
    h = int8_engine.health()
    assert h["kv_cache_dtype"] == "int8"
    assert h["capacity_tokens"] == \
        int8_engine.max_batch * int8_engine.max_len
    assert h["free_tokens"] <= h["capacity_tokens"]
    hp = int8_paged_engine.health()
    assert hp["kv_cache_dtype"] == "int8"
    assert hp["capacity_tokens"] == \
        (int8_paged_engine._alloc.n_pages - 1) * \
        int8_paged_engine.page_size
    assert hp["free_tokens"] == \
        int8_paged_engine._alloc.free_pages() * \
        int8_paged_engine.page_size


def test_kv_dtype_gauge_and_bytes_saved(tiny_gpt):
    """Engine construction publishes the serve.cache.kv_dtype info
    gauge and the gen.cache.quant.bytes_saved accounting (int8 values
    + bf16 scales vs the wide dtype)."""
    from paddle_tpu.core import monitor
    from paddle_tpu.profiler import metrics
    monitor.enable()
    try:
        b0 = _counter("gen.cache.quant.bytes_saved")
        engine = ServingEngine(_config(tiny_gpt), poll_every=2)
        snap = metrics.snapshot()
        assert snap["serve.cache.kv_dtype{dtype=int8}"]["value"] == 1.0
        saved = _counter("gen.cache.quant.bytes_saved") - b0
        # k+v elements * (4 - 1) bytes minus the bf16 scale sidecars
        k = engine._cache.k
        expect = 2 * k.size * 3 - 2 * (k.size // k.shape[-1]) * 2
        assert saved == expect
        engine.shutdown()
    finally:
        monitor.disable()


# ------------------------------------------------------------- knobs


def test_resolve_cache_dtype_env(monkeypatch):
    assert resolve_cache_dtype(None) is None
    assert resolve_cache_dtype("int8") == "int8"
    with pytest.raises(ValueError):
        resolve_cache_dtype("int3")
    monkeypatch.setenv("PADDLE_KV_CACHE_DTYPE", "int8")
    assert resolve_cache_dtype(None) == "int8"
    monkeypatch.setenv("PADDLE_KV_CACHE_DTYPE", "garbage")
    assert resolve_cache_dtype(None) is None   # swallowed, falls wide
    monkeypatch.setenv("PADDLE_KV_CACHE_DTYPE", "off")
    assert resolve_cache_dtype(None) is None


def test_generate_session_dtype_mismatch_raises(tiny_gpt):
    from paddle_tpu.generation.api import GenerationSession, generate
    sess = GenerationSession(tiny_gpt)   # full-width session
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        generate(tiny_gpt, np.arange(1, 9, dtype=np.int32)[None, :],
                 4, session=sess, kv_cache_dtype="int8")
    with pytest.raises(ValueError):
        Config().enable_generation(kv_cache_dtype="int3")
    with pytest.raises(ValueError):
        Config().enable_serving(weight_bits=5)
