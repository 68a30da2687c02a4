"""Speculative decoding tests (ISSUE 11): the n-gram prompt-lookup
drafter, greedy + rejection-sampling acceptance, the session and engine
verify paths (greedy bitwise-equal to sequential decode — the
acceptance gate), the eos/budget/ring overshoot clamps at their exact
boundaries, the q-len guard, GQA verify-window kernel parity, the
gen.spec.* metrics family, audit gates, the Predictor bucket path, and
the chaos-tier drain.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.generation import (GenerationConfig, GenerationSession,
                                   SpeculativeConfig, generate,
                                   ngram_propose, spec_accept)
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.models.gpt import gpt
from paddle_tpu.serving import RequestParams, RequestStatus, ServingEngine

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(0)
    m = gpt("test-tiny")
    m.eval()
    return m


@pytest.fixture(scope="module")
def draft_gpt():
    paddle.seed(7)
    m = gpt("test-tiny-draft")
    m.eval()
    return m


@pytest.fixture(scope="module")
def prompt_ids():
    return np.random.RandomState(0).randint(
        0, 512, (2, 12)).astype(np.int32)


def _counter(name):
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot().get(name)
    return int(snap["value"]) if snap else 0


# -------------------------------------------------------------- drafter


def test_ngram_propose_finds_most_recent_continuation():
    # row 0: suffix (7, 8) occurred twice; the MOST RECENT match (at 4)
    # must win, proposing its continuation 9, 1, 7
    buf = np.zeros((2, 16), np.int32)
    buf[0, :10] = [7, 8, 3, 5, 7, 8, 9, 1, 7, 8]
    # row 1: suffix (5, 6) never occurred earlier -> repeat last token
    buf[1, :6] = [1, 2, 3, 4, 5, 6]
    out = np.asarray(ngram_propose(jnp.asarray(buf),
                                   jnp.asarray([10, 6], np.int32),
                                   k=3, n=2))
    np.testing.assert_array_equal(out[0], [9, 1, 7])
    np.testing.assert_array_equal(out[1], [6, 6, 6])


def test_ngram_propose_clamps_continuation_to_known_tokens():
    # the match continuation runs off the valid region: missing slots
    # fall back to the last token, never read padding garbage
    buf = np.full((1, 12), 99, np.int32)
    buf[0, :7] = [4, 5, 1, 2, 4, 5, 1]
    out = np.asarray(ngram_propose(jnp.asarray(buf),
                                   jnp.asarray([7], np.int32),
                                   k=4, n=2))
    # match at 0 (suffix 4,5 at 4..5 -> wait: suffix is buf[5:7]=(5,1);
    # its earlier occurrence is at 1..2, continuation 2, 4, 5, then the
    # clamp repeats the last known token (1), never 99
    np.testing.assert_array_equal(out[0], [2, 4, 5, 1])
    assert 99 not in out


def test_ngram_propose_short_history_falls_back():
    buf = np.zeros((1, 8), np.int32)
    buf[0, :2] = [3, 4]
    out = np.asarray(ngram_propose(jnp.asarray(buf),
                                   jnp.asarray([2], np.int32),
                                   k=2, n=3))
    np.testing.assert_array_equal(out[0], [4, 4])


# ----------------------------------------------------------- acceptance


def test_spec_accept_greedy_prefix_and_correction():
    # vocab 6; target argmax per position: [2, 3, 4] (k=2, window 3)
    logits = np.full((1, 3, 6), -5.0, np.float32)
    logits[0, 0, 2] = 5.0
    logits[0, 1, 3] = 5.0
    logits[0, 2, 4] = 5.0
    cfg = GenerationConfig()
    # draft [2, 3]: both match -> n_accept 2, bonus token 4 at index 2
    emitted, n = spec_accept(jnp.asarray(logits),
                             jnp.asarray([[2, 3]], np.int32),
                             jax.random.PRNGKey(0), cfg)
    assert int(n[0]) == 2
    np.testing.assert_array_equal(np.asarray(emitted)[0], [2, 3, 4])
    # draft [2, 9]: mismatch at index 1 -> accept 1, correction 3 there
    emitted, n = spec_accept(jnp.asarray(logits),
                             jnp.asarray([[2, 9]], np.int32),
                             jax.random.PRNGKey(0), cfg)
    assert int(n[0]) == 1
    np.testing.assert_array_equal(np.asarray(emitted)[0, :2], [2, 3])
    # draft [9, 9]: immediate mismatch -> accept 0, correction 2 first
    emitted, n = spec_accept(jnp.asarray(logits),
                             jnp.asarray([[9, 9]], np.int32),
                             jax.random.PRNGKey(0), cfg)
    assert int(n[0]) == 0
    assert int(np.asarray(emitted)[0, 0]) == 2


def test_spec_accept_rejection_matches_target_distribution():
    """The distributional satellite: with a deterministic (point-mass)
    drafter, accept-with-prob-p(d) + residual resampling must emit the
    FIRST token exactly from the target distribution — empirically,
    over many keys, against the analytic softmax."""
    probs = np.array([0.45, 0.25, 0.15, 0.10, 0.05], np.float64)
    logits = np.log(probs)[None, None, :].repeat(2, axis=1)  # [1, 2, 5]
    draft = jnp.asarray([[0]], np.int32)       # draft the likeliest token
    cfg = GenerationConfig(do_sample=True, temperature=1.0)
    n_trials = 800
    counts = np.zeros(5)
    for i in range(n_trials):
        emitted, _ = spec_accept(jnp.asarray(logits, jnp.float32), draft,
                                 jax.random.PRNGKey(i), cfg)
        counts[int(np.asarray(emitted)[0, 0])] += 1
    emp = counts / n_trials
    tv = 0.5 * np.abs(emp - probs).sum()
    assert tv < 0.1, f"total variation {tv:.3f}: emp={emp} vs {probs}"


def test_spec_accept_temperature_filters_apply():
    # top_k=1 collapses the filtered distribution to argmax: rejection
    # sampling must then behave exactly greedily for any key
    logits = np.zeros((1, 2, 8), np.float32)
    logits[0, 0, 3] = 4.0
    logits[0, 1, 5] = 4.0
    cfg = GenerationConfig(do_sample=True, temperature=1.7, top_k=1)
    for i in range(10):
        emitted, n = spec_accept(jnp.asarray(logits),
                                 jnp.asarray([[3]], np.int32),
                                 jax.random.PRNGKey(i), cfg)
        assert int(n[0]) == 1
        np.testing.assert_array_equal(np.asarray(emitted)[0], [3, 5])


# ------------------------------------------------------------ config


def test_spec_config_validation():
    from paddle_tpu.kernels.flash_attention import MAX_DECODE_QLEN
    with pytest.raises(ValueError, match="mode"):
        SpeculativeConfig(mode="telepathy")
    with pytest.raises(ValueError, match="draft_k"):
        SpeculativeConfig(k=0)
    # the q-len guard at the API boundary, naming the kernel limit
    with pytest.raises(ValueError, match="MAX_DECODE_QLEN"):
        SpeculativeConfig(k=MAX_DECODE_QLEN)
    SpeculativeConfig(k=MAX_DECODE_QLEN - 1)     # boundary: window == 8
    with pytest.raises(ValueError, match="ngram"):
        SpeculativeConfig(ngram=0)


def test_spec_mode_model_crosschecks(tiny_gpt, draft_gpt, prompt_ids):
    with pytest.raises(ValueError, match="draft_model"):
        tiny_gpt.generate(prompt_ids, max_new_tokens=4,
                          speculative="draft")
    with pytest.raises(ValueError, match="ngram"):
        tiny_gpt.generate(prompt_ids, max_new_tokens=4,
                          speculative="ngram", draft_model=draft_gpt)
    with pytest.raises(TypeError, match="SpeculativeConfig"):
        tiny_gpt.generate(prompt_ids, max_new_tokens=4, speculative=3)


# --------------------------------------------- session greedy parity


@pytest.mark.parametrize("k", [1, 4])
def test_generate_ngram_greedy_bitwise(tiny_gpt, prompt_ids, k):
    """THE acceptance gate (session path): greedy speculative output is
    bitwise-equal to sequential decode, eos padding included."""
    ref = np.asarray(tiny_gpt.generate(prompt_ids,
                                       max_new_tokens=16)._data)
    out = np.asarray(tiny_gpt.generate(
        prompt_ids, max_new_tokens=16,
        speculative=SpeculativeConfig(k=k))._data)
    np.testing.assert_array_equal(out, ref)
    eos = int(ref[0, 3])
    ref_e = np.asarray(tiny_gpt.generate(
        prompt_ids, max_new_tokens=16, eos_token_id=eos,
        pad_token_id=499)._data)
    out_e = np.asarray(tiny_gpt.generate(
        prompt_ids, max_new_tokens=16, eos_token_id=eos,
        pad_token_id=499, speculative=SpeculativeConfig(k=k))._data)
    np.testing.assert_array_equal(out_e, ref_e)


def test_generate_ngram_ragged_rows_bitwise(tiny_gpt, prompt_ids):
    ref = np.asarray(tiny_gpt.generate(
        prompt_ids, max_new_tokens=8, prompt_len=[5, 12],
        cache_max_len=128)._data)
    out = np.asarray(tiny_gpt.generate(
        prompt_ids, max_new_tokens=8, prompt_len=[5, 12],
        cache_max_len=128, speculative="ngram")._data)
    np.testing.assert_array_equal(out, ref)


def test_generate_draft_model_greedy_bitwise(tiny_gpt, draft_gpt,
                                             prompt_ids):
    """Draft-model path: an arbitrary (even useless) draft model never
    changes greedy output — and a perfect drafter (the target itself)
    accepts everything while still matching bitwise."""
    from paddle_tpu.core import monitor
    from paddle_tpu.profiler import metrics
    ref = np.asarray(tiny_gpt.generate(prompt_ids,
                                       max_new_tokens=12)._data)
    out = np.asarray(tiny_gpt.generate(
        prompt_ids, max_new_tokens=12, speculative="draft",
        draft_model=draft_gpt)._data)
    np.testing.assert_array_equal(out, ref)
    monitor.enable()
    try:
        p0, a0 = _counter("gen.spec.proposed"), _counter("gen.spec.accepted")
        # max_new 11 = prefill token + two FULL k=4 windows, so the
        # budget clamp never discards an over-budget acceptance and the
        # self-draft accept rate is exactly 1.0
        out_self = np.asarray(tiny_gpt.generate(
            prompt_ids, max_new_tokens=11, speculative="draft",
            draft_model=tiny_gpt)._data)
        dp = _counter("gen.spec.proposed") - p0
        da = _counter("gen.spec.accepted") - a0
    finally:
        monitor.disable()
    np.testing.assert_array_equal(out_self, ref[:, :11])
    assert dp > 0 and da == dp    # self-draft: every proposal accepted


def test_generate_spec_sampling_seeded(tiny_gpt, prompt_ids):
    kw = dict(max_new_tokens=8, do_sample=True, temperature=1.3,
              top_k=50, speculative="ngram")
    a = np.asarray(tiny_gpt.generate(prompt_ids, seed=11, **kw)._data)
    b = np.asarray(tiny_gpt.generate(prompt_ids, seed=11, **kw)._data)
    c = np.asarray(tiny_gpt.generate(prompt_ids, seed=12, **kw)._data)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.shape == (2, 8) and (a >= 0).all() and (a < 512).all()


# ------------------------------------------- overshoot clamps (satellite)


def _looping_prompt(n=24):
    # a repeated motif makes the prompt-lookup drafter accept (the
    # boundary tests need real multi-token acceptances to clamp)
    motif = np.array([11, 7, 42, 99, 3, 5], np.int32)
    return np.tile(motif, n // motif.size + 1)[None, :n]


def test_spec_budget_boundary_never_overshoots(tiny_gpt):
    """max_new_tokens lands MID verify window (k=4, window 5, budget 6
    with high accept): the clamp emits exactly the budget, bitwise
    equal to sequential decode, nothing written past the buffer."""
    ids = _looping_prompt()
    for max_new in (5, 6, 7):
        ref = np.asarray(tiny_gpt.generate(
            ids, max_new_tokens=max_new)._data)
        out = np.asarray(tiny_gpt.generate(
            ids, max_new_tokens=max_new, speculative="ngram")._data)
        assert out.shape == (1, max_new)
        np.testing.assert_array_equal(out, ref)


def test_spec_ring_capacity_exact_boundary(tiny_gpt):
    """The ring must carry spec.k slack for the last window's
    unaccepted overhang: the exact bound passes, one below raises up
    front (never discovered as ring corruption)."""
    ids = _looping_prompt()                       # prompt 24
    k, max_new = 4, 8
    exact = 24 + max_new + k
    out = np.asarray(tiny_gpt.generate(
        ids, max_new_tokens=max_new, cache_max_len=exact,
        speculative=SpeculativeConfig(k=k))._data)
    ref = np.asarray(tiny_gpt.generate(
        ids, max_new_tokens=max_new, cache_max_len=exact)._data)
    np.testing.assert_array_equal(out, ref)
    with pytest.raises(ValueError, match="overhang"):
        tiny_gpt.generate(ids, max_new_tokens=max_new,
                          cache_max_len=exact - 1,
                          speculative=SpeculativeConfig(k=k))
    # the same budget fits fine without speculation
    tiny_gpt.generate(ids, max_new_tokens=max_new,
                      cache_max_len=exact - 1)


def test_spec_position_table_overhang_guard(tiny_gpt, prompt_ids):
    # prompt 12 + max_new 113 fits max_position_embeddings=128 plain,
    # but not with the k=4 verify-window overhang
    with pytest.raises(ValueError, match="overhang"):
        tiny_gpt.generate(prompt_ids, max_new_tokens=113,
                          speculative="ngram")


# --------------------------------------------------- retraces + metrics


def test_spec_generate_compiles_once(prompt_ids):
    """First speculative call compiles prefill + draft + verify; the
    repeat adds zero (the no-retrace contract, same gate shape as the
    plain exactly-two-compiles test)."""
    from paddle_tpu.core import monitor
    paddle.seed(1)
    m = gpt("test-tiny")
    monitor.enable()
    try:
        t0 = _counter("jit.compile.total")
        s0 = _counter("jit.compile{cause=new_shape}")
        m.generate(prompt_ids, max_new_tokens=6, speculative="ngram")
        first = _counter("jit.compile.total") - t0
        assert first == 3        # prefill + spec draft + spec verify
        m.generate(prompt_ids, max_new_tokens=6, speculative="ngram")
        assert _counter("jit.compile.total") - t0 == first
        assert _counter("jit.compile{cause=new_shape}") - s0 == 0
    finally:
        monitor.disable()


def test_spec_metrics_family(tiny_gpt):
    from paddle_tpu.core import monitor
    from paddle_tpu.profiler import metrics
    ids = _looping_prompt()
    monitor.enable()
    try:
        p0, a0 = _counter("gen.spec.proposed"), _counter("gen.spec.accepted")
        tiny_gpt.generate(ids, max_new_tokens=12, speculative="ngram")
        dp = _counter("gen.spec.proposed") - p0
        da = _counter("gen.spec.accepted") - a0
        assert dp > 0
        assert 0 < da <= dp     # the looping prompt really accepts
        rate = metrics.snapshot().get("gen.spec.accept_rate")
        assert rate and 0.0 < rate["value"] <= 1.0
    finally:
        monitor.disable()


# ------------------------------------------------------------ audit gate


def test_session_audit_speculative_gate(tiny_gpt, draft_gpt):
    """Tier-1 gate: the draft + single-dispatch verify programs audit
    at zero ERRORs with full donation coverage on verify (cache, token
    buffers, and every lane in place across windows)."""
    sess = GenerationSession(tiny_gpt)
    for spec_kw in (dict(speculative="ngram"),
                    dict(speculative="draft", draft_network=draft_gpt)):
        reports = sess.audit(2, 16, 128, GenerationConfig(),
                             max_new=8, **spec_kw)
        assert len(reports) == 4
        for rep in reports:
            rep.raise_on_error()
        draft_rep, verify_rep = reports[2], reports[3]
        assert verify_rep.donation_coverage == 1.0
        assert not verify_rep.by_check("host_sync")
        assert draft_rep.donation_coverage == 1.0


# -------------------------------------- GQA verify-window kernel parity


@pytest.mark.parametrize("hq,hk", [(4, 2), (8, 1)])
def test_decode_kernel_gqa_verify_window_equivalence(hq, hk):
    """MQA/GQA satellite: a q-len-4 verify window through the
    head-index-mapped decode kernel equals four sequential q-len-1
    calls at incrementing kv_len — the exact shape speculative verify
    dispatches on grouped-head models."""
    from paddle_tpu.kernels.flash_attention import flash_attention_decode
    rng = np.random.RandomState(5)
    b, d, t, w, base = 2, 64, 128, 4, 9
    q = rng.randn(b, w, hq, d).astype(np.float32)
    kc = rng.randn(b, t, hk, d).astype(np.float32)
    vc = rng.randn(b, t, hk, d).astype(np.float32)
    window = np.asarray(flash_attention_decode(
        q, kc, vc, np.full((b,), base + w, np.int32)))
    for i in range(w):
        step = np.asarray(flash_attention_decode(
            q[:, i:i + 1], kc, vc,
            np.full((b,), base + i + 1, np.int32)))
        np.testing.assert_allclose(window[:, i], step[:, 0],
                                   rtol=2e-5, atol=2e-5)


def test_decode_kernel_qlen_guard_names_limit():
    from paddle_tpu.kernels.flash_attention import (MAX_DECODE_QLEN,
                                                    flash_attention_decode)
    assert MAX_DECODE_QLEN == 8
    z = np.zeros((1, MAX_DECODE_QLEN + 1, 2, 64), np.float32)
    c = np.zeros((1, 128, 2, 64), np.float32)
    with pytest.raises(ValueError, match="MAX_DECODE_QLEN"):
        flash_attention_decode(z, c, c, np.array([9], np.int32))


# ------------------------------------------------------------- predictor


def test_predictor_speculative_buckets(tiny_gpt, prompt_ids):
    """Predictor path: spec draft+verify AOT-compiled per bucket, zero
    compiles under traffic, greedy parity with the plain predictor."""
    from paddle_tpu.core import monitor
    spec = [paddle.to_tensor(prompt_ids)]
    pred = create_predictor(
        Config().from_layer(tiny_gpt, spec)
        .enable_generation(max_new_tokens=6, prefill_buckets=(16, 32),
                           max_batch=2, speculative="ngram"))
    plain = create_predictor(
        Config().from_layer(tiny_gpt, spec)
        .enable_generation(max_new_tokens=6, prefill_buckets=(16, 32),
                           max_batch=2))
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 512, n).tolist() for n in (5, 12, 30)]
    monitor.enable()
    try:
        t0 = _counter("jit.compile.total")
        outs = pred.generate(prompts)
        assert _counter("jit.compile.total") - t0 == 0
    finally:
        monitor.disable()
    for got, ref in zip(outs, plain.generate(prompts)):
        np.testing.assert_array_equal(got, ref)
    # the audit covers the spec pair per bucket at zero errors
    reports = pred.audit_generation()
    assert ("spec_verify", 16) in reports and ("spec_draft", 16) in reports
    for rep in reports.values():
        rep.raise_on_error()
    assert reports[("spec_verify", 16)].donation_coverage == 1.0


def test_predictor_spec_smaller_max_new_stays_warm(tiny_gpt,
                                                   prompt_ids):
    """Review regression: generate(max_new_tokens=<below the compiled
    budget>) must decode into the compiled out-buffer width (budget is
    a lane) and hit the AOT verify executable — zero compiles, result
    still the requested length, parity with the plain path."""
    from paddle_tpu.core import monitor
    spec = [paddle.to_tensor(prompt_ids)]
    pred = create_predictor(
        Config().from_layer(tiny_gpt, spec)
        .enable_generation(max_new_tokens=8, prefill_buckets=(16,),
                           max_batch=2, speculative="ngram"))
    plain = create_predictor(
        Config().from_layer(tiny_gpt, spec)
        .enable_generation(max_new_tokens=8, prefill_buckets=(16,),
                           max_batch=2))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
    monitor.enable()
    try:
        t0 = _counter("jit.compile.total")
        outs = pred.generate(prompts, max_new_tokens=4)
        assert _counter("jit.compile.total") - t0 == 0
    finally:
        monitor.disable()
    assert all(o.size <= 4 for o in outs)
    for got, ref in zip(outs, plain.generate(prompts, max_new_tokens=4)):
        np.testing.assert_array_equal(got, ref)


def test_draft_model_position_table_guard(tiny_gpt):
    """Review regression: a draft model whose position table is
    smaller than the decode range fails up front, not as a silently
    clipped gather producing garbage proposals."""
    paddle.seed(9)
    short_draft = gpt("test-tiny-draft", max_position_embeddings=16)
    short_draft.eval()
    ids = np.random.RandomState(0).randint(0, 512, (1, 12)) \
        .astype(np.int32)
    with pytest.raises(ValueError, match="DRAFT"):
        tiny_gpt.generate(ids, max_new_tokens=8, speculative="draft",
                          draft_model=short_draft)


def test_predictor_spec_bucket_overhang_filter(tiny_gpt, prompt_ids):
    # 118 + 6 fits max_position_embeddings=128 plain but not with k=4
    spec = [paddle.to_tensor(prompt_ids)]
    plain = create_predictor(
        Config().from_layer(tiny_gpt, spec)
        .enable_generation(max_new_tokens=6, prefill_buckets=(16, 122)))
    assert plain._gen_buckets == [16, 122]
    pred = create_predictor(
        Config().from_layer(tiny_gpt, spec)
        .enable_generation(max_new_tokens=6, prefill_buckets=(16, 122),
                           speculative="ngram"))
    assert pred._gen_buckets == [16]


# ---------------------------------------------------------------- engine


def _spec_config(m, *, max_new=8, buckets=(16, 32), max_batch=2,
                 eos=None, speculative="ngram"):
    return (Config()
            .from_layer(m, [paddle.to_tensor(np.zeros((2, 12), np.int32))])
            .enable_generation(max_new_tokens=max_new,
                               prefill_buckets=buckets,
                               max_batch=max_batch, eos_token_id=eos,
                               speculative=speculative))


def test_engine_rejects_draft_mode(tiny_gpt, draft_gpt):
    with pytest.raises(ValueError, match="ngram"):
        ServingEngine(_spec_config(
            tiny_gpt, speculative=SpeculativeConfig(mode="draft")),
            warmup=False)


def test_engine_speculative_ragged_bitwise(tiny_gpt):
    """THE engine acceptance gate: ragged prompts/budgets with
    mid-decode arrivals through the speculative slot scheduler — zero
    new-shape retraces after warmup, every request bitwise-equal to
    the sequential non-speculative Predictor."""
    from paddle_tpu.core import monitor
    eng = ServingEngine(_spec_config(tiny_gpt), poll_every=2)
    rng = np.random.RandomState(0)
    lens = (5, 12, 20, 7, 3)
    budgets = (8, 3, 6, 5, 8)
    prompts = [rng.randint(0, 512, n).astype(np.int32) for n in lens]
    monitor.enable()
    try:
        ns0 = _counter("jit.compile{cause=new_shape}")
        tot0 = _counter("jit.compile.total")
        handles = [eng.submit(p, RequestParams(max_new_tokens=b))
                   for p, b in zip(prompts[:2], budgets[:2])]
        for _ in range(3):
            eng.step()
        handles += [eng.submit(p, RequestParams(max_new_tokens=b))
                    for p, b in zip(prompts[2:], budgets[2:])]
        while eng.busy:
            eng.step()
        assert _counter("jit.compile{cause=new_shape}") - ns0 == 0
        assert _counter("jit.compile.total") - tot0 == 0
        # the poll drained the on-device counters into gen.spec.*
        assert _counter("gen.spec.proposed") > 0
    finally:
        monitor.disable()
    assert all(h.status is RequestStatus.COMPLETED for h in handles)
    assert eng.stats["spec_proposed"] > 0
    # speculation actually amortized dispatches: fewer decode steps
    # than tokens decoded (5 requests, budgets sum 30, batch 2)
    assert eng.stats["spec_accepted"] > 0
    pred = create_predictor(
        Config()
        .from_layer(tiny_gpt,
                    [paddle.to_tensor(np.zeros((2, 12), np.int32))])
        .enable_generation(max_new_tokens=8, prefill_buckets=(16, 32),
                           max_batch=1))
    for p, b, h in zip(prompts, budgets, handles):
        ref = pred.generate([p], max_new_tokens=b)[0]
        np.testing.assert_array_equal(h.result(), ref)


def test_engine_spec_budget_exact_boundary(tiny_gpt):
    """A verify window spanning the budget (looping prompt => real
    multi-token acceptance) emits EXACTLY the budget: the overshoot
    clamp satellite at its boundary, bitwise vs the sequential path."""
    eng = ServingEngine(_spec_config(tiny_gpt, max_new=8,
                                     buckets=(32,), max_batch=1),
                        poll_every=1)
    prompt = _looping_prompt()[0]
    for budget in (2, 3, 5):
        h = eng.submit(prompt, RequestParams(max_new_tokens=budget))
        out = h.result(timeout=60)
        assert out.size == budget
        assert int(np.asarray(eng._steps)[0]) == budget
    pred = create_predictor(
        Config()
        .from_layer(tiny_gpt,
                    [paddle.to_tensor(np.zeros((2, 12), np.int32))])
        .enable_generation(max_new_tokens=8, prefill_buckets=(32,),
                           max_batch=1))
    ref = pred.generate([prompt], max_new_tokens=5)[0]
    h = eng.submit(prompt, RequestParams(max_new_tokens=5))
    np.testing.assert_array_equal(h.result(timeout=60), ref)


def test_engine_spec_eos_trims_within_window(tiny_gpt):
    """An eos landing mid-acceptance finishes the row there: emitted
    tokens stop at the eos, the result is eos-trimmed, matching the
    sequential reference exactly."""
    prompt = np.arange(1, 7, dtype=np.int32)
    pred = create_predictor(
        Config()
        .from_layer(tiny_gpt,
                    [paddle.to_tensor(np.zeros((2, 12), np.int32))])
        .enable_generation(max_new_tokens=8, prefill_buckets=(16,),
                           max_batch=1))
    ref = pred.generate([prompt])[0]
    eos = int(ref[3])
    eng = ServingEngine(_spec_config(tiny_gpt, max_new=8, buckets=(16,),
                                     max_batch=1, eos=eos),
                        poll_every=1)
    h = eng.submit(prompt)
    out = h.result(timeout=60)
    first = int(np.nonzero(ref == eos)[0][0])
    np.testing.assert_array_equal(out, ref[:first])
    assert h.n_emitted == first + 1


def test_engine_speculative_audit_gate(tiny_gpt):
    """Tier-1 gate: the speculative slot-decode program (fused ngram
    draft + verify) and the spec admit program audit at zero ERRORs
    with full donation coverage — cache, token buffers, counters all
    in place across polls."""
    eng = ServingEngine(_spec_config(tiny_gpt), warmup=False)
    reports = eng.audit()
    assert set(reports) == {("prefill", 16), ("prefill", 32), "decode",
                            "admit", "free", "poll_view"}
    for rep in reports.values():
        rep.raise_on_error()
    assert not reports["decode"].by_check("host_sync")
    assert reports["decode"].donation_coverage == 1.0
    assert reports["admit"].donation_coverage == 1.0


def test_engine_spec_cache_overhang_validation(tiny_gpt):
    # exact bound passes, one below names the speculative overhang
    ServingEngine(_spec_config(tiny_gpt, max_new=8, buckets=(16,),
                               max_batch=1), warmup=False,
                  cache_max_len=16 + 8 + 4)
    with pytest.raises(ValueError, match="overhang"):
        ServingEngine(_spec_config(tiny_gpt, max_new=8, buckets=(16,),
                                   max_batch=1), warmup=False,
                      cache_max_len=16 + 8 + 3)


# ----------------------------------------------------------------- chaos


@pytest.mark.chaos
def test_sigterm_mid_speculative_serve_drains(tiny_gpt):
    """SIGTERM mid-speculative-serve (the chaos satellite): every
    handle reaches a terminal status, queued requests reject cleanly,
    and cancelled in-flight requests keep ONLY accepted tokens — their
    partial output is a bitwise prefix of the sequential reference,
    never unverified draft garbage."""
    import signal
    from paddle_tpu.distributed.resilience import GracefulShutdown
    from paddle_tpu.utils.fault_injection import KillAfter

    eng = ServingEngine(_spec_config(tiny_gpt, max_new=8,
                                     buckets=(16,), max_batch=2),
                        poll_every=2, drain_timeout_s=0.0)
    pred = create_predictor(
        Config()
        .from_layer(tiny_gpt,
                    [paddle.to_tensor(np.zeros((2, 12), np.int32))])
        .enable_generation(max_new_tokens=8, prefill_buckets=(16,),
                           max_batch=1))
    rng = np.random.RandomState(1)
    traffic = [rng.randint(0, 512, 4 + i).astype(np.int32)
               for i in range(5)]
    killer = KillAfter(3, signal.SIGTERM)
    with GracefulShutdown(exit_on_save=False) as gs:
        handles = eng.serve_forever(
            iter(traffic), on_step=lambda e: killer.step())
        assert gs.preempted
    assert killer.fired
    assert len(handles) == 5
    assert all(h.done() for h in handles), "a request hung"
    assert all(h.status.terminal for h in handles)
    rejected = [h for h in handles if h.status is RequestStatus.REJECTED]
    assert all(h.detail == "shutdown" for h in rejected)
    # zero-length drain window: in-flight rows were evicted mid-decode
    # with partial tokens — accepted-only, a prefix of the reference
    partial = [h for h in handles
               if h.status is RequestStatus.CANCELLED
               and h.tokens is not None]
    for h in partial:
        assert 0 < h.tokens.size < 8
        ref = pred.generate([h.prompt])[0]
        np.testing.assert_array_equal(h.tokens, ref[:h.tokens.size])
    # at least one request actually exercised the partial-trim path
    assert partial or any(h.status is RequestStatus.COMPLETED
                          for h in handles)
