"""LFM2-MoE at tiny widths on the CPU: the model assembled from
``models/decoder.py``'s pieces (a mixer and an MLP chosen per layer), the
gated short convolution, the sigmoid router with a selection bias, the
cache that holds convolution state beside KV pages, and the model served
by ``ServingEngine`` in ``Decode`` mode, each against the plain
reference the benchmark uses (``benchmarks/reference/lfm2.py``, loaded by
path: there is one reference).

``layer_types`` conv, conv, attn, conv, conv, attn with 2 dense layers:
both mixers occur under both MLP kinds.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flight_recorder, monitor
from paddle_tpu.distributed.parallel.moe import DroplessMoE, dropless_moe
from paddle_tpu.generation.hybrid_cache import HybridCache, window_state
from paddle_tpu.generation.kv_cache import KVCache
from paddle_tpu.generation.paged_cache import PagedKVCache
from paddle_tpu.models.decoder import RotaryGQAttention, gated_short_conv

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference/lfm2.py", "reference_lfm2_t1")
fam = _load("families/lfm2.py", "family_lfm2_t1")

V = 96


def tiny_cfg(precision="float32", generation=None, **serving):
    """hidden 64, 4 heads over 2 kv heads of 16, 8 experts top-2.
    ``init_std`` 0.12: at width 64 the published 0.02 leaves a position
    little but its token's embedding."""
    return dict(
        vocab_size=V, hidden_size=64, num_hidden_layers=6,
        layer_types=["conv", "conv", "full_attention", "conv", "conv",
                     "full_attention"],
        num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
        conv_bias=False, intermediate_size=96, num_dense_layers=2,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=True, routed_scaling_factor=1.0,
        use_expert_bias=True, norm_eps=1e-5, rope_theta=1e6,
        max_position_embeddings=256, dtype="float32", init_std=0.12,
        serve=dict(
            precision=precision, do_sample=False,
            generation=dict(dict(max_new_tokens=16,
                                 prefill_buckets=[16, 32], max_batch=4),
                            **(generation or {})),
            serving=dict(dict(paged=True, kv_page_size=8, kv_pages=40,
                              cache_max_len=64, max_queue=64), **serving)))


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return ref.make_params(tiny_cfg(), ref.seed_key(3), jnp.float32)


def build(cfg, params):
    """(model, engine factory) with the seeded weights."""
    model, make = fam.build_engine(cfg)
    fam.set_weights(model, fam.program_layout(params, cfg))
    return model, make


#: prompts whose lengths are no bucket's, more requests than lanes: the
#: lanes are of different ages at every step, and slots freed by the
#: short ones are taken again (by the shorter prompts at the end)
JOBS = [(5, 9), (13, 16), (21, 7), (9, 12), (3, 5), (17, 16), (4, 11)]


def serve(engine, jobs=JOBS, seed=1):
    rng = np.random.default_rng(seed)
    reqs = []
    for n, budget in jobs:
        prompt = rng.integers(0, V, n).astype(np.int32)
        reqs.append((prompt, fam.submit(engine, prompt, budget)))
    while engine.busy:
        engine.step()
    return reqs


def served_gaps(reqs, params, cfg):
    """Per served token, how far the reference's logit of it lies below
    the reference's best at that position of the FULL forward pass of
    prompt + served tokens (0: the served token is the reference's)."""
    gaps = []
    for prompt, req in reqs:
        toks = np.asarray(req.tokens)
        seq = np.concatenate([prompt, toks]).astype(np.int32)
        at = np.arange(prompt.size - 1, seq.size - 1)
        lg = np.array(ref.logits_at(params, jnp.asarray(seq),
                                    jnp.asarray(at), cfg))
        gaps.append(lg.max(-1) - lg[np.arange(at.size), toks])
    return np.concatenate(gaps)


# ------------------------------------------------------------ the model

def test_full_forward_against_the_reference(params):
    """(a) every position's logits; float32 on both sides, so what is
    left is the order of the sums: 2e-5 on logits of size 3."""
    cfg = tiny_cfg()
    model = fam._model(cfg)
    model.eval()
    fam.set_weights(model, fam.program_layout(params, cfg))
    ids = np.random.default_rng(0).integers(0, V, 24).astype(np.int32)
    got = np.array(model(paddle.to_tensor(ids[None]))._data)[0]
    want = np.array(ref.logits_at(params, jnp.asarray(ids),
                                  jnp.arange(24), cfg))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5)
    # causal in every mixer: a later token moves nothing before it, and
    # the convolution reaches exactly L - 1 = 2 positions back in a layer
    other = ids.copy()
    other[10] = (other[10] + 1) % V
    moved = np.array(ref.logits_at(params, jnp.asarray(other),
                                   jnp.arange(24), cfg))
    np.testing.assert_array_equal(moved[:10], want[:10])
    assert np.abs(moved[10:] - want[10:]).max() > 1e-3


def test_prefill_then_decode_logits_through_the_cache(params):
    """The cache protocol without the engine: a batch of two prompts
    prefilled at a padded window, then decoded a token at a time, gives
    the reference's full-forward logits at every position."""
    cfg = tiny_cfg()
    model = fam._model(cfg)
    model.eval()
    fam.set_weights(model, fam.program_layout(params, cfg))
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, V, 20).astype(np.int32) for _ in range(2)]
    plen = np.array([7, 12], np.int32)
    ids = np.zeros((2, 16), np.int32)
    for r, (s, p) in enumerate(zip(seqs, plen)):
        ids[r, :p] = s[:p]
        ids[r, p:] = 77            # padding that must reach nothing
    logits, cache = model(paddle.to_tensor(ids), use_cache=True,
                          prompt_len=paddle.to_tensor(plen),
                          cache_max_len=32)
    assert isinstance(cache, HybridCache)
    assert cache.kv.k.shape[0] == 2 and [s.shape for s in cache.state] == [(4, 2, 2, 64)]
    want = [np.array(ref.logits_at(params, jnp.asarray(s), jnp.arange(20),
                                   cfg)) for s in seqs]
    got = np.array(logits._data)
    for r in range(2):
        np.testing.assert_allclose(got[r, 0], want[r][plen[r] - 1],
                                   atol=2e-5)
    for step in range(6):
        tok = np.array([[s[p + step]] for s, p in zip(seqs, plen)],
                       np.int32)
        logits, cache = model(paddle.to_tensor(tok), cache=cache)
        got = np.array(logits._data)
        for r in range(2):
            np.testing.assert_allclose(got[r, 0],
                                       want[r][plen[r] + step], atol=2e-5)


# --------------------------------------------------- through the engine

@pytest.fixture(scope="module")
def served(params):
    """(cfg, engine, reqs): one warm tiny paged engine that has served
    JOBS, for the module."""
    cfg = tiny_cfg()
    with jax.default_matmul_precision("highest"):
        _, make = build(cfg, params)
        engine = make()
        monitor.enable()
        recorder_was = flight_recorder.is_enabled()
        since = flight_recorder.now_ns()      # this fixture's spans alone
        flight_recorder.enable()
        try:
            before = {k: fam.counter(k)
                      for k in ("moe.rows", "moe.expert_rows_max")}
            reqs = serve(engine)
            moe = {k: fam.counter(k) - v for k, v in before.items()}
            spans = flight_recorder.spans_between(since, 2 ** 62)
        finally:
            flight_recorder.configure(on=recorder_was)
            monitor.disable()
    yield cfg, engine, reqs, moe, spans
    engine.shutdown()


def test_engine_serves_the_references_tokens(served, params):
    """(b) prefill then decode through ``ServingEngine`` (paged,
    bucketed, 4 lanes of different ages, prompt lengths that are no
    bucket's, slots freed and taken again by shorter prompts) against
    the reference's FULL forward pass of prompt + served tokens, at
    every served position. Tolerance 1e-4 on the served token's logit
    gap: both sides are float32, so a served token can differ from the
    reference's argmax only where the two best logits lie within the
    order of the sums (1e-6 of logits of size 3)."""
    cfg, engine, reqs, _, _ = served
    for (n, budget), (_, req) in zip(JOBS, reqs):
        assert np.asarray(req.tokens).size == budget
    gaps = served_gaps(reqs, params, cfg)
    assert gaps.size == sum(b for _, b in JOBS)
    assert gaps.max() <= 1e-4
    assert engine.stats["slots_reused"] >= len(JOBS) - 4
    engine._alloc.assert_conserved()


def test_bfloat16_in_place_of_float32_fails_that_tolerance(params):
    """The tolerance of (b) is tight enough: the same engine computing in
    bfloat16 serves tokens the float32 reference ranks visibly below its
    best."""
    cfg = tiny_cfg(precision="bfloat16")
    _, make = build(cfg, params)
    engine = make()
    try:
        gaps = served_gaps(serve(engine), params, cfg)
    finally:
        engine.shutdown()
    assert gaps.max() > 1e-3


@pytest.mark.parametrize("serving", [
    dict(paged=False), dict(kv_cache_dtype="int8"),
    dict(paged=False, kv_cache_dtype="int8")],
    ids=["dense", "paged-int8", "dense-int8"])
def test_other_cache_kinds_carry_the_state(params, serving):
    """The state rides beside whatever KV cache the engine was asked
    for. A wide cache serves the reference's tokens; an int8 one rounds
    K and V to 8 bits, so its tokens are held to what that costs (logits
    of size 3: a tenth)."""
    cfg = tiny_cfg(**serving)
    _, make = build(cfg, params)
    engine = make()
    try:
        assert isinstance(engine._cache, HybridCache)
        gaps = served_gaps(serve(engine), params, cfg)
    finally:
        engine.shutdown()
    assert gaps.max() <= (0.1 if "kv_cache_dtype" in serving else 1e-4)


def test_decode_mode_books_the_routing_counters(served):
    """(g) ``moe.rows`` / ``moe.expert_rows_max`` drained at the poll in
    ``Decode`` mode: every decode step routes all 4 lanes to 2 experts in
    each of the 4 expert layers."""
    _, engine, _, moe, spans = served
    per_step = 4 * 2 * 4
    assert moe["moe.rows"] == per_step * engine.stats["decode_steps"]
    assert moe["moe.rows"] / 8 <= moe["moe.expert_rows_max"] \
        <= moe["moe.rows"]
    polls = [s for s in spans if s.name == "serve.poll"]
    assert sum(s.fields.get("moe_rows", 0) for s in polls) \
        == moe["moe.rows"]


def test_spans_carry_the_state_bytes(served):
    """``setup.cache_alloc`` tells KV bytes from state bytes; every
    ``serve.admit`` says what state it installed (4 conv layers x 2
    columns x 64 x 4 B)."""
    _, engine, _, _, spans = served
    row = 4 * 2 * 64 * 4
    assert engine._state_row_bytes == row
    admits = [s for s in spans if s.name == "serve.admit"]
    assert len(admits) == len(JOBS)
    assert all(s.fields["state_bytes"] == row for s in admits)


def test_cache_alloc_span_splits_kv_and_state(params):
    cfg = tiny_cfg()
    _, make = build(cfg, params)
    recorder_was = flight_recorder.is_enabled()
    since = flight_recorder.now_ns()      # this fixture's spans alone
    flight_recorder.enable()
    try:
        engine = make()
        spans = flight_recorder.spans_between(since, 2 ** 62)
    finally:
        flight_recorder.configure(on=recorder_was)
    engine.shutdown()
    sp = [s for s in spans if s.name == "setup.cache_alloc"][-1].fields
    assert sp["state_bytes"] == 4 * 4 * 2 * 64 * 4
    # 2 attention layers x (K, V) x 40 pages x 2 heads x 8 x 16 floats,
    # the page table and kv_len
    assert sp["kv_bytes"] == 2 * 2 * 40 * 2 * 8 * 16 * 4 + 4 * 8 * 4 + 4 * 4
    assert sp["bytes"] > sp["kv_bytes"] + sp["state_bytes"]


@pytest.mark.parametrize("options, reason", [
    (dict(generation=dict(speculative="ngram")),
     "speculative decoding rolls a lane's cache back"),
    (dict(prefill_chunk_tokens=16),
     "chunked prefill hands a side cache from chunk to chunk")],
    ids=["speculative", "chunked-prefill"])
def test_engine_refuses_what_cannot_carry_state(params, options, reason):
    """(f) each refusal, by its message, at the constructor."""
    cfg = tiny_cfg()
    gen = options.pop("generation", {})
    model = fam._model(cfg)
    model.eval()
    from paddle_tpu.inference import Config
    from paddle_tpu.serving import ServingEngine
    s = cfg["serve"]
    conf = (Config().from_layer(
        model, [paddle.to_tensor(np.zeros((1, 16), np.int32))])
        .enable_tpu("float32")
        .enable_generation(max_new_tokens=16, prefill_buckets=(16, 32),
                           max_batch=4, do_sample=False, **gen)
        .enable_serving(**dict(s["serving"], **options)))
    with pytest.raises(ValueError, match=reason) as e:
        ServingEngine(conf)
    assert "per-lane state of a fixed width" in str(e.value)


# ----------------------------------------------------------- the router

def _router_case(seed=0, t=64, h=32, e=8, f=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (t, h), jnp.float32)
    wr = jax.random.normal(ks[1], (h, e), jnp.float32) * 0.2
    wgu = jax.random.normal(ks[2], (e, h, 2 * f), jnp.float32) * 0.2
    wd = jax.random.normal(ks[3], (e, f, h), jnp.float32) * 0.2
    bias = jax.random.normal(ks[4], (e,), jnp.float32) * 0.1
    return x, wr, wgu, wd, bias


def _plain_sigmoid_moe(x, wr, wgu, wd, k, select, weigh):
    """Dense arithmetic: choose by ``s + select``, weigh by ``s + weigh``."""
    f = wd.shape[1]
    s = jax.nn.sigmoid(x @ wr)
    _, idx = jax.lax.top_k(s + select, k)
    top = jnp.take_along_axis(s + weigh, idx, axis=-1)
    top = top / (top.sum(-1, keepdims=True) + 1e-6)
    gu = jnp.einsum("th,ehf->tef", x, wgu)
    ys = jnp.einsum("tef,efh->teh", jax.nn.silu(gu[..., :f]) * gu[..., f:],
                    wd)
    return jnp.einsum("tkh,tk->th",
                      jnp.take_along_axis(ys, idx[:, :, None], axis=1), top)


def test_sigmoid_router_selects_by_the_biased_score_only():
    """(c) selection by ``s + b`` with weights from ``s``: equal to the
    plain arithmetic, different from "bias nowhere" and from "bias in the
    weights as well" on a seeded case."""
    x, wr, wgu, wd, bias = _router_case()
    got, rows = dropless_moe(x, wr, wgu, wd, 2, True, "sigmoid", bias)
    assert int(rows.sum()) == 64 * 2
    zero = jnp.zeros_like(bias)
    want = _plain_sigmoid_moe(x, wr, wgu, wd, 2, bias, zero)
    np.testing.assert_allclose(np.array(got), np.array(want), atol=1e-5)
    nowhere = _plain_sigmoid_moe(x, wr, wgu, wd, 2, zero, zero)
    both = _plain_sigmoid_moe(x, wr, wgu, wd, 2, bias, bias)
    assert np.abs(np.array(got - nowhere)).max() > 1e-2
    assert np.abs(np.array(got - both)).max() > 1e-2
    # without a bias the layer is the "bias nowhere" arithmetic
    plain, _ = dropless_moe(x, wr, wgu, wd, 2, True, "sigmoid")
    np.testing.assert_allclose(np.array(plain), np.array(nowhere),
                               atol=1e-5)
    # the scaling factor scales, the 1e-6 is in the denominator
    twice, _ = dropless_moe(x, wr, wgu, wd, 2, True, "sigmoid", bias,
                            scaling=2.0)
    np.testing.assert_allclose(np.array(twice), 2 * np.array(got),
                               atol=1e-5)


def test_reference_bias_changes_the_chosen_set_for_a_visible_share(params):
    """The reference draws the per-expert bias from the seed at a scale
    that matters: it changes which experts are chosen for a visible
    share of tokens (a zero bias would test nothing)."""
    cfg = tiny_cfg()
    lp = params["layers"][2]
    y = jax.random.normal(jax.random.PRNGKey(1), (512, 64), jnp.float32)
    _, with_bias = ref.route(y, lp["wr"], lp["bias"], cfg)
    _, without = ref.route(y, lp["wr"], jnp.zeros_like(lp["bias"]), cfg)
    changed = np.mean(np.sort(np.array(with_bias), -1)
                      != np.sort(np.array(without), -1))
    assert 0.05 < changed < 0.9


def _softmax_moe_as_it_was(x, router_w, w_gate_up, w_down, top_k,
                           norm_topk_prob=True):
    """``dropless_moe`` as the parent commit had it, word for word."""
    t, h = x.shape
    e, f = w_down.shape[0], w_down.shape[1]
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    rows = jnp.zeros((e,), jnp.int32).at[flat].add(1)
    xs = x[order // top_k]
    gu = jax.lax.ragged_dot(xs, w_gate_up, rows,
                            preferred_element_type=jnp.float32)
    z = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(x.dtype)
    ys = jax.lax.ragged_dot(z, w_down, rows,
                            preferred_element_type=jnp.float32)
    ys = ys[jnp.argsort(order)].reshape(t, top_k, h)
    return jnp.einsum("tkh,tk->th", ys, top).astype(x.dtype), rows


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_softmax_router_is_bit_for_bit_what_it_was(norm, dtype):
    x, wr, wgu, wd, _ = _router_case(seed=2)
    x, wgu, wd = x.astype(dtype), wgu.astype(dtype), wd.astype(dtype)
    got, rows = jax.jit(lambda *a: dropless_moe(*a, 2, norm))(x, wr, wgu, wd)
    want, rows_w = jax.jit(
        lambda *a: _softmax_moe_as_it_was(*a, 2, norm))(x, wr, wgu, wd)
    np.testing.assert_array_equal(np.array(got, np.float32),
                                  np.array(want, np.float32))
    np.testing.assert_array_equal(np.array(rows), np.array(rows_w))


def test_router_kinds_are_checked():
    with pytest.raises(ValueError, match="unknown router kind"):
        DroplessMoE(8, 4, 4, 2, router="tanh")
    with pytest.raises(ValueError, match="selection bias belongs"):
        DroplessMoE(8, 4, 4, 2, select_bias=True)
    layer = DroplessMoE(8, 4, 4, 2, router="sigmoid", select_bias=True)
    assert tuple(layer.select_bias.shape) == (4,)


# ------------------------------------------------------- the convolution

def _conv_case(b=2, s=9, h=8, taps=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (b, s, 3 * h), jnp.float32),
            jax.random.normal(ks[1], (h, taps), jnp.float32))


def test_short_conv_window_by_window_is_the_whole_sequence():
    """Any split of a sequence into windows, the state handed from one
    to the next, gives the whole sequence's output."""
    bcx, w = _conv_case()
    whole, last = gated_short_conv(bcx, w)
    state, outs = None, []
    for lo, hi in ((0, 4), (4, 5), (5, 9)):
        y, state = gated_short_conv(bcx[:, lo:hi], w, state)
        outs.append(y)
    np.testing.assert_allclose(np.array(jnp.concatenate(outs, 1)),
                               np.array(whole), atol=1e-6)
    np.testing.assert_array_equal(np.array(state), np.array(last))
    h = w.shape[0]
    z = bcx[..., :h] * bcx[..., 2 * h:]
    np.testing.assert_array_equal(np.array(last), np.array(z[:, -2:]))


@pytest.mark.parametrize("valid", [[9, 9], [4, 7], [1, 0], [2, 9]])
def test_state_is_taken_at_the_valid_length_under_padding(valid):
    """(d) a prefill runs at a padded bucket: the state it hands on is
    the one after ``valid`` positions, reaching back into the prior state
    (zeros at a prefill) where fewer than ``L - 1`` are real."""
    bcx, w = _conv_case()
    h = w.shape[0]
    z = np.array(bcx[..., :h] * bcx[..., 2 * h:])
    _, state = gated_short_conv(bcx, w, None, jnp.asarray(valid, jnp.int32))
    padded = np.concatenate([np.zeros((2, 2, h), np.float32), z], axis=1)
    for r, n in enumerate(valid):
        np.testing.assert_array_equal(np.array(state[r]),
                                      padded[r, n:n + 2])
    np.testing.assert_array_equal(
        np.array(window_state(jnp.zeros((2, 2, h)), jnp.asarray(z),
                              jnp.asarray(valid))), np.array(state))


# ------------------------------------------------------------ the cache

def _dense_row(kv_len, layers=2, heads=2, d=4, max_len=16, seed=0):
    """A batch-1 prefill cache: KV rows beside a state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (layers, 1, max_len, heads, d)
    kv = KVCache(jax.random.normal(ks[0], shape), jax.random.normal(
        ks[1], shape), jnp.asarray([kv_len], jnp.int32))
    return HybridCache(kv, (jax.random.normal(ks[2], (3, 1, 2, 8)),))


def _pool(batch=3):
    kv = PagedKVCache.create(2, batch, 9, 4, 4, 2, 4)
    return HybridCache.create(kv, 3, (((2, 8), None),), jnp.float32)


def test_hybrid_cache_is_a_pytree_that_delegates_the_kv_protocol():
    cache = _pool()
    leaves, tree = jax.tree_util.tree_flatten(cache)
    assert len(leaves) == 5             # k, v, table, kv_len, state
    again = jax.tree_util.tree_unflatten(tree, leaves)
    assert isinstance(again, HybridCache) and isinstance(again.kv,
                                                         PagedKVCache)
    assert cache.page_table.shape == (3, 4) and cache.max_len == 16
    assert getattr(cache, "k_scale", None) is None
    assert cache.state_bytes == 3 * 3 * 2 * 8 * 4
    dense = _dense_row(5)
    assert getattr(dense, "page_table", None) is None
    # the paged form keeps the state a row a lane
    avals = jax.eval_shape(lambda: HybridCache.create(
        KVCache.create(2, 3, 16, 2, 4), 3, (((2, 8), None),),
        jnp.float32))
    paged = avals.paged(9, 4, 4)
    assert paged.kv.k.shape == (2, 9, 2, 4, 4)
    assert [s.shape for s in paged.state] == [(3, 3, 2, 8)]


def test_install_row_and_reset_rows_move_state_with_the_kv_row():
    """(d) admission installs the prefill's state over whatever the
    slot's last holder left; a freed row's state goes back to zero;
    other rows keep theirs."""
    cache = _pool()
    left = jnp.full((3, 3, 2, 8), 7.0)
    cache = HybridCache(cache.kv, (left,))
    src = _dense_row(6)
    table = jnp.asarray([3, 5, 0, 0], jnp.int32)
    out = cache.install_row(src, 1, table, 0)
    np.testing.assert_array_equal(np.array(out.state[0][:, 1]),
                                  np.array(src.state[0][:, 0]))
    np.testing.assert_array_equal(np.array(out.state[0][:, 0]), 7.0)
    np.testing.assert_array_equal(np.array(out.state[0][:, 2]), 7.0)
    assert list(np.array(out.kv_len)) == [0, 6, 0]
    assert list(np.array(out.page_table[1])) == [3, 5, 0, 0]
    # K of position 5 sits in page 5, offset 1
    np.testing.assert_array_equal(np.array(out.k[:, 5, :, 1]),
                                  np.array(src.k[:, 0, 5]))
    for rows in (1, jnp.asarray([False, True, False])):
        freed = out.reset_rows(rows)
        assert list(np.array(freed.kv_len)) == [0, 0, 0]
        assert not np.array(freed.page_table[1]).any()
        assert not np.array(freed.state[0][:, 1]).any()
        np.testing.assert_array_equal(np.array(freed.state[0][:, 0]), 7.0)


def test_update_with_kv_len_and_with_state_touch_their_own_half():
    cache = _pool().install_row(_dense_row(6), 0,
                                jnp.asarray([2, 4, 0, 0], jnp.int32), 0)
    k_new = jnp.ones((3, 1, 2, 4))
    out = cache.update(1, k_new, 2 * k_new, cache.kv_len)
    np.testing.assert_array_equal(np.array(out.state[0]),
                                  np.array(cache.state[0]))
    # row 0 writes position 6: page 4, offset 2; idle rows: the null page
    np.testing.assert_array_equal(np.array(out.k[1, 4, :, 2]), 1.0)
    np.testing.assert_array_equal(np.array(out.v[1, 4, :, 2]), 2.0)
    grown = out.with_kv_len(out.kv_len + 1)
    assert list(np.array(grown.kv_len)) == [7, 1, 1]
    np.testing.assert_array_equal(np.array(grown.state[0]),
                                  np.array(cache.state[0]))
    new = jnp.full((3, 2, 8), 5.0)
    stated = grown.with_state(2, (new,))
    np.testing.assert_array_equal(np.array(stated.state[0][2]), 5.0)
    np.testing.assert_array_equal(np.array(stated.state[0][:2]),
                                  np.array(cache.state[0][:2]))
    np.testing.assert_array_equal(np.array(stated.k), np.array(grown.k))
    assert list(np.array(stated.positions(2)[0])) == [7, 8]


# -------------------------------------------------------- the attention

@pytest.mark.parametrize("heads, kv_heads", [(4, 2), (8, 2), (4, 4)])
def test_causal_prefill_with_grouped_kv_heads(heads, kv_heads):
    """(e) ``RotaryGQAttention`` prefilling a cache under the plain
    causal mask against plain attention with K and V repeated."""
    d, hidden, s = 8, 32, 12
    paddle.seed(4)
    attn = RotaryGQAttention(hidden, heads, kv_heads, d, 1e4, 1e-5,
                             std=0.3, out_std=0.3)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, s, hidden))
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    cache = KVCache.create(1, 2, 16, kv_heads, d)
    out, cache = attn(paddle.to_tensor(x), paddle.to_tensor(pos),
                      cache=cache, layer_idx=0, decode=False)
    q = attn.q_proj(paddle.to_tensor(x)).reshape([2, s, heads, d])
    k = attn.k_proj(paddle.to_tensor(x)).reshape([2, s, kv_heads, d])
    v = attn.v_proj(paddle.to_tensor(x)).reshape([2, s, kv_heads, d])
    q, k = attn._qk(q, k, paddle.to_tensor(pos))
    g = heads // kv_heads
    kr, vr = jnp.repeat(k._data, g, axis=2), jnp.repeat(v._data, g, axis=2)
    att = jnp.einsum("bqnd,bknd->bnqk", q._data, kr) / np.sqrt(d)
    att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -jnp.inf)
    o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(att, -1), vr)
    want = attn.o_proj(paddle.to_tensor(o.reshape(2, s, -1)))
    np.testing.assert_allclose(np.array(out._data), np.array(want._data),
                               atol=1e-5)
    # the cache holds the kv heads only, rotated
    np.testing.assert_allclose(np.array(cache.k[0, :, :s]),
                               np.array(k._data), atol=1e-6)
