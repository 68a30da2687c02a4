"""Nemotron-H at tiny widths on the CPU: the model stated as a pattern of
single-mixer blocks (Mamba-2, attention without positions, an expert
layer that holds a share of its experts beside a shared one), the cache
that holds a tuple of states beside KV pages, the one-step state-update
kernel, and the model served by ``ServingEngine`` in ``Decode`` mode,
each against the plain reference the benchmark uses
(``benchmarks/reference/nemotron_h.py``, loaded by path: there is one
reference).

``hybrid_override_pattern`` ``MEM*EME``: three Mamba-2 blocks, one
attention block, three expert blocks holding 4 of the 8 experts their
router ranks.
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
from paddle_tpu.generation.hybrid_cache import HybridCache
from paddle_tpu.generation.kv_cache import KVCache
from paddle_tpu.generation.paged_cache import PagedKVCache
from paddle_tpu.kernels import ssm_update as ssm_kernel
from paddle_tpu.models.decoder import DecoderBlock, Relu2MLP, ssm_scan

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference/nemotron_h.py", "reference_nemotron_h_t1")
fam = _load("families/nemotron_h.py", "family_nemotron_h_t1")

V = 96


def tiny_cfg(precision="float32", generation=None, **serving):
    """hidden 64; 8 Mamba heads of 8 in 2 groups, state 128, chunks of
    8; 4 query heads over 2 kv heads of 16; 4 held of 8 experts of 24,
    top-3, a shared expert of 48. ``init_std`` 0.12: at width 64 the
    published 0.02 leaves a position little but its token's embedding."""
    return dict(
        vocab_size=V, hidden_size=64, num_hidden_layers=7,
        hybrid_override_pattern="MEM*EME", mamba_num_heads=8,
        mamba_head_dim=8, n_groups=2, ssm_state_size=128, conv_kernel=4,
        chunk_size=8, use_conv_bias=True, time_step_min=0.001,
        time_step_max=0.1, time_step_floor=1e-4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=48, n_shared_experts=1,
        n_routed_experts=4, router_experts=8, first_expert=0,
        num_experts_per_tok=3, n_group=1, topk_group=1,
        norm_topk_prob=True, routed_scaling_factor=2.5,
        layer_norm_epsilon=1e-5, max_position_embeddings=256,
        dtype="float32", init_std=0.12,
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


@pytest.fixture(scope="module")
def model(params):
    """The float32 model with the seeded weights, for the module."""
    cfg = tiny_cfg()
    net = fam._model(cfg)
    net.eval()
    fam.set_weights(net, fam.program_layout(params, cfg))
    return net


def build(cfg, params):
    """(model, engine factory) with the seeded weights."""
    model, make = fam.build_engine(cfg)
    fam.set_weights(model, fam.program_layout(params, cfg))
    return model, make


#: prompts whose lengths are no bucket's, more requests than lanes: the
#: lanes are of different ages at every step, and slots freed by the
#: short ones are taken again
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


T_REF = 40     # every sequence here is shorter: one compile of the reference


@pytest.fixture(scope="module")
def ref_logits(params):
    """The reference's logits at every position of a sequence, padded on
    the right to ``T_REF`` (causal: the padding reaches nothing before
    it), compiled once for the module."""
    cfg = tiny_cfg()
    run = jax.jit(lambda ids: ref.logits_at(params, ids, jnp.arange(T_REF),
                                            cfg))

    def logits(seq):
        ids = np.zeros((T_REF,), np.int32)
        ids[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            return np.array(run(jnp.asarray(ids)))[:len(seq)]
    return logits


def served_gaps(reqs, ref_logits):
    """Per served token, how far the reference's logit of it lies below
    the reference's best at that position of the FULL forward pass of
    prompt + served tokens (0: the served token is the reference's)."""
    gaps = []
    for prompt, req in reqs:
        toks = np.asarray(req.tokens)
        seq = np.concatenate([prompt, toks]).astype(np.int32)
        at = np.arange(prompt.size - 1, seq.size - 1)
        lg = ref_logits(seq)[at]
        gaps.append(lg.max(-1) - lg[np.arange(at.size), toks])
    return np.concatenate(gaps)


# ------------------------------------------------------------ the model

def test_full_forward_against_the_reference(model, ref_logits):
    """Every position's logits; float32 on both sides, the program by
    the chunked scan (27 positions: three whole chunks of 8 and a part)
    and the reference by the sequential recurrence."""
    ids = np.random.default_rng(0).integers(0, V, 27).astype(np.int32)
    got = np.array(model(paddle.to_tensor(ids[None]))._data)[0]
    want = ref_logits(ids)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=5e-5)
    # causal in every mixer: a later token moves nothing before it
    other = ids.copy()
    other[10] = (other[10] + 1) % V
    moved = ref_logits(other)
    np.testing.assert_array_equal(moved[:10], want[:10])
    assert np.abs(moved[10:] - want[10:]).max() > 1e-3


def test_prefill_then_decode_logits_through_the_cache(model, ref_logits):
    """The cache protocol without the engine: a batch of two prompts
    prefilled at a padded window (both states taken at ``prompt_len``),
    then decoded a token at a time, gives the reference's full-forward
    logits at every position."""
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, V, 24).astype(np.int32) for _ in range(2)]
    plen = np.array([7, 12], np.int32)
    ids = np.zeros((2, 16), np.int32)
    for r, (s, p) in enumerate(zip(seqs, plen)):
        ids[r, :p] = s[:p]
        ids[r, p:] = 77            # padding that must reach nothing
    logits, cache = model(paddle.to_tensor(ids), use_cache=True,
                          prompt_len=paddle.to_tensor(plen),
                          cache_max_len=32)
    assert isinstance(cache, HybridCache)
    # one KV layer (the attention block); two states a Mamba block
    assert cache.kv.k.shape[0] == 1
    assert [(s.shape, s.dtype) for s in cache.state] == [
        ((3, 2, 3, 64 + 2 * 2 * 128), jnp.float32),
        ((3, 2, 8, 8, 128), jnp.float32)]
    want = [ref_logits(s) for s in seqs]
    got = np.array(logits._data)
    for r in range(2):
        np.testing.assert_allclose(got[r, 0], want[r][plen[r] - 1],
                                   atol=5e-5)
    for step in range(6):
        tok = np.array([[s[p + step]] for s, p in zip(seqs, plen)],
                       np.int32)
        logits, cache = model(paddle.to_tensor(tok), cache=cache)
        got = np.array(logits._data)
        for r in range(2):
            np.testing.assert_allclose(got[r, 0],
                                       want[r][plen[r] + step], atol=5e-5)


def test_state_dtypes_follow_the_model_and_the_activations(model):
    """Served in bfloat16 the convolution's window is bfloat16 (the
    activations' type) and the SSM state stays float32."""
    specs = model.model._state_specs
    assert [dt for _, dt in specs] == [None, jnp.float32]
    cache = HybridCache.create(KVCache.create(1, 2, 16, 2, 16), 3, specs,
                               jnp.bfloat16)
    assert [s.dtype for s in cache.state] == [jnp.bfloat16, jnp.float32]
    assert [s.shape for s in cache.state] == [(3, 2, 3, 576),
                                              (3, 2, 8, 8, 128)]


# --------------------------------------------------- through the engine

@pytest.fixture(scope="module")
def served(params):
    """(cfg, engine, reqs, counters, spans): one warm tiny paged engine
    that has served JOBS, for the module."""
    cfg = tiny_cfg()
    names = ("moe.rows", "moe.expert_rows_max", "moe.rows_elsewhere",
             "ssm.kernel_layers", "ssm.fallback_layers")
    with jax.default_matmul_precision("highest"):
        monitor.enable()
        recorder_was = flight_recorder.is_enabled()
        since = flight_recorder.now_ns()      # this fixture's spans alone
        flight_recorder.enable()
        try:
            before = {k: fam.counter(k) for k in names}
            _, make = build(cfg, params)
            engine = make()
            reqs = serve(engine)
            seen = {k: fam.counter(k) - v for k, v in before.items()}
            spans = flight_recorder.spans_between(since, 2 ** 62)
        finally:
            flight_recorder.configure(on=recorder_was)
            monitor.disable()
    yield cfg, engine, reqs, seen, spans
    engine.shutdown()


def test_engine_serves_the_references_tokens(served, ref_logits):
    """Prefill then decode through ``ServingEngine`` (paged, bucketed, 4
    lanes of different ages, prompt lengths that are no bucket's, slots
    freed and taken again: a reused slot starts from its own prefill's
    states) against the reference's FULL forward pass of prompt + served
    tokens, at every served position."""
    cfg, engine, reqs, _, _ = served
    for (n, budget), (_, req) in zip(JOBS, reqs):
        assert np.asarray(req.tokens).size == budget
    gaps = served_gaps(reqs, ref_logits)
    assert gaps.size == sum(b for _, b in JOBS)
    assert gaps.max() <= 1e-4
    assert engine.stats["slots_reused"] >= len(JOBS) - 4
    engine._alloc.assert_conserved()


def test_bfloat16_in_place_of_float32_fails_that_tolerance(params,
                                                           ref_logits):
    cfg = tiny_cfg(precision="bfloat16")
    _, make = build(cfg, params)
    engine = make()
    try:
        gaps = served_gaps(serve(engine, JOBS[:4]), ref_logits)
    finally:
        engine.shutdown()
    assert gaps.max() > 1e-3


def test_counters_split_the_rows_held_from_the_rows_elsewhere(served):
    """``moe.rows`` counts the held experts' rows, ``moe.rows_elsewhere``
    the rest: together every decode step routes all 4 lanes to 3 experts
    in each of the 3 expert blocks. The gauges say which path each traced
    Mamba block's one-step update took (the CPU: XLA's fusion)."""
    _, engine, _, seen, spans = served
    per_step = 4 * 3 * 3
    assert seen["moe.rows"] + seen["moe.rows_elsewhere"] \
        == per_step * engine.stats["decode_steps"]
    assert 0 < seen["moe.rows"] and 0 < seen["moe.rows_elsewhere"]
    assert seen["moe.rows"] / 4 <= seen["moe.expert_rows_max"] \
        <= seen["moe.rows"]
    polls = [s for s in spans if s.name == "serve.poll"]
    assert sum(s.fields.get("moe_rows", 0) for s in polls) \
        == seen["moe.rows"]
    assert seen["ssm.kernel_layers"] == 0
    assert seen["ssm.fallback_layers"] >= 3


def test_spans_carry_the_state_bytes_of_both_states(served):
    """``setup.cache_alloc`` and every ``serve.admit`` sum the state
    tuple: 3 Mamba blocks x (3 x 576 window + 8 x 8 x 128 state) x 4 B a
    lane."""
    _, engine, _, _, spans = served
    row = 3 * (3 * 576 + 8 * 8 * 128) * 4
    assert engine._state_row_bytes == row
    alloc = [s for s in spans if s.name == "setup.cache_alloc"][-1].fields
    assert alloc["state_bytes"] == 4 * row
    assert alloc["bytes"] > alloc["kv_bytes"] + alloc["state_bytes"]
    admits = [s for s in spans if s.name == "serve.admit"]
    assert len(admits) == len(JOBS)
    assert all(s.fields["state_bytes"] == row for s in admits)


@pytest.mark.parametrize("options, reason", [
    (dict(generation=dict(speculative="ngram")),
     "speculative decoding rolls a lane's cache back"),
    (dict(prefill_chunk_tokens=16),
     "chunked prefill hands a side cache from chunk to chunk")],
    ids=["speculative", "chunked-prefill"])
def test_engine_refuses_what_cannot_carry_state(params, options, reason):
    """Each refusal at the constructor, its message naming the states'
    shapes."""
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
    assert "(3, 4, 3, 576) float32, (3, 4, 8, 8, 128) float32" \
        in str(e.value)


# ------------------------------------------------------------ the share

def _layer_case(seed=0, t=40):
    cfg = tiny_cfg()
    lp = ref.layer_params(cfg, 1, jax.random.PRNGKey(seed), jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(seed + 1), (t, 64))
    return cfg, lp, u


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed results of the two halves
    (``held`` (0, 4) and (4, 4)) plus the shared expert counted ONCE
    equal the uncut 8-expert reference layer."""
    cfg, lp, u = _layer_case()
    whole = dict(cfg, n_routed_experts=8, router_experts=8)
    wu = jax.random.normal(jax.random.PRNGKey(7), (8, 64, 24)) * 0.12
    wd = jax.random.normal(jax.random.PRNGKey(8), (8, 24, 64)) * 0.05
    uncut = ref.expert_layer(u, dict(lp, wu=wu, wd=wd), whole, None)
    shared = ref.relu2_mlp(u, lp["ws_up"], lp["ws_down"], None)
    parts, rows = [], []
    for first in (0, 4):
        y, r = dropless_moe(
            u, lp["wr"], wu[first:first + 4], wd[first:first + 4], 3, True,
            "sigmoid", lp["bias"], 2.5, gated=False, held=(first, 4),
            norm_eps=1e-20)
        parts.append(y)
        rows.append(np.array(r))
    np.testing.assert_allclose(np.array(shared + parts[0] + parts[1]),
                               np.array(uncut), atol=2e-5)
    # each half counts its own rows and, last, the rows it sent away
    assert rows[0].shape == (5,) and rows[0].sum() == 40 * 3
    assert rows[0][:4].sum() == rows[1][4] and rows[1][:4].sum() == rows[0][4]
    # and each half is what the reference computes for that share
    for first, part in zip((0, 4), parts):
        half = dict(cfg, first_expert=first)
        want = ref.expert_layer(
            u, dict(lp, wu=wu[first:first + 4], wd=wd[first:first + 4]),
            half, None) - shared
        np.testing.assert_allclose(np.array(part), np.array(want),
                                   atol=2e-5)


def test_reference_bias_changes_the_chosen_set_for_a_visible_share():
    cfg, lp, u = _layer_case(t=400)
    _, with_bias = ref.route(u, lp["wr"], lp["bias"], cfg)
    _, without = ref.route(u, lp["wr"], jnp.zeros_like(lp["bias"]), cfg)
    changed = np.mean(np.any(np.sort(np.array(with_bias), -1)
                             != np.sort(np.array(without), -1), axis=-1))
    assert 0.1 < changed < 1.0


def _plain_moe(x, wr, wup, wd, k, router, gated, bias, scaling, eps, held):
    """A token at a time, an expert at a time, in numpy."""
    x, wr, wup, wd = (np.asarray(a, np.float64) for a in (x, wr, wup, wd))
    logits = x @ wr
    if router == "softmax":
        score = np.exp(logits - logits.max(-1, keepdims=True))
        score = rank = score / score.sum(-1, keepdims=True)
    else:
        score = 1.0 / (1.0 + np.exp(-logits))
        rank = score if bias is None else score + np.asarray(bias)
    first, count = held or (0, wr.shape[1])
    f = wd.shape[1]
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        chosen = np.argsort(-rank[t], kind="stable")[:k]
        w = score[t, chosen]
        w = w / (w.sum() + (eps if router == "sigmoid" else 0.0))
        w = w * (scaling if router == "sigmoid" else 1.0)
        for e, we in zip(chosen, w):
            if not first <= e < first + count:
                continue
            h = x[t] @ wup[e - first]
            if gated:
                z = h[:f] / (1.0 + np.exp(-h[:f])) * h[f:]
            else:
                z = np.maximum(h, 0.0) ** 2
            out[t] += we * (z @ wd[e - first])
    return out


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "held"])
@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_dropless_moe_kinds_against_a_plain_loop(gated, router, held):
    """One body, the kinds as arguments: gated / ungated experts, softmax
    / sigmoid router, all experts / a held share."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    t, h, e, f, k = 48, 32, 8, 16, 3
    here = e if held is None else held[1]
    x = jax.random.normal(ks[0], (t, h))
    wr = jax.random.normal(ks[1], (h, e)) * 0.3
    wup = jax.random.normal(ks[2], (here, h, (2 if gated else 1) * f)) * 0.2
    wd = jax.random.normal(ks[3], (here, f, h)) * 0.2
    bias = jax.random.normal(ks[4], (e,)) * 0.1 if router == "sigmoid" \
        else None
    got, rows = dropless_moe(x, wr, wup, wd, k, True, router, bias, 2.5,
                             gated=gated, held=held, norm_eps=1e-20)
    want = _plain_moe(x, wr, wup, wd, k, router, gated, bias, 2.5, 1e-20,
                      held)
    np.testing.assert_allclose(np.array(got), want, atol=1e-4)
    assert int(np.array(rows).sum()) == t * k
    assert rows.shape == (here + (held is not None),)


def test_dropless_layer_holds_a_share_stored_in_whole_tiles():
    """``DroplessMoE`` told which experts it holds, ungated, its width
    stored padded with zeros: the parameters' shapes, the counts, and
    padding changes nothing."""
    paddle.seed(0)
    layer = DroplessMoE(16, 12, 8, 2, router="sigmoid", select_bias=True,
                        scaling=2.5, gated=False, held=(4, 4),
                        norm_eps=1e-20, pad_to=8)
    assert layer.router.shape == [16, 8] and layer.up.shape == [4, 16, 16]
    assert layer.down.shape == [4, 16, 16]
    assert not np.array(layer.up._data[:, :, 12:]).any()
    assert not np.array(layer.down._data[:, 12:]).any()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 16))
    y = layer(paddle.to_tensor(np.array(x)))
    want, rows = dropless_moe(
        x.reshape(10, 16), layer.router._data, layer.up._data[:, :, :12],
        layer.down._data[:, :12], 2, True, "sigmoid",
        layer.select_bias._data, 2.5, gated=False, held=(4, 4),
        norm_eps=1e-20)
    np.testing.assert_allclose(np.array(y._data).reshape(10, 16),
                               np.array(want), atol=1e-6)
    assert int(np.array(layer.rows._data).sum()) + int(
        layer.rows_elsewhere._data) == 20
    np.testing.assert_array_equal(np.array(layer.rows._data),
                                  np.array(rows[:-1]))
    with pytest.raises(ValueError, match="outside the router's"):
        DroplessMoE(16, 12, 8, 2, held=(6, 4))
    with pytest.raises(ValueError, match="ungated kind alone"):
        DroplessMoE(16, 12, 8, 2, pad_to=8)


def test_a_block_is_a_mixer_an_mlp_or_both():
    with pytest.raises(ValueError, match="a block of nothing"):
        DecoderBlock(16, 1e-5)
    alone = DecoderBlock(16, 1e-5, mlp=Relu2MLP(16, 24))
    assert sorted(n for n, _ in alone.named_parameters()) == [
        "mlp.down_proj.weight", "mlp.up_proj.weight", "norm2.weight"]
    x = paddle.to_tensor(np.ones((1, 3, 16), np.float32))
    assert alone(x, None).shape == [1, 3, 16]


# --------------------------------------------------------- the recurrence

def _scan_case(b=2, s=19, heads=4, p=4, groups=2, n=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, heads)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (heads,)))
    bm = jax.random.normal(ks[3], (b, s, groups, n))
    cm = jax.random.normal(ks[4], (b, s, groups, n))
    s0 = jax.random.normal(ks[5], (b, heads, p, n))
    return x, dt, a, bm, cm, s0


@jax.jit
def _sequential(x, dt, a, bm, cm, s0):
    """The recurrence a position at a time (one compiled scan)."""
    def step(s, xs):
        y, s = ssm_kernel.ssm_update_reference(s, xs[0], xs[1], a, xs[2],
                                               xs[3])
        return s, y
    s, ys = jax.lax.scan(step, s0, tuple(
        jnp.swapaxes(v, 0, 1) for v in (x, dt, bm, cm)))
    return jnp.swapaxes(ys, 0, 1), s


@pytest.mark.parametrize("s, chunk", [(19, 8), (8, 8), (5, 8), (33, 16)])
def test_chunked_scan_is_the_sequential_recurrence(s, chunk):
    """Lengths that are not whole chunks, from a state that is not zero."""
    x, dt, a, bm, cm, s0 = _scan_case(s=s)
    got_y, got_s = jax.jit(ssm_scan, static_argnums=6)(x, dt, a, bm, cm,
                                                       s0, chunk)
    want_y, want_s = _sequential(x, dt, a, bm, cm, s0)
    np.testing.assert_allclose(np.array(got_y), np.array(want_y),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.array(got_s), np.array(want_s),
                               rtol=2e-4, atol=2e-4)


def test_scan_hands_on_the_state_at_the_valid_length():
    """``dt`` = 0 past a row's real positions: the state after the padded
    window is the state at the valid length."""
    x, dt, a, bm, cm, s0 = _scan_case(s=16)
    valid = jnp.asarray([5, 16])
    masked = jnp.where(jnp.arange(16)[None, :, None] < valid[:, None, None],
                       dt, 0.0)
    _, got = jax.jit(ssm_scan, static_argnums=6)(x, masked, a, bm, cm, s0,
                                                 8)
    for r, n in enumerate((5, 16)):
        _, want = _sequential(x[r:r + 1, :n], dt[r:r + 1, :n], a,
                              bm[r:r + 1, :n], cm[r:r + 1, :n],
                              s0[r:r + 1])
        np.testing.assert_allclose(np.array(got[r]), np.array(want[0]),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("live", [
    [1, 1, 1, 1, 1], [0, 1, 0, 1, 0], [0, 0, 1, 1, 0], [0, 0, 0, 0, 0],
    [1, 0, 0, 0, 0]], ids=lambda v: "".join(map(str, v)))
def test_ssm_update_kernel_in_interpret_mode(live):
    """The kernel against the ``jax.numpy`` update: its own layer's live
    lanes updated, every other layer's and every idle lane's state
    handed through untouched (the aliased operand), idle lanes' ``y``
    zero."""
    layers, b, heads, p, n, groups = 3, 5, 8, 8, 128, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    state = jax.random.normal(ks[0], (layers, b, heads, p, n))
    x = jax.random.normal(ks[1], (b, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (b, heads)))
    a = -jnp.exp(jax.random.normal(ks[3], (heads,)))
    bm = jax.random.normal(ks[4], (b, groups, n))
    cm = jax.random.normal(ks[5], (b, groups, n))
    live = jnp.asarray(live, bool)
    assert ssm_kernel.supports(state.shape, groups, state.dtype)
    y, new = ssm_kernel.ssm_update(state, 1, x, dt, a, bm, cm, live)
    want_y, want_s = ssm_kernel.ssm_update_reference(state[1], x, dt, a,
                                                     bm, cm)
    np.testing.assert_allclose(
        np.array(new[1]),
        np.array(jnp.where(live[:, None, None, None], want_s, state[1])),
        rtol=1e-6, atol=1e-6)
    for other in (0, 2):
        np.testing.assert_array_equal(np.array(new[other]),
                                      np.array(state[other]))
    np.testing.assert_allclose(
        np.array(y), np.array(jnp.where(live[:, None, None], want_y, 0.0)),
        rtol=1e-5, atol=1e-5)
    assert not ssm_kernel.supports(state.shape, groups, jnp.bfloat16)
    assert not ssm_kernel.supports((3, 5, 8, 8, 64), groups, jnp.float32)


# ----------------------------------------------------- the state tuple

def _dense_row(kv_len, seed=0):
    """A batch-1 prefill cache: KV rows beside two states."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (2, 1, 16, 2, 4)
    kv = KVCache(jax.random.normal(ks[0], shape), jax.random.normal(
        ks[1], shape), jnp.asarray([kv_len], jnp.int32))
    return HybridCache(kv, (
        jax.random.normal(ks[2], (3, 1, 2, 8)).astype(jnp.bfloat16),
        jax.random.normal(ks[3], (3, 1, 4, 4, 8))))


SPECS = (((2, 8), jnp.bfloat16), ((4, 4, 8), jnp.float32))


def _pool(batch=3):
    kv = PagedKVCache.create(2, batch, 9, 4, 4, 2, 4)
    return HybridCache.create(kv, 3, SPECS, jnp.float32)


def test_state_tuple_is_a_pytree_with_its_own_dtypes():
    cache = _pool()
    leaves, tree = jax.tree_util.tree_flatten(cache)
    assert len(leaves) == 6             # k, v, table, kv_len, two states
    again = jax.tree_util.tree_unflatten(tree, leaves)
    assert isinstance(again, HybridCache) and len(again.state) == 2
    assert [s.dtype for s in cache.state] == [jnp.bfloat16, jnp.float32]
    assert cache.state_bytes == 3 * 3 * (2 * 8 * 2 + 4 * 4 * 8 * 4)
    avals = jax.eval_shape(lambda: HybridCache.create(
        KVCache.create(2, 3, 16, 2, 4), 3, SPECS, jnp.float32))
    paged = avals.paged(9, 4, 4)
    assert paged.kv.k.shape == (2, 9, 2, 4, 4)
    assert [s.shape for s in paged.state] == [(3, 3, 2, 8),
                                              (3, 3, 4, 4, 8)]
    assert "(3, 3, 2, 8) bfloat16, (3, 3, 4, 4, 8) float32" in repr(cache)


def test_install_row_and_reset_rows_go_over_the_tuple():
    """Admission installs BOTH of the prefill's states over whatever the
    slot's last holder left; a freed row's states go back to zero; other
    rows keep theirs."""
    cache = _pool()
    cache = HybridCache(cache.kv, tuple(jnp.full(s.shape, 7, s.dtype)
                                        for s in cache.state))
    src = _dense_row(6)
    out = cache.install_row(src, 1, jnp.asarray([3, 5, 0, 0], jnp.int32), 0)
    for got, want in zip(out.state, src.state):
        np.testing.assert_array_equal(np.array(got[:, 1], np.float32),
                                      np.array(want[:, 0], np.float32))
        np.testing.assert_array_equal(np.array(got[:, 0], np.float32), 7.0)
        np.testing.assert_array_equal(np.array(got[:, 2], np.float32), 7.0)
    assert list(np.array(out.kv_len)) == [0, 6, 0]
    for rows in (1, jnp.asarray([False, True, False])):
        freed = out.reset_rows(rows)
        assert list(np.array(freed.kv_len)) == [0, 0, 0]
        for s in freed.state:
            assert not np.array(s[:, 1], np.float32).any()
            np.testing.assert_array_equal(np.array(s[:, 0], np.float32),
                                          7.0)


def test_with_state_and_with_stacked_replace_what_they_are_given():
    cache = _pool()
    one = cache.with_state(2, (jnp.full((3, 2, 8), 5.0), None))
    np.testing.assert_array_equal(np.array(one.state[0][2], np.float32),
                                  5.0)
    assert one.state[0].dtype == jnp.bfloat16
    assert one.state[1] is cache.state[1]
    assert not np.array(one.state[0][:2], np.float32).any()
    stacked = jnp.ones((3, 3, 4, 4, 8))
    both = one.with_stacked(1, stacked)
    assert both.state[1] is stacked and both.state[0] is one.state[0]
    assert both.kv is cache.kv
