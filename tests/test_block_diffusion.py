"""Block diffusion end to end at tiny widths on the CPU: the SDAR-MoE
model, the dropless expert layer, the two new masks of the attention
kernels, ``generation/block_diffusion.py`` and the ``ServingEngine`` step
built from it, each against the plain reference the benchmark uses
(``benchmarks/reference/sdar.py``, loaded by path: there is one
reference).
"""
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor
from paddle_tpu.distributed.parallel.moe import DroplessMoE, dropless_moe
from paddle_tpu.generation.block_diffusion import (
    BlockDiffusionConfig, apply_block_step, first_block, select_unmask)
from paddle_tpu.generation.kv_cache import KVCache
from paddle_tpu.profiler import metrics

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _load(rel, name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference/sdar.py", "reference_sdar_t1")
fam = _load("families/sdar.py", "family_sdar_t1")

M = 95
STATIC = dict(block_length=4, denoising_steps=2,
              remasking="low_confidence_static", confidence_threshold=0.9,
              mask_token_id=M)
DYNAMIC = dict(STATIC, remasking="low_confidence_dynamic",
               confidence_threshold=0.7)


def tiny_cfg(bd=STATIC, **serving):
    return dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6,
        max_position_embeddings=256, dtype="float32",
        serve=dict(
            precision="float32", do_sample=False, block_diffusion=bd,
            generation=dict(max_new_tokens=16, prefill_buckets=[16, 32],
                            max_batch=4),
            serving=dict(dict(paged=True, kv_page_size=8, kv_pages=40,
                              cache_max_len=64, max_queue=64), **serving)))


def seeded_params(cfg, seed=3, gain=6.0, head_gain=1.0):
    """The reference's seeded weights, the matrices scaled up: at width
    32 the reference's Normal(0, 0.02) leaves the mask token's embedding
    all a position holds, and every masked position would decode alike."""
    params = ref.make_params(cfg, ref.seed_key(seed), jnp.float32)

    def scale(name, x):
        if name.startswith("g"):
            return x
        return x * (gain * head_gain if name == "head" else gain)

    out = {k: scale(k, v) for k, v in params.items() if k != "layers"}
    out["layers"] = [{k: scale(k, v) for k, v in lp.items()}
                     for lp in params["layers"]]
    return out


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def served():
    """(cfg, params, engine): one warm tiny engine for the module."""
    cfg = tiny_cfg()
    params = seeded_params(cfg)
    with jax.default_matmul_precision("highest"):
        model, make = fam.build_engine(cfg)
        fam.set_weights(model, fam.program_layout(params, cfg))
        engine = make()
    yield cfg, params, engine
    engine.shutdown()


def serve(engine, jobs, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for n, budget in jobs:
        prompt = rng.integers(0, M, n).astype(np.int32)
        reqs.append((prompt, budget, fam.submit(engine, prompt, budget)))
    while engine.busy:
        engine.step()
    return reqs


# ------------------------------------------------------------ the model

def test_prefill_logits_under_the_block_causal_mask():
    cfg = tiny_cfg()
    params = seeded_params(cfg)
    model = fam._model(cfg)
    model.eval()
    fam.set_weights(model, fam.program_layout(params, cfg))
    ids = np.random.default_rng(0).integers(0, M, 24).astype(np.int32)
    got = np.array(model(paddle.to_tensor(ids[None]),
                         block_length=4)._data)[0]
    want = np.array(ref.state_logits(params, jnp.asarray(ids), cfg))
    keep = np.arange(96) != M
    np.testing.assert_allclose(got[:, keep], want[:, keep], atol=2e-5)
    # block-causal, not causal: position 0 sees position 3
    other = ids.copy()
    other[3] = (other[3] + 1) % M
    moved = np.array(ref.state_logits(params, jnp.asarray(other), cfg))
    assert np.abs(moved[0, keep] - want[0, keep]).max() > 1e-4
    assert np.abs(moved[:4, keep] - want[:4, keep]).max() > 1e-4
    other = ids.copy()
    other[4] = (other[4] + 1) % M       # the next block is not seen
    moved = np.array(ref.state_logits(params, jnp.asarray(other), cfg))
    np.testing.assert_array_equal(moved[:4], want[:4])


def test_reference_block_on_a_prefix_is_its_full_forward():
    """``block_logits`` on ``prefix_kv`` of a request's FINAL sequence is
    ``state_logits`` of an earlier state at the block's rows: the chip's
    comparison computes the rows before a block once a request."""
    cfg = tiny_cfg()
    params = seeded_params(cfg)
    rng = np.random.default_rng(2)
    final = rng.integers(0, M, 22).astype(np.int32)     # 5 blocks and 2
    padded = np.concatenate([final, np.zeros(10, np.int32)])
    kv = ref.prefix_kv(params, jnp.asarray(padded), cfg)
    for start, masked in ((8, [1, 3]), (0, [0, 1, 2]), (20, [1, 2, 3])):
        state = np.full(start + 4, M, np.int32)
        state[:min(start + 4, 22)] = final[:start + 4]
        state[[start + j for j in masked]] = M
        want = np.array(ref.state_logits(
            params, jnp.asarray(state), cfg,
            at=jnp.arange(start, start + 4)))
        got = np.array(ref.block_logits(
            params, kv, jnp.asarray(state[start:]), start, cfg))
        keep = np.arange(96) != M
        np.testing.assert_allclose(got[:, keep], want[:, keep], atol=2e-5)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_committed_k_and_v_equal_the_full_forwards(paged):
    """Prefill two blocks, denoise a third with masks in it, then commit
    it with its final ids: the cache then holds what a prefill of all
    three blocks writes."""
    from paddle_tpu.generation.paged_cache import PagedKVCache
    cfg = tiny_cfg()
    params = seeded_params(cfg)
    model = fam._model(cfg)
    model.eval()
    fam.set_weights(model, fam.program_layout(params, cfg))
    ids = np.random.default_rng(1).integers(0, M, 12).astype(np.int32)
    t = paddle.to_tensor
    _, full = model(t(ids[None]), use_cache=True, cache_max_len=16,
                    block_length=4)
    _, cache = model(t(ids[None, :8]), use_cache=True, cache_max_len=16,
                     block_length=4)
    if paged:
        pool = PagedKVCache.create(2, 1, 3, 8, 2, 2, 8, jnp.float32)
        cache = pool.install_row(cache, 0, jnp.asarray([1, 2]), 0)
    noisy = ids[8:].copy()
    noisy[[1, 3]] = M
    logits, dirty = model(t(noisy[None]), cache=cache, block_length=4)
    assert logits.shape == [1, 4, 96]
    _, done = model(t(ids[None, 8:]),
                    cache=dirty.with_kv_len(cache.kv_len), block_length=4)
    assert int(done.kv_len[0]) == 12

    def rows(c):
        if not paged:
            return np.asarray(c.k)[:, 0, :12], np.asarray(c.v)[:, 0, :12]
        k = np.asarray(c.k)[:, [1, 2]]      # [L, pages, H, page, D]
        v = np.asarray(c.v)[:, [1, 2]]
        flat = lambda a: a.transpose(0, 1, 3, 2, 4).reshape(  # noqa: E731
            a.shape[0], -1, a.shape[2], a.shape[4])[:, :12]
        return flat(k), flat(v)

    k, v = rows(done)
    np.testing.assert_allclose(k, np.asarray(full.k)[:, 0, :12], atol=1e-5)
    np.testing.assert_allclose(v, np.asarray(full.v)[:, 0, :12], atol=1e-5)
    # and while masks were in the block they did not
    assert np.abs(rows(dirty)[0][:, 8:] - k[:, 8:]).max() > 1e-3


# ------------------------------------------------------ the expert layer

def _per_token_loop(x, router, gate_up, down, k, norm):
    f = down.shape[1]
    out = np.zeros_like(x)
    p = np.asarray(jax.nn.softmax(jnp.asarray(x @ router), axis=-1))
    for t in range(x.shape[0]):
        top = np.argsort(-p[t], kind="stable")[:k]
        w = p[t, top] / (p[t, top].sum() if norm else 1.0)
        for e, we in zip(top, w):
            gu = x[t] @ gate_up[e]
            z = np.asarray(jax.nn.silu(jnp.asarray(gu[:f]))) * gu[f:]
            out[t] += we * (z @ down[e])
    return out


@pytest.mark.parametrize("routing", ["spread", "one_expert", "one_empty"])
def test_dropless_experts_against_a_per_token_loop(routing):
    rng = np.random.default_rng(5)
    t, h, f, e, k = 24, 16, 8, 6, 2
    x = rng.normal(size=(t, h)).astype(np.float32)
    router = rng.normal(size=(h, e)).astype(np.float32)
    if routing == "one_expert":     # every row's first choice: expert 4
        router[:, 4] = 0
        x[:, 0] = 30.0
        router[0] = [0, 0, 0, 0, 1, 0]
        k = 1
    if routing == "one_empty":      # no row ever chooses expert 2
        x[:, 0] = 30.0
        router[0] = [0, 0, -50, 0, 0, 0]
    gate_up = rng.normal(size=(e, h, 2 * f)).astype(np.float32) * 0.3
    down = rng.normal(size=(e, f, h)).astype(np.float32) * 0.3
    got, rows = dropless_moe(jnp.asarray(x), jnp.asarray(router),
                             jnp.asarray(gate_up), jnp.asarray(down), k)
    rows = np.asarray(rows)
    assert rows.sum() == t * k
    if routing == "one_expert":
        assert rows[4] == t
    if routing == "one_empty":
        assert rows[2] == 0
    want = _per_token_loop(x, router, gate_up, down, k, True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_dropless_layer_reports_its_rows():
    from paddle_tpu.distributed.parallel.moe import routing_stats
    layer = DroplessMoE(16, 8, 6, 2)
    y = layer(paddle.to_tensor(np.ones((2, 5, 16), np.float32)))
    assert y.shape == [2, 5, 16]
    rows, busiest, elsewhere = routing_stats(layer)
    assert int(rows) == 20 and int(busiest) == 10   # equal rows: one route
    assert int(elsewhere) == 0          # the layer holds all its experts


# ------------------------------------------------------------ the masks

def _naive(q, k, v, allowed):
    s = np.einsum("qd,kd->qk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(allowed, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ v


@pytest.mark.parametrize("block", [2, 4, 8])
def test_block_causal_flash_kernel_against_a_naive_mask(block):
    rng = np.random.default_rng(block)
    q, k, v = (rng.normal(size=(2, 256, 64)).astype(np.float32)
               for _ in range(3))
    out, _ = fa._flash_fwd(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           0.125, True, 128, 128, block=block)
    whole, _ = fa._flash_fwd(jnp.asarray(q), jnp.asarray(k),
                             jnp.asarray(v), 0.125, True, 256, 256,
                             block=block)
    b = np.arange(256) // block
    for i in range(2):
        want = _naive(q[i], k[i], v[i], b[None, :] <= b[:, None])
        np.testing.assert_allclose(np.asarray(out)[i], want, atol=2e-5)
        np.testing.assert_allclose(np.asarray(whole)[i], want, atol=2e-5)


def test_block_causal_needs_a_block_that_divides_the_grid():
    q = jnp.zeros((1, 256, 4, 64))
    with pytest.raises(NotImplementedError):
        fa.flash_attention(q, q, q, causal=True, block=3)
    with pytest.raises(NotImplementedError):
        fa.flash_attention(q, q, q, causal=False, block=4)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("cache", ["dense", "paged"])
def test_full_window_decode_against_a_naive_mask(kernel, cache, monkeypatch):
    """A window of 4 queries that all see ``kv_len`` columns, 4 query
    heads over 2 kv heads (stacked into one grid row a kv head)."""
    rng = np.random.default_rng(7)
    b, sq, hq, hk, d, page, slots = 3, 4, 4, 2, 64, 128, 2
    t = page * slots
    q = rng.normal(size=(b, sq, hq, d)).astype(np.float32)
    kd = rng.normal(size=(b, t, hk, d)).astype(np.float32)
    vd = rng.normal(size=(b, t, hk, d)).astype(np.float32)
    kv_len = np.array([4, 130, 77], np.int32)
    if kernel == "pallas":      # interpret mode, through the public entry
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(fa, "_interpret", lambda: True)
    if cache == "dense":
        got = fa.flash_attention_decode(
            jnp.asarray(q), jnp.asarray(kd), jnp.asarray(vd),
            jnp.asarray(kv_len), window_causal=False)
        causal = fa.flash_attention_decode(
            jnp.asarray(q), jnp.asarray(kd), jnp.asarray(vd),
            jnp.asarray(kv_len))
    else:
        # pool [L, pages, H, page, D]; row r holds pages 1 + 2r, 2 + 2r
        def pool(dense):
            p = np.zeros((2, 1 + b * slots, hk, page, d), np.float32)
            p[1, 1:] = dense.reshape(b * slots, page, hk, d) \
                .transpose(0, 2, 1, 3)
            return jnp.asarray(p)
        table = jnp.asarray(1 + np.arange(b * slots).reshape(b, slots),
                            jnp.int32)
        args = (jnp.asarray(q), pool(kd), pool(vd), table,
                jnp.asarray(kv_len), 1)
        got = fa.flash_attention_decode_paged(*args, window_causal=False)
        causal = fa.flash_attention_decode_paged(*args)
    got = np.asarray(got)
    for r in range(b):
        for h in range(hq):
            allowed = np.broadcast_to(np.arange(t) < kv_len[r], (sq, t))
            want = _naive(q[r, :, h], kd[r, :, h // 2], vd[r, :, h // 2],
                          allowed)
            np.testing.assert_allclose(got[r, :, h], want, atol=3e-5)
    # the default is still the ragged-causal window
    assert np.abs(np.asarray(causal)[:, 0] - got[:, 0]).max() > 1e-3
    np.testing.assert_allclose(np.asarray(causal)[:, -1], got[:, -1],
                               atol=3e-5)


# -------------------------------------------------- the step's arithmetic

def test_first_block_opens_with_the_prompts_left_over_tokens():
    bd = BlockDiffusionConfig(**STATIC)
    whole, blk, out0 = first_block(np.arange(10, 17, dtype=np.int32), bd)
    assert (whole, out0) == (4, -3)
    np.testing.assert_array_equal(blk, [14, 15, 16, M])
    whole, blk, out0 = first_block(np.arange(8, dtype=np.int32), bd)
    assert (whole, out0) == (8, 0) and (blk == M).all()


def test_select_unmask_static_and_dynamic():
    conf = jnp.log(jnp.asarray([[0.2, 0.9, 0.5, 0.9],
                                [0.1, 0.2, 0.3, 0.95]]))
    cand = jnp.asarray([[True, True, True, True],
                        [True, True, True, False]])
    static = select_unmask(conf, cand, BlockDiffusionConfig(**STATIC))
    np.testing.assert_array_equal(
        np.asarray(static), [[False, True, False, True],   # tie: earlier
                             [False, True, True, False]])
    dyn = select_unmask(conf, cand, BlockDiffusionConfig(
        **dict(DYNAMIC, confidence_threshold=0.4)))
    np.testing.assert_array_equal(
        np.asarray(dyn), [[False, True, True, True],
                          [False, False, True, False]])    # the best, always


def test_config_is_checked_at_the_boundary():
    with pytest.raises(ValueError):
        BlockDiffusionConfig(block_length=3)
    with pytest.raises(ValueError):
        BlockDiffusionConfig(block_length=16)
    with pytest.raises(ValueError):
        BlockDiffusionConfig(block_length=4, denoising_steps=5)
    with pytest.raises(ValueError):
        BlockDiffusionConfig(remasking="random")
    from paddle_tpu.inference import Config
    with pytest.raises(TypeError):
        Config().enable_generation(block_diffusion="sdar")


def test_a_commit_advances_the_cache_and_opens_the_next_block():
    bd = BlockDiffusionConfig(**STATIC)
    cache = KVCache.create(1, 2, 16, 1, 8).with_kv_len(jnp.asarray([8, 4]))
    logits = jnp.zeros((2, 4, 96)).at[:, :, 7].set(5.0)
    blk = jnp.asarray([[1, 2, 3, 4], [M, M, 9, M]], jnp.int32)
    z = jnp.zeros((2,), jnp.int32)
    out = apply_block_step(
        logits, bd, cache, cache.kv_len, jnp.zeros((2,), bool), z + 4,
        z + 12, jnp.zeros((2, 12), jnp.int32),
        jnp.full((2, 12), -1, jnp.int8), blk, z + 2, jnp.asarray([0, 4]),
        jnp.zeros((3,), jnp.int32))
    cache, fin, steps, out_buf, usteps, blk, blk_step, out0, ctr = out
    np.testing.assert_array_equal(np.asarray(cache.kv_len), [12, 4])
    np.testing.assert_array_equal(np.asarray(blk),
                                  [[M] * 4, [7, 7, 9, M]])
    np.testing.assert_array_equal(np.asarray(out0), [4, 4])
    np.testing.assert_array_equal(np.asarray(blk_step), [0, 3])
    np.testing.assert_array_equal(np.asarray(steps), [4, 6])
    np.testing.assert_array_equal(np.asarray(out_buf)[1, 4:8], [7, 7, 0, 0])
    np.testing.assert_array_equal(np.asarray(usteps)[1, 4:8],
                                  [2, 2, -1, -1])
    np.testing.assert_array_equal(np.asarray(ctr), [2, 2, 1])


# ------------------------------------------------------- through the engine

JOBS = [(4, 8), (5, 7), (6, 16), (7, 5), (13, 9), (16, 1), (9, 4), (32, 3)]


def check_against_reference(cfg, params, reqs, bd):
    from paddle_tpu.serving import RequestStatus
    for prompt, budget, req in reqs:
        assert req.status is RequestStatus.COMPLETED
        toks, steps, states = ref.generate(params, prompt, budget, cfg, bd)
        np.testing.assert_array_equal(req.tokens, toks)
        np.testing.assert_array_equal(req.unmask_steps, steps)
        assert req.tokens.size == budget == req.n_emitted
        assert (req.tokens != bd["mask_token_id"]).all()
        assert req.unmask_steps.dtype == np.int8
        # every (block, step) state can be rebuilt from what was served
        assert ref.request_states(prompt.size, budget, req.unmask_steps,
                                  bd) == [(s[0], s[1]) for s in states]
        for start, step, ids, _, pick in states:
            seq, masked = ref.rebuild_state(prompt, req.tokens,
                                            req.unmask_steps, start, step,
                                            bd)
            np.testing.assert_array_equal(seq, ids)
            at = np.arange(start, start + bd["block_length"])
            np.testing.assert_array_equal(
                at[pick], masked[req.unmask_steps[masked - prompt.size]
                                 == step])


def test_engine_serves_what_the_reference_generates(served):
    """Prompts with n mod B in {0..3}, budgets that are and are not
    multiples of B, more requests than slots: tokens and unmask steps
    equal the reference's at every (block, step)."""
    cfg, params, engine = served
    reqs = serve(engine, JOBS)
    check_against_reference(cfg, params, reqs, STATIC)
    assert len({tuple(r.tokens[:4]) for _, _, r in reqs}) > 3
    for _, budget, req in reqs:
        # static schedule: two positions a step, so steps 0 and 1 only
        assert set(np.unique(req.unmask_steps)) <= {0, 1}


def test_engine_counts_tokens_not_steps(served):
    cfg, params, engine = served
    before = dict(engine.stats)
    monitor.enable()
    try:
        c0 = {k: _counter(k) for k in (
            "gen.diffusion.forwards", "gen.diffusion.unmasked",
            "gen.diffusion.commits", "moe.rows", "moe.expert_rows_max")}
        reqs = serve(engine, [(8, 16), (8, 16), (8, 16), (8, 16)], seed=2)
        d = {k: _counter(k) - v for k, v in c0.items()}
    finally:
        monitor.disable()
    assert all(r.n_emitted == 16 for _, _, r in reqs)
    assert engine.stats["emitted_tokens"] - before["emitted_tokens"] == 64
    # 4 blocks a lane: 2 denoise steps each, a commit after all but the
    # last; the poll that sees the lanes finished comes every 4th step,
    # and with every slot full it has dispatched the next step before
    # it reads
    steps = engine.stats["decode_steps"] - before["decode_steps"]
    assert steps == 12 + 1 and 12 >= 4 * 2 + 3
    assert d["gen.diffusion.unmasked"] == 64
    assert d["gen.diffusion.forwards"] == 4 * 11
    assert d["gen.diffusion.commits"] == 4 * 3
    # 4 lanes x 4 positions x top-2 rows a layer, 2 layers, every step
    # the last poll's read covers (a finished lane's rows are computed
    # too: the batch is fixed): the step dispatched ahead of that read
    # is in no poll's counters yet
    assert d["moe.rows"] == 4 * 4 * 2 * 2 * (steps - 1)
    assert d["moe.rows"] / 8 <= d["moe.expert_rows_max"] <= d["moe.rows"]


def _counter(name):
    snap = metrics.snapshot().get(name)
    return int(snap["value"]) if snap else 0


def test_no_compile_after_warm_up(served):
    cfg, params, engine = served
    monitor.enable()
    try:
        c0 = _counter("jit.compile{cause=new_shape}")
        serve(engine, JOBS, seed=9)
        assert _counter("jit.compile{cause=new_shape}") == c0
    finally:
        monitor.disable()


def test_poll_span_carries_forwards_and_commits(served):
    from paddle_tpu.core import flight_recorder as fr
    cfg, params, engine = served
    t0 = fr.now_ns()
    serve(engine, [(8, 8)], seed=4)
    polls = [s for s in fr.spans_between(t0, 2 ** 62)
             if s.name == "serve.poll"]
    assert polls and all("forwards" in s.fields and "commits" in s.fields
                         for s in polls)
    assert sum(s.fields["forwards"] for s in polls) == 5
    assert sum(s.fields["commits"] for s in polls) == 1


def test_engine_refuses_what_block_diffusion_cannot_serve(served):
    cfg, params, engine = served
    with pytest.raises(ValueError, match="shorter than one block"):
        engine.submit(np.array([1, 2, 3], np.int32))
    bad = tiny_cfg()
    bad["serve"]["do_sample"] = True
    model, make = fam.build_engine(bad)
    with pytest.raises(ValueError, match="greedy"):
        make()
    bad = tiny_cfg(prefill_chunk_tokens=8)
    model, make = fam.build_engine(bad)
    with pytest.raises(ValueError, match="chunked prefill"):
        make()


def test_eviction_keeps_the_prefix_before_the_first_mask(served):
    cfg, params, engine = served
    prompt = np.arange(8, dtype=np.int32)
    req = fam.submit(engine, prompt, 16)
    for _ in range(4):      # admit + a few steps: mid-block
        engine.step()
    slot = engine._slots.index(req)
    engine._evict(slot, req, "test", int(np.asarray(engine._steps)[slot]))
    toks, steps, _ = ref.generate(params, prompt, 16, cfg)
    n = req.tokens.size
    assert 0 < n < 16 and req.unmask_steps.size == n
    np.testing.assert_array_equal(req.tokens, toks[:n])
    assert (req.unmask_steps >= 0).all()
    while engine.busy:
        engine.step()


def test_dense_cache_and_dynamic_unmasking():
    """The dense (not paged) cache, and the dynamic rule on weights whose
    confidences pass the threshold: several positions a step, more than
    the static two on some step, one at least on every step."""
    cfg = tiny_cfg(DYNAMIC, paged=False)
    params = seeded_params(cfg, head_gain=8.0)
    model, make = fam.build_engine(cfg)
    fam.set_weights(model, fam.program_layout(params, cfg))
    engine = make()
    try:
        reqs = serve(engine, JOBS[:6], seed=3)
        check_against_reference(cfg, params, reqs, DYNAMIC)
        per_step = [np.bincount(r.unmask_steps[i:i + 4]).max()
                    for _, _, r in reqs
                    for i in range(0, r.unmask_steps.size, 4)]
        assert max(per_step) > 2
        assert max(int(r.unmask_steps.max()) for _, _, r in reqs) >= 1
    finally:
        engine.shutdown()
