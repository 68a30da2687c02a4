"""Paged KV cache with shared-prefix reuse (ISSUE 12).

Covers: PagedKVCache write/install parity with the dense ring cache
(including the dead-lane null-page contract), the PageAllocator's
prefix registry / refcounts / reclaim / conservation invariant, the
paged Pallas decode kernel (interpret mode) against the XLA gather
fallback, THE bitwise-parity gate (ragged mixed-length traffic with
mid-decode arrivals, slot turnover re-anchoring rows at position 0 —
the paged analog of ring-wrap — and zero post-warmup retraces),
mid-decode eviction returning pages, COW-after-share divergence,
speculative (ngram) decode windows over a paged cache, the
no_free_pages/no_free_slots health distinction, the serve.cache.* /
gen.cache.* metrics family, the tier-1 audit gate over the paged
admit/decode/free trio with a seeded regression, and the chaos
SIGTERM drain with shared pages live (free-list conserved).
"""
import dataclasses
import importlib
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.generation.kv_cache import KVCache
from paddle_tpu.generation.paged_cache import PagedKVCache, PageAllocator
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


def _config(m, *, max_new=8, buckets=(16,), max_batch=2, eos=None,
            speculative=None, **serving_kw):
    cfg = (Config().from_layer(m, _spec())
           .enable_generation(max_new_tokens=max_new,
                              prefill_buckets=buckets,
                              max_batch=max_batch, eos_token_id=eos,
                              speculative=speculative))
    cfg.enable_serving(**serving_kw)
    return cfg


@pytest.fixture(scope="module")
def paged_engine(tiny_gpt):
    """Shared 2-slot paged engine (page 16 over the 128-token cache):
    reused across the parity, COW, eviction, and metrics tests — all
    of which leave it drained of traffic but serviceable."""
    return ServingEngine(_config(tiny_gpt, buckets=(16, 32), paged=True,
                                 kv_page_size=16), poll_every=2)


@pytest.fixture(scope="module")
def reference(tiny_gpt):
    """Sequential one-request-at-a-time dense reference."""
    pred = create_predictor(_config(tiny_gpt, buckets=(16, 32),
                                    max_batch=1))
    return lambda p, b: pred.generate([p], max_new_tokens=b)[0]


def _counter(name):
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot().get(name)
    return int(snap["value"]) if snap else 0


# ---------------------------------------------------------- cache unit


def _logical_rows(pool, pages):
    """The pages ``pages`` of ``pool`` ([L, n_pages, H, page, ...]) as
    the dense cache lays a row out: [L, len(pages) * page, H, ...]."""
    g = np.swapaxes(np.asarray(pool)[:, np.asarray(pages)], 2, 3)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def test_paged_update_matches_dense_and_null_routes():
    """Writes through the page table land where the dense ring would
    put them; a dead lane (write base 0 — the engine's parked-slot
    contract) routes to the null page and cannot corrupt pages its
    stale table still names."""
    rng = np.random.RandomState(0)
    L, B, T, H, D, ps = 2, 2, 32, 2, 8, 8
    P = T // ps
    table = np.arange(1, 1 + B * P, dtype=np.int32).reshape(B, P)
    paged = PagedKVCache.create(L, B, n_pages=1 + B * P, page_size=ps,
                                pages_per_row=P, num_heads=H, head_dim=D)
    paged = PagedKVCache(paged.k, paged.v, jnp.asarray(table),
                         jnp.asarray([5, 9], np.int32))
    dense = KVCache.create(L, B, T, H, D).with_kv_len(
        jnp.asarray([5, 9], np.int32))
    k1 = rng.randn(B, 1, H, D).astype(np.float32)
    v1 = rng.randn(B, 1, H, D).astype(np.float32)
    for layer in range(L):
        paged = paged.update(layer, jnp.asarray(k1), jnp.asarray(v1),
                             paged.kv_len)
        dense = dense.update(layer, jnp.asarray(k1), jnp.asarray(v1),
                             dense.kv_len)
    for r, pos in enumerate((5, 9)):
        page, off = table[r][pos // ps], pos % ps
        np.testing.assert_array_equal(
            np.asarray(paged.k[:, page, :, off]),
            np.asarray(dense.k[:, r, pos]))
    # dead lane: kv_len 0 -> the write must land on the null page only
    dead = paged.with_kv_len(paged.kv_len.at[1].set(0))
    before = np.asarray(dead.k[:, table[1]])
    dead2 = dead.update(0, jnp.asarray(k1), jnp.asarray(v1), dead.kv_len)
    np.testing.assert_array_equal(np.asarray(dead2.k[:, table[1]]),
                                  before)
    # reset_rows severs the row's pointers too
    reset = paged.reset_rows(jnp.asarray([0]))
    assert np.asarray(reset.page_table)[0].sum() == 0
    assert int(np.asarray(reset.kv_len)[0]) == 0


def test_install_row_skips_shared_prefix_positions():
    """install_row writes only positions >= start: the shared-prefix
    pages' content is referenced, never re-written."""
    rng = np.random.RandomState(1)
    L, T, H, D, ps = 2, 32, 2, 8, 8
    row = KVCache.create(L, 1, T, H, D)
    for layer in range(L):
        row = row.update(layer,
                         jnp.asarray(rng.randn(1, 20, H, D), jnp.float32),
                         jnp.asarray(rng.randn(1, 20, H, D), jnp.float32),
                         jnp.zeros((1,), jnp.int32))
    row = row.with_kv_len(20)
    paged = PagedKVCache.create(L, 1, n_pages=8, page_size=ps,
                                pages_per_row=4, num_heads=H, head_dim=D)
    sentinel = np.full_like(np.asarray(paged.k[:, 1]), 7.0)
    paged = PagedKVCache(paged.k.at[:, 1].set(sentinel), paged.v,
                         paged.page_table, paged.kv_len)
    table_row = jnp.asarray([1, 2, 3, 0], jnp.int32)
    out = paged.install_row(row, 0, table_row, jnp.asarray(8, jnp.int32))
    # page 1 (positions 0..7, below start=8) kept its sentinel content
    np.testing.assert_array_equal(np.asarray(out.k[:, 1]), sentinel)
    # pages 2..3 carry the row's positions 8..19
    np.testing.assert_array_equal(_logical_rows(out.k, [2, 3])[:, :12],
                                  np.asarray(row.k[:, 0, 8:20]))
    assert int(np.asarray(out.kv_len)[0]) == 20


@pytest.mark.parametrize("n", [21, 27], ids=["ends-in-page", "past-table"])
@pytest.mark.parametrize("cache_dtype", [None, "int8"],
                         ids=["wide", "int8"])
def test_install_span_merges_into_whole_pages(cache_dtype, n):
    """The install writes whole pages (gather, merge, scatter back), so
    it has to leave every position outside ``[start, kv_len)`` as it
    was: a start inside a page, an end inside a page, a source row that
    is no whole number of pages and one longer than the table."""
    rng = np.random.RandomState(5)
    L, T, H, D, ps, start = 2, 44, 2, 8, 8, 11
    row = KVCache.create(L, 1, T, H, D, cache_dtype=cache_dtype)
    for layer in range(L):
        row = row.update(layer,
                         jnp.asarray(rng.randn(1, T, H, D), jnp.float32),
                         jnp.asarray(rng.randn(1, T, H, D), jnp.float32),
                         jnp.zeros((1,), jnp.int32))
    row = row.with_kv_len(n)
    paged = PagedKVCache.create(L, 1, n_pages=8, page_size=ps,
                                pages_per_row=3, num_heads=H, head_dim=D,
                                cache_dtype=cache_dtype)
    names = ("k", "v") + (("k_scale", "v_scale") if cache_dtype else ())
    for name in names:      # every page starts as a sentinel
        setattr(paged, name, jnp.full_like(getattr(paged, name), 3))
    out = paged.install_span(row, jnp.asarray([5, 2, 6], jnp.int32),
                             jnp.asarray(start, jnp.int32))
    assert not np.asarray(out.page_table).any()
    assert not np.asarray(out.kv_len).any()
    for name in names:
        got = _logical_rows(getattr(out, name), [5, 2, 6])   # 24 positions
        want = np.asarray(getattr(row, name))[:, 0]
        # page 5 (positions 0..7) lies below start: not written at all;
        # positions 8..10 of page 2 keep the sentinel, 11..min(n, 24)
        # are the row's (24..26 are past the 3-page table), the rest of
        # page 6 keeps the sentinel
        end = min(n, 24)
        assert (got[:, :start] == 3).all() and (got[:, end:] == 3).all()
        np.testing.assert_array_equal(got[:, start:end],
                                      want[:, start:end])
        untouched = np.asarray(getattr(out, name))[:, [0, 1, 3, 4, 7]]
        assert (untouched == 3).all()


@pytest.mark.parametrize("cache_dtype", [None, "int8"],
                         ids=["wide", "int8"])
def test_pool_shape_properties_read_the_right_axes(cache_dtype):
    """The pool is [layers, n_pages, heads, page_size, head_dim] (tokens
    on the second-minor axis, the block the decode kernel streams):
    every size differs here, so a property reading the wrong axis
    shows."""
    c = PagedKVCache.create(2, 3, n_pages=11, page_size=8,
                            pages_per_row=5, num_heads=4, head_dim=16,
                            cache_dtype=cache_dtype)
    assert c.k.shape == c.v.shape == (2, 11, 4, 8, 16)
    assert (c.num_layers, c.n_pages, c.page_size) == (2, 11, 8)
    assert (c.batch, c.pages_per_row, c.max_len) == (3, 5, 40)
    if cache_dtype:
        assert c.k_scale.shape == c.v_scale.shape == (2, 11, 4, 8)


@pytest.mark.parametrize("s", [1, 4], ids=["s1", "s4"])
@pytest.mark.parametrize("cache_dtype", [None, "int8"],
                         ids=["wide", "int8"])
def test_idle_lane_write_lands_on_null_page(cache_dtype, s):
    """An idle lane (kv_len 0) writes its ``s`` tokens to the null page
    0 at offsets 0..s-1 of every head and to no other page, decode and
    speculative-verify windows alike; the live lane beside it writes
    through its table."""
    rng = np.random.RandomState(3)
    L, B, H, D, ps, P = 2, 2, 2, 8, 8, 2
    table = np.arange(1, 1 + B * P, dtype=np.int32).reshape(B, P)
    c = PagedKVCache.create(L, B, n_pages=1 + B * P, page_size=ps,
                            pages_per_row=P, num_heads=H, head_dim=D,
                            cache_dtype=cache_dtype)
    c = c.with_kv_len(jnp.asarray([0, 6], np.int32))
    c.page_table = jnp.asarray(table)
    k = jnp.asarray(rng.randn(B, s, H, D), jnp.float32)
    out = c.update(1, k, k, c.kv_len)
    dense = KVCache.create(L, B, P * ps, H, D, cache_dtype=cache_dtype)
    dense = dense.update(1, k, k, jnp.asarray([0, 6], np.int32))
    for name in ("k", "v") + (("k_scale", "v_scale") if cache_dtype
                              else ()):
        got, want = getattr(out, name), np.asarray(getattr(dense, name))
        # the idle lane's stale table names pages 1..2: untouched
        assert not np.asarray(got)[:, table[0]].any()
        np.testing.assert_array_equal(
            _logical_rows(got, [0])[1, :s], want[1, 0, :s])
        np.testing.assert_array_equal(
            _logical_rows(got, table[1])[1, 6:6 + s], want[1, 1, 6:6 + s])
        assert not np.asarray(got)[0].any()      # layer 0 never written


@pytest.mark.parametrize("cache_dtype", [None, "int8"],
                         ids=["wide", "int8"])
def test_install_row_then_gather_equals_dense_row_bitwise(cache_dtype):
    """install_row, then the off-TPU gather of the stacked pool at each
    layer, attends exactly what the dense decode attends over the
    batch-1 row it was installed from: bit for bit, both dtypes."""
    from paddle_tpu.kernels.flash_attention import (
        flash_attention_decode, flash_attention_decode_paged)
    rng = np.random.RandomState(4)
    L, T, H, D, ps, n = 2, 32, 2, 8, 8, 19
    row = KVCache.create(L, 1, T, H, D, cache_dtype=cache_dtype)
    for layer in range(L):
        row = row.update(layer,
                         jnp.asarray(rng.randn(1, n, H, D), jnp.float32),
                         jnp.asarray(rng.randn(1, n, H, D), jnp.float32),
                         jnp.zeros((1,), jnp.int32))
    row = row.with_kv_len(n)
    paged = PagedKVCache.create(L, 2, n_pages=9, page_size=ps,
                                pages_per_row=T // ps, num_heads=H,
                                head_dim=D, cache_dtype=cache_dtype)
    table_row = jnp.asarray([7, 2, 5, 0], jnp.int32)
    paged = paged.install_row(row, 1, table_row, 0)
    assert np.asarray(paged.page_table)[1].tolist() == [7, 2, 5, 0]
    assert np.asarray(paged.kv_len).tolist() == [0, n]
    q = jnp.asarray(rng.randn(1, 1, 2 * H, D), jnp.float32)
    for layer in range(L):
        sc = dict(k_scale=paged.k_scale, v_scale=paged.v_scale) \
            if cache_dtype else {}
        got = flash_attention_decode_paged(
            q, paged.k, paged.v, paged.page_table[1:], paged.kv_len[1:],
            layer, **sc)
        sc = dict(k_scale=row.k_scale[layer], v_scale=row.v_scale[layer]) \
            if cache_dtype else {}
        want = flash_attention_decode(q, row.k[layer], row.v[layer],
                                      row.kv_len, **sc)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------- allocator


def test_allocator_prefix_registry_and_conservation():
    a = PageAllocator(16, 8)
    ids = np.arange(20, dtype=np.int32)
    plan = a.plan(ids, extra_tokens=8)
    assert (plan.n_private, plan.total_pages, plan.shared_pages,
            plan.cow) == (4, 4, [], False)
    pages = a.commit(plan)
    a.register(plan, pages)
    # identical prompt: both full pages shared, divergence inside the
    # partial third page -> COW
    plan2 = a.plan(ids, extra_tokens=8)
    assert plan2.shared_pages == pages[:2] and plan2.cow
    pages2 = a.commit(plan2)
    assert pages2[:2] == pages[:2] and len(pages2) == 4
    assert a.stats["prefix_hits"] == 1 and a.stats["shared_pages"] == 2
    # a prompt diverging at the second page shares only the first
    ids3 = np.concatenate([ids[:8], ids[:8] + 1, ids[16:]])
    plan3 = a.plan(ids3, extra_tokens=8)
    assert plan3.shared_pages == pages[:1] and not plan3.cow
    # frees: shared pages stay (other rows + registry), private return
    a.free_row(pages2)
    a.free_row(pages)
    a.assert_conserved()
    # registered refcount-0 pages are allocatable and reclaimed LRU
    free_before = a.free_pages()
    big = a.plan(np.arange(100, 164, dtype=np.int32), extra_tokens=48)
    got = a.commit(big)
    assert got is not None and a.stats["reclaimed"] > 0
    a.free_row(got)
    a.assert_conserved()
    assert a.free_pages() == free_before


def test_allocator_exhaustion_returns_none():
    a = PageAllocator(4, 8)   # 3 allocatable pages
    p1 = a.commit(a.plan(np.arange(8, dtype=np.int32), 8))
    assert p1 is not None and len(p1) == 2
    assert a.commit(a.plan(np.arange(24, dtype=np.int32), 8)) is None
    a.free_row(p1)
    a.assert_conserved()


# ------------------------------------------------------- paged kernel


# (sq, query heads a kv head, window_causal): the causal window of plain
# decode and of speculative verify, MHA and GQA, and the full window of
# block diffusion (8 query heads a kv head, a block of 4)
_KERNEL_MODES = [pytest.param(sq, g, True, id=f"causal-q{sq}-g{g}")
                 for sq in (1, 4, 8) for g in (1, 4)] \
    + [pytest.param(4, 8, False, id="full-q4-g8")]


def _ragged_pools(rng, quant, sq, window_causal, L=3, Hk=2, D=64, ps=8,
                  P=4):
    """A stacked pool and a ragged batch over it: a lane that holds
    nothing, one with a single token (a causal window: with its window
    alone), one ending exactly on a page boundary, one filling its whole
    table, one inside a page. Every table slot past a lane's ``kv_len``
    names page 1, which holds NaN (the int8 pools: NaN scales): returns
    that table, and a clean one naming the null page there, for the
    reference."""
    kv_len = np.array([0, sq if window_causal else 1, 2 * ps, P * ps, 13],
                      np.int32)
    B, poison = len(kv_len), 1
    shape = (L, 2 + B * P, Hk, ps, D)
    k, v = rng.randn(*shape), rng.randn(*shape)
    k[:, 0] = v[:, 0] = 0
    table = np.full((B, P), poison, np.int32)
    clean = np.zeros((B, P), np.int32)
    for b in range(B):
        n = -(-kv_len[b] // ps)
        table[b, :n] = clean[b, :n] = 2 + b * P + np.arange(n)
    if quant:
        pools, scales = [], {}
        for name, x in (("k_scale", k), ("v_scale", v)):
            scale = np.abs(x).max(-1) / 127 + 1e-6
            pools.append(jnp.asarray(np.round(x / scale[..., None]),
                                     jnp.int8))
            scale[:, poison] = np.nan
            scales[name] = jnp.asarray(scale, jnp.bfloat16)
    else:
        k[:, poison] = v[:, poison] = np.nan
        pools, scales = [jnp.asarray(k, jnp.float32),
                         jnp.asarray(v, jnp.float32)], {}
    return pools, scales, table, clean, kv_len


def _paged_kernel_interpreted(q, pools, scales, table, kv_len, layer,
                              window_causal):
    """The Pallas kernel itself off the TPU: under the TPU interpreter,
    whose buffers start as NaN, so a block the kernel never wrote, or a
    page it should not have read, shows."""
    import importlib
    from jax.experimental.pallas import tpu as pltpu
    # the module, not the function of the same name that kernels/ exports
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    b, sq, hq, d = q.shape
    out = fa._paged_decode_pallas(
        fa._fold_group(jnp.asarray(q), pools[0].shape[2]), *pools,
        jnp.asarray(table), jnp.asarray(kv_len), float(d ** -0.5), layer,
        sq, interpret=pltpu.InterpretParams(),
        window_causal=window_causal, **scales)
    return np.asarray(fa._unfold_group(out, sq))


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("sq,group,window_causal", _KERNEL_MODES)
@pytest.mark.parametrize("quant", [False, True], ids=["wide", "int8"])
def test_paged_pallas_kernel_interpret_matches_fallback(
        quant, sq, group, window_causal, layer):
    """The Pallas kernel (interpreted off-TPU) and the XLA gather
    fallback agree, in every mode and at every layer of the stacked
    pool, on a ragged batch: the kernel walks the lanes' valid pages
    alone (the NaN page the slots past ``kv_len`` name never reaches a
    result), attends all kv heads of a page at once, and tells the rows
    of a folded causal window apart. This is the test that holds the
    paged kernel and the dense decode math to the same numbers off the
    chip: the fallback is ``_decode_xla`` over the gathered rows."""
    from paddle_tpu.kernels.flash_attention import \
        flash_attention_decode_paged
    rng = np.random.RandomState(7)
    pools, scales, table, clean, kv_len = _ragged_pools(
        rng, quant, sq, window_causal)
    hk, d = pools[0].shape[2], pools[0].shape[-1]
    q = rng.randn(len(kv_len), sq, hk * group, d).astype(np.float32)
    ref = np.asarray(flash_attention_decode_paged(
        jnp.asarray(q), *pools, jnp.asarray(clean), jnp.asarray(kv_len),
        layer, window_causal=window_causal, **scales))
    out = _paged_kernel_interpreted(q, pools, scales, table, kv_len, layer,
                                    window_causal)
    live = kv_len > 0
    assert np.isfinite(out).all()              # no NaN page, no stale block
    assert not out[~live].any()                # nothing held: zeros
    np.testing.assert_allclose(out[live], ref[live], atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("quant", [False, True], ids=["wide", "int8"])
def test_paged_pallas_kernel_head_blocks_and_an_empty_batch(quant,
                                                            monkeypatch):
    """Where a page of all kv heads is too large a copy the kernel walks
    the list once a block of heads (forced here: one head a step); a
    batch that holds nothing runs no grid step at all and is zeros."""
    import importlib
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    flash_attention_decode_paged = fa.flash_attention_decode_paged
    rng = np.random.RandomState(8)
    pools, scales, table, clean, kv_len = _ragged_pools(rng, quant, 4, True)
    q = rng.randn(len(kv_len), 4, 4, 64).astype(np.float32)
    ref = np.asarray(flash_attention_decode_paged(
        jnp.asarray(q), *pools, jnp.asarray(clean), jnp.asarray(kv_len), 1,
        **scales))
    monkeypatch.setattr(fa, "_PAGED_COPY_BYTES", 1)
    assert fa._paged_heads(2, 8, 64, 4) == 1
    out = _paged_kernel_interpreted(q, pools, scales, table, kv_len, 1,
                                    True)
    np.testing.assert_allclose(out[1:], ref[1:], atol=5e-5, rtol=5e-5)
    out = _paged_kernel_interpreted(q, pools, scales, table,
                                    np.zeros_like(kv_len), 1, True)
    assert not out.any()


def test_cached_attention_walks_no_page_for_an_idle_row(monkeypatch):
    """A row of the pool that holds nothing is idle (its write goes to
    the null page): the paged branch of the cached attention hands the
    kernel ``kv_len`` 0 for it, not the window's length, so the kernel
    walks no page for a lane that waits for its next request."""
    import importlib

    import paddle_tpu as paddle
    from paddle_tpu.generation.attention import cached_attention
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    seen = {}
    real = fa.flash_attention_decode_paged

    def spy(q, kp, vp, table, kv_len, layer, **kw):
        seen["kv_len"] = np.asarray(kv_len)
        return real(q, kp, vp, table, kv_len, layer, **kw)

    monkeypatch.setattr(fa, "flash_attention_decode_paged", spy)
    cache = PagedKVCache.create(1, 3, n_pages=5, page_size=8,
                                pages_per_row=2, num_heads=2, head_dim=8)
    cache = PagedKVCache(cache.k, cache.v,
                         jnp.asarray([[1, 2], [0, 0], [3, 0]], jnp.int32),
                         jnp.asarray([9, 0, 4], jnp.int32))
    rng = np.random.RandomState(3)
    q, k, v = (paddle.to_tensor(rng.randn(3, 1, 2, 8).astype(np.float32))
               for _ in range(3))
    out, cache = cached_attention(q, k, v, cache, 0, decode=True,
                                  causal=True)
    assert seen["kv_len"].tolist() == [10, 0, 5]
    assert np.isfinite(out.numpy()).all()


# ------------------------------------------- THE bitwise-parity gate


def test_paged_ragged_traffic_bitwise_equal_dense(tiny_gpt, paged_engine,
                                                  reference):
    """THE acceptance gate: ragged prompts and budgets through the
    PAGED engine — arrivals mid-decode, slot turnover re-anchoring
    reused rows at position 0 (the paged ring-wrap analog), zero
    retraces after warmup — with every request bitwise-equal to the
    dense sequential reference, and the free list conserved."""
    from paddle_tpu.core import monitor
    engine = paged_engine
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
        assert _counter("jit.compile{cause=new_shape}") - ns0 == 0
        assert _counter("jit.compile.total") - tot0 == 0
    finally:
        monitor.disable()

    assert all(h.status is RequestStatus.COMPLETED for h in handles)
    assert engine.stats["slots_reused"] - reused0 >= 3   # turnover hit
    for p, b, h in zip(prompts, budgets, handles):
        np.testing.assert_array_equal(h.result(), reference(p, b))
    engine._alloc.assert_conserved()


def test_mid_decode_eviction_returns_pages(tiny_gpt, paged_engine,
                                           reference):
    """Deadline eviction mid-decode frees the slot AND its pages; the
    next admission reuses them and still decodes bit-for-bit."""
    engine = paged_engine
    used0 = engine._alloc.used_pages()
    slow = engine.submit(np.arange(1, 8, dtype=np.int32),
                         RequestParams(deadline_s=60.0))
    engine.step()                      # admitted
    assert slow.status is RequestStatus.RUNNING
    assert engine._alloc.used_pages() > used0
    slow.deadline = time.monotonic() - 1e-3
    while not slow.done():
        engine.step()
    assert slow.status is RequestStatus.CANCELLED
    assert engine._alloc.used_pages() == used0   # pages back
    engine._alloc.assert_conserved()
    p = np.arange(3, 9, dtype=np.int32)
    nxt = engine.submit(p, RequestParams(max_new_tokens=6))
    np.testing.assert_array_equal(nxt.result(timeout=60),
                                  reference(p, 6))


def test_cow_after_share_divergence(tiny_gpt, paged_engine, reference):
    """Two requests with an identical 20-token prompt (20 % 16 != 0):
    the second references the first's full page and privatizes the
    partial tail (copy-on-write) before its decode writes diverge.
    Both match the dense reference bit-for-bit."""
    from paddle_tpu.core import monitor
    engine = paged_engine
    stats0 = dict(engine._alloc.stats)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 512, 20).astype(np.int32)
    monitor.enable()
    try:
        cow0 = _counter("serve.cache.cow_copies")
        hit0 = _counter("serve.cache.prefix_hits")
        h1 = engine.submit(prompt, RequestParams(max_new_tokens=6))
        while engine.busy:
            engine.step()
        # second arrival AFTER the first finished: its pages are cached
        # in the prefix registry (prefill once, reference many)
        h2 = engine.submit(prompt.copy(), RequestParams(max_new_tokens=8))
        while engine.busy:
            engine.step()
        assert _counter("serve.cache.cow_copies") - cow0 >= 1
        assert _counter("serve.cache.prefix_hits") - hit0 >= 1
    finally:
        monitor.disable()
    s = engine._alloc.stats
    assert s["prefix_hits"] - stats0["prefix_hits"] == 1
    assert s["shared_pages"] - stats0["shared_pages"] == 1
    assert s["cow_copies"] - stats0["cow_copies"] == 1
    np.testing.assert_array_equal(h1.result(), reference(prompt, 6))
    np.testing.assert_array_equal(h2.result(), reference(prompt, 8))
    engine._alloc.assert_conserved()


def test_page_metrics_family(tiny_gpt, paged_engine):
    """serve.cache.* / gen.cache.* land in the registry at the poll
    cadence (the dead-metric lint keeps them recorded; this keeps them
    MOVING)."""
    from paddle_tpu.core import monitor
    from paddle_tpu.profiler import metrics
    engine = paged_engine
    monitor.enable()
    try:
        al0 = _counter("gen.cache.pages_allocated")
        fr0 = _counter("gen.cache.pages_freed")
        hs = [engine.submit(np.arange(1, 6 + i, dtype=np.int32),
                            RequestParams(max_new_tokens=4))
              for i in range(3)]
        while engine.busy:
            engine.step()
        for h in hs:
            h.result(timeout=60)
        assert _counter("gen.cache.pages_allocated") - al0 > 0
        assert _counter("gen.cache.pages_freed") - fr0 > 0
        snap = metrics.snapshot()
        assert snap["serve.cache.page_occupancy"]["peak"] > 0
    finally:
        monitor.disable()


def test_page_blocked_flag_clears_when_head_leaves_queue(tiny_gpt):
    """A page-blocked queue head removed by the deadline sweep must
    clear the pressure flag — health() must not keep steering the
    router toward no_free_pages after the blocker is gone."""
    eng = ServingEngine(_config(tiny_gpt, max_batch=2, paged=True,
                                kv_page_size=16, kv_pages=3,
                                max_queue=4), poll_every=1)
    a = eng.submit(np.arange(1, 16, dtype=np.int32))   # takes both pages
    eng.step()
    late = eng.submit(np.arange(2, 17, dtype=np.int32),
                      RequestParams(deadline_s=60.0))
    eng.step()                                         # blocked on pages
    assert eng.health()["queue_blocked_on"] == "pages"
    late.deadline = time.monotonic() - 1e-3
    eng.step()                                         # sweep cancels it
    assert late.status is RequestStatus.CANCELLED
    assert eng.health()["queue_blocked_on"] is None
    assert a.result(timeout=60).size == 8
    eng._alloc.assert_conserved()
    eng.shutdown()


def test_admission_failure_releases_pages(tiny_gpt):
    """An admission that raises after its page plan committed must roll
    the pages back (no pool shrink, conservation holds) and the engine
    keeps serving."""
    from paddle_tpu.serving import RequestFailed
    eng = ServingEngine(_config(tiny_gpt, max_new=4, max_batch=1,
                                paged=True, kv_page_size=16),
                        poll_every=1)
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
    assert doomed.done() and doomed.status is RequestStatus.CANCELLED
    with pytest.raises(RequestFailed, match="injected device failure"):
        doomed.result(timeout=5)
    assert ok.result(timeout=60).size == 4   # engine kept serving
    assert eng._alloc.used_pages() == 0      # nothing leaked
    eng._alloc.assert_conserved()
    eng.shutdown()


# ----------------------------------------------- speculative windows


def test_speculative_ngram_over_paged_cache(tiny_gpt):
    """ngram speculative decode windows (k+1-token verify writes +
    rollback) over the paged cache: bitwise-equal to the dense
    speculative engine under greedy decoding."""
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 64, n).astype(np.int32)
               for n in (5, 11, 20, 9)]
    outs = []
    for paged in (False, True):
        eng = ServingEngine(
            _config(tiny_gpt, buckets=(16, 32), speculative="ngram",
                    paged=paged, **({"kv_page_size": 16} if paged
                                    else {})),
            poll_every=2)
        hs = [eng.submit(p, RequestParams(max_new_tokens=8))
              for p in prompts]
        while eng.busy:
            eng.step()
        outs.append([h.result(timeout=60) for h in hs])
        if paged:
            eng._alloc.assert_conserved()
        eng.shutdown()
    for o_dense, o_paged in zip(*outs):
        np.testing.assert_array_equal(o_dense, o_paged)


# --------------------------------------------------- admission health


def test_health_distinguishes_pages_from_slots(tiny_gpt):
    """The item-1 router signal: a queue blocked on POOL MEMORY reports
    no_free_pages; one blocked on decode lanes reports no_free_slots."""
    # 3-page pool (2 allocatable): the second request cannot commit
    eng = ServingEngine(_config(tiny_gpt, max_batch=2, paged=True,
                                kv_page_size=16, kv_pages=3,
                                max_queue=2), poll_every=1)
    a = eng.submit(np.arange(1, 16, dtype=np.int32))   # 2 pages
    eng.step()                                         # admit a
    b = eng.submit(np.arange(2, 17, dtype=np.int32))   # blocked on pages
    eng.submit(np.arange(3, 10, dtype=np.int32))       # queue at bound
    eng.step()
    h = eng.health()
    assert h["queue_blocked_on"] == "pages"
    assert not h["ready"] and "no_free_pages" in h["reason"]
    assert h["free_pages"] == 0 and h["total_pages"] == 2
    while eng.busy:
        eng.step()
    assert a.status is RequestStatus.COMPLETED
    assert b.status is RequestStatus.COMPLETED
    eng._alloc.assert_conserved()
    eng.shutdown()

    # dense engine, both slots busy, queue at bound -> slots
    eng2 = ServingEngine(_config(tiny_gpt, max_batch=1, max_queue=1),
                         poll_every=1)
    eng2.submit(np.arange(1, 8, dtype=np.int32))
    eng2.step()
    eng2.submit(np.arange(1, 5, dtype=np.int32))
    h2 = eng2.health()
    assert h2["queue_blocked_on"] == "slots"
    assert not h2["ready"] and "no_free_slots" in h2["reason"]
    while eng2.busy:
        eng2.step()
    eng2.shutdown()


def test_pool_too_small_for_one_request_fails_fast(tiny_gpt):
    """A pool that could never cover one full-size request must raise
    at construction (naming the knobs), not stall the queue head
    forever."""
    with pytest.raises(ValueError, match="kv_pages"):
        ServingEngine(_config(tiny_gpt, max_batch=1, paged=True,
                              kv_page_size=16, kv_pages=2),
                      warmup=False)


# ------------------------------------------------------- tier-1 audit


def test_paged_audit_gate(tiny_gpt):
    """Zero analysis ERRORs across the paged program trio, donation
    coverage 1.0 on decode and admit — the pool and page tables must
    stay in-place across scheduler steps."""
    eng = ServingEngine(_config(tiny_gpt, buckets=(16, 32), paged=True,
                                kv_page_size=16), warmup=False)
    reports = eng.audit()
    assert set(reports) == {("prefill", 16), ("prefill", 32), "decode",
                            "admit", "free", "poll_view"}
    for rep in reports.values():
        rep.raise_on_error()
    assert not reports["decode"].by_check("host_sync")
    assert reports["decode"].donation_coverage == 1.0
    assert reports["admit"].donation_coverage == 1.0


def test_paged_audit_gate_not_vacuous(tiny_gpt):
    """Seeded regression: a host callback smuggled into the PAGED
    decode program must fail the gate — the new programs are held to
    the same zero-ERROR bar, not grandfathered."""
    import jax
    from paddle_tpu.analysis import AuditError
    eng = ServingEngine(_config(tiny_gpt, max_new=4, max_batch=1,
                                paged=True, kv_page_size=16),
                        warmup=False)
    step = eng._programs[("step",)]

    def poisoned(*args):
        cache, lanes, key = step.fn(*args)
        leak = jax.pure_callback(
            lambda t: np.asarray(t),
            jax.ShapeDtypeStruct((1,), jnp.int32), lanes.tok)
        return cache, lanes._replace(tok=lanes.tok + leak * 0), key

    eng._programs[("step",)] = dataclasses.replace(step, fn=poisoned)
    with pytest.raises(AuditError):
        eng.audit()["decode"].raise_on_error()


# ----------------------------------------------------------------- chaos


@pytest.mark.chaos
def test_sigterm_mid_serve_with_shared_pages_conserves(tiny_gpt):
    """SIGTERM mid-serve while rows SHARE prefix pages: the drain
    leaves every handle terminal and the free list conserved — no
    leaked pages, no double frees, shared refcounts fully unwound."""
    import signal
    from paddle_tpu.distributed.resilience import GracefulShutdown
    from paddle_tpu.utils.fault_injection import KillAfter

    eng = ServingEngine(_config(tiny_gpt, buckets=(16, 32), max_batch=2,
                                max_queue=8, paged=True, kv_page_size=16,
                                drain_timeout_s=60.0), poll_every=2)
    rng = np.random.RandomState(1)
    base = rng.randint(0, 512, 20).astype(np.int32)
    # every prompt shares the same 20-token prefix -> live shared pages
    # (and COW tails) at the moment the signal lands
    traffic = [np.concatenate([base, rng.randint(0, 512, i + 1)
                               .astype(np.int32)])[:32]
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
    assert any(h.status is RequestStatus.COMPLETED for h in handles)
    eng._alloc.assert_conserved()
    assert eng._alloc.used_pages() == 0
    with pytest.raises(RuntimeError, match="shut down"):
        eng.submit(traffic[0])


@pytest.mark.parametrize("cache_dtype", [None, "int8"])
def test_heads_of_64_live_in_128_lanes_and_serve_the_dense_result(
        cache_dtype):
    """``pool_head_dim``: a pool of heads of 64 is 128 lanes wide, the
    upper half zero (on the TPU the kernel then reads the pool where it
    lies), and install, token write and decode give bit for bit what
    the dense cache gives."""
    from paddle_tpu.generation.paged_cache import pool_head_dim
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    assert [pool_head_dim(d) for d in (16, 64, 128, 256)] \
        == [16, 128, 128, 256]
    L, B, H, D, ps, P = 2, 3, 2, 64, 8, 4
    rng = np.random.default_rng(0)
    paged = PagedKVCache.create(L, B, 1 + B * P, ps, P, H, D,
                                cache_dtype=cache_dtype)
    assert paged.k.shape == (L, 1 + B * P, H, ps, 128)
    dense = []
    for r, n in enumerate((5, 17, 9)):
        src = KVCache.create(L, 1, ps * P, H, D, cache_dtype=cache_dtype)
        k = jnp.asarray(rng.normal(size=(1, n, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, n, H, D)), jnp.float32)
        for layer in range(L):
            src = src.update(layer, k + layer, v - layer, 0)
        src = src.with_kv_len(n)
        table = jnp.asarray(1 + r * P + np.arange(P), jnp.int32)
        paged = paged.install_row(src, r, table, 0)
        dense.append(src)
    kn = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, 4, D)), jnp.float32)
    paged = paged.update(1, kn, vn, paged.kv_len)
    sc = dict(k_scale=paged.k_scale, v_scale=paged.v_scale) \
        if cache_dtype else {}
    out = fa.flash_attention_decode_paged(
        q, paged.k, paged.v, paged.page_table, paged.kv_len + 1, 1, **sc)
    assert out.shape == (B, 1, 4, D)
    for r, src in enumerate(dense):
        row = src.update(1, kn[r:r + 1], vn[r:r + 1], src.kv_len)
        sc = dict(k_scale=row.k_scale[1], v_scale=row.v_scale[1]) \
            if cache_dtype else {}
        want = fa.flash_attention_decode(q[r:r + 1], row.k[1], row.v[1],
                                         row.kv_len + 1, **sc)
        np.testing.assert_array_equal(np.array(out[r:r + 1]),
                                      np.array(want))
    assert not np.array(paged.k[..., D:]).any()
    assert not np.array(paged.v[..., D:]).any()
