"""The page pool's write kernel (``kernels/paged_write.py``) in interpret
mode on the CPU, called directly: the pool it leaves equals the pool
``paged_cache._scatter_tokens`` leaves bit for bit on every page but the
null page, for every window, idle pattern and shape a serving step can
hand it; which path ``PagedKVCache.update`` takes where (the CPU, the
int8 pool and few rows a lane keep the scatter) and the two gauges that
say so. The chip's compiler sees the kernel at the cells' widths in
``tests/test_tpu_compile.py``; times come from
``experiments/kv_write_bench.py`` on the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import metrics, monitor
from paddle_tpu.generation.paged_cache import (PagedKVCache, _scatter_tokens,
                                               _to_pool_width)
from paddle_tpu.kernels import paged_write as pw

LAYERS, SLOTS = 3, 3
IDLE = {
    "none_idle": lambda n: np.zeros(n, bool),
    "all_idle": lambda n: np.ones(n, bool),
    "every_other_idle": lambda n: np.arange(n) % 2 == 1,
    "first_and_last_idle": lambda n: np.isin(np.arange(n), (0, n - 1)),
}


def windows(s, page_size, tile):
    """Where a lane's window of ``s`` positions starts, one lane a
    situation: the row's first free position, inside a tile, up to a
    tile's last row, across a tile's edge, across a page's edge (twice),
    up to the table's last slot, across the table's end, past it."""
    top = SLOTS * page_size
    return [1, tile + 2, 2 * tile - s, tile - 1, page_size - 1,
            2 * page_size - (s + 1) // 2, top - s, top - s // 2 - 1,
            top - 1, top]


def pool_and_rows(s, heads, d, page_size, dtype, idle):
    """A cache whose lanes own ``SLOTS`` shuffled pages each, the new
    rows, and the rows' destinations as ``update`` computes them."""
    starts = windows(s, page_size, pw.sublane_tile(dtype))
    lanes = len(starts)
    keys = jax.random.split(jax.random.PRNGKey(s + heads + d + page_size), 5)
    cache = PagedKVCache.create(LAYERS, lanes, 1 + lanes * SLOTS, page_size,
                                SLOTS, heads, d, dtype)
    table = 1 + jax.random.permutation(keys[0], lanes * SLOTS)
    kv_len = np.where(IDLE[idle](lanes), 0, starts)
    cache = PagedKVCache(
        jax.random.normal(keys[1], cache.k.shape).astype(dtype),
        jax.random.normal(keys[2], cache.v.shape).astype(dtype),
        table.reshape(lanes, SLOTS).astype(jnp.int32),
        jnp.asarray(kv_len, jnp.int32))
    new = [jax.random.normal(key, (lanes, s, heads, d)).astype(dtype)
           for key in keys[3:]]
    return cache, new, cache._token_dest(cache.kv_len, lanes, s)


def bits(x):
    """The array's bits: NaNs and signed zeros compare as what they are."""
    x = np.asarray(x)
    return x.view(np.uint16 if x.dtype.itemsize == 2 else np.uint32)


CASES = [
    # s, kv heads, head_dim, page_size, dtype, layer, idle lanes
    (1, 32, 128, 16, jnp.bfloat16, 0, "none_idle"),
    (1, 2, 64, 128, jnp.bfloat16, LAYERS - 1, "every_other_idle"),
    (1, 8, 128, 16, jnp.float32, LAYERS - 1, "all_idle"),
    (1, 4, 64, 16, jnp.float32, 0, "first_and_last_idle"),
    (1, 32, 128, 128, jnp.bfloat16, LAYERS - 1, "first_and_last_idle"),
    (4, 4, 128, 128, jnp.bfloat16, LAYERS - 1, "none_idle"),
    (4, 8, 64, 16, jnp.float32, 0, "first_and_last_idle"),
    (4, 32, 128, 16, jnp.bfloat16, 0, "all_idle"),
    (4, 2, 128, 128, jnp.float32, LAYERS - 1, "every_other_idle"),
    (4, 8, 64, 16, jnp.bfloat16, 1, "none_idle"),
    (5, 2, 128, 16, jnp.bfloat16, LAYERS - 1, "none_idle"),
    (5, 4, 128, 128, jnp.float32, 0, "every_other_idle"),
    (5, 8, 64, 128, jnp.bfloat16, 0, "first_and_last_idle"),
    (5, 32, 64, 16, jnp.float32, LAYERS - 1, "all_idle"),
    (5, 4, 64, 16, jnp.float32, 1, "none_idle"),
    (8, 8, 128, 16, jnp.bfloat16, 1, "every_other_idle"),
]


@pytest.mark.parametrize(
    "s,heads,d,page_size,dtype,layer,idle", CASES,
    ids=[f"s{c[0]}-h{c[1]}-d{c[2]}-page{c[3]}-{jnp.dtype(c[4]).name}-"
         f"layer{c[5]}-{c[6]}" for c in CASES])
def test_equals_the_scatter_off_the_null_page(s, heads, d, page_size, dtype,
                                              layer, idle):
    cache, (k_new, v_new), (page, off) = pool_and_rows(
        s, heads, d, page_size, dtype, idle)
    want = [_scatter_tokens(buf, layer, page, off, new)
            for buf, new in ((cache.k, k_new), (cache.v, v_new))]
    got = pw.paged_kv_write(
        cache.k, cache.v, layer, page, off, _to_pool_width(k_new, cache.k),
        _to_pool_width(v_new, cache.v))
    for g, w, was in zip(got, want, (cache.k, cache.v)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(bits(g)[:, 1:], bits(w)[:, 1:])
        # the null page is not written at all, nor any other layer
        np.testing.assert_array_equal(bits(g)[:, 0], bits(was)[:, 0])
        others = [i for i in range(LAYERS) if i != layer]
        np.testing.assert_array_equal(bits(g)[others], bits(was)[others])
    if idle != "all_idle":      # the case writes something
        assert not np.array_equal(bits(got[0])[layer], bits(cache.k)[layer])


def test_a_longer_window_than_a_tile_is_refused():
    cache, (k_new, v_new), (page, off) = pool_and_rows(
        9, 4, 128, 16, jnp.float32, "none_idle")
    with pytest.raises(ValueError, match="supports"):
        pw.paged_kv_write(cache.k, cache.v, 0, page, off, k_new, v_new)


@pytest.mark.parametrize("live,src,act", [
    ([1, 0, 1, 1, 0, 0], [0, 0, 2, 3, 3, 3], [1, 0, 1, 1, 0, 0]),
    ([0, 0, 1, 0, 1, 0], [2, 2, 2, 2, 4, 4], [0, 0, 1, 0, 1, 0]),
    ([0, 0, 0, 0], [0, 0, 0, 0], [2, 0, 0, 0]),
    ([1, 1, 1], [0, 1, 2], [1, 1, 1]),
], ids=["gaps", "leading_idle", "none_live", "all_live"])
def test_idle_visits_stay_on_a_live_visits_block(live, src, act):
    got_src, got_act = pw._visits(jnp.asarray(live, bool))
    assert got_src.tolist() == src and got_act.tolist() == act


POOL = (8, 65, 32, 128, 128)


@pytest.mark.parametrize("shape,dtype,s,backend,takes", [
    (POOL, jnp.bfloat16, 1, "tpu", True),
    (POOL, jnp.float32, 8, "tpu", True),
    (POOL, jnp.bfloat16, 16, "tpu", True),
    (POOL, jnp.bfloat16, 1, "cpu", False),         # not a TPU
    (POOL, jnp.int8, 1, "tpu", False),             # the int8 pool
    (POOL, jnp.bfloat16, 17, "tpu", False),        # longer than a tile
    (POOL, jnp.float32, 9, "tpu", False),
    ((8, 65, 32, 24, 128), jnp.bfloat16, 1, "tpu", False),  # ragged pages
    ((8, 65, 32, 128, 96), jnp.bfloat16, 1, "tpu", False),  # ragged lanes
    ((6, 65, 4, 128, 128), jnp.bfloat16, 4, "tpu", True),   # 16 rows a lane
    ((3, 65, 8, 128, 128), jnp.bfloat16, 1, "tpu", True),   # 8 rows a lane
    ((3, 65, 2, 128, 128), jnp.bfloat16, 2, "tpu", True),   # 4 rows a lane
    ((2, 65, 2, 128, 128), jnp.bfloat16, 1, "tpu", False),  # 2 rows a lane
])
def test_supports_reads_backend_dtype_and_shapes(monkeypatch, shape, dtype,
                                                 s, backend, takes):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert pw.supports(shape, dtype, s) is takes


def _gauges():
    return {k: metrics.gauge(k).value
            for k in ("kv.write_kernel_layers", "kv.write_scatter_layers")}


@pytest.fixture
def monitored():
    monitor.enable()
    yield
    monitor.disable()


def _traced_update(cache, s, heads, d):
    """``update`` traced (never run) for ``s`` new positions a lane."""
    new = jnp.zeros((cache.batch, s, heads, d), jnp.bfloat16)
    return str(jax.make_jaxpr(
        lambda c, k, v: c.update(1, k, v, c.kv_len))(cache, new, new))


def test_on_the_cpu_update_takes_the_scatter(monitored):
    cache, (k_new, v_new), _ = pool_and_rows(1, 32, 128, 16, jnp.bfloat16,
                                             "none_idle")
    before = _gauges()
    assert "pallas_call" not in _traced_update(cache, 1, 32, 128)
    after = _gauges()
    assert after["kv.write_scatter_layers"] \
        == before["kv.write_scatter_layers"] + 1
    assert after["kv.write_kernel_layers"] == before["kv.write_kernel_layers"]


@pytest.mark.parametrize("heads,s,kernel", [
    (32, 1, True), (4, 4, True), (8, 1, True), (4, 1, True), (2, 1, False),
    (1, 2, False)], ids=["32x1", "4x4", "8x1", "4x1", "2x1", "1x2"])
def test_on_a_tpu_update_chooses_by_the_rows_a_lane(monkeypatch, monitored,
                                                    heads, s, kernel):
    cache = PagedKVCache.create(2, 4, 9, 128, 2, heads, 128, jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = _gauges()
    text = _traced_update(cache, s, heads, 128)
    after = _gauges()
    assert ("paged_kv_write" in text) is kernel
    assert ("pallas_call" in text) is kernel
    took = {k: after[k] - before[k] for k in after}
    assert took == {"kv.write_kernel_layers": int(kernel),
                    "kv.write_scatter_layers": int(not kernel)}


def test_the_int8_pool_never_takes_the_kernel(monkeypatch, monitored):
    cache = PagedKVCache.create(2, 4, 9, 128, 2, 32, 128, jnp.bfloat16,
                                cache_dtype="int8")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = _gauges()
    text = _traced_update(cache, 1, 32, 128)
    after = _gauges()
    assert "pallas_call" not in text and "scatter" in text
    assert after["kv.write_scatter_layers"] \
        == before["kv.write_scatter_layers"] + 1
    assert after["kv.write_kernel_layers"] == before["kv.write_kernel_layers"]


@pytest.mark.parametrize("s", [1, 4], ids=["s1", "s4"])
def test_update_through_the_kernel_equals_update_through_the_scatter(
        monkeypatch, s):
    """``PagedKVCache.update`` itself on both paths (the kernel
    interpreted): the same pool off the null page, ``kv_len`` and the
    table untouched, heads of 64 padded to the pool's lanes."""
    cache, (k_new, v_new), _ = pool_and_rows(s, 16, 64, 16, jnp.bfloat16,
                                             "every_other_idle")
    want = cache.update(1, k_new, v_new, cache.kv_len)
    monkeypatch.setattr(pw, "supports", lambda *a: True)
    got = cache.update(1, k_new, v_new, cache.kv_len)
    for g, w in ((got.k, want.k), (got.v, want.v)):
        np.testing.assert_array_equal(bits(g)[:, 1:], bits(w)[:, 1:])
    assert got.kv_len is cache.kv_len and got.page_table is cache.page_table
