"""The experts' grouped-product kernel (``kernels/grouped_matmul.py``) in
interpret mode on the CPU, at small K and N: equal to
``jax.lax.ragged_dot`` for every group pattern a router can produce, the
same gradients, the work list it walks, the tiles it picks for the
serving cells' shapes, and which path ``dropless_moe`` takes where (the
CPU and an 'ep' axis keep ``ragged_dot``, bit for bit what the parent
commit computed). The chip's compiler sees the kernel at the real widths
in ``tests/test_tpu_compile.py``; times come from
``experiments/grouped_matmul_bench.py`` on the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import metrics, monitor
from paddle_tpu.distributed import topology
from paddle_tpu.distributed.parallel import moe
from paddle_tpu.distributed.parallel.moe import DroplessMoE, dropless_moe
from paddle_tpu.kernels import grouped_matmul as gm
# ``dropless_moe`` as commit 7d48d5f had it, word for word: two
# ``ragged_dot`` calls
from tests.test_lfm2 import _softmax_moe_as_it_was as _moe_as_the_parent_had_it

K, N = 128, 256
# name -> (rows, group sizes); the row tile is 128 at 256 rows and more
PATTERNS = {
    "balanced": (256, [64, 64, 64, 64]),
    "skewed_2.3x": (512, [147, 40, 70, 31, 64, 50, 60, 50]),
    "all_rows_in_one_group": (256, [0, 0, 256, 0]),
    "most_groups_empty": (256, [0] * 5 + [200] + [0] * 9 + [56]),
    "boundary_on_a_tiles_edge": (384, [128, 100, 28, 128]),
    "boundary_off_a_tiles_edge": (384, [127, 2, 200, 55]),
    "first_and_last_group_empty": (256, [0, 100, 156, 0]),
    "one_row_groups": (128, [1] * 16 + [112]),
    "fewer_rows_than_a_tile": (64, [1, 2, 3, 58]),
    "rows_past_the_groups_are_zero": (384, [10, 20, 30, 40]),
}


def _operands(rows, groups, dtype=jnp.float32, seed=0):
    k0, k1 = jax.random.split(jax.random.PRNGKey(seed))
    lhs = jax.random.normal(k0, (rows, K), jnp.float32).astype(dtype)
    rhs = jax.random.normal(k1, (groups, K, N), jnp.float32).astype(dtype)
    return lhs, rhs


def _ragged(lhs, rhs, sizes):
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_equals_ragged_dot(pattern, dtype):
    rows, sizes = PATTERNS[pattern]
    lhs, rhs = _operands(rows, len(sizes), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(gm.grouped_matmul)(lhs, rhs, sizes)
    assert got.dtype == jnp.float32 and got.shape == (rows, N)
    np.testing.assert_allclose(np.array(got), np.array(_ragged(lhs, rhs,
                                                               sizes)),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("pattern", ["skewed_2.3x", "most_groups_empty",
                                     "boundary_off_a_tiles_edge"])
def test_gradients_are_ragged_dots(pattern):
    rows, sizes = PATTERNS[pattern]
    lhs, rhs = _operands(rows, len(sizes))
    sizes = jnp.asarray(sizes, jnp.int32)
    weight = jax.random.normal(jax.random.PRNGKey(3), (rows, N))

    def loss(product):
        return lambda a, b: jnp.sum(weight * product(a, b, sizes) ** 2)

    got = jax.jit(jax.grad(loss(gm.grouped_matmul), (0, 1)))(lhs, rhs)
    want = jax.jit(jax.grad(loss(_ragged), (0, 1)))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.array(g), np.array(w), rtol=1e-4,
                                   atol=1e-3)


@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_work_list_visits_every_row_once_and_no_empty_group(pattern):
    rows, sizes = PATTERNS[pattern]
    tm = gm.row_tile(rows)
    group, tile, start, end, count = map(
        np.array, gm.work_list(jnp.asarray(sizes, jnp.int32), rows, tm))
    e = len(sizes)
    assert len(group) == rows // tm + e and count <= len(group)
    group, tile = group[:count], tile[:count]
    covered = np.zeros(rows, int)
    for g, t in zip(group, tile):
        lo, hi = max(start[g], t * tm), min(end[g], (t + 1) * tm)
        if g < e:
            assert sizes[g] > 0 and hi > lo     # a visit holds a row
            covered[lo:hi] += 1
    assert (covered[:sum(sizes)] == 1).all() and not covered[sum(sizes):].any()
    # in row order: a tile's visits are consecutive (its block stays in
    # VMEM), a group's too (its weights are fetched once); every tile is
    # visited, the ones past the groups by the tail that owns no row
    assert (np.diff(tile) >= 0).all() and (np.diff(group) >= 0).all()
    assert set(tile) == set(range(rows // tm))
    assert count == sum(
        (sum(sizes[:g + 1]) - 1) // tm - sum(sizes[:g]) // tm + 1
        for g in range(e) if sizes[g]) + (
        (rows - 1) // tm - sum(sizes) // tm + 1 if sum(sizes) < rows else 0)


# (rows, K, N) -> (row tile, column tile): both products of both MoE
# cells at the decode step's rows and at the four prefill buckets
@pytest.mark.parametrize("rows", [512, 1024, 2048, 4096, 8192])
@pytest.mark.parametrize("k,n,tn", [
    (2048, 1536, 1536), (768, 2048, 2048),      # sdar: gate+up, down
    (2048, 3584, 1792), (1792, 2048, 2048)])    # lfm2: gate+up, down
def test_tiles_at_the_cells_shapes(rows, k, n, tn):
    assert gm.supports(rows, k, n)
    assert gm.row_tile(rows) == 128 and gm.col_tile(k, n) == tn
    assert 2 << 20 <= k * tn * 2 <= 8 << 20     # a copy of 2-8 MB a visit


# the 1856-wide ungated experts of a 2688-wide model are stored in 1920
# columns (15 lane tiles; ``DroplessMoE(pad_to=128)``): up 2688 x 1920 in
# three blocks of 640, down 1920 x 2688 in three of 896, 3.4 MB a visit;
# at the step's 1,536 rows and the four prefill buckets' 768-6,144
@pytest.mark.parametrize("rows", [1536, 768, 3072, 6144])
@pytest.mark.parametrize("k,n,tn", [(2688, 1920, 640), (1920, 2688, 896)])
def test_tiles_at_the_padded_1856_wide_experts(rows, k, n, tn):
    assert gm.supports(rows, k, n)
    assert gm.row_tile(rows) == 128 and gm.col_tile(k, n) == tn
    assert 2 << 20 <= k * tn * 2 <= 8 << 20
    # the published width itself is whole sublane tiles, not whole lanes
    assert not gm.supports(rows, 2688, 1856)
    assert not gm.supports(rows, 1856, 2688)


@pytest.mark.parametrize("rows,k,n", [
    (200, 128, 128),        # 200 rows are no whole tiles of 128
    (72, 128, 128),         # 72 rows are no whole bf16 sublane tiles
    (256, 96, 128), (256, 128, 192),    # K or N off the lanes
    (256, 1 << 16, 128)])   # 128 columns of such a K are 16 MB
def test_shapes_the_kernel_leaves_to_ragged_dot(rows, k, n):
    assert not gm.supports(rows, k, n)
    with pytest.raises(ValueError, match="supports"):
        gm.grouped_matmul(jnp.zeros((rows, k), jnp.bfloat16),
                          jnp.zeros((2, k, n), jnp.bfloat16),
                          jnp.array([rows, 0], jnp.int32))


# ---- which product dropless_moe runs, and that the CPU's is the parent's

def _mesh(**axes):
    n = int(np.prod(list(axes.values())))
    return topology.create_mesh(axes, jax.devices()[:n])


def _layer_avals(rows, h=128, f=128, e=4, dtype=jnp.bfloat16):
    sds = jax.ShapeDtypeStruct
    return (sds((rows, h), dtype), sds((e, h, 2 * f), dtype),
            sds((e, f, h), dtype))


@pytest.mark.parametrize("case,rows,kw,kernel", [
    ("tpu_no_mesh", 256, dict(backend="tpu"), True),
    ("tpu_fewer_rows_than_a_tile", 32, dict(backend="tpu"), True),
    ("cpu", 256, dict(backend="cpu"), False),
    ("tpu_ep_axis_of_two", 256, dict(backend="tpu", mesh=("ep", 2)), False),
    ("tpu_ep_axis_of_one", 256, dict(backend="tpu", mesh=("ep", 1)), True),
    ("tpu_data_parallel_mesh", 256, dict(backend="tpu", mesh=("dp", 2)),
     True),
    ("tpu_rows_off_the_tile", 200, dict(backend="tpu"), False),
    ("tpu_width_off_the_lanes", 256, dict(backend="tpu", h=96), False),
    ("tpu_expert_width_off_the_lanes", 256, dict(backend="tpu", f=64),
     False),
    ("tpu_float32", 256, dict(backend="tpu", dtype=jnp.float32), False),
])
def test_grouped_product_is_chosen_by_what_the_code_sees(case, rows, kw,
                                                         kernel):
    kw = dict(kw)
    mesh = kw.pop("mesh", None)
    backend = kw.pop("backend")
    product = moe.grouped_product(
        *_layer_avals(rows, **kw), backend=backend,
        mesh=_mesh(**{mesh[0]: mesh[1]}) if mesh else None)
    assert product is (gm.grouped_matmul if kernel else gm.ragged_dot)


def _layer_case(tokens, h, f, e, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (tokens, h), jnp.float32).astype(dtype),
            jax.random.normal(ks[1], (h, e), jnp.float32) * 0.5,
            (jax.random.normal(ks[2], (e, h, 2 * f)) * 0.1).astype(dtype),
            (jax.random.normal(ks[3], (e, f, h)) * 0.1).astype(dtype))


def _gauges():
    return {k: metrics.gauge(k).value for k in
            ("moe.grouped_kernel_layers", "moe.ragged_dot_layers")}


@pytest.fixture
def _no_mesh_after():
    prev = topology.get_hybrid_communicate_group()
    yield
    topology.set_hybrid_communicate_group(prev)


# shapes the kernel would take on a TPU (128 rows of 128 x 256) and
# shapes it would not: on the CPU both are the parent's ragged_dot
@pytest.mark.parametrize("mesh", [None, "ep"], ids=["no_mesh", "ep_mesh"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("tokens,h,f", [(64, 128, 128), (25, 48, 24)],
                         ids=["on_the_tiles", "off_the_tiles"])
def test_on_the_cpu_dropless_moe_is_bit_equal_to_the_parents(
        tokens, h, f, dtype, mesh, _no_mesh_after):
    if mesh:
        topology.set_hybrid_communicate_group(
            topology.HybridCommunicateGroup(ep_degree=2))
        assert dict(topology.get_mesh().shape)["ep"] == 2
    operands = _layer_case(tokens, h, f, 4, dtype)
    monitor.enable()
    try:
        before = _gauges()
        got, rows = jax.jit(lambda *a: dropless_moe(*a, 2))(*operands)
        after = _gauges()
    finally:
        monitor.disable()
    want, rows_w = jax.jit(
        lambda *a: _moe_as_the_parent_had_it(*a, 2))(*operands)
    np.testing.assert_array_equal(np.array(got, np.float32),
                                  np.array(want, np.float32))
    np.testing.assert_array_equal(np.array(rows), np.array(rows_w))
    assert after["moe.ragged_dot_layers"] \
        == before["moe.ragged_dot_layers"] + 1
    assert after["moe.grouped_kernel_layers"] \
        == before["moe.grouped_kernel_layers"]


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The TPU's choice of product on the CPU: the kernel, interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gm, "_interpret", lambda: True)


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
@pytest.mark.parametrize("tokens,experts", [(64, 8), (256, 4), (8, 8)],
                         ids=["128_rows", "512_rows", "16_rows"])
def test_dropless_moe_through_the_kernel(as_on_tpu, tokens, experts,
                                         router):
    dtype = jnp.bfloat16
    x, wr, wgu, wd = _layer_case(tokens, 128, 128, experts, dtype, seed=1)
    monitor.enable()
    try:
        before = _gauges()
        got, rows = jax.jit(lambda *a: dropless_moe(
            *a, 2, router=router))(x, wr, wgu, wd)
        after = _gauges()
    finally:
        monitor.disable()
    assert after["moe.grouped_kernel_layers"] \
        == before["moe.grouped_kernel_layers"] + 1
    assert after["moe.ragged_dot_layers"] == before["moe.ragged_dot_layers"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "grouped_product", lambda *a, **k: gm.ragged_dot)
        want, rows_w = jax.jit(lambda *a: dropless_moe(
            *a, 2, router=router))(x, wr, wgu, wd)
    np.testing.assert_array_equal(np.array(rows), np.array(rows_w))
    tol = 2e-2
    np.testing.assert_allclose(np.array(got, np.float32),
                               np.array(want, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("held", [None, (4, 4)], ids=["whole", "held"])
def test_ungated_experts_stored_padded_take_the_kernel(as_on_tpu, held):
    """A width off the lane tile (the 1856 of 2688 x 1856, here 72 of
    128 x 72) falls back to ``ragged_dot``; stored padded to whole tiles
    it takes the kernel, and padding with zeros changes nothing. Rows of
    experts held elsewhere are the kernel's zero tail."""
    dtype, e, h, f, fs = jnp.bfloat16, 8, 128, 72, 128
    here = e if held is None else held[1]
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (64, h), jnp.float32).astype(dtype)
    wr = jax.random.normal(ks[1], (h, e), jnp.float32) * 0.3
    up = (jax.random.normal(ks[2], (here, h, f)) * 0.1).astype(dtype)
    down = (jax.random.normal(ks[3], (here, f, h)) * 0.1).astype(dtype)
    kw = dict(gated=False, held=held, norm_eps=1e-20)
    run = lambda u, d: jax.jit(lambda *a: dropless_moe(  # noqa: E731
        *a, 2, True, "sigmoid", None, 2.5, **kw))(x, wr, u, d)
    assert moe.grouped_product(x[:1].repeat(128, 0), up, down) \
        is gm.ragged_dot
    want, rows_w = run(up, down)
    up_s = jnp.pad(up, ((0, 0), (0, 0), (0, fs - f)))
    down_s = jnp.pad(down, ((0, 0), (0, fs - f), (0, 0)))
    assert moe.grouped_product(x[:1].repeat(128, 0), up_s, down_s) \
        is gm.grouped_matmul
    got, rows = run(up_s, down_s)
    np.testing.assert_array_equal(np.array(rows), np.array(rows_w))
    assert int(np.array(rows).sum()) == 128
    np.testing.assert_allclose(np.array(got, np.float32),
                               np.array(want, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_layer_trains_through_the_kernel(as_on_tpu):
    """Eager forward and backward of a bfloat16 ``DroplessMoE`` on the
    kernel's path: the gradients of every parameter are the
    ``ragged_dot`` path's (the backward IS ``ragged_dot``'s; the forward
    differs by float32 summation order before the bfloat16 cast)."""
    import paddle_tpu as paddle

    def grads(through_kernel):
        paddle.seed(7)
        layer = DroplessMoE(128, 128, 4, 2, std=0.1, dtype="bfloat16")
        x = paddle.to_tensor(np.random.RandomState(0).standard_normal(
            (64, 128)).astype(np.float32)).astype("bfloat16")
        with pytest.MonkeyPatch.context() as mp:
            if not through_kernel:
                mp.setattr(moe, "grouped_product",
                           lambda *a, **k: gm.ragged_dot)
            before = _gauges()
            monitor.enable()
            try:
                (layer(x).astype("float32") ** 2).sum().backward()
            finally:
                monitor.disable()
            took = _gauges()["moe.grouped_kernel_layers"] \
                - before["moe.grouped_kernel_layers"]
        assert took == (1 if through_kernel else 0)
        return [np.array(p.grad.astype("float32").numpy())
                for p in layer.parameters()]

    for got, want in zip(grads(True), grads(False)):
        assert np.abs(want).max() > 0
        np.testing.assert_allclose(got, want, rtol=5e-2,
                                   atol=2e-2 * np.abs(want).max())
