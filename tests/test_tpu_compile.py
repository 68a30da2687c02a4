"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached (on-chip-measurement guide, section 2).
Interpret mode accepts block shapes the chip's compiler front end
refuses (a 1-row block of a taller array, a (1, 1) SMEM block), so every
kernel of the training and serving path is compiled here at GPT-2 small
widths (12 heads of 64) and at gpt3-6.7b widths (heads of 128), and the
compiled text must hold the kernel (``tpu_custom_call``).

Nothing runs: this guards lowering, tiling and VMEM, not results. The
results are checked on the chip by ``chip_smoke.py``.

The topology is described inside a module-scoped fixture — never at
import, in a ``skipif`` or in a ``parametrize`` argument — so that under
xdist only the worker that is given this file loads the TPU library.
"""
import importlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import topology

from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels import paged_write as pw

# the module, not the function of the same name that kernels/ exports
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

KERNEL = "tpu_custom_call"
BF16 = jnp.bfloat16
# (head_dim, heads): GPT-2 small, gpt3-6.7b
WIDTHS = [pytest.param(64, 12, id="d64"), pytest.param(128, 32, id="d128")]
CACHE_LEN = 1024
PAGE = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Steer the code onto its TPU branches: real kernels instead of
    interpret mode, and the Pallas path instead of the XLA reference."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(pw, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def compiled_text(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()


def attention_loss(q, k, v):
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                         training=False)
    return out._data.astype(jnp.float32).sum()


@pytest.mark.parametrize("d,heads,batch,seq", [
    pytest.param(64, 12, 16, 1024, id="d64-s1024"),
    pytest.param(64, 12, 8, 2048, id="d64-s2048"),
    pytest.param(128, 32, 2, 1024, id="d128-s1024"),
    pytest.param(128, 32, 1, 2048, id="d128-s2048"),
])
def test_training_flash_fwd_bwd(one_chip, as_on_tpu, d, heads, batch, seq):
    a = jax.ShapeDtypeStruct((batch, seq, heads, d), BF16,
                             sharding=one_chip)
    text = compiled_text(jax.grad(attention_loss, argnums=(0, 1, 2)),
                         a, a, a)
    assert text.count(KERNEL) >= 2   # forward and backward


def test_training_flash_on_a_mesh(topo, as_on_tpu):
    """XLA cannot partition a Mosaic kernel: under the hybrid mesh the
    flash call is a shard_map (batch over sharding, heads over mp)."""
    hcg = topology.HybridCommunicateGroup(
        mp_degree=2, sharding_degree=2, devices=list(topo.devices))
    topology.set_hybrid_communicate_group(hcg)
    try:
        sh = NamedSharding(hcg.mesh, P(("dp", "sharding"), None, "mp"))
        a = jax.ShapeDtypeStruct((16, 1024, 12, 64), BF16, sharding=sh)
        text = compiled_text(jax.grad(attention_loss, argnums=(0, 1, 2)),
                             a, a, a)
    finally:
        topology.set_hybrid_communicate_group(None)
    assert text.count(KERNEL) >= 2


def cache_avals(one_chip, d, heads, quant, batch=8):
    """(k/v cache, [scales]) of one layer of a dense KV cache."""
    kv = jax.ShapeDtypeStruct((batch, CACHE_LEN, heads, d),
                              jnp.int8 if quant else BF16,
                              sharding=one_chip)
    scale = jax.ShapeDtypeStruct((batch, CACHE_LEN, heads), BF16,
                                 sharding=one_chip)
    kv_len = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    return kv, ([scale, scale] if quant else []), kv_len


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("q_len", [1, 5], ids=["q1", "q5"])
@pytest.mark.parametrize("d,heads", WIDTHS)
def test_dense_decode(one_chip, as_on_tpu, d, heads, q_len, quant):
    kv, scales, kv_len = cache_avals(one_chip, d, heads, quant)
    q = jax.ShapeDtypeStruct((8, q_len, heads, d), BF16, sharding=one_chip)

    def fn(q, k, v, kv_len, *sc):
        return fa.flash_attention_decode(
            q, k, v, kv_len,
            **(dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}))

    assert KERNEL in compiled_text(fn, q, kv, kv, kv_len, *scales)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("q_len", [128, 512, 40], ids=["q128", "q512",
                                                       "q40"])
@pytest.mark.parametrize("d,heads", WIDTHS)
def test_chunk_prefill(one_chip, as_on_tpu, d, heads, q_len, quant):
    kv, scales, kv_len = cache_avals(one_chip, d, heads, quant, batch=1)
    q = jax.ShapeDtypeStruct((1, q_len, heads, d), BF16, sharding=one_chip)

    def fn(q, k, v, kv_len, *sc):
        return fa.flash_attention_chunk(
            q, k, v, kv_len,
            **(dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}))

    assert KERNEL in compiled_text(fn, q, kv, kv, kv_len, *scales)


def pool_avals(one_chip, d, heads, quant, layers, batch):
    """(PagedKVCache of avals, sds): ``layers`` stacked layers of a
    page pool that holds ``batch`` full rows and the null page."""
    from paddle_tpu.generation.paged_cache import (PagedKVCache,
                                                   QuantPagedKVCache)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    slots = CACHE_LEN // PAGE
    shape = (layers, batch * slots + 1, heads, PAGE, d)
    pool = sds(shape, jnp.int8 if quant else BF16)
    table, kv_len = sds((batch, slots), jnp.int32), sds((batch,), jnp.int32)
    if not quant:
        return PagedKVCache(pool, pool, table, kv_len), sds
    scale = sds(shape[:-1], BF16)
    return QuantPagedKVCache(pool, pool, table, kv_len, scale, scale,
                             sds((), jnp.int32)), sds


def paged_scales(cache):
    if cache.cache_dtype is None:
        return {}
    return dict(k_scale=cache.k_scale, v_scale=cache.v_scale)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("d,heads", WIDTHS)
def test_paged_decode(one_chip, as_on_tpu, d, heads, quant):
    cache, sds = pool_avals(one_chip, d, heads, quant, layers=2, batch=8)

    def fn(q, cache):
        return fa.flash_attention_decode_paged(
            q, cache.k, cache.v, cache.page_table, cache.kv_len, 1,
            **paged_scales(cache))

    assert KERNEL in compiled_text(fn, sds((8, 1, heads, d), BF16), cache)


# the serve cells' own shapes: (lanes, window, query / kv heads of 128,
# pages, layers, window_causal), 16 table slots a lane
CELL_SHAPES = [
    pytest.param(64, 1, 32, 32, 512, 8, True, id="gpt3l8"),
    pytest.param(128, 4, 32, 4, 1024, 6, False, id="sdar-l6"),
]


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("lanes,sq,hq,hk,pages,layers,window_causal",
                         CELL_SHAPES)
def test_paged_decode_at_the_cells_shapes(one_chip, lanes, sq, hq, hk, pages,
                                          layers, window_causal, quant,
                                          as_on_tpu):
    """The walk over the valid pages at the size the cells run it: a
    page of all kv heads a step (1 MB of K at 32 heads, 128 KB at 4),
    the last layer of the stacked pool, and no temporary worth naming
    beside it (the work list and the padded query rows)."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    pool = sds((layers, pages, hk, PAGE, 128), jnp.int8 if quant else BF16)
    scale = sds((layers, pages, hk, PAGE), BF16)

    def fn(q, k, v, table, kv_len, *sc):
        return fa.flash_attention_decode_paged(
            q, k, v, table, kv_len, layers - 1, window_causal=window_causal,
            **(dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}))

    compiled = jax.jit(fn).lower(
        sds((lanes, sq, hq, 128), BF16), pool, pool,
        sds((lanes, 16), jnp.int32), sds((lanes,), jnp.int32),
        *([scale, scale] if quant else [])).compile()
    assert "flash_decode_paged" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


# ---- block diffusion at the published widths of its cell: 32 query
# heads over 4 kv heads of 128, blocks of 4, 128 experts of 768 top-8

def test_block_causal_prefill_kernel(one_chip, as_on_tpu):
    sds = lambda shape: jax.ShapeDtypeStruct(shape, BF16,  # noqa: E731
                                             sharding=one_chip)

    def fn(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block=4)

    text = compiled_text(fn, sds((1, 1024, 32, 128)), sds((1, 1024, 4, 128)),
                         sds((1, 1024, 4, 128)))
    assert KERNEL in text and "flash_fwd" in text


def test_full_window_paged_decode_stacks_the_group(one_chip, as_on_tpu):
    """A block of 4 queries under ``window_causal=False``: the 8 query
    heads of a kv head share the 32 query rows of its tile, and a
    lane's 4 kv heads one block: the kernel's q is [batch, 4, 32, 128]."""
    cache, sds = pool_avals(one_chip, 128, 4, False, layers=2, batch=8)

    def fn(q, cache):
        return fa.flash_attention_decode_paged(
            q, cache.k, cache.v, cache.page_table, cache.kv_len, 1,
            window_causal=False)

    text = compiled_text(fn, sds((8, 4, 32, 128), BF16), cache)
    assert KERNEL in text and "flash_decode_paged" in text
    assert "bf16[8,4,32,128]" in text       # [batch, kv heads, 8 x 4, d]


def test_grouped_expert_products(one_chip, as_on_tpu):
    from paddle_tpu.distributed.parallel.moe import dropless_moe
    sds = lambda shape: jax.ShapeDtypeStruct(shape, BF16,  # noqa: E731
                                             sharding=one_chip)

    def fn(x, router, gate_up, down):
        return dropless_moe(x, router, gate_up, down, 8)

    text = compiled_text(fn, sds((512, 2048)), sds((2048, 128)),
                         sds((128, 2048, 1536)), sds((128, 768, 2048)))
    # the repo's grouped-product kernel, once for gate+up and once for
    # down, and XLA's own op nowhere
    assert len(set(GROUPED.findall(text))) == 2 and KERNEL in text
    assert "ragged-dot" not in text


# ---- a hybrid MoE at the published widths of its cell: 32 query heads
# over 8 kv heads of 64 in 3 attention layers, 32 experts of 1792 top-4
# behind a sigmoid router with a selection bias, 128 lanes

def test_paged_decode_d64_grouped_at_the_cells_shapes(one_chip, as_on_tpu):
    """The paged decode kernel's first use at heads of 64 WITH grouped kv
    heads under the causal window: 4 query heads a kv head. The pool
    holds heads of 64 in 128 lanes (``pool_head_dim``), so the kernel
    reads it where it lies: with 64 lanes the TPU lays the pool out
    page-minor and the program copies all 0.4 GB of K and of V into the
    kernel's layout, every layer, every step (1.6 GB of scratch)."""
    from paddle_tpu.generation.paged_cache import pool_head_dim
    sds = lambda shape, dt=BF16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    assert pool_head_dim(64) == 128 and pool_head_dim(128) == 128
    pool = sds((3, 1024, 8, PAGE, pool_head_dim(64)))

    def fn(q, k, v, table, kv_len):
        return fa.flash_attention_decode_paged(q, k, v, table, kv_len, 2)

    compiled = jax.jit(fn).lower(
        sds((128, 1, 32, 64)), pool, pool, sds((128, 16), jnp.int32),
        sds((128,), jnp.int32)).compile()
    assert "flash_decode_paged" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("seq", [512, 1024])
def test_causal_prefill_kernel_with_grouped_kv_heads(one_chip, as_on_tpu,
                                                     seq):
    """The causal prefill of grouped kv heads goes through the
    block-causal path at a block of 1: the flash kernel at the buckets
    that reach its gate."""
    from paddle_tpu.generation.attention import block_causal_attention
    sds = lambda shape: jax.ShapeDtypeStruct(shape, BF16,  # noqa: E731
                                             sharding=one_chip)

    def fn(q, k, v):
        return block_causal_attention(q, k, v, 1)

    text = compiled_text(fn, sds((1, seq, 32, 64)), sds((1, seq, 8, 64)),
                         sds((1, seq, 8, 64)))
    assert KERNEL in text and "flash_fwd" in text


def test_grouped_expert_products_behind_a_sigmoid_router(one_chip,
                                                         as_on_tpu):
    from paddle_tpu.distributed.parallel.moe import dropless_moe
    sds = lambda shape: jax.ShapeDtypeStruct(shape, BF16,  # noqa: E731
                                             sharding=one_chip)

    def fn(x, router, gate_up, down, bias):
        return dropless_moe(x, router, gate_up, down, 4, True, "sigmoid",
                            bias)

    text = compiled_text(fn, sds((128, 2048)), sds((2048, 32)),
                         sds((32, 2048, 3584)), sds((32, 1792, 2048)),
                         sds((32,)))
    assert len(set(GROUPED.findall(text))) == 2 and KERNEL in text
    assert "ragged-dot" not in text


# ---- the experts' grouped-product kernel (kernels/grouped_matmul.py) at
# both MoE cells' real shapes: the (token, expert) rows of the decode
# step (sdar 128 lanes x 4 positions x 8 experts, lfm2 128 x 4) and of the
# four prefill buckets (128-1024 tokens x 8 or x 4), both products

# name -> (experts, K, N, the rows the cell's programs hand it)
EXPERT_PRODUCTS = {
    "sdar_gate_up": (128, 2048, 1536, (1024, 2048, 4096, 8192)),
    "sdar_down": (128, 768, 2048, (1024, 2048, 4096, 8192)),
    "lfm2_gate_up": (32, 2048, 3584, (512, 1024, 2048, 4096)),
    "lfm2_down": (32, 1792, 2048, (512, 1024, 2048, 4096)),
}
# the kernel's instances in a compiled text: grouped_matmul, .1, .2 ...
GROUPED = re.compile(r"%(grouped_matmul[.\d]*) = ")
# the kernel's blocks live in VMEM; what it leaves in HBM beside its
# result is the work list (a few hundred int32) and XLA's bookkeeping
KERNEL_TEMP_BYTES = 1 << 20


@pytest.mark.parametrize("product,rows", [
    pytest.param(name, rows, id=f"{name}-{rows}")
    for name, (_, _, _, all_rows) in EXPERT_PRODUCTS.items()
    for rows in all_rows])
def test_grouped_matmul_at_the_cells_shapes(one_chip, as_on_tpu, product,
                                            rows):
    e, k, n, _ = EXPERT_PRODUCTS[product]
    sds = lambda shape, dt=BF16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    assert gm.supports(rows, k, n)
    compiled = jax.jit(gm.grouped_matmul).lower(
        sds((rows, k)), sds((e, k, n)), sds((e,), jnp.int32)).compile()
    text = compiled.as_text()
    assert KERNEL in text and GROUPED.search(text)
    assert "ragged-dot" not in text
    assert f"f32[{rows},{n}]" in text       # float32 off the accumulator
    assert compiled.memory_analysis().temp_size_in_bytes < KERNEL_TEMP_BYTES


def _sdar_two_layers():
    from paddle_tpu.models.sdar import SDARConfig, SDARForCausalLM
    model = SDARForCausalLM(SDARConfig(
        dtype="bfloat16", vocab_size=151936, hidden_size=2048,
        num_hidden_layers=2, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, moe_intermediate_size=768, num_experts=128,
        num_experts_per_tok=8, rope_theta=1e6))
    return model, "block_step", dict(block_diffusion=dict(
        block_length=4, denoising_steps=2,
        remasking="low_confidence_static", confidence_threshold=0.9,
        mask_token_id=151669))


def _lfm2_two_layers():
    from paddle_tpu.models.lfm2 import LFM2Config, LFM2ForCausalLM
    # one layer of each mixer kind, both with experts (the cell's two
    # leading layers are the dense ones)
    model = LFM2ForCausalLM(LFM2Config(
        dtype="bfloat16", num_hidden_layers=2,
        layer_types=("conv", "full_attention"), num_dense_layers=0))
    return model, "step", {}


@pytest.mark.parametrize("build", [_sdar_two_layers, _lfm2_two_layers],
                         ids=["sdar", "lfm2"])
def test_step_of_a_two_layer_model_holds_the_kernel(one_chip, as_on_tpu,
                                                    monkeypatch, build):
    """The engine's own ``step`` program of a two-layer model at each MoE
    cell's widths, 128 lanes over a paged cache, as the cell builds it:
    both expert layers run the kernel twice and XLA's op is gone; the
    gauges say which path each traced layer took."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core import metrics, monitor
    from paddle_tpu.inference import Config
    from paddle_tpu.nn import initializer
    from paddle_tpu.serving import ServingEngine
    # weights are never read here: zero pages the OS hands out lazily
    # (drawing 1.2 B normals on the CPU takes most of a minute)
    monkeypatch.setattr(
        initializer.Normal, "__call__",
        lambda self, shape, dtype=None: jnp.asarray(np.zeros(
            tuple(shape), jnp.dtype(dtype or "float32"))))
    model, step, mode = build()
    model.eval()
    conf = (Config().from_layer(
        model, [paddle.to_tensor(np.zeros((1, 128), np.int32))])
        .enable_tpu("bfloat16")
        .enable_generation(max_new_tokens=512, prefill_buckets=(128,),
                           max_batch=128, do_sample=False, **mode)
        .enable_serving(paged=True, kv_page_size=128, kv_pages=256,
                        cache_max_len=2048))
    monitor.enable()
    try:
        engine = ServingEngine(conf, warmup=False)
        program = engine._programs[(step,)]
        static = program._argnums(program.static)
        avals = [
            op if i in static else jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), op)
            for i, op in enumerate(program.operands())]
        before = {k: metrics.gauge(k).value for k in (
            "moe.grouped_kernel_layers", "moe.ragged_dot_layers")}
        text = jax.jit(program.fn, static_argnums=static,
                       donate_argnums=program.donation_intent) \
            .lower(*avals).compile().as_text()
        after = {k: metrics.gauge(k).value for k in before}
    finally:
        monitor.disable()
        engine.shutdown()
    assert len(set(GROUPED.findall(text))) == 4     # 2 layers x 2 products
    assert "ragged-dot" not in text
    assert after["moe.grouped_kernel_layers"] \
        == before["moe.grouped_kernel_layers"] + 2
    assert after["moe.ragged_dot_layers"] == before["moe.ragged_dot_layers"]


# ---- the page pool is read and written where it lies (gpt3-6.7b widths)
#
# An 8-layer stacked pool of 32 heads of 128. One layer of it is 0.27 GB
# of bf16 here (0.54 GB in the cell, whose pool is twice as many pages):
# a program that slices a layer out of the pool, transposes it, or lets
# XLA's layout assignment flip the pool around a scatter, copies that
# much on every call. None of the programs below may hold one.

POOL_LAYERS, POOL_BATCH, POOL_HEADS, POOL_D = 8, 32, 32, 128
RELAYOUTS = ("copy", "transpose", "slice", "reshape")
HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")


def pool_sized_relayouts(text, layer_elements):
    """Instructions of ``text`` (fused ones included) that are a copy,
    transpose, slice or reshape, async halves too, of at least one
    layer's pool."""
    found = []
    for line in text.splitlines():
        m = HLO_RESULT.match(line)
        if not m or not m.group(2).startswith(RELAYOUTS):
            continue
        if math.prod(int(n) for n in m.group(1).split(",") if n) \
                >= layer_elements:
            found.append(line.strip()[:160])
    return found


def compiled_donating(fn, *avals):
    return jax.jit(fn, donate_argnums=(0,)).lower(*avals).compile() \
        .as_text()


WRITE = re.compile(r"%(paged_kv_write[.\d]*) = ")
DECODE = re.compile(r"%(flash_decode_paged[.\d]*) = ")
WRITE_GAUGES = ("kv.write_kernel_layers", "kv.write_scatter_layers")


def update_then_decode(cache, q, k, v):
    """Per-layer ``update`` then the paged decode, over every layer of
    the cache's stacked pool."""
    out = 0
    for layer in range(cache.num_layers):
        cache = cache.update(layer, k, v, cache.kv_len)
        out += fa.flash_attention_decode_paged(
            q, cache.k, cache.v, cache.page_table,
            cache.kv_len + k.shape[1], layer, **paged_scales(cache))
    return cache, out


def compiled_with_write_gauges(fn, *avals):
    """(compiled text, how far each of the two gauges rose while ``fn``
    was traced)."""
    from paddle_tpu.core import metrics, monitor
    monitor.enable()
    try:
        before = {k: metrics.gauge(k).value for k in WRITE_GAUGES}
        text = compiled_donating(fn, *avals)
        return text, {k: metrics.gauge(k).value - before[k]
                      for k in WRITE_GAUGES}
    finally:
        monitor.disable()


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("s", [1, 4], ids=["s1", "s4"])
def test_paged_step_reads_the_pool_in_place(one_chip, as_on_tpu, s, quant):
    """Per-layer ``update`` (decode, and a speculative window of 4) then
    the paged decode, over all 8 layers of a donated pool: the bf16
    pool's rows go through the write kernel, the int8 pool's (values and
    scale sidecars) through the scatter, and neither copies the pool."""
    cache, sds = pool_avals(one_chip, POOL_D, POOL_HEADS, quant,
                            POOL_LAYERS, POOL_BATCH)
    new = sds((POOL_BATCH, s, POOL_HEADS, POOL_D), BF16)
    text, took = compiled_with_write_gauges(update_then_decode, cache, new,
                                            new, new)
    assert len(set(DECODE.findall(text))) == POOL_LAYERS
    writes = 0 if quant else POOL_LAYERS
    assert len(set(WRITE.findall(text))) == writes
    assert took == {"kv.write_kernel_layers": writes,
                    "kv.write_scatter_layers": POOL_LAYERS - writes}
    assert not pool_sized_relayouts(text, cache.k.size // POOL_LAYERS)


@pytest.mark.parametrize("lanes,s,hq,hk,pages,layers,writes", [
    pytest.param(64, 1, 32, 32, 512, 8, 8, id="gpt3l8"),
    pytest.param(128, 4, 32, 4, 1024, 6, 6, id="sdar-l6"),
    pytest.param(128, 1, 32, 8, 1024, 3, 3, id="lfm2-l14"),
    pytest.param(256, 1, 32, 2, 2048, 2, 0, id="nemotron3n-l13"),
])
def test_paged_step_at_the_cells_shapes(one_chip, as_on_tpu, lanes, s, hq, hk,
                                        pages, layers, writes):
    """``update`` + paged decode at the serve cells' own shapes (bf16, 16
    table slots a lane): which cells' new rows go through the write
    kernel (``paged_write.supports``: the rows a lane), and every
    cell's program writes K and V into the donated pools where they lie
    (aliased, no scratch of a layer's size)."""
    from paddle_tpu.generation.paged_cache import PagedKVCache
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    pool = sds((layers, pages, hk, PAGE, 128), BF16)
    cache = PagedKVCache(pool, pool, sds((lanes, 16), jnp.int32),
                         sds((lanes,), jnp.int32))
    new = sds((lanes, s, hk, 128), BF16)
    compiled = jax.jit(update_then_decode, donate_argnums=(0,)).lower(
        cache, sds((lanes, s, hq, 128), BF16), new, new).compile()
    text = compiled.as_text()
    assert len(set(DECODE.findall(text))) == layers
    assert len(set(WRITE.findall(text))) == writes
    assert not pool_sized_relayouts(text, cache.k.size // layers)
    layer_bytes = 2 * cache.k.size // layers
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes // 4
    assert mem.alias_size_in_bytes >= 2 * layers * layer_bytes


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("program", ["install_row", "install_span"])
def test_paged_install_writes_the_pool_in_place(one_chip, program, quant):
    """Admission and chunked prefill scatter a batch-1 dense row into
    the donated pool: no kernel, and no pool-sized temporary."""
    from paddle_tpu.generation.kv_cache import KVCache, QuantKVCache
    cache, sds = pool_avals(one_chip, POOL_D, POOL_HEADS, quant,
                            POOL_LAYERS, POOL_BATCH)
    row = (POOL_LAYERS, 1, CACHE_LEN, POOL_HEADS, POOL_D)
    kv, one = sds(row, cache.k.dtype), sds((1,), jnp.int32)
    if quant:
        scale = sds(row[:-1], BF16)
        src = QuantKVCache(kv, kv, one, scale, scale, sds((), jnp.int32))
    else:
        src = KVCache(kv, kv, one)
    table_row = sds((CACHE_LEN // PAGE,), jnp.int32)
    at = sds((), jnp.int32)
    if program == "install_row":
        text = compiled_donating(
            lambda c, src, slot, row, start:
                c.install_row(src, slot, row, start),
            cache, src, at, table_row, at)
    else:
        text = compiled_donating(
            lambda c, src, row, start: c.install_span(src, row, start),
            cache, src, table_row, at)
    assert not pool_sized_relayouts(text, cache.k.size // POOL_LAYERS)


# ---- a state-space MoE hybrid's programs at its cell's shapes: the
# engine's own ``step``, ``prefill`` and ``admit`` of one block of each
# kind (Mamba-2, experts, attention) at Nemotron-3-Nano's widths, 256
# lanes over a paged cache and a float32 SSM state of 0.54 GB a layer

SSM = re.compile(r"%(ssm_update[.\d]*) = ")


@pytest.fixture(scope="module")
def nemotron_programs(topo):
    """{key: (compiled text, memory analysis)} of a three-block engine's
    programs, with the gauges' changes, built once for the module."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core import metrics, monitor
    from paddle_tpu.inference import Config
    from paddle_tpu.kernels import ssm_update as su
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)
    from paddle_tpu.nn import initializer
    from paddle_tpu.serving import ServingEngine
    one_chip = SingleDeviceSharding(topo.devices[0])
    names = ("ssm.kernel_layers", "ssm.fallback_layers",
             "moe.grouped_kernel_layers", "moe.ragged_dot_layers")
    with pytest.MonkeyPatch.context() as mp:
        # weights are never read here: zeros, not 1.3 B normals
        mp.setattr(initializer.Normal, "__call__",
                   lambda self, shape, dtype=None: jnp.asarray(np.zeros(
                       tuple(shape), jnp.dtype(dtype or "float32"))))
        for mod in (fa, gm, su, pw):
            mp.setattr(mod, "_interpret", lambda: False)
        model = NemotronHForCausalLM(NemotronHConfig(
            dtype="bfloat16", vocab_size=65536, num_hidden_layers=3,
            hybrid_override_pattern="ME*", n_routed_experts=64,
            router_experts=128))
        model.eval()
        conf = (Config().from_layer(
            model, [paddle.to_tensor(np.zeros((1, 128), np.int32))])
            .enable_tpu("bfloat16")
            .enable_generation(max_new_tokens=512,
                               prefill_buckets=(128, 1024), max_batch=256,
                               do_sample=False)
            .enable_serving(paged=True, kv_page_size=128, kv_pages=2048,
                            cache_max_len=2048))
        engine = ServingEngine(conf, warmup=False)
        mp.setattr(jax, "default_backend", lambda: "tpu")
        monitor.enable()
        out = {}
        try:
            before = {k: metrics.gauge(k).value for k in names}
            for key in (("step",), ("prefill", 128), ("prefill", 1024),
                        ("admit",)):
                program = engine._programs[key]
                static = program._argnums(program.static)
                avals = [
                    op if i in static else jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(
                            a.shape, a.dtype, sharding=one_chip), op)
                    for i, op in enumerate(program.operands())]
                compiled = jax.jit(
                    program.fn, static_argnums=static,
                    donate_argnums=program.donation_intent) \
                    .lower(*avals).compile()
                out[key] = (compiled.as_text(), compiled.memory_analysis())
            out["gauges"] = {k: metrics.gauge(k).value - before[k]
                             for k in names}
        finally:
            monitor.disable()
            engine.shutdown()
    return out


@pytest.mark.parametrize("key,grouped,ssm", [
    (("step",), 2, 1), (("prefill", 128), 2, 0),
    (("prefill", 1024), 2, 0), (("admit",), 0, 0)],
    ids=["step", "prefill-128", "prefill-1024", "admit"])
def test_state_space_moe_programs_hold_their_kernels(nemotron_programs, key,
                                                     grouped, ssm):
    """``step``: the one-step state update and both grouped products are
    custom calls, XLA's ``ragged-dot`` is gone (1856 columns stored as
    1920), and the stacked float32 state (0.54 GB) is updated where it
    lies: aliased, no state-sized scratch. ``prefill``: the chunked scan
    is plain XLA, the experts take the kernel. ``admit`` writes the
    slot's rows of both states in place."""
    text, mem = nemotron_programs[key]
    assert len(set(GROUPED.findall(text))) == grouped
    assert len(set(SSM.findall(text))) == ssm
    assert "ragged-dot" not in text
    state_bytes = 256 * 64 * 64 * 128 * 4
    assert mem.temp_size_in_bytes < state_bytes // 4
    if key in (("step",), ("admit",)):
        assert mem.alias_size_in_bytes >= state_bytes


def test_state_space_moe_gauges_say_which_path(nemotron_programs):
    g = nemotron_programs["gauges"]
    assert g["ssm.kernel_layers"] == 1 and g["ssm.fallback_layers"] == 0
    # one expert block traced in the step and in both prefill buckets
    # (and wherever a program's operands are a prefill's shapes)
    assert g["moe.grouped_kernel_layers"] >= 3
    assert g["moe.ragged_dot_layers"] == 0
