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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import topology

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


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("d,heads", WIDTHS)
def test_paged_decode(one_chip, as_on_tpu, d, heads, quant):
    batch, slots = 8, CACHE_LEN // PAGE
    n_pages = batch * slots + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    q = sds((batch, 1, heads, d), BF16)
    pool = sds((n_pages, PAGE, heads, d), jnp.int8 if quant else BF16)
    scales = [sds((n_pages, PAGE, heads), BF16)] * 2 if quant else []
    table = sds((batch, slots), jnp.int32)
    kv_len = sds((batch,), jnp.int32)

    def fn(q, kp, vp, table, kv_len, *sc):
        return fa.flash_attention_decode_paged(
            q, kp, vp, table, kv_len,
            **(dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}))

    assert KERNEL in compiled_text(fn, q, pool, pool, table, kv_len,
                                   *scales)
