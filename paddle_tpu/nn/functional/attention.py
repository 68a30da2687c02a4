"""Attention functional. The XLA path is a plain softmax(QK^T)V — XLA fuses
it decently; the Pallas flash kernel (paddle_tpu.kernels.flash_attention)
is used automatically for long sequences on TPU. Reference analog:
paddle/fluid/operators/fused/fused_attention_op.cu (hand-fused CUDA);
here fusion is the compiler's job with a Pallas override for the hot case.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...ops.op_registry import op

_FLASH_MIN_SEQ = 512  # r3: lowering the gate from 1024 to 512 lifted
# full-model ERNIE-base +36% and BERT-large +34% tokens/sec — the XLA
# path materializes [B, H, S, S] score/softmax buffers (fwd + saved
# residuals + bwd), ~200 MB/layer at b32 s512, which flash never forms


def _sdpa_xla(q, k, v, mask=None, dropout_p=0.0, is_causal=False, scale=None,
              dropout_rng=None):
    # q,k,v: [B, S, H, D] (paddle convention)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qh = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32) * scale
    if is_causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        logits = jnp.where(causal, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def _flash(query, key, value, causal, scale):
    """The flash kernel, per shard under the active hybrid mesh. XLA's
    partitioner cannot split a Mosaic kernel (lowering refuses one in a
    program over several devices), so on a mesh the call is a shard_map
    over the axes attention is parallel in: batch over the data axes,
    heads over ``mp``. An axis that does not divide its dim stays out of
    the specs, which replicates that dim."""
    from ...distributed import topology
    from ...kernels.flash_attention import flash_attention
    mesh = topology.get_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention(query, key, value, causal=causal,
                               scale=scale)
    from jax.sharding import PartitionSpec as P
    from ...distributed.fleet.train_step import DATA_AXES
    b, heads_kv = query.shape[0], key.shape[2]
    data = tuple(a for a in DATA_AXES if mesh.shape[a] > 1)
    n_data = math.prod(mesh.shape[a] for a in data)
    mp = mesh.shape["mp"]
    spec = P(data if data and b % n_data == 0 else None, None,
             "mp" if mp > 1 and heads_kv % mp == 0 else None, None)
    return jax.shard_map(
        functools.partial(flash_attention, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(query, key, value)


@op("scaled_dot_product_attention")
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, dropout_rng=None):
    """query/key/value: [batch, seq, num_heads, head_dim]. Attention dropout
    draws from `dropout_rng` if given, else the global eager key (tracing
    without an explicit rng disables dropout rather than baking a key)."""
    if dropout_p > 0.0 and training and dropout_rng is None:
        if not isinstance(query, jax.core.Tracer):
            from ...core import random as random_mod
            dropout_rng = random_mod.next_key()
    if not training:
        dropout_p = 0.0
    # NOTE r4: widening this gate to big-batch short sequences (ViT-L
    # b64 s197, 35% of whose step is the XLA attention path —
    # experiments/vit_attention_share.py) was measured and REJECTED:
    # the padded flash path (197 -> 256 via the kernel's kv_len
    # masking) ran 210.3 img/s vs 234.9 on the XLA path — the +69%
    # padded score compute and the kernel's exp cost outweigh the
    # materialized-buffer traffic at this size. The ragged/kv_len
    # support stays in the kernel (tests/test_kernels.py) for callers
    # that need it; the gate stays at seq >= 512.
    use_flash = (attn_mask is None and dropout_p == 0.0
                 and query.shape[1] >= _FLASH_MIN_SEQ
                 and query.shape[1] == key.shape[1]
                 and query.shape[-1] in (64, 128, 256)
                 and jax.default_backend() == "tpu")
    if use_flash:
        try:
            return _flash(query, key, value, is_causal, scale)
        except NotImplementedError:
            pass  # declared unsupported shape (e.g. ragged causal):
            #      the XLA path is the intended fallback; any other
            #      kernel failure propagates
    return _sdpa_xla(query, key, value, attn_mask, dropout_p, is_causal,
                     scale, dropout_rng)
