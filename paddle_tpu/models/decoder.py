"""Pieces of a present-day decoder block, to be assembled by a model file:
a norm (``nn.RMSNorm``), a position scheme (:func:`rotary`), an attention
kind (:class:`RotaryGQAttention`: grouped-query heads, optional per-head
q/k norms, rotary positions, the shared KV-cache choreography of
``generation.attention``), an MLP kind (any layer: a gated dense MLP, or
``distributed.parallel.moe.DroplessMoE``), and the trunk that drives
them through the KV-cache protocol (:class:`DecoderTrunk`).

``models/gpt.py`` predates this file and keeps its own GPT-2-era block; a
new architecture is a module that picks its pieces here (``models/sdar.py``)
and not a branch inside ``GPTAttention``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor, dispatch
from ..nn import initializer as I
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..nn.layers_common import Embedding, RMSNorm
from ._common import spec_linear


def rotary(x, pos, theta: float):
    """Rotary position embedding over the whole head (``rotate_half``
    convention): x [b, s, heads, d] raw, pos [b, s] or [s] absolute
    positions. Angles in float32, result in x's dtype."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    pos = jnp.asarray(pos, jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    ang = pos[..., None] * inv                               # [b, s, d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    out = xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    return out.astype(x.dtype)


def _head_rms(x, g, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * g.astype(jnp.float32)).astype(x.dtype)


class RotaryGQAttention(Layer):
    """Grouped-query attention with rotary positions: ``num_heads`` query
    heads over ``num_kv_heads`` key/value heads of ``head_dim`` (query
    head i reads kv head ``i // group``), no biases, optional RMSNorm
    over each q and k head (``qk_norm``). The cache holds the kv heads
    only."""

    def __init__(self, hidden: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, theta: float, eps: float,
                 qk_norm: bool = True, std: float = 0.02,
                 out_std: float = 0.02):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads not divisible by "
                             f"{num_kv_heads} kv heads")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.theta, self.eps = head_dim, float(theta), eps
        self.q_proj = spec_linear(hidden, num_heads * head_dim, std,
                                  P(None, "mp"), has_bias=False)
        self.k_proj = spec_linear(hidden, num_kv_heads * head_dim, std,
                                  P(None, "mp"), has_bias=False)
        self.v_proj = spec_linear(hidden, num_kv_heads * head_dim, std,
                                  P(None, "mp"), has_bias=False)
        self.o_proj = spec_linear(num_heads * head_dim, hidden, out_std,
                                  P("mp", None), has_bias=False)
        self.q_norm = self.k_norm = None
        if qk_norm:
            self.q_norm = self.create_parameter(
                (head_dim,), default_initializer=I.Constant(1.0))
            self.k_norm = self.create_parameter(
                (head_dim,), default_initializer=I.Constant(1.0))

    def _qk(self, q, k, pos):
        """Head norms then rotary, on Tensors [b, s, heads, d]."""
        def impl(q_, k_, pos_, *g):
            if g:
                q_ = _head_rms(q_, g[0], self.eps)
                k_ = _head_rms(k_, g[1], self.eps)
            return rotary(q_, pos_, self.theta), \
                rotary(k_, pos_, self.theta)
        norms = () if self.q_norm is None else (self.q_norm, self.k_norm)
        return dispatch("qk_norm_rotary", impl, (q, k, pos) + norms, {})

    def forward(self, x, pos, cache=None, layer_idx=0, decode=False,
                block=None):
        """x [b, s, hidden]; pos [b, s] absolute positions. With a cache:
        (out, cache) through ``generation.attention.cached_attention``
        (``block``: block-causal prefill, full-window decode). Without:
        causal (or block-causal) self-attention over the window."""
        b, s, _ = x.shape
        d = self.head_dim
        q = self.q_proj(x).reshape([b, s, self.num_heads, d])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, d])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, d])
        q, k = self._qk(q, k, pos)
        if cache is not None:
            from ..generation.attention import cached_attention
            if block is None and not decode \
                    and self.num_kv_heads != self.num_heads:
                raise NotImplementedError(
                    "causal prefill with grouped kv heads: pass block= "
                    "(block-causal) or use equal head counts")
            out, cache = cached_attention(
                q, k, v, cache, layer_idx, decode=decode, causal=True,
                block=block)
            return self.o_proj(out.reshape([b, s, -1])), cache
        from ..generation.attention import block_causal_attention
        out = dispatch(
            "block_causal_attention",
            lambda q_, k_, v_: block_causal_attention(q_, k_, v_,
                                                      block or 1),
            (q, k, v), {})
        return self.o_proj(out.reshape([b, s, -1]))


class DecoderBlock(Layer):
    """Pre-norm residual block: ``x + attn(norm1(x))``, then
    ``x + mlp(norm2(x))``; the regions ``attn`` / ``mlp`` are named for a
    device trace."""

    def __init__(self, hidden: int, eps: float, attn: Layer, mlp: Layer):
        super().__init__()
        self.norm1 = RMSNorm(hidden, epsilon=eps)
        self.attn = attn
        self.norm2 = RMSNorm(hidden, epsilon=eps)
        self.mlp = mlp

    def forward(self, x, pos, cache=None, layer_idx=0, decode=False,
                block=None):
        if cache is not None:
            with jax.named_scope("attn"):
                a, cache = self.attn(self.norm1(x), pos, cache=cache,
                                     layer_idx=layer_idx, decode=decode,
                                     block=block)
                x = x + a
            with jax.named_scope("mlp"):
                x = x + self.mlp(self.norm2(x))
            return x, cache
        with jax.named_scope("attn"):
            x = x + self.attn(self.norm1(x), pos, block=block)
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.norm2(x))
        return x


class DecoderTrunk(Layer):
    """Token embedding, the blocks, the final norm; drives the KV-cache
    protocol the serving surfaces use (prefill creates and fills the
    cache, decode consumes one: ``models/gpt.py``'s contract, with the
    kv heads' count as the cache's)."""

    def __init__(self, vocab: int, hidden: int, eps: float, blocks,
                 num_kv_heads: int, head_dim: int, max_positions: int,
                 std: float = 0.02):
        super().__init__()
        self.embed = Embedding(
            vocab, hidden,
            weight_attr=I.ParamAttr(initializer=I.Normal(0.0, std)))
        self.embed.weight.spec = P("mp", None)
        self.blocks = LayerList(list(blocks))
        self.norm = RMSNorm(hidden, epsilon=eps)
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.max_positions = int(max_positions)

    def forward(self, input_ids, cache=None, use_cache=False,
                prompt_len=None, cache_max_len=None, cache_dtype=None,
                block=None):
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            x = self.embed(input_ids)
        if cache is None and not use_cache:
            pos = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
            for blk in self.blocks:
                x = blk(x, pos, block=block)
            return self.norm(x)
        from ..generation.kv_cache import KVCache
        decode = cache is not None
        if decode:
            pos = Tensor(cache.positions(s))
        else:
            pos = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
            cache = KVCache.create(
                len(self.blocks), b, int(cache_max_len
                                         or self.max_positions),
                self.num_kv_heads, self.head_dim, dtype=x._data.dtype,
                cache_dtype=cache_dtype)
        for i, blk in enumerate(self.blocks):
            x, cache = blk(x, pos, cache=cache, layer_idx=i,
                           decode=decode, block=block)
        if decode:
            new_len = cache.kv_len + s
            if prompt_len is not None:
                new_len = jnp.minimum(new_len, _raw_i32(prompt_len))
            cache = cache.with_kv_len(new_len)
        else:
            cache = cache.with_kv_len(
                s if prompt_len is None else prompt_len)
        return self.norm(x), cache


def _raw_i32(x):
    return jnp.asarray(x._data if isinstance(x, Tensor) else x, jnp.int32)


def gather_last(h, prompt_len, base=None):
    """Hidden state at each row's last real position -> [b, 1, hidden]
    (``base``: the window's first absolute position, for a decode
    window)."""
    idx = _raw_i32(prompt_len) - 1
    if base is not None:
        idx = idx - _raw_i32(base)
    return dispatch(
        "gather_last_hidden",
        lambda hr, ir: jnp.take_along_axis(
            hr, jnp.maximum(ir, 0)[:, None, None], axis=1),
        (h, idx), {}, differentiable=False)


def residual_std(std: float, num_layers: int) -> float:
    """GPT-2's rule for the projections back into the residual."""
    return std / math.sqrt(2 * num_layers)
