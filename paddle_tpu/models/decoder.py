"""Pieces of a present-day decoder block, to be assembled by a model file:
a norm (``nn.RMSNorm``), a position scheme (:func:`rotary`), a MIXER kind
per layer (:class:`RotaryGQAttention`: grouped-query heads, optional
per-head q/k norms, rotary positions, the shared KV-cache choreography
of ``generation.attention``; :class:`GatedShortConv`: a gated depthwise
causal convolution of a few taps that carries a state of fixed width
and knows no position), an MLP kind per layer (:class:`GatedMLP`, or
``distributed.parallel.moe.DroplessMoE``), and the trunk that drives
them through the KV-cache protocol (:class:`DecoderTrunk`): its cache
has a KV layer for each attention mixer and, where a mixer carries
state, a state row beside it (``generation.hybrid_cache``).

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

    #: the region a device trace shows this mixer under
    scope = "attn"
    #: per-lane state beside the KV rows: none
    state_shape = None

    def forward(self, x, pos, cache=None, layer_idx=0, decode=False,
                block=None, valid=None):
        """x [b, s, hidden]; pos [b, s] absolute positions. With a cache:
        (out, cache) through ``generation.attention.cached_attention``
        (``block``: block-causal prefill, full-window decode; ``valid``
        is for mixers that carry state: the cache's ``kv_len`` masks
        here). Without: causal (or block-causal) self-attention over
        the window."""
        b, s, _ = x.shape
        d = self.head_dim
        q = self.q_proj(x).reshape([b, s, self.num_heads, d])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, d])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, d])
        q, k = self._qk(q, k, pos)
        if cache is not None:
            from ..generation.attention import cached_attention
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


def gated_short_conv(bcx, taps, prior=None, valid=None):
    """Body of :class:`GatedShortConv` on raw arrays: ``bcx`` [b, s, 3H]
    is the input projection's ``[B, C, x]``; ``z = B * x``;
    ``c_t = sum_j taps[:, j] * z_{t-(L-1)+j}`` (depthwise, causal, ``z``
    = ``prior`` [b, L-1, H] before the window, zero when None); returns
    (``C * c`` [b, s, H], the state after the window's first ``valid``
    positions [b, L-1, H]; ``valid`` None: all ``s``). The taps'
    multiply-adds are accumulated in float32."""
    from ..generation.hybrid_cache import window_state
    b, s, h3 = bcx.shape
    h, taps_n = h3 // 3, taps.shape[1]
    gate_b, gate_c, x = bcx[..., :h], bcx[..., h:2 * h], bcx[..., 2 * h:]
    z = gate_b * x
    if prior is None:
        prior = jnp.zeros((b, taps_n - 1, h), z.dtype)
    both = jnp.concatenate([prior.astype(z.dtype), z], axis=1)
    w = taps.astype(jnp.float32)
    c = sum(both[:, j:j + s].astype(jnp.float32) * w[:, j]
            for j in range(taps_n))
    if valid is None:
        valid = jnp.full((b,), s, jnp.int32)
    return gate_c * c.astype(z.dtype), window_state(prior, z, valid)


class GatedShortConv(Layer):
    """Gated short convolution mixer: ``[B, C, x] = W_in u``,
    ``y = W_out (C * conv(B * x))`` with a depthwise causal convolution of
    ``taps`` taps, no bias, no activation, no position. Decoding needs
    the last ``taps - 1`` columns of ``B * x`` a lane: ``state_shape``,
    which the trunk's cache holds beside the KV rows."""
    scope = "short_conv"

    def __init__(self, hidden: int, taps: int, std: float = 0.02,
                 out_std: float = 0.02):
        super().__init__()
        if taps < 2:
            raise ValueError(f"a convolution of {taps} taps mixes nothing")
        self.in_proj = spec_linear(hidden, 3 * hidden, std, P(None, "mp"),
                                   has_bias=False)
        self.conv = self.create_parameter(
            (hidden, taps),
            default_initializer=I.Normal(0.0, 1.0 / math.sqrt(taps)))
        self.conv.spec = P("mp", None)
        self.out_proj = spec_linear(hidden, hidden, out_std, P("mp", None),
                                    has_bias=False)
        self.state_shape = (taps - 1, hidden)

    def forward(self, x, pos, cache=None, layer_idx=0, decode=False,
                block=None, valid=None):
        """x [b, s, hidden]. With a cache: (out, cache), the state read
        from and handed back to ``cache.state[layer_idx]``; ``valid``
        [b]: how many of the window's positions are real."""
        if block is not None:
            raise NotImplementedError(
                "a causal convolution under a block-causal mask")
        bcx = self.in_proj(x)
        if cache is None:
            y = dispatch("gated_short_conv",
                         lambda a, w: gated_short_conv(a, w)[0],
                         (bcx, self.conv), {})
            return self.out_proj(y)
        y, state = dispatch(
            "gated_short_conv",
            lambda a, w, st, n: gated_short_conv(a, w, st, n),
            (bcx, self.conv, cache.state[layer_idx], valid), {},
            differentiable=False)
        return self.out_proj(y), cache.with_state(layer_idx, state)


class GatedMLP(Layer):
    """Dense gated MLP: ``W_down (silu(W_gate u) * W_up u)``, no bias."""

    def __init__(self, hidden: int, width: int, std: float = 0.02,
                 out_std: float = 0.02):
        super().__init__()
        self.gate_proj = spec_linear(hidden, width, std, P(None, "mp"),
                                     has_bias=False)
        self.up_proj = spec_linear(hidden, width, std, P(None, "mp"),
                                   has_bias=False)
        self.down_proj = spec_linear(width, hidden, out_std, P("mp", None),
                                     has_bias=False)

    def forward(self, x):
        from ..nn import functional as F
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class DecoderBlock(Layer):
    """Pre-norm residual block: ``x + mixer(norm1(x))``, then
    ``x + mlp(norm2(x))``. The mixer sits in the ``attn`` slot whatever
    its kind; its region in a device trace is the mixer's own ``scope``
    (``attn`` / ``short_conv``), the MLP's is ``mlp``."""

    def __init__(self, hidden: int, eps: float, attn: Layer, mlp: Layer):
        super().__init__()
        self.norm1 = RMSNorm(hidden, epsilon=eps)
        self.attn = attn
        self.norm2 = RMSNorm(hidden, epsilon=eps)
        self.mlp = mlp

    def forward(self, x, pos, cache=None, layer_idx=0, decode=False,
                block=None, valid=None):
        if cache is not None:
            with jax.named_scope(self.attn.scope):
                a, cache = self.attn(self.norm1(x), pos, cache=cache,
                                     layer_idx=layer_idx, decode=decode,
                                     block=block, valid=valid)
                x = x + a
            with jax.named_scope("mlp"):
                x = x + self.mlp(self.norm2(x))
            return x, cache
        with jax.named_scope(self.attn.scope):
            x = x + self.attn(self.norm1(x), pos, block=block)
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.norm2(x))
        return x


class DecoderTrunk(Layer):
    """Token embedding, the blocks, the final norm; drives the KV-cache
    protocol the serving surfaces use (prefill creates and fills the
    cache, decode consumes one: ``models/gpt.py``'s contract, with the
    kv heads' count as the cache's). The cache it creates has one KV
    layer for each mixer WITHOUT a ``state_shape`` (the attention
    layers) and, if any mixer has one, a state row for each of those
    beside it (``generation.hybrid_cache.HybridCache``); every mixer is
    passed its own index among its kind."""

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
        # block -> its index among the KV layers or among the state layers
        shapes = [blk.attn.state_shape for blk in self.blocks]
        self._state_shape = next((s for s in shapes if s is not None), None)
        if any(s not in (None, self._state_shape) for s in shapes):
            raise ValueError(f"mixers with states of different shapes: "
                             f"{sorted(set(shapes) - {None})}")
        self._cache_idx, n = [], [0, 0]
        for s in shapes:
            self._cache_idx.append(n[s is not None])
            n[s is not None] += 1
        self._kv_layers, self._state_layers = n

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
            new_len = cache.kv_len + s
            if prompt_len is not None:
                new_len = jnp.minimum(new_len, _raw_i32(prompt_len))
            # the window's real positions (what a state may take in)
            valid = new_len - cache.kv_len
        else:
            pos = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
            cache = KVCache.create(
                self._kv_layers, b, int(cache_max_len
                                        or self.max_positions),
                self.num_kv_heads, self.head_dim, dtype=x._data.dtype,
                cache_dtype=cache_dtype)
            if self._state_layers:
                from ..generation.hybrid_cache import HybridCache
                cache = HybridCache.create(cache, self._state_layers,
                                           self._state_shape, x._data.dtype)
            new_len = jnp.broadcast_to(
                jnp.int32(s) if prompt_len is None
                else _raw_i32(prompt_len), (b,))
            valid = new_len
        for i, blk in enumerate(self.blocks):
            x, cache = blk(x, pos, cache=cache,
                           layer_idx=self._cache_idx[i], decode=decode,
                           block=block, valid=valid)
        return self.norm(x), cache.with_kv_len(new_len)


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
