"""Pieces of a present-day decoder block, to be assembled by a model file:
a norm (``nn.RMSNorm``), a position scheme (:func:`rotary`), a MIXER kind
per layer (:class:`RotaryGQAttention`: grouped-query heads, optional
per-head q/k norms, rotary positions or none, the shared KV-cache
choreography of ``generation.attention``; :class:`GatedShortConv`: a
gated depthwise causal convolution of a few taps that carries a state of
fixed width and knows no position; :class:`Mamba2Mixer`: a selective
state-space scan that carries its convolution's window AND a state
matrix a head), an MLP kind per layer (:class:`GatedMLP`,
:class:`Relu2MLP`, or ``distributed.parallel.moe.DroplessMoE``), a block
of a mixer and an MLP or of either ALONE (:class:`DecoderBlock`), and
the trunk that drives them through the KV-cache protocol
(:class:`DecoderTrunk`): its cache has a KV layer for each attention
mixer and, where mixers carry states, a row of each beside it
(``generation.hybrid_cache``).

``models/gpt.py`` predates this file and keeps its own GPT-2-era block; a
new architecture is a module that picks its pieces here (``models/sdar.py``)
and not a branch inside ``GPTAttention``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import monitor as _monitor
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
    over each q and k head (``qk_norm``). ``theta`` None: no position
    embedding at all (a model whose other mixers carry position). The
    cache holds the kv heads only."""

    def __init__(self, hidden: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, theta: float, eps: float,
                 qk_norm: bool = True, std: float = 0.02,
                 out_std: float = 0.02):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads not divisible by "
                             f"{num_kv_heads} kv heads")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.eps = head_dim, eps
        self.theta = None if theta is None else float(theta)
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
        if self.theta is None and self.q_norm is None:
            return q, k

        def impl(q_, k_, pos_, *g):
            if g:
                q_ = _head_rms(q_, g[0], self.eps)
                k_ = _head_rms(k_, g[1], self.eps)
            if self.theta is None:
                return q_, k_
            return rotary(q_, pos_, self.theta), \
                rotary(k_, pos_, self.theta)
        norms = () if self.q_norm is None else (self.q_norm, self.k_norm)
        return dispatch("qk_norm_rotary", impl, (q, k, pos) + norms, {})

    #: the region a device trace shows this mixer under
    scope = "attn"
    #: per-lane states beside the KV rows: none (the mixer keeps keys
    #: and values: the trunk gives it a KV layer)
    state_specs = None

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
    the last ``taps - 1`` columns of ``B * x`` a lane: ``state_specs``
    (one state, in the activations' type), which the trunk's cache holds
    beside the KV rows."""
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
        self.state_specs = (((taps - 1, hidden), None),)

    def forward(self, x, pos, cache=None, layer_idx=0, decode=False,
                block=None, valid=None):
        """x [b, s, hidden]. With a cache: (out, cache), the state read
        from ``cache.state[0][layer_idx]`` and handed back; ``valid``
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
            (bcx, self.conv, cache.state[0][layer_idx], valid), {},
            differentiable=False)
        return self.out_proj(y), cache.with_state(layer_idx, (state,))


def ssm_scan(x, dt, a, b, c, s0=None, chunk: int = 128):
    """The selective state-space recurrence of a window, a head at a
    time a state matrix ``S`` [P, N]:

        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,    y_t = S_t C_t

    computed chunk by chunk (``chunk`` positions: inside a chunk the
    positions see each other through a [chunk, chunk] decay matrix, the
    chunks hand their states on one to the next), which is algebraically
    the recurrence. ``x`` [b, s, heads, P], ``dt`` [b, s, heads] (after
    its softplus; 0 at a padded position: the state passes it
    unchanged and takes nothing in), ``a`` [heads] negative, ``b`` /
    ``c`` [b, s, groups, N] (head ``i`` reads group ``i // (heads //
    groups)``), ``s0`` [b, heads, P, N] the state before the window
    (None: zero). Everything in float32, the products at
    ``Precision.HIGHEST``. Returns (``y`` [b, s, heads, P], the state
    after the window [b, heads, P, N])."""
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    bsz, s, nh, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = nh // g
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] *
                               (v.ndim - 2)) for v in (x, dt, b, c))
    nc = (s + pad) // chunk
    x = x.astype(f32).reshape(bsz, nc, chunk, g, rep, p)
    dt = dt.astype(f32).reshape(bsz, nc, chunk, g, rep)
    b = b.astype(f32).reshape(bsz, nc, chunk, g, n)
    c = c.astype(f32).reshape(bsz, nc, chunk, g, n)
    cum = jnp.cumsum(dt * a.astype(f32).reshape(g, rep), axis=2)
    dtx = dt[..., None] * x
    # inside a chunk: position i takes position j <= i in, decayed
    cb = jnp.einsum("bcign,bcjgn->bcgij", c, b, precision=hi)
    seg = cum[:, :, :, None] - cum[:, :, None, :]       # [b,c,i,j,g,r]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    y = jnp.einsum("bcgij,bcijgr,bcjgrp->bcigrp", cb, decay, dtx,
                   precision=hi)
    # what each chunk adds to the state, decayed to the chunk's end
    last = cum[:, :, -1]                                # [b,c,g,r]
    to_end = jnp.exp(last[:, :, None] - cum)
    add = jnp.einsum("bcjgr,bcjgrp,bcjgn->bcgrpn", to_end, dtx, b,
                     precision=hi)
    state = jnp.zeros((bsz, g, rep, p, n), f32) if s0 is None \
        else s0.astype(f32).reshape(bsz, g, rep, p, n)
    before = []
    for k in range(nc):     # the states the chunks start from
        before.append(state)
        state = jnp.exp(last[:, k])[..., None, None] * state + add[:, k]
    before = jnp.stack(before, axis=1)                  # [b,c,g,r,p,n]
    y = y + jnp.einsum("bcign,bcgrpn,bcigr->bcigrp", c, before,
                       jnp.exp(cum), precision=hi)
    return y.reshape(bsz, s + pad, nh, p)[:, :s], \
        state.reshape(bsz, nh, p, n)


def _grouped_rms(y, weight, groups: int, eps: float):
    """RMSNorm over each of ``groups`` equal runs of the last axis
    apart (float32 sums), one weight a channel."""
    shape = y.shape
    yf = y.astype(jnp.float32).reshape(shape[:-1] + (groups, -1))
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps)).reshape(shape) \
        * weight.astype(jnp.float32)


class Mamba2Mixer(Layer):
    """Mamba-2 selective state-space mixer: ``[z, xBC, dt] = W_in u``
    (widths ``d_inner``, ``d_inner + 2 G N``, ``heads``); ``xBC`` through
    a depthwise causal convolution of ``taps`` taps with a bias, then
    SiLU; ``[x, B, C] = split(xBC)``; ``dt = softplus(dt + dt_bias)``,
    ``A = -exp(A_log)``; the recurrence of :func:`ssm_scan` a head of
    ``head_dim`` on a state ``[head_dim, N]``, plus ``D x``; the gated
    group norm ``RMSNorm_groups(y * silu(z))`` (gate BEFORE the norm,
    the mean square over each of the ``G`` groups apart); ``W_out``. No
    position. Decoding carries two states a lane: the last ``taps - 1``
    columns of the pre-convolution ``xBC`` (in the activations' type)
    and the state matrices ``[heads, head_dim, N]`` in ``state_dtype``.

    A window of one position under ``decode`` is the one-step update
    (``kernels/ssm_update.py`` on a TPU, its ``jax.numpy`` form
    elsewhere); any other window is the chunked scan from the state it
    finds. The softplus, ``exp(dt A)``, the recurrence and the norm's
    sums are float32 whatever the activations' type."""
    scope = "ssm"

    def __init__(self, hidden: int, heads: int, head_dim: int, groups: int,
                 state_size: int, taps: int, chunk: int, eps: float,
                 std: float = 0.02, out_std: float = 0.02,
                 state_dtype="float32", dt_range=(1e-3, 0.1),
                 dt_floor: float = 1e-4):
        super().__init__()
        if heads % groups:
            raise ValueError(f"{heads} heads not divisible by {groups} "
                             "groups")
        self.heads, self.head_dim, self.groups = heads, head_dim, groups
        self.state_size, self.taps, self.chunk = state_size, taps, chunk
        self.eps = eps
        d_inner = self.d_inner = heads * head_dim
        conv_dim = self.conv_dim = d_inner + 2 * groups * state_size
        self.in_proj = spec_linear(hidden, d_inner + conv_dim + heads, std,
                                   P(None, "mp"), has_bias=False)
        self.conv = self.create_parameter(
            (conv_dim, taps),
            default_initializer=I.Normal(0.0, 1.0 / math.sqrt(taps)))
        self.conv.spec = P("mp", None)
        self.conv_bias = self.create_parameter((conv_dim,), is_bias=True)
        # the Mamba-2 publication's: A in [1, 16], dt log-uniform in
        # dt_range (its inverse softplus is the bias), D = 1
        u = jnp.linspace(0.0, 1.0, heads, dtype=jnp.float32)
        self.A_log = self.create_parameter(
            (heads,), default_initializer=I.Assign(jnp.log(1.0 + 15.0 * u)))
        dt = jnp.maximum(jnp.exp(math.log(dt_range[0]) + u * (
            math.log(dt_range[1]) - math.log(dt_range[0]))), dt_floor)
        self.dt_bias = self.create_parameter(
            (heads,), default_initializer=I.Assign(
                dt + jnp.log(-jnp.expm1(-dt))))
        self.D = self.create_parameter(
            (heads,), default_initializer=I.Constant(1.0))
        self.norm = self.create_parameter(
            (d_inner,), default_initializer=I.Constant(1.0))
        self.out_proj = spec_linear(d_inner, hidden, out_std, P("mp", None),
                                    has_bias=False)
        self.state_specs = (
            ((taps - 1, conv_dim), None),
            ((heads, head_dim, state_size), jnp.dtype(state_dtype)))

    # ------------------------------------------------------- raw bodies
    def _split(self, zxbcdt):
        d, c = self.d_inner, self.conv_dim
        return zxbcdt[..., :d], zxbcdt[..., d:d + c], zxbcdt[..., d + c:]

    def _conv(self, xbc, w, bias, prior):
        """Causal depthwise convolution then SiLU over the window
        ``xbc`` [b, s, C] that follows ``prior`` [b, taps - 1, C]."""
        with jax.named_scope("ssm_conv"):
            s = xbc.shape[1]
            both = jnp.concatenate([prior.astype(xbc.dtype), xbc], axis=1)
            wf = w.astype(jnp.float32)
            out = sum(both[:, j:j + s].astype(jnp.float32) * wf[:, j]
                      for j in range(self.taps)) + bias.astype(jnp.float32)
            return jax.nn.silu(out)

    def _xbc(self, conv_out):
        b, s, _ = conv_out.shape
        d, gn = self.d_inner, self.groups * self.state_size
        x = conv_out[..., :d].reshape(b, s, self.heads, self.head_dim)
        bm = conv_out[..., d:d + gn].reshape(b, s, self.groups, -1)
        cm = conv_out[..., d + gn:].reshape(b, s, self.groups, -1)
        return x, bm, cm

    @staticmethod
    def _dt(dt, dt_bias, real):
        """``softplus(dt + dt_bias)`` in float32, 0 where not ``real``
        (a padded position, an idle lane: the state passes it unchanged
        and takes nothing in)."""
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + dt_bias.astype(jnp.float32))
        return jnp.where(real[..., None], dt, 0.0)

    def _finish(self, y, x, z, d_skip, norm_w, dtype):
        """``D x``, the gate, the group norm: [b, s, d_inner] in the
        activations' type."""
        with jax.named_scope("ssm_norm"):
            y = y + d_skip.astype(jnp.float32)[:, None] * x
            y = y.reshape(y.shape[:2] + (self.d_inner,))
            y = y * jax.nn.silu(z.astype(jnp.float32))
            return _grouped_rms(y, norm_w, self.groups,
                                self.eps).astype(dtype)

    def _window(self, zxbcdt, w, bias, a_log, dt_bias, d_skip, norm_w,
                prior, s0, valid):
        """Any window by the chunked scan: (y [b, s, d_inner], the conv
        window after ``valid`` positions, the state after them)."""
        from ..generation.hybrid_cache import window_state
        z, xbc, dt = self._split(zxbcdt)
        b, s, _ = xbc.shape
        if prior is None:
            prior = jnp.zeros((b, self.taps - 1, self.conv_dim), xbc.dtype)
        if valid is None:
            valid = jnp.full((b,), s, jnp.int32)
        x, bm, cm = self._xbc(self._conv(xbc, w, bias, prior))
        dt = self._dt(dt, dt_bias, jnp.arange(
            s, dtype=jnp.int32)[None, :] < valid[:, None])
        with jax.named_scope("ssm_scan"):
            y, state = ssm_scan(x, dt, -jnp.exp(a_log.astype(jnp.float32)),
                                bm, cm, s0, self.chunk)
        return (self._finish(y, x, z, d_skip, norm_w, zxbcdt.dtype),
                window_state(prior, xbc, valid), state)

    def _step(self, zxbcdt, w, bias, a_log, dt_bias, d_skip, norm_w,
              prior, states, valid, layer):
        """One position a lane against the STACKED states ``states``
        [layers, b, heads, P, N]: (y [b, 1, d_inner], the conv window,
        the stacked states with ``layer``'s rows updated)."""
        from ..generation.hybrid_cache import window_state
        from ..kernels import ssm_update as _ssm
        z, xbc, dt = self._split(zxbcdt)
        x, bm, cm = self._xbc(self._conv(xbc, w, bias, prior))
        live = valid > 0
        dt = self._dt(dt[:, 0], dt_bias, live)
        a = -jnp.exp(a_log.astype(jnp.float32))
        kernel = jax.default_backend() == "tpu" and _ssm.supports(
            states.shape, self.groups, states.dtype)
        _monitor.record_ssm_path(kernel=kernel)
        with jax.named_scope("ssm_update"):
            if kernel:
                y, states = _ssm.ssm_update(states, layer, x[:, 0], dt, a,
                                            bm[:, 0], cm[:, 0], live)
            else:
                y, new = _ssm.ssm_update_reference(
                    states[layer].astype(jnp.float32), x[:, 0], dt, a,
                    bm[:, 0], cm[:, 0])
                states = states.at[layer].set(new.astype(states.dtype))
        return (self._finish(y[:, None], x, z, d_skip, norm_w,
                             zxbcdt.dtype),
                window_state(prior, xbc, valid), states)

    def forward(self, x, pos, cache=None, layer_idx=0, decode=False,
                block=None, valid=None):
        """x [b, s, hidden]. With a cache: (out, cache), the two states
        read from and handed back to ``cache.state``; ``valid`` [b]: how
        many of the window's positions are real."""
        if block is not None:
            raise NotImplementedError(
                "a causal scan under a block-causal mask")
        weights = (self.conv, self.conv_bias, self.A_log, self.dt_bias,
                   self.D, self.norm)
        zxbcdt = self.in_proj(x)
        if cache is None:
            y = dispatch(
                "mamba2_window",
                lambda a, *w: self._window(a, *w, None, None, None)[0],
                (zxbcdt,) + weights, {})
            return self.out_proj(y)
        window, states = cache.state
        if decode and x.shape[1] == 1:
            y, window, states = dispatch(
                "mamba2_step",
                functools.partial(self._step, layer=layer_idx),
                (zxbcdt,) + weights + (window[layer_idx], states, valid),
                {}, differentiable=False)
            return self.out_proj(y), cache.with_state(
                layer_idx, (window, None)).with_stacked(1, states)
        y, window, state = dispatch(
            "mamba2_window", self._window,
            (zxbcdt,) + weights + (window[layer_idx], states[layer_idx],
                                   valid), {}, differentiable=False)
        return self.out_proj(y), cache.with_state(layer_idx,
                                                  (window, state))


class Relu2MLP(Layer):
    """Dense ungated MLP: ``W_down relu(W_up u)^2``, no bias."""

    def __init__(self, hidden: int, width: int, std: float = 0.02,
                 out_std: float = 0.02):
        super().__init__()
        self.up_proj = spec_linear(hidden, width, std, P(None, "mp"),
                                   has_bias=False)
        self.down_proj = spec_linear(width, hidden, out_std, P("mp", None),
                                     has_bias=False)

    def forward(self, x):
        from ..nn import functional as F
        h = F.relu(self.up_proj(x))
        return self.down_proj(h * h)


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
    ``x + mlp(norm2(x))``; or either ALONE (``attn`` or ``mlp`` None: a
    model stated as a pattern of single-mixer blocks), with the one norm
    that goes with it. The mixer sits in the ``attn`` slot whatever its
    kind; its region in a device trace is the mixer's own ``scope``
    (``attn`` / ``short_conv`` / ``ssm``), the MLP's is ``mlp``."""

    def __init__(self, hidden: int, eps: float, attn: Layer = None,
                 mlp: Layer = None):
        super().__init__()
        if attn is None and mlp is None:
            raise ValueError("a block of nothing")
        self.attn = self.mlp = None
        if attn is not None:
            self.norm1 = RMSNorm(hidden, epsilon=eps)
            self.attn = attn
        if mlp is not None:
            self.norm2 = RMSNorm(hidden, epsilon=eps)
            self.mlp = mlp

    def forward(self, x, pos, cache=None, layer_idx=0, decode=False,
                block=None, valid=None):
        if self.attn is not None:
            with jax.named_scope(self.attn.scope):
                if cache is not None:
                    a, cache = self.attn(self.norm1(x), pos, cache=cache,
                                         layer_idx=layer_idx, decode=decode,
                                         block=block, valid=valid)
                else:
                    a = self.attn(self.norm1(x), pos, block=block)
                x = x + a
        if self.mlp is not None:
            with jax.named_scope("mlp"):
                x = x + self.mlp(self.norm2(x))
        return x if cache is None else (x, cache)


class DecoderTrunk(Layer):
    """Token embedding, the blocks, the final norm; drives the KV-cache
    protocol the serving surfaces use (prefill creates and fills the
    cache, decode consumes one: ``models/gpt.py``'s contract, with the
    kv heads' count as the cache's). The cache it creates has one KV
    layer for each mixer WITHOUT ``state_specs`` (the attention layers)
    and, if any mixer has them (a ``(shape, dtype)`` for each state it
    carries a lane; all stateful mixers of a model alike), a row of each
    state for each of those beside it
    (``generation.hybrid_cache.HybridCache``); every mixer is passed its
    own index among its kind. A block without a mixer has neither."""

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
        mixers = [blk.attn for blk in self.blocks]
        specs = {m.state_specs for m in mixers
                 if m is not None and m.state_specs is not None}
        if len(specs) > 1:
            raise ValueError(f"stateful mixers of different states in one "
                             f"model: {sorted(specs, key=repr)}")
        self._state_specs = next(iter(specs), None)
        self._cache_idx, n = [], {"kv": 0, "state": 0}
        for m in mixers:
            kind = None if m is None else \
                "kv" if m.state_specs is None else "state"
            self._cache_idx.append(n.get(kind, 0))
            if kind is not None:
                n[kind] += 1
        self._kv_layers, self._state_layers = n["kv"], n["state"]

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
            # the window's real positions (what a state may take in). A
            # lane at length 0 holds no request (a sequence enters
            # through a prefill; the engine pins dead slots there): its
            # states take nothing in, and a kernel may skip it
            valid = new_len - cache.kv_len
            if self._state_layers:
                valid = jnp.where(cache.kv_len > 0, valid, 0)
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
                                           self._state_specs, x._data.dtype)
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
