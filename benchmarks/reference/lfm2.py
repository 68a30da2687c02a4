"""Plain reference for the ``lfm2`` family: an LFM2-MoE decoder
(LiquidAI/LFM2-8B-A1B, ``model_type: lfm2_moe``), served autoregressively.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no cache, no batching of lanes.  It imports nothing of the
program; weights come from :func:`make_params`, which the harness also
uses (through the family adapter) to fill the program.  The tier-1 tests
load this same file by path, so there is one reference.

On hidden ``h`` [T, H], layer ``l`` of ``layer_types``:

* ``u = RMSNorm(h; g1)``, ``h <- h + mixer_l(u)``:

  - ``conv``: ``[B, C, x] = split3(u W_in)`` (``W_in``: H -> 3H, in that
    order along the output); ``z = B * x``;
    ``c_t = sum_{j<L} taps[:, j] * z_{t-(L-1)+j}`` (depthwise, causal,
    ``z`` = 0 before the sequence); ``mixer = (C * c) W_out``.  No bias,
    no activation, no position.
  - ``full_attention``: ``q = u Wq`` [T, Hq, D], ``k = u Wk``, ``v = u Wv``
    [T, Hkv, D], no bias; ``q <- RMSNorm_D(q; gq)``, ``k <- RMSNorm_D(k;
    gk)`` per head, BEFORE rotary; rotary over the whole head
    (``rotate_half``), ``rope_theta``, absolute positions; query head
    ``i`` reads kv head ``i // (Hq // Hkv)``; causal softmax at scale
    ``D ** -0.5``; ``mixer = concat(a v) Wo``.

* ``y = RMSNorm(h; g2)``, ``h <- h + ffn_l(y)``: for ``l <
  num_dense_layers`` ``(silu(y W1) * (y W3)) W2``; else
  ``s = sigmoid(y Wr)`` over all experts in float32, ``S`` = the
  ``num_experts_per_tok`` largest of ``s + b`` (``b``: the per-expert
  bias, in the SELECTION only), ``w_e = s_e / (sum_S s + 1e-6)`` (if
  ``norm_topk_prob``) times ``routed_scaling_factor``,
  ``ffn = sum_{e in S} w_e (silu(y Wg_e) * (y Wu_e)) Wd_e``.  No shared
  expert, no token dropped.
* ``logits = RMSNorm(h; gf) embed^T``: the head is the embedding.

Departures from the published code, each on purpose:

* weights are kept in the type they are served in and widened to float32
  where they are used, a layer and an expert at a time (4.7 B parameters
  in float32 pass one chip), the experts computed as a sum over *all*
  experts with zero weights for the ones not chosen;
* the head is tied: ``config.json`` as catalogued has no key for it and
  the published 8.3 B count needs it;
* initialisation is this file's, and chosen so that the comparison can
  see what it has to (PERF.md section 2 has the readings). Normal(0, 0.02)
  matrices, the projections back into the residual over sqrt(2 L), the
  block norms' gains around 1. Then, each for a reason:

  - the convolution's taps Normal(0, 1 / L): at 0.02 the mixer would be
    nought to rounding and no fault in its state could show;
  - the experts' down projections a further quarter: under bfloat16 the
    router's near ties fall the other way (at the cell's size this
    reference itself, its products' operands rounded to bfloat16, picks
    another expert than in float32 in some layer for 64% of the tokens),
    and one expert swapped for another moves the residual as no rounding
    does; at full scale sound runs agree with this reference on 73% of
    the greedy tokens, and no limit separates them from the fp8 control.
    The price: fp8 in the experts' products ALONE then reads under a
    sound run (``tests/witness_lfm2.py``; PERF.md section 7); a fault
    that changes which experts answer, or with what weight, does not;
  - the q/k head norms' gains 1 + Normal(0, 0.6): under uniform gains a
    norm before rotary and one after it are the same function;
  - the per-expert bias Normal(``expert_bias_mean`` -0.75,
    ``expert_bias_std`` 0.05): not zeros, a zero bias tests nothing. The
    spread changes the chosen set for a visible share of tokens
    (``tests/test_lfm2.py`` counts it). The mean changes no choice (the
    largest of ``s + b`` are the largest of ``s + b + c``) and no weight
    of a sound program; a trained bias has one (nothing pins it); a
    program that lets the bias into the weights divides by a sum near
    nought.

  :func:`layer_params` depends on a layer's KINDS alone, not on its
  index: the harness makes one weight program a kind of layer.

``quant="fp8"`` is the control: both operands of every matrix product
rounded to e4m3 with a per-tensor scale.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_MIXER = {"conv": ("w_in", "taps", "w_out"),
          "full_attention": ("wq", "wk", "wv", "gq", "gk", "wo")}
_FFN = {"dense": ("w1", "w3", "w2"), "moe": ("wr", "bias", "wgu", "wd")}


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_kind(cfg: dict, i: int) -> tuple:
    """(mixer kind, ffn kind) of layer ``i``."""
    return cfg["layer_types"][i], \
        "dense" if i < cfg["num_dense_layers"] else "moe"


def layer_keys(cfg: dict, i: int) -> tuple:
    mixer, ffn = layer_kind(cfg, i)
    return ("g1",) + _MIXER[mixer] + ("g2",) + _FFN[ffn]


def layer_shapes(cfg: dict) -> dict:
    """Every leaf a layer of any kind can hold."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, e, fe = cfg["intermediate_size"], cfg["num_experts"], \
        cfg["moe_intermediate_size"]
    return {"g1": (h,), "g2": (h,),
            "w_in": (h, 3 * h), "taps": (h, cfg["conv_L_cache"]),
            "w_out": (h, h),
            "wq": (h, hq * d), "wk": (h, hk * d), "wv": (h, hk * d),
            "gq": (d,), "gk": (d,), "wo": (hq * d, h),
            "w1": (h, f), "w3": (h, f), "w2": (f, h),
            "wr": (h, e), "bias": (e,),
            # gate and up side by side: [E, H, 2F], gate first
            "wgu": (e, h, 2 * fe), "wd": (e, fe, h)}


def _draw(cfg, k, shape, leaf, dtype):
    """The module docstring's initialisation (a rehearsal configuration of
    tiny widths states a larger ``init_std``: at width 64 the published
    0.02 leaves every position its token's embedding and nothing else)."""
    x = jax.random.normal(k, shape, jnp.float32)
    if leaf == "taps":
        x = x / math.sqrt(shape[-1])
    elif leaf == "bias":
        x = float(cfg.get("expert_bias_mean", -0.75)) \
            + x * float(cfg.get("expert_bias_std", 0.05))
    elif leaf in ("gq", "gk"):
        x = 1.0 + 0.6 * x
    else:
        x = x * float(cfg.get("init_std", 0.02))
    if leaf in ("wo", "w_out", "w2", "wd"):
        x = x / math.sqrt(2.0 * cfg["num_hidden_layers"])
    if leaf == "wd":
        x = x / 4.0
    if leaf in ("g1", "g2", "gf"):
        x = 1.0 + x
    return x.astype(dtype)


def param_keys(cfg: dict, key):
    """(key of the top leaves, [one key a layer])."""
    k_top, k_lay = jax.random.split(key)
    return k_top, jax.random.split(k_lay, cfg["num_hidden_layers"])


def top_params(cfg: dict, k_top, dtype=jnp.bfloat16) -> dict:
    ke, kg = jax.random.split(k_top)
    return {"embed": _draw(cfg, ke, (cfg["vocab_size"], cfg["hidden_size"]),
                           "embed", dtype),
            "gf": _draw(cfg, kg, (cfg["hidden_size"],), "gf", dtype)}


def layer_params(cfg: dict, i: int, k_layer, dtype=jnp.bfloat16) -> dict:
    """Layer ``i``'s leaves: those of its mixer kind and its ffn kind."""
    shapes, keys = layer_shapes(cfg), layer_keys(cfg, i)
    ks = jax.random.split(k_layer, len(keys))
    return {leaf: _draw(cfg, k, shapes[leaf], leaf, dtype)
            for leaf, k in zip(keys, ks)}


def make_params(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Weights from ``key`` in the reference's own layout: ``embed``,
    ``gf`` and ``layers``, a list with one dict a layer (the layers are
    of different kinds and hold different leaves).  Traceable; every leaf
    is drawn and rounded on its own, so the float32 draw of one leaf is
    the largest temporary.  :func:`top_params` and :func:`layer_params`
    give the same values piece by piece (the program is filled a layer at
    a time beside the weights it already holds)."""
    k_top, k_layers = param_keys(cfg, key)
    return {**top_params(cfg, k_top, dtype),
            "layers": [layer_params(cfg, i, k, dtype)
                       for i, k in enumerate(k_layers)]}


def leaf_norms(tree) -> dict:
    """L2 norm of every leaf, ``{leaf: [one per layer that has it]}``."""
    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    out = {k: norm(v)[None] for k, v in tree.items() if k != "layers"}
    for lp in tree["layers"]:
        for k, v in lp.items():
            out.setdefault(k, []).append(norm(v))
    return {k: jnp.stack(v) if isinstance(v, list) else v
            for k, v in out.items()}


# ------------------------------------------------------------- precision

def _round(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, quant):
    return jnp.matmul(_round(a, quant), _round(b.astype(jnp.float32), quant),
                      precision=HIGHEST)


def _rms(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotary over the whole head, ``rotate_half`` convention: x [T, N, D],
    pos [T]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


# ---------------------------------------------------------------- mixers

def short_conv(u, lp, quant):
    """The gated short convolution on rows ``u`` [T, H] of one sequence
    from its start."""
    t, h = u.shape
    bcx = _mm(u, lp["w_in"], quant)
    gate_b, gate_c, x = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
    z = gate_b * x
    taps = lp["taps"].astype(jnp.float32)                      # [H, L]
    n = taps.shape[1]
    zp = jnp.concatenate([jnp.zeros((n - 1, h), z.dtype), z])
    c = sum(zp[j:j + t] * taps[:, j] for j in range(n))
    return _mm(gate_c * c, lp["w_out"], quant)


def attention(u, lp, pos, cfg, quant):
    """Causal grouped-query attention on rows ``u`` [T, H] at ``pos``."""
    t = u.shape[0]
    d = head_dim(cfg)
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["norm_eps"]
    q = _rms(_mm(u, lp["wq"], quant).reshape(t, hq, d), lp["gq"], eps)
    k = _rms(_mm(u, lp["wk"], quant).reshape(t, hk, d), lp["gk"], eps)
    v = _mm(u, lp["wv"], quant).reshape(t, hk, d)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    k = jnp.repeat(k, hq // hk, axis=1)      # query head i: kv head i // g
    v = jnp.repeat(v, hq // hk, axis=1)
    att = jnp.einsum("qnd,knd->nqk", _round(q, quant), _round(k, quant),
                     precision=HIGHEST) / math.sqrt(d)
    allowed = pos[None, :] <= pos[:, None]
    att = jax.nn.softmax(jnp.where(allowed[None], att, -jnp.inf), axis=-1)
    o = jnp.einsum("nqk,knd->qnd", _round(att, quant), _round(v, quant),
                   precision=HIGHEST).reshape(t, hq * d)
    return _mm(o, lp["wo"], quant)


# ------------------------------------------------------------------ ffns

def dense_mlp(y, lp, quant):
    return _mm(jax.nn.silu(_mm(y, lp["w1"], quant)) * _mm(y, lp["w3"], quant),
               lp["w2"], quant)


def route(y, wr, bias, cfg):
    """(weights [T, E] with zeros off the chosen experts, chosen [T, k]):
    float32 sigmoid scores, the k largest of score + bias, weighted by
    the scores without it."""
    s = jax.nn.sigmoid(jnp.matmul(y, wr.astype(jnp.float32),
                                  precision=HIGHEST))
    biased = s + bias.astype(jnp.float32) if cfg["use_expert_bias"] else s
    _, idx = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-6)
    top = top * float(cfg["routed_scaling_factor"])
    w = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(top)
    return w, idx


def experts(y, w, wgu, wd, quant):
    """sum_e w[:, e] * (silu(y Wg_e) * (y Wu_e)) Wd_e, one expert at a
    time over all of them (the weight is zero where e was not chosen)."""
    f = wd.shape[1]

    def one(acc, xs):
        w_e, wgu_e, wd_e = xs
        gu = _mm(y, wgu_e, quant)
        z = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        return acc + w_e[:, None] * _mm(z, wd_e, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (w.T, wgu, wd))
    return out


# ----------------------------------------------------------- whole model

def _layer(h, lp, pos, cfg, quant):
    u = _rms(h, lp["g1"], cfg["norm_eps"])
    if "w_in" in lp:
        h = h + short_conv(u, lp, quant)
    else:
        h = h + attention(u, lp, pos, cfg, quant)
    y = _rms(h, lp["g2"], cfg["norm_eps"])
    if "w1" in lp:
        return h + dense_mlp(y, lp, quant)
    w, _ = route(y, lp["wr"], lp["bias"], cfg)
    return h + experts(y, w, lp["wgu"], lp["wd"], quant)


def hidden_states(params, ids, cfg, quant=None):
    """Final-norm hidden states [T, H] of ONE sequence ``ids`` [T]."""
    pos = jnp.arange(ids.shape[0])
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(jnp.float32)[ids]
        for lp in params["layers"]:
            h = _layer(h, lp, pos, cfg, quant)
        return _rms(h, params["gf"], cfg["norm_eps"])


def logits_at(params, ids, positions, cfg, quant=None):
    """Next-token logits [len(positions), vocab] of ONE sequence ids [s] at
    the given positions (position i predicts token i + 1).  Causal, so a
    right-padded ``ids`` changes nothing at earlier positions."""
    hid = hidden_states(params, ids, cfg, quant)
    with jax.default_matmul_precision("highest"):
        return _mm(hid[positions], params["embed"].astype(jnp.float32).T,
                   quant)
