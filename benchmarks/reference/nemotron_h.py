"""Plain reference for the ``nemotron_h`` family: a Nemotron-H decoder
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type: nemotron_h``),
served autoregressively, as ONE CHIP'S SHARE of it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no cache, no batching of lanes, the state-space layers as the
plain sequential recurrence (``lax.scan`` over the positions).  It imports
nothing of the program; weights come from :func:`make_params`, which the
harness also uses (through the family adapter) to fill the program.  The
tier-1 tests load this same file by path, so there is one reference.

On hidden ``h`` [T, H], block ``l`` is ``h <- h + mixer_l(RMSNorm(h; g))``
with ONE mixer, chosen by letter ``l`` of ``hybrid_override_pattern``:

* ``M`` (Mamba-2): ``[z, xBC, dt] = split(u W_in)`` with widths
  ``d_inner`` = ``mamba_num_heads x mamba_head_dim``, ``d_inner + 2 G N``
  (``G`` = ``n_groups``, ``N`` = ``ssm_state_size``), ``mamba_num_heads``,
  in that order; ``xBC_t <- silu(b_c + sum_j w_c[:, j] xBC_{t-(K-1)+j})``
  (depthwise, causal, ``K`` = ``conv_kernel`` taps, zeros before the
  sequence); ``[x, B, C] = split(xBC)`` with widths ``d_inner``, ``G N``,
  ``G N``: ``x_t`` as [heads, P], ``B_t`` / ``C_t`` as [G, N], head ``i``
  reading group ``i // (heads / G)``; ``dt_t = softplus(dt_t + dt_bias)``
  a head, ``A = -exp(A_log)`` a head; a head's state ``S`` [P, N]:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = S_t C_t + D x_t``; then the gated group norm
  ``y <- RMSNorm_groups(y * silu(z); gn)`` (the gate BEFORE the norm, the
  mean square over each of the ``G`` groups of ``d_inner / G`` channels
  apart); ``mixer = y W_out``.
* ``*`` (attention): ``q = u Wq`` [T, Hq, D], ``k = u Wk``, ``v = u Wv``
  [T, Hkv, D], no bias, no q/k norm and NO position embedding (the
  Mamba layers carry position; ``rope_theta`` is read by nothing); query
  head ``i`` reads kv head ``i // (Hq / Hkv)``; causal softmax at scale
  ``D ** -0.5``; ``mixer = concat(a v) Wo``.
* ``E`` (experts): ``s = sigmoid(u Wr)`` over all ``router_experts`` in
  float32; ``S`` = the ``num_experts_per_tok`` largest of ``s + b``
  (``b``: ``e_score_correction_bias``, in the SELECTION only; ``n_group``
  = ``topk_group`` = 1, so there is no group step);
  ``w_e = s_e / (sum_S s + 1e-20)`` times ``routed_scaling_factor``;
  each routed expert ``relu(u Wu_e)^2 Wd_e`` (no gate); the shared expert
  the same form at its own width, every token;
  ``mixer = shared(u) + sum_{e in S, e held} w_e expert_e(u)``.

Then ``logits = RMSNorm(h; gf) head`` (untied).

**The share** (``model-configs`` guide, section 4).  ``n_routed_experts``
experts are HELD, ``first_expert`` on, of the ``router_experts`` the
router ranks: the router, the top-k and the normalisation go over all of
them; what the experts held elsewhere would have added is LEFT OUT, and
that partial result goes on to the next block.  The shared expert is
whole.  The vocabulary is the slice the configuration states
(``vocab_size`` rows of the embedding and of the head).

Departures from the published code, each on purpose:

* weights are kept in the type they are served in and widened to float32
  where they are used, a layer and an expert at a time, the experts
  computed as a sum over all the HELD experts with zero weights for the
  ones not chosen;
* initialisation is this file's where the publication has none.  As the
  Mamba-2 publication has it: ``A_log = log(U(1, 16))``, ``dt_bias`` the
  inverse softplus of ``dt`` drawn log-uniform in [``time_step_min``,
  ``time_step_max``] and floored at ``time_step_floor``, ``D = 1``.
  Normal(0, 0.02) matrices, the projections back into the residual
  (``w_out``, ``wo``, ``wd``, ``ws_down``) over sqrt(2 L), norm gains
  around 1.  Then, each for a reason (``reference/lfm2.py`` argues the
  same choices with readings):

  - the convolution's taps Normal(0, 1 / sqrt(K)) and its bias
    Normal(0, 0.1): at 0.02 the convolution would pass nothing and no
    fault in its window could show; a zero bias tests nothing;
  - the routed experts' down projections a further quarter: under
    bfloat16 the router's near ties fall the other way for some tokens,
    and one expert swapped for another (or for one held elsewhere) moves
    the residual as no rounding does; at full scale that noise hides the
    control.  The price is the blind spot PERF.md section 7 states;
  - the selection bias Normal(``expert_bias_mean`` -0.75,
    ``expert_bias_std`` 0.05): not zeros.  The spread changes the chosen
    set for a visible share of tokens (``tests/test_nemotron_h.py``
    counts it); the mean changes no choice and no weight of a sound
    program, and a program that lets the bias into the weights divides
    by a sum near nought.

  :func:`layer_params` depends on a layer's KIND alone, not on its
  index: the harness makes one weight program a kind of layer.

``quant="fp8"`` is the control: both operands of every matrix product
rounded to e4m3 with a per-tensor scale (the recurrence, which is no
matrix product, stays float32).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_KEYS = {"M": ("g", "w_in", "conv_w", "conv_b", "a_log", "dt_bias", "d",
               "gn", "w_out"),
         "*": ("g", "wq", "wk", "wv", "wo"),
         "E": ("g", "wr", "bias", "wu", "wd", "ws_up", "ws_down")}


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def d_inner(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def router_width(cfg: dict) -> int:
    return int(cfg.get("router_experts", cfg["n_routed_experts"]))


def layer_kind(cfg: dict, i: int) -> str:
    """``M``, ``*`` or ``E``."""
    return cfg["hybrid_override_pattern"][i]


def layer_keys(cfg: dict, i: int) -> tuple:
    return _KEYS[layer_kind(cfg, i)]


def layer_shapes(cfg: dict) -> dict:
    """Every leaf a layer of any kind can hold."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    nh, di, cd = cfg["mamba_num_heads"], d_inner(cfg), conv_dim(cfg)
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    return {"g": (h,),
            "w_in": (h, di + cd + nh), "conv_w": (cd, cfg["conv_kernel"]),
            "conv_b": (cd,), "a_log": (nh,), "dt_bias": (nh,), "d": (nh,),
            "gn": (di,), "w_out": (di, h),
            "wq": (h, hq * d), "wk": (h, hk * d), "wv": (h, hk * d),
            "wo": (hq * d, h),
            "wr": (h, router_width(cfg)), "bias": (router_width(cfg),),
            "wu": (e, h, f), "wd": (e, f, h),
            "ws_up": (h, fs), "ws_down": (fs, h)}


def _draw(cfg, k, shape, leaf, dtype):
    """The module docstring's initialisation (a rehearsal configuration of
    tiny widths states a larger ``init_std``: at width 64 the published
    0.02 leaves every position its token's embedding and nothing else)."""
    if leaf == "d":
        return jnp.ones(shape, dtype)
    if leaf == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0,
                                          16.0)).astype(dtype)
    if leaf == "dt_bias":
        lo, hi = math.log(cfg["time_step_min"]), math.log(
            cfg["time_step_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, lo, hi)), cfg["time_step_floor"])
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    x = jax.random.normal(k, shape, jnp.float32)
    if leaf == "conv_w":
        x = x / math.sqrt(shape[-1])
    elif leaf == "conv_b":
        x = 0.1 * x
    elif leaf == "bias":
        x = float(cfg.get("expert_bias_mean", -0.75)) \
            + x * float(cfg.get("expert_bias_std", 0.05))
    else:
        x = x * float(cfg.get("init_std", 0.02))
    if leaf in ("w_out", "wo", "wd", "ws_down"):
        x = x / math.sqrt(2.0 * cfg["num_hidden_layers"])
    if leaf == "wd":
        x = x / 4.0
    if leaf in ("g", "gn", "gf"):
        x = 1.0 + x
    return x.astype(dtype)


def param_keys(cfg: dict, key):
    """(key of the top leaves, [one key a layer])."""
    k_top, k_lay = jax.random.split(key)
    return k_top, jax.random.split(k_lay, cfg["num_hidden_layers"])


def top_params(cfg: dict, k_top, dtype=jnp.bfloat16) -> dict:
    ke, kg, kh = jax.random.split(k_top, 3)
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return {"embed": _draw(cfg, ke, (v, h), "embed", dtype),
            "gf": _draw(cfg, kg, (h,), "gf", dtype),
            "head": _draw(cfg, kh, (h, v), "head", dtype)}


def layer_params(cfg: dict, i: int, k_layer, dtype=jnp.bfloat16) -> dict:
    """Layer ``i``'s leaves: those of its kind."""
    shapes, keys = layer_shapes(cfg), layer_keys(cfg, i)
    ks = jax.random.split(k_layer, len(keys))
    return {leaf: _draw(cfg, k, shapes[leaf], leaf, dtype)
            for leaf, k in zip(keys, ks)}


def make_params(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Weights from ``key`` in the reference's own layout: ``embed``,
    ``gf``, ``head`` and ``layers``, a list with one dict a layer (the
    layers are of different kinds and hold different leaves).  Traceable;
    every leaf is drawn and rounded on its own.  :func:`top_params` and
    :func:`layer_params` give the same values piece by piece."""
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


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(g)


# ---------------------------------------------------------------- mixers

def mamba2(u, lp, cfg, quant):
    """The Mamba-2 mixer on rows ``u`` [T, H] of one sequence from its
    start, by the sequential recurrence."""
    t = u.shape[0]
    nh, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    di, cd, taps = d_inner(cfg), conv_dim(cfg), cfg["conv_kernel"]
    zxbcdt = _mm(u, lp["w_in"], quant)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + cd], zxbcdt[:, di + cd:]
    w = _f32(lp["conv_w"])                                      # [C, K]
    padded = jnp.concatenate([jnp.zeros((taps - 1, cd), xbc.dtype), xbc])
    xbc = jax.nn.silu(_f32(lp["conv_b"]) + sum(
        padded[j:j + t] * w[:, j] for j in range(taps)))
    x = xbc[:, :di].reshape(t, nh, p)
    b = jnp.repeat(xbc[:, di:di + g * n].reshape(t, g, n), nh // g, axis=1)
    c = jnp.repeat(xbc[:, di + g * n:].reshape(t, g, n), nh // g, axis=1)
    dt = jax.nn.softplus(dt + _f32(lp["dt_bias"]))              # [T, heads]
    a = -jnp.exp(_f32(lp["a_log"]))

    def step(s, xs):
        x_t, b_t, c_t, dt_t = xs
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), jnp.float32),
                        (x, b, c, dt))
    y = y + _f32(lp["d"])[:, None] * x                          # [T, heads, P]
    y = y.reshape(t, di) * jax.nn.silu(z)
    yg = y.reshape(t, g, di // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
                            + cfg["layer_norm_epsilon"])
    return _mm(yg.reshape(t, di) * _f32(lp["gn"]), lp["w_out"], quant)


def attention(u, lp, cfg, quant):
    """Causal grouped-query attention on rows ``u`` [T, H], no positions."""
    t, d = u.shape[0], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = _mm(u, lp["wq"], quant).reshape(t, hq, d)
    k = _mm(u, lp["wk"], quant).reshape(t, hk, d)
    v = _mm(u, lp["wv"], quant).reshape(t, hk, d)
    k = jnp.repeat(k, hq // hk, axis=1)      # query head i: kv head i // g
    v = jnp.repeat(v, hq // hk, axis=1)
    att = jnp.einsum("qnd,knd->nqk", _round(q, quant), _round(k, quant),
                     precision=HIGHEST) / math.sqrt(d)
    pos = jnp.arange(t)
    allowed = pos[None, :] <= pos[:, None]
    att = jax.nn.softmax(jnp.where(allowed[None], att, -jnp.inf), axis=-1)
    o = jnp.einsum("nqk,knd->qnd", _round(att, quant), _round(v, quant),
                   precision=HIGHEST).reshape(t, hq * d)
    return _mm(o, lp["wo"], quant)


def route(u, wr, bias, cfg):
    """(weights [T, router width] with zeros off the chosen experts,
    chosen [T, k]): float32 sigmoid scores, the k largest of score +
    bias, weighted by the scores without it."""
    s = jax.nn.sigmoid(jnp.matmul(u, _f32(wr), precision=HIGHEST))
    _, idx = jax.lax.top_k(s + _f32(bias), cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * float(cfg["routed_scaling_factor"])
    w = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(top)
    return w, idx


def relu2_mlp(u, w_up, w_down, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(u, w_up, quant))), w_down, quant)


def experts(u, w, wu, wd, quant):
    """sum_e w[:, e] * relu(u Wu_e)^2 Wd_e over the experts given, one at
    a time (the weight is zero where e was not chosen)."""
    def one(acc, xs):
        w_e, wu_e, wd_e = xs
        return acc + w_e[:, None] * relu2_mlp(u, wu_e, wd_e, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (w.T, wu, wd))
    return out


def expert_layer(u, lp, cfg, quant):
    """The shared expert whole plus the HELD experts' part of the routed
    result (the module docstring's share)."""
    w, _ = route(u, lp["wr"], lp["bias"], cfg)
    first = int(cfg.get("first_expert", 0))
    held = w[:, first:first + cfg["n_routed_experts"]]
    return relu2_mlp(u, lp["ws_up"], lp["ws_down"], quant) \
        + experts(u, held, lp["wu"], lp["wd"], quant)


# ----------------------------------------------------------- whole model

def _layer(h, lp, cfg, quant):
    u = _rms(h, lp["g"], cfg["layer_norm_epsilon"])
    if "w_in" in lp:
        return h + mamba2(u, lp, cfg, quant)
    if "wq" in lp:
        return h + attention(u, lp, cfg, quant)
    return h + expert_layer(u, lp, cfg, quant)


def hidden_states(params, ids, cfg, quant=None):
    """Final-norm hidden states [T, H] of ONE sequence ``ids`` [T]."""
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[ids]
        for lp in params["layers"]:
            h = _layer(h, lp, cfg, quant)
        return _rms(h, params["gf"], cfg["layer_norm_epsilon"])


def logits_at(params, ids, positions, cfg, quant=None):
    """Next-token logits [len(positions), vocab] of ONE sequence ids [s] at
    the given positions (position i predicts token i + 1).  Causal, so a
    right-padded ``ids`` changes nothing at earlier positions."""
    hid = hidden_states(params, ids, cfg, quant)
    with jax.default_matmul_precision("highest"):
        return _mm(hid[positions], params["head"], quant)
