"""Plain reference for the ``gpt`` family: GPT-2 / GPT-3 decoder.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no cache, no batching tricks.  It imports nothing of the
program and takes nothing the program made: weights come from
:func:`make_params`, which the harness also uses (through the family
adapter) to fill the program, so both sides start from the same seed.

Architecture, as published (Radford et al. 2019; Brown et al. 2020, dense
attention in every layer): learned token and position embeddings, pre-LN
blocks (LayerNorm -> fused-bias QKV -> causal softmax attention ->
projection, LayerNorm -> 4h GELU(tanh) MLP), final LayerNorm, tied output
head, mean next-token cross-entropy.  AdamW with decoupled decay.

``quant`` selects the *control*: the same mathematics with both operands
of every matrix product rounded to a lower precision (``"fp8"``: e4m3
with a per-tensor scale), the step below bfloat16 that would tempt a later
PR.  ``None`` is the reference proper.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("ln1_g", "ln1_b", "wq", "wk", "wv", "bq", "bk", "bv", "wo",
              "bo", "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def param_shapes(cfg: dict) -> dict:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    n, v, p = cfg["num_layers"], cfg["vocab_size"], \
        cfg["max_position_embeddings"]
    mat = {"wq": (h, h), "wk": (h, h), "wv": (h, h), "wo": (h, h),
           "w1": (h, f), "w2": (f, h)}
    vec = {"ln1_g": h, "ln1_b": h, "bq": h, "bk": h, "bv": h, "bo": h,
           "ln2_g": h, "ln2_b": h, "b1": f, "b2": h}
    layers = {k: (n,) + s for k, s in mat.items()}
    layers.update({k: (n, s) for k, s in vec.items()})
    return {"wte": (v, h), "wpe": (p, h), "layers": layers,
            "lnf_g": (h,), "lnf_b": (h,)}


def make_params(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Weights from ``key`` (:func:`seed_key` of the run's seed) in the
    reference's own layout (layers stacked on a leading axis; q, k, v
    kept apart).  Normal(0, 0.02) everywhere, the
    two projections back into the residual scaled by 1/sqrt(2L) (GPT-2's
    rule), gains around 1, biases non-zero so that they are exercised.
    Traceable: the harness calls it inside one jitted program."""
    std = 0.02
    n = cfg["num_layers"]
    shapes = param_shapes(cfg)
    flat = [("wte", shapes["wte"]), ("wpe", shapes["wpe"]),
            ("lnf_g", shapes["lnf_g"]), ("lnf_b", shapes["lnf_b"])] + \
        [("layers." + k, shapes["layers"][k]) for k in LAYER_KEYS]
    keys = jax.random.split(key, len(flat))
    out = {"layers": {}}
    for (name, shape), key in zip(flat, keys):
        leaf = name.split(".")[-1]
        x = jax.random.normal(key, shape, jnp.float32) * std
        if leaf in ("wo", "w2"):
            x = x / math.sqrt(2.0 * n)
        if leaf.endswith("_g"):
            x = 1.0 + x
        x = x.astype(dtype)
        if name.startswith("layers."):
            out["layers"][leaf] = x
        else:
            out[name] = x
    return out


# ------------------------------------------------------------- precision

def _round(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    # straight-through: the backward pass sees the identity
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant):
    return jnp.matmul(_round(a, quant), _round(b, quant),
                      precision=HIGHEST)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lp, cfg, quant):
    """One pre-LN block on x [b, s, h] (float32)."""
    b, s, h = x.shape
    nh = cfg["num_heads"]
    d = h // nh
    eps = cfg["layer_norm_epsilon"]
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    y = _layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps)
    q = (_mm(y, lp["wq"], quant) + lp["bq"]).reshape(b, s, nh, d)
    k = (_mm(y, lp["wk"], quant) + lp["bk"]).reshape(b, s, nh, d)
    v = (_mm(y, lp["wv"], quant) + lp["bv"]).reshape(b, s, nh, d)
    att = jnp.einsum("bqnd,bknd->bnqk", _round(q, quant), _round(k, quant),
                     precision=HIGHEST) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jnp.where(causal, att, -jnp.inf)
    att = jax.nn.softmax(att, axis=-1)
    o = jnp.einsum("bnqk,bknd->bqnd", _round(att, quant), _round(v, quant),
                   precision=HIGHEST).reshape(b, s, h)
    x = x + _mm(o, lp["wo"], quant) + lp["bo"]
    y = _layer_norm(x, lp["ln2_g"], lp["ln2_b"], eps)
    y = _gelu_tanh(_mm(y, lp["w1"], quant) + lp["b1"])
    return x + _mm(y, lp["w2"], quant) + lp["b2"]


def hidden_states(params, ids, cfg, quant=None, remat=False):
    """Final-LayerNorm hidden states [b, s, h] for token ids [b, s]."""
    s = ids.shape[1]
    x = params["wte"].astype(jnp.float32)[ids] \
        + params["wpe"].astype(jnp.float32)[jnp.arange(s)]

    def body(x, lp):
        return _block(x, lp, cfg, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return _layer_norm(x, params["lnf_g"].astype(jnp.float32),
                       params["lnf_b"].astype(jnp.float32),
                       cfg["layer_norm_epsilon"])


def logits_at(params, ids, positions, cfg, quant=None):
    """Next-token logits [len(positions), vocab] of ONE sequence ids [s] at
    the given positions (position i predicts token i + 1).  Causal, so a
    right-padded ``ids`` changes nothing at earlier positions."""
    hid = hidden_states(params, ids[None], cfg, quant)[0]
    return _mm(hid[positions], params["wte"].astype(jnp.float32).T, quant)


def lm_loss_sum(params, ids, labels, cfg, quant=None):
    """Sum of next-token cross-entropies over a block of rows, and the
    count: the caller adds blocks and divides once (mean over all rows)."""
    hid = hidden_states(params, ids, cfg, quant, remat=True)
    logits = _mm(hid[:, :-1], params["wte"].astype(jnp.float32).T, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - gold), jnp.float32(gold.size)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant", "block"))
def _loss_and_grad(params, ids, labels, cfg_key, quant, block):
    cfg = dict(cfg_key)
    nblk = ids.shape[0] // block
    ids = ids.reshape(nblk, block, -1)
    labels = labels.reshape(nblk, block, -1)

    def one(carry, xs):
        (tot, cnt), g = jax.value_and_grad(
            lambda p: lm_loss_sum(p, xs[0], xs[1], cfg, quant),
            has_aux=True)(params)
        return (carry[0] + tot, carry[1] + cnt,
                jax.tree_util.tree_map(jnp.add, carry[2], g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (tot, cnt, g), _ = jax.lax.scan(
        one, (jnp.float32(0), jnp.float32(0), zero), (ids, labels))
    return tot / cnt, jax.tree_util.tree_map(lambda a: a / cnt, g)


def loss_and_grad(params, ids, labels, cfg, quant=None, block=2):
    """Mean loss over all rows and its gradient, rows taken ``block`` at a
    time so that float32 activations fit."""
    block = math.gcd(block, ids.shape[0])
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, bool))))
    return _loss_and_grad(params, ids, labels, key, quant, block)


@functools.partial(jax.jit, static_argnames=("hp_key",))
def _adamw(params, grads, m, v, step, hp_key):
    hp = dict(hp_key)
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["epsilon"]
    lr, wd = hp["learning_rate"], hp["weight_decay"]

    def upd(p, g, m_, v_):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * jnp.square(g)
        mhat = m_ / (1 - b1 ** step)
        vhat = v_ / (1 - b2 ** step)
        p = p * (1.0 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
        return p, m_, v_

    out = jax.tree_util.tree_map(upd, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def adamw_update(params, grads, m, v, step, hp: dict):
    """One AdamW step (decoupled decay on every leaf, as the configuration
    states), float32 throughout."""
    key = tuple(sorted((k, float(x)) for k, x in hp.items()
                       if isinstance(x, (int, float))))
    return _adamw(params, grads, m, v, jnp.float32(step), key)


def leaf_norms(tree) -> dict:
    """L2 norm of every leaf, one per layer for stacked leaves: the unit in
    which program and reference are compared ("the worst leaf")."""
    out = {}
    for name in ("wte", "wpe", "lnf_g", "lnf_b"):
        out[name] = jnp.sqrt(jnp.sum(jnp.square(
            tree[name].astype(jnp.float32))))[None]
    for k, x in tree["layers"].items():
        x = x.astype(jnp.float32)
        out[k] = jnp.sqrt(jnp.sum(jnp.square(x),
                                  axis=tuple(range(1, x.ndim))))
    return out
