"""Plain reference for the ``sdar`` family: an SDAR-MoE decoder served by
block diffusion (JetLM/SDAR-30B-A3B-Chat, ``model_type: sdar_moe``).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no cache, no batching of lanes.  It imports nothing of the
program; weights come from :func:`make_params`, which the harness also
uses (through the family adapter) to fill the program.  The tier-1 tests
load this same file by path, so there is one reference.

Per layer, on hidden ``h`` [T, H], block length ``B``:

* ``x = RMSNorm(h; g1)``; ``q = x Wq`` [T, Hq, D], ``k = x Wk``,
  ``v = x Wv`` [T, Hkv, D], no biases; ``q <- RMSNorm_D(q; gq)``,
  ``k <- RMSNorm_D(k; gk)`` per head; rotary over the whole head
  (``rotate_half``), ``theta``, absolute positions; query head ``i`` uses
  key/value head ``i // (Hq // Hkv)``; softmax over
  ``{j : j // B <= i // B}`` (block-causal); ``h <- h + concat(a v) Wo``.
* ``y = RMSNorm(h; g2)``; ``p = softmax(y Wr)`` over all experts in
  float32; ``S`` = the ``k`` largest; ``w_e = p_e / sum_S p`` (if
  ``norm_topk_prob``); ``h <- h + sum_{e in S} w_e (silu(y Wg_e) *
  (y Wu_e)) Wd_e``.  No token is dropped, no shared expert.
* ``logits = RMSNorm(h; gf) Whead``, the head not tied to the embedding.

Generation (:func:`generate`; SDAR's ``generate.py``,
``low_confidence_static`` / ``low_confidence_dynamic``): the prompt's
``n // B`` whole blocks are context, the ``n mod B`` left over open the
first generated block; a denoise step is one forward of the sequence so
far, the logits *at* a masked position predict its token (no shift).

Departures from the published code, each on purpose:

* the logit of the mask id is set to ``-inf`` before the argmax and the
  softmax (with random weights the mask would be drawn once in ~V tokens
  and the block would never finish);
* the q/k head norms are Qwen3-MoE's, which SDAR is initialised from
  (config.json has no key for them);
* positions of the last block beyond the budget stay masked and are never
  unmasked (``generate.py`` denoises the whole block and cuts the text:
  the tokens kept would then depend on tokens never returned, which no
  comparison could rebuild);
* weights are kept in the type they are served in and widened to float32
  where they are used (4.4 B parameters in float32 pass one chip), the
  experts computed as a sum over *all* experts with zero weights for the
  ones not chosen, one expert at a time.

``quant="fp8"`` is the control: both operands of every matrix product
rounded to e4m3 with a per-tensor scale.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("g1", "wq", "wk", "wv", "gq", "gk", "wo", "g2", "wr",
              "wgu", "wd")


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def layer_shapes(cfg: dict) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    return {"g1": (h,), "wq": (h, hq * d), "wk": (h, hk * d),
            "wv": (h, hk * d), "gq": (d,), "gk": (d,), "wo": (hq * d, h),
            "g2": (h,), "wr": (h, e),
            # gate and up side by side: [E, H, 2F], gate first
            "wgu": (e, h, 2 * f), "wd": (e, f, h)}


def _draw(cfg, k, shape, leaf, dtype):
    """Normal(0, 0.02) (a rehearsal configuration of tiny widths states a
    larger ``init_std``: at width 64 the published 0.02 leaves every
    position its token's embedding and nothing else); the two projections
    back into the residual scaled by 1/sqrt(2L); gains around 1."""
    x = jax.random.normal(k, shape, jnp.float32) \
        * float(cfg.get("init_std", 0.02))
    if leaf in ("wo", "wd"):
        x = x / math.sqrt(2.0 * cfg["num_hidden_layers"])
    if leaf.startswith("g"):
        x = 1.0 + x
    return x.astype(dtype)


def param_keys(cfg: dict, key):
    """(key of the top leaves, [one key a layer])."""
    k_top, k_lay = jax.random.split(key)
    return k_top, jax.random.split(k_lay, cfg["num_hidden_layers"])


def top_params(cfg: dict, k_top, dtype=jnp.bfloat16) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    ke, kh, kg = jax.random.split(k_top, 3)
    return {"embed": _draw(cfg, ke, (v, h), "embed", dtype),
            "head": _draw(cfg, kh, (h, v), "head", dtype),
            "gf": _draw(cfg, kg, (h,), "gf", dtype)}


def layer_params(cfg: dict, k_layer, dtype=jnp.bfloat16) -> dict:
    shapes = layer_shapes(cfg)
    ks = jax.random.split(k_layer, len(LAYER_KEYS))
    return {leaf: _draw(cfg, k, shapes[leaf], leaf, dtype)
            for leaf, k in zip(LAYER_KEYS, ks)}


def make_params(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Weights from ``key`` in the reference's own layout: ``embed``,
    ``head``, ``gf`` and ``layers``, a list with one dict a layer (not
    stacked: a stacked leaf of six layers' experts could not be taken
    apart again on a chip it nearly fills).  Traceable; every leaf is
    drawn and rounded on its own, so the float32 draw of one leaf is the
    largest temporary.  :func:`top_params` and :func:`layer_params` give
    the same values piece by piece (the program is filled a layer at a
    time beside the weights it already holds)."""
    k_top, k_layers = param_keys(cfg, key)
    return {**top_params(cfg, k_top, dtype),
            "layers": [layer_params(cfg, k, dtype) for k in k_layers]}


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


def route(y, wr, cfg):
    """(weights [T, E] with zeros off the chosen experts, chosen [T, k]):
    float32 softmax over all experts, the k largest, renormalised."""
    p = jax.nn.softmax(jnp.matmul(y, wr.astype(jnp.float32),
                                  precision=HIGHEST), axis=-1)
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    w = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], idx].set(top)
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


def _layer(h, lp, pos, allowed, cfg, quant, past=None):
    """One layer on rows ``h`` [T, H] at positions ``pos``; ``allowed``
    [T, past + T] says which keys a row sees, the keys being ``past``'s
    (rotated K, V of earlier rows, when given) and then the rows' own.
    Returns (h, (k, v)) with the rows' own rotated K and V."""
    t = h.shape[0]
    d = cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    x = _rms(h, lp["g1"], eps)
    q = _rms(_mm(x, lp["wq"], quant).reshape(t, hq, d), lp["gq"], eps)
    k = _rms(_mm(x, lp["wk"], quant).reshape(t, hk, d), lp["gk"], eps)
    v = _mm(x, lp["wv"], quant).reshape(t, hk, d)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    own = (k, v)
    if past is not None:
        k = jnp.concatenate([past[0], k])
        v = jnp.concatenate([past[1], v])
    k = jnp.repeat(k, hq // hk, axis=1)      # query head i: kv head i // g
    v = jnp.repeat(v, hq // hk, axis=1)
    att = jnp.einsum("qnd,knd->nqk", _round(q, quant), _round(k, quant),
                     precision=HIGHEST) / math.sqrt(d)
    att = jax.nn.softmax(jnp.where(allowed[None], att, -jnp.inf), axis=-1)
    o = jnp.einsum("nqk,knd->qnd", _round(att, quant), _round(v, quant),
                   precision=HIGHEST).reshape(t, hq * d)
    h = h + _mm(o, lp["wo"], quant)
    y = _rms(h, lp["g2"], eps)
    w, _ = route(y, lp["wr"], cfg)
    return h + experts(y, w, lp["wgu"], lp["wd"], quant), own


def block_causal(t: int, block: int):
    """allowed[i, j] = j // B <= i // B."""
    b = jnp.arange(t) // block
    return b[None, :] <= b[:, None]


def _forward(params, ids, cfg, block, quant=None):
    """(final-norm hidden states [T, H], per layer the rotated K and V
    [L, T, Hkv, D] each) of one sequence under the block-causal mask."""
    t = ids.shape[0]
    pos = jnp.arange(t)
    allowed = block_causal(t, block)
    h = params["embed"].astype(jnp.float32)[ids]
    ks, vs = [], []
    for lp in params["layers"]:
        h, (k, v) = _layer(h, lp, pos, allowed, cfg, quant)
        ks.append(k)
        vs.append(v)
    return _rms(h, params["gf"], cfg["rms_norm_eps"]), \
        (jnp.stack(ks), jnp.stack(vs))


def hidden_states(params, ids, cfg, block, quant=None):
    """Final-norm hidden states [T, H] of one sequence ``ids`` [T] (which
    may hold the mask id: it is a token like any other) under the
    block-causal mask."""
    return _forward(params, ids, cfg, block, quant)[0]


def _block_length(cfg, block):
    return int(block or cfg["serve"]["block_diffusion"]["block_length"])


def _head(params, h, cfg, quant):
    logits = _mm(h, params["head"], quant)
    return logits.at[:, int(mask_id(cfg))].set(-jnp.inf)


def state_logits(params, ids, cfg, quant=None, at=None, block=None):
    """Logits [len(at), V] (all positions when ``at`` is None) of the full
    block-causal forward of ``ids`` [T], with the mask id's logit at
    ``-inf``.  Positions after the ones asked for may hold anything: under
    the block-causal mask nothing before them sees them."""
    h = hidden_states(params, ids, cfg, _block_length(cfg, block), quant)
    return _head(params, h if at is None else h[at], cfg, quant)


def prefix_kv(params, ids, cfg, quant=None, block=None):
    """The rotated K and V ([L, T, Hkv, D] each) of every layer of the
    full block-causal forward of ``ids``.  Under that mask a row depends
    on nothing after its own block, so for a request's FINAL sequence
    these are, before any block, what every earlier state of the request
    holds there: :func:`block_logits` evaluates one block's state on top
    of them, which is :func:`state_logits` of that state at the block's
    rows, computed once a request and not once a state (the tier-1 tests
    hold the two equal)."""
    return _forward(params, ids, cfg, _block_length(cfg, block), quant)[1]


def block_logits(params, kv, block_ids, start, cfg, quant=None):
    """Logits [B, V] of the block ``block_ids`` [B] at absolute positions
    ``start ..``, whose rows see ``kv``'s positions before ``start`` (what
    :func:`prefix_kv` gave; later ones are masked off) and each other."""
    b = block_ids.shape[0]
    pos = start + jnp.arange(b)
    width = kv[0].shape[1]
    allowed = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(width) < start, (b, width)),
        jnp.ones((b, b), bool)], axis=1)
    h = params["embed"].astype(jnp.float32)[block_ids]
    for i, lp in enumerate(params["layers"]):
        h, _ = _layer(h, lp, pos, allowed, cfg, quant,
                      past=(kv[0][i], kv[1][i]))
    return _head(params, _rms(h, params["gf"], cfg["rms_norm_eps"]), cfg,
                 quant)


def mask_id(cfg) -> int:
    return int(cfg["serve"]["block_diffusion"]["mask_token_id"])


def confidence(logits):
    """(x0, log c): the argmax and the log of its softmax probability."""
    x0 = jnp.argmax(logits, axis=-1)
    return x0, jnp.max(logits, axis=-1) - jax.nn.logsumexp(logits, axis=-1)


def n_unmask(gen: dict) -> int:
    return -(-int(gen["block_length"]) // int(gen["denoising_steps"]))


def choose(conf, cand, gen):
    """Which candidate positions a denoise step unmasks: bool like
    ``cand``.  Static: the ``ceil(B / steps)`` most confident candidates.
    Dynamic: every candidate above the threshold, and the most confident
    one always.  Ties go to the earlier position."""
    conf = np.where(cand, conf, -np.inf)
    order = np.argsort(-conf, kind="stable")
    if gen["remasking"] == "low_confidence_static":
        pick = order[:n_unmask(gen)]
    elif gen["remasking"] == "low_confidence_dynamic":
        tau = math.log(float(gen["confidence_threshold"]))
        pick = [j for j in order if conf[j] > tau] or [order[0]]
    else:
        raise ValueError(f"unknown remasking {gen['remasking']!r}")
    out = np.zeros(cand.shape, bool)
    out[np.asarray(pick, np.int64)] = True
    return out & cand


def generate(params, prompt, budget, cfg, gen=None, quant=None,
             logits_fn=None):
    """Block diffusion of one request, the slow plain way: every denoise
    step is a full forward of the sequence so far (there is no cache, so
    there is no commit step either: what the commit writes is what the
    next block's forward computes anyway).  Returns ``(tokens [budget],
    unmask_steps [budget], states)`` with ``states`` a list of ``(block
    start, step, ids of the sequence at that step, logits at the block,
    unmasked positions)`` for the tests."""
    gen = gen or cfg["serve"]["block_diffusion"]
    B, M = int(gen["block_length"]), int(gen["mask_token_id"])
    prompt = np.asarray(prompt, np.int64)
    n = prompt.size
    start = n // B * B
    total = n + int(budget)
    seq = np.full(-(-total // B) * B, M, np.int64)
    seq[:n] = prompt
    steps = np.full(seq.size, -1, np.int64)
    if logits_fn is None:
        logits_fn = jax.jit(lambda p, ids, at: state_logits(
            p, ids, cfg, quant, at, B))
    states = []
    while start < total:
        at = np.arange(start, start + B)
        step = 0
        while True:
            cand = (seq[at] == M) & (at < total)
            if not cand.any():
                break
            logits = logits_fn(params, jnp.asarray(seq[:start + B]),
                               jnp.asarray(at))
            x0, conf = (np.asarray(a) for a in confidence(logits))
            pick = choose(conf, cand, gen)
            states.append((start, step, seq[:start + B].copy(),
                           np.asarray(logits), pick))
            seq[at[pick]] = x0[pick]
            steps[at[pick]] = step
            step += 1
        start += B
    return seq[n:total].astype(np.int32), steps[n:total].astype(np.int8), \
        states


def rebuild_state(prompt, tokens, unmask_steps, block_start, step, gen):
    """The ids of the sequence up to the end of the block at
    ``block_start``, as they stood when denoise step ``step`` of that block
    ran: earlier blocks final, in this block the positions unmasked at an
    earlier step hold their tokens and the others the mask id.  Also the
    positions (absolute) still masked and inside the budget then."""
    B, M = int(gen["block_length"]), int(gen["mask_token_id"])
    prompt = np.asarray(prompt, np.int64)
    n, total = prompt.size, prompt.size + len(tokens)
    seq = np.full(block_start + B, M, np.int64)
    upto = min(total, block_start + B)
    full = np.concatenate([prompt, np.asarray(tokens, np.int64)])[:upto]
    when = np.concatenate([np.full(n, -1, np.int64),
                           np.asarray(unmask_steps, np.int64)])[:upto]
    seq[:upto] = full
    at = np.arange(block_start, upto)
    masked = at[when[at] >= step]
    seq[masked] = M
    return seq, masked


def request_states(prompt_len, n_tokens, unmask_steps, gen):
    """Every (block start, step) at which a denoise step ran for a
    request, from its ``unmask_steps``."""
    B = int(gen["block_length"])
    first = prompt_len // B * B
    out = []
    for b0 in range(first, prompt_len + n_tokens, B):
        lo, hi = max(b0, prompt_len), min(b0 + B, prompt_len + n_tokens)
        s = np.asarray(unmask_steps[lo - prompt_len:hi - prompt_len])
        out += [(b0, k) for k in range(int(s.max()) + 1)]
    return out
