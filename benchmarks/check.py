"""What decides ``correct``: the timed path's output against the plain
reference, number by number, each with a limit of its own.

The numbers and the rule for each are the contract's ("How ``correct`` is
decided"); the limits live in ``limits/<config>.<train|serve>.json`` with
the readings they were set from in PERF.md.  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


# ---------------------------------------------------------------- training

def _flat(norms: dict) -> dict:
    """{leaf: [per layer]} -> {(leaf, layer): norm} as floats."""
    out = {}
    for k, v in norms.items():
        v = np.asarray(v, np.float64).reshape(-1)
        for i, x in enumerate(v):
            out[(k, i)] = float(x)
    return out


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple:
    """max over leaves of |‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf's
    ‖ref‖): the gap between the norms, not the norm of the difference.
    Returns (gap, leaf)."""
    p, r = _flat(prog), _flat(ref)
    med = float(np.median(list(r.values())))
    worst, where = 0.0, None
    for leaf, rv in r.items():
        if leaf in skip:
            continue
        gap = abs(p[leaf] - rv) / max(rv, med, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap > worst or where is None:
            worst, where = gap, leaf
    return worst, where


def round_off_leaves(ref_grad_norms: dict, share: float = 1e-3) -> set:
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's): under Adam they move by round-off
    alone, so their change is not compared.  A rule on the gradient, not a
    list of names (in GPT it finds the key bias, which softmax ignores)."""
    r = _flat(ref_grad_norms)
    med = float(np.median(list(r.values())))
    return {leaf for leaf, v in r.items() if v < share * med}


def reference_train(ref, cfg, key, batches, steps, block, quant=None,
                    fault=None) -> dict:
    """The reference through the first ``steps`` steps: each step's loss,
    the first gradient's leaf norms, and the leaf norms of the parameters'
    change after the last.  ``quant`` makes it the control; ``fault``
    plants one of the faults a training step can have:
    ``"half_batch"`` (the second half of the rows left out, the mean taken
    over the rest) or ``"no_update"`` (the state returned unchanged)."""
    hp = cfg["train"]
    init = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: ref.make_params(cfg, k, jnp.dtype(cfg["dtype"])))(
            key))
    params = init
    m = jax.tree_util.tree_map(jnp.zeros_like, init)
    v = jax.tree_util.tree_map(jnp.zeros_like, init)
    losses, grad_norms = [], None
    for i in range(steps):
        ids = jnp.asarray(batches[i % len(batches)])
        if fault == "half_batch":
            ids = ids[: ids.shape[0] // 2]
        loss, g = ref.loss_and_grad(params, ids, ids, cfg, quant, block)
        losses.append(float(loss))
        if i == 0:
            grad_norms = jax.device_get(jax.jit(ref.leaf_norms)(g))
        if fault != "no_update":
            params, m, v = ref.adamw_update(params, g, m, v, i + 1, hp)
        del g
    delta = jax.jit(lambda a, b: ref.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(params, init)
    return {"loss": losses, "grad_norms": grad_norms,
            "delta_norms": jax.device_get(delta)}


def compare_train(prog: dict, ref: dict) -> dict:
    """{number: value}: each step's relative loss gap, the worst leaf's
    first-gradient norm gap, the worst leaf's parameter-change norm gap."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        gap = abs(a - b) / abs(b)
        out[f"loss_gap_step{i + 1}"] = gap if np.isfinite(gap) \
            else float("inf")
    g, where = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    out["grad_norm_gap"] = g
    skip = round_off_leaves(ref["grad_norms"])
    d, where_d = worst_leaf_gap(prog["delta_norms"], ref["delta_norms"],
                                skip)
    out["param_change_gap"] = d
    notes = {"grad_worst_leaf": list(where), "change_worst_leaf":
             list(where_d), "round_off_leaves": sorted(
                 f"{k}[{i}]" for k, i in skip)[:8],
             "round_off_leaf_count": len(skip)}
    return out, notes


# ----------------------------------------------------------------- serving

def serve_gaps(ref, cfg, key, ids, next_ids, mask, quant=None) -> dict:
    """For K served sequences ``ids`` [K, T] (prompt then served tokens,
    right-padded), with ``next_ids[k, i]`` the token that followed
    position i and ``mask`` marking the positions whose next token was
    *served*: the gap by which each served token's reference logit lies
    below the reference's best.  With ``quant``, also the same gap for the
    token the lower precision puts first (the control need not decode)."""
    dtype = jnp.dtype(cfg["dtype"])

    def one(params, row):
        seq, nxt = row
        pos = jnp.arange(seq.shape[0])
        logits = ref.logits_at(params, seq, pos, cfg, None)
        best = jnp.max(logits, axis=-1)
        served = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        out = [best - served, jnp.argmax(logits, axis=-1)]
        if quant is not None:
            low = ref.logits_at(params, seq, pos, cfg, quant)
            first = jnp.argmax(low, axis=-1)
            out.append(best - jnp.take_along_axis(
                logits, first[:, None], axis=-1)[:, 0])
        return tuple(out)

    # weights in a program of their own, so that they are the rounded
    # values the program was given (see drivers/train_steps.py)
    params = jax.jit(lambda k: ref.make_params(cfg, k, dtype))(key)

    @jax.jit
    def run(params, ids, next_ids):
        return jax.lax.map(lambda row: one(params, row), (ids, next_ids))

    res = jax.device_get(run(params, jnp.asarray(ids),
                             jnp.asarray(next_ids)))
    mask = np.asarray(mask, bool)
    out = {"served_gap": np.where(mask, res[0], 0.0),
           "greedy_agree": float((res[1] == next_ids)[mask].mean())}
    if quant is not None:
        out["control_gap"] = np.where(mask, res[2], 0.0)
    return out


def verdict(compared: dict, limits: dict, not_compared=()) -> tuple:
    """({name: {"value", "limit"}}, correct).  A number without a limit in
    the cell's file fails: a limit is never guessed at run time.  A number
    the file lists under ``not_compared`` (no control and no fault reads
    above sound runs, PERF.md) is read and printed, and decides nothing."""
    rows, ok = {}, True
    for name, value in compared.items():
        if name in not_compared:
            rows[name] = {"value": value, "limit": "not compared"}
            continue
        limit = limits.get(name)
        rows[name] = {"value": value, "limit": limit}
        if limit is None or not (value <= limit):
            ok = False
    return rows, ok
