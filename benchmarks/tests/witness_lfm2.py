"""Two witnesses, at the cell's own size, for how ``reference/lfm2.py``
draws its weights (PERF.md sections 2 and 7).  Each is an otherwise
whole run of an ``lfm2`` cell with ``--control 1``; what is changed is
the reference (and, through it, the weights both sides are given),
never the program.  By hand, on the chip as on the CPU:

    python3 benchmarks/tests/witness_lfm2.py standard_init_bf16 \\
        --workload lfm2-l14-offline --seed 7 --seconds 20 --control 1

* ``standard_init_bf16``: the experts' down projections at 0.02 /
  sqrt(2L) like every other projection into the residual (not the
  further quarter), and in the control's place the reference with both
  operands of every product rounded to BFLOAT16.  If what sound runs
  read at that initialisation (a largest gap above the fp8 control's)
  is bfloat16 deciding the router's near ties, this control, which has
  no kernel, no cache and no engine, reads the same, on the same tokens,
  and the gap sits on the tokens whose 4th and 5th biased scores lie
  close in some layer.  The line ``witness {...}`` on standard error
  has, over the compared tokens: the share with a gap above nought
  (``flipped``) of the program and of the control, how many of the
  program's the control flips too, the control's largest gap on the
  tokens where it chose the float32 reference's experts in all layers
  and on the rest, and the program's and the control's largest gap and
  flipped share over the half of the tokens whose smallest router margin
  (4th less 5th biased score, the least over the expert layers, in the
  float32 reference) is widest and over the other half.
* ``experts_only_fp8``: the final initialisation; the control rounds to
  fp8 in the experts' products ALONE.  What it reads against the cell's
  limits says whether the quartered down projections left the
  comparison able to see the layer that is most of the step.
  ``experts_only_fp8_standard_init`` is the same control with the down
  projections at full scale: what the quarter costs.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "drivers")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _ref():
    return sys.modules["reference_lfm2"]


def _gaps_with_routes(ref, cfg, key, ids, next_ids, mask, quant=None):
    """``check.serve_gaps`` (the same numbers under the same names) that
    also notes, a layer and token, which experts each pass chose and the
    float32 pass's margin between the last chosen and the first left."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    k = int(cfg["num_experts_per_tok"])

    def forward(params, seq, q):
        seen, sound = [], ref.route

        def noting(y, wr, bias, c):
            w, idx = sound(y, wr, bias, c)
            s = jax.nn.sigmoid(jnp.matmul(y, wr.astype(jnp.float32),
                                          precision=ref.HIGHEST))
            s = s + bias.astype(jnp.float32)
            top = jax.lax.top_k(s, k + 1)[0]
            seen.append((jnp.sort(idx, axis=-1), top[:, k - 1] - top[:, k]))
            return w, idx

        ref.route = noting
        try:
            logits = ref.logits_at(params, seq, jnp.arange(seq.shape[0]),
                                   cfg, q)
        finally:
            ref.route = sound
        return logits, jnp.stack([s[0] for s in seen]), \
            jnp.stack([s[1] for s in seen])

    def one(params, row):
        seq, nxt = row
        logits, sets, margin = forward(params, seq, None)
        low, low_sets, _ = forward(params, seq, quant)
        best = jnp.max(logits, axis=-1)

        def below(tok):
            return best - jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]

        return (below(nxt), jnp.argmax(logits, axis=-1),
                below(jnp.argmax(low, axis=-1)),
                jnp.any(sets != low_sets, axis=(0, 2)),
                jnp.min(margin, axis=0))

    params = jax.jit(lambda kk: ref.make_params(
        cfg, kk, jnp.dtype(cfg["dtype"])))(key)
    res = jax.device_get(jax.jit(lambda p, a, b: jax.lax.map(
        lambda row: one(p, row), (a, b)))(
            params, jnp.asarray(ids), jnp.asarray(next_ids)))
    m = np.asarray(mask, bool)
    served, control, swapped, margin = (res[i][m] for i in (0, 2, 3, 4))
    wide = margin >= np.median(margin)

    def read(gap, where):
        g = gap[where]
        return {"tokens": int(g.size), "largest": float(g.max(initial=0.0)),
                "mean": float(g.mean()) if g.size else 0.0,
                "flipped": float((g > 0).mean()) if g.size else 0.0}

    print("witness " + json.dumps({
        "tokens": int(m.sum()),
        "program": read(served, slice(None)),
        "control": read(control, slice(None)),
        "program_flips_the_control_flips_too":
            float((control[served > 0] > 0).mean()),
        "control_swaps_an_expert": float(swapped.mean()),
        "control_where_no_expert_swapped": read(control, ~swapped),
        "control_where_one_swapped": read(control, swapped),
        "margin_median": float(np.median(margin)),
        "program_wide_margins": read(served, wide),
        "program_narrow_margins": read(served, ~wide),
        "control_wide_margins": read(control, wide),
        "control_narrow_margins": read(control, ~wide)}),
        file=sys.stderr, flush=True)
    return {"served_gap": np.where(m, res[0], 0.0),
            "greedy_agree": float((res[1] == next_ids)[m].mean()),
            "control_gap": np.where(m, res[2], 0.0)}


def _down_projections_at_full_scale(ref):
    import jax.numpy as jnp
    drawn = ref._draw

    def draw(cfg, k, shape, leaf, dtype):
        x = drawn(cfg, k, shape, leaf, jnp.float32)
        return (x * 4.0 if leaf == "wd" else x).astype(dtype)

    ref._draw = draw


def standard_init_bf16(fam):
    import jax.numpy as jnp
    import check
    ref = _ref()
    _down_projections_at_full_scale(ref)
    ref._round = lambda x, quant: x if quant is None else \
        x.astype(jnp.bfloat16).astype(jnp.float32)
    check.serve_gaps = _gaps_with_routes


def experts_only_fp8(fam):
    ref = _ref()
    for name in ("short_conv", "attention", "dense_mlp"):
        def unrounded(*a, _sound=getattr(ref, name)):
            return _sound(*a[:-1], None)
        setattr(ref, name, unrounded)

    def logits_at(params, ids, positions, cfg, quant=None):
        """The experts rounded, the head not: the head's product at the
        hidden states the rounded experts left."""
        hid = ref.hidden_states(params, ids, cfg, quant)
        return ref._mm(hid[positions],
                       params["embed"].astype("float32").T, None)

    ref.logits_at = logits_at


def experts_only_fp8_standard_init(fam):
    _down_projections_at_full_scale(_ref())
    experts_only_fp8(fam)


VARIANTS = {"standard_init_bf16": standard_init_bf16,
            "experts_only_fp8": experts_only_fp8,
            "experts_only_fp8_standard_init": experts_only_fp8_standard_init}

if __name__ == "__main__":
    import run as run_mod
    sys.exit(run_mod.main(sys.argv[2:], patch=VARIANTS[sys.argv[1]]))
