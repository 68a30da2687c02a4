"""Driver for ``kind: closed_loop_counted``: ``closed_loop`` for a family
whose work is not GPT's and whose program counts its experts' rows.

It RUNS ``serve_loop.run`` and copies none of it.  What differs is
supplied from outside, through what the loop is handed:

* **weights** go in a layer at a time (``ref.layer_params``): the whole
  seeded set beside the model's own initial weights passes one chip, and
  the layers are of different kinds (``Run.fill_weights`` is shadowed on
  the run);
* **work** is counted with the family's own formula: ``serve_loop._work``
  reaches ``forward_flops`` through its module name ``work``, which is
  pointed at ``work_<family>.py`` for the length of the run;
* **kernel classes**: the trace is reduced once more per class of the
  family's ``KERNEL_CLASSES`` (``closed_loop_diffusion.trace_stop``, in
  ``Run.trace_stop``'s place);
* **the comparison** reads two numbers off ``check.serve_gaps``'s gaps
  (how far each served token's reference logit lies below the
  reference's best) where ``serve_loop.compare_serve`` reads one: the
  LARGEST gap, ``served_logit_gap``, and their MEAN over the compared
  tokens, ``served_logit_gap_mean``.  A sparse-expert model under
  bfloat16 flips a near-tied expert for a few tokens in a hundred, and
  such a token's gap is as large as a fault's that touches every token
  a little (q/k norms misplaced, the router's bias in the weights): the
  largest gap sees what wrecks a few tokens (a stale convolution
  state), the mean what shifts them all (PERF.md section 2);
* **counters**: the loop calls the family's ``lane_progress`` at the
  window's open, at the trace's start and at the close, behind a fence
  each time; the family handed to it notes the program's ``moe.*``
  counters there, and their deltas go to ``run.counters`` and
  ``records["counters_in_trace"]`` (the program drains them at its
  polls, every fourth step, so a delta may lag by that much).
"""
from __future__ import annotations

import os

import check
import closed_loop_diffusion
import common
import serve_loop

COMPARES = serve_loop.COMPARES
COUNTERS = ("moe.rows", "moe.expert_rows_max")


class Counted:
    """The family, its ``lane_progress`` noting ``COUNTERS`` as well."""

    def __init__(self, family):
        self._family = family
        self.seen = []          # one reading a call

    def __getattr__(self, name):
        return getattr(self._family, name)

    def lane_progress(self, engine) -> dict:
        lanes = self._family.lane_progress(engine)      # the fence
        self.seen.append(dict(
            {c: self._family.counter(c) for c in COUNTERS},
            submitted=engine.stats["submitted"]))
        return lanes


def fill_weights(r, model) -> None:
    """Weights from ``--seed``, made on the device in the type they are
    served in, the top leaves and then a layer at a time (one program a
    kind of layer): each layer's seeded weights replace the model's own
    before the next are made."""
    import jax
    import jax.numpy as jnp
    fam, ref, cfg = r.family, r.ref, r.cfg
    dtype = jnp.dtype(cfg["dtype"])
    k_top, k_layers = ref.param_keys(cfg, ref.seed_key(r.seed))
    fam.set_weights(model, jax.jit(lambda k: fam.top_layout(
        ref.top_params(cfg, k, dtype)))(k_top), part=True)
    make = {}
    for i, k in enumerate(k_layers):
        kind = ref.layer_kind(cfg, i)
        if kind not in make:
            make[kind] = jax.jit(
                lambda k, i=i: ref.layer_params(cfg, i, k, dtype))
        fam.set_weights(model, fam.layer_layout(i, make[kind](k)),
                        part=True)


def compare_serve(ref, cfg, seed, samples, width, quant=None):
    """``serve_loop.compare_serve`` with the mean gap beside the largest
    (the control's mean goes through the notes: ``run`` moves it)."""
    if not samples:
        return {}, {"compared_requests": 0}
    ids, nxt, mask = serve_loop.pack(samples, width)
    got = check.serve_gaps(ref, cfg, ref.seed_key(seed), ids, nxt, mask,
                           quant)
    n = int(mask.sum())
    out = {"served_logit_gap": float(got["served_gap"].max()),
           "served_logit_gap_mean": float(got["served_gap"].sum() / n)}
    notes = {"compared_requests": len(samples), "compared_tokens": n,
             "greedy_agree": got["greedy_agree"],
             "longest_compared": int(max(p.size + t.size
                                         for p, t in samples))}
    if quant is not None:
        notes["control_logit_gap"] = float(got["control_gap"].max())
        notes["control_logit_gap_mean"] = float(
            got["control_gap"].sum() / n)
    return out, notes


def run(r) -> None:
    family = r.family
    counted = Counted(family)
    r.family = counted
    r.fill_weights = lambda model: fill_weights(r, model)
    r.trace_stop = lambda: closed_loop_diffusion.trace_stop(r)
    theirs = serve_loop.work, serve_loop.compare_serve
    # the family's own counts, for the loop and for the metric readers
    r.family_work = serve_loop.work = common.load_module(
        os.path.join(common.HERE, f"work_{r.cfg['family']}.py"),
        f"work_{r.cfg['family']}")
    serve_loop.compare_serve = compare_serve
    try:
        serve_loop.run(r, open_loop=False)
    finally:
        serve_loop.work, serve_loop.compare_serve = theirs
        r.family = family
    if "control" in r.stand_ins:
        r.stand_ins["control"]["served_logit_gap_mean"] = \
            r.notes.pop("control_logit_gap_mean")
    opened, closed = counted.seen[0], counted.seen[-1]
    for c in COUNTERS:
        r.counters[c] = closed[c] - opened[c]
    if len(counted.seen) == 3:      # a traced run: open, trace, close
        r.records["counters_in_trace"] = {
            c: closed[c] - counted.seen[1][c] for c in COUNTERS}
    r.notes["moe"] = {c: r.counters[c] for c in COUNTERS}
    # all the engine was handed up to the close, warm-up included: what
    # a traffic file's pool_size is found from
    r.notes["submitted_by_close"] = closed["submitted"]
