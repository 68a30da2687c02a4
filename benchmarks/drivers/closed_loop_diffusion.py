"""Driver for ``kind: closed_loop_diffusion``: the closed loop of
``serve_loop.py`` for an engine that generates by diffusion over blocks.

The loop itself is ``serve_loop.run``'s with ``open_loop=False`` (queue
kept topped up, the slots start full, one thread submits and pumps; what
can be imported from it is).  It is a copy because three things differ and
``serve_loop.py`` may not be edited (PERF.md section 7 lists what a
``benchmark`` issue should fold back):

* **weights** go in a layer at a time (``ref.layer_params``): the whole
  seeded set beside the model's own initial weights passes one chip;
* **work** is counted in position-forwards of the active parameters
  (``work_moe.py``): a lane yields 0..B tokens a step;
* **the comparison** rebuilds, for a seeded sample of the (block, step)
  states of each checked request, the ids the block held when that
  denoise step ran (from ``prompt``, ``tokens`` and ``unmask_steps``), runs
  the plain reference's forward of it (the rows before the block once a
  request, ``ref.prefix_kv``; the block's state on top, ``ref.block_logits``:
  the full forward's logits at the block's rows) and reads two numbers:
  ``served_logit_gap`` (how far the reference's logit of each token
  unmasked at that step lies below the reference's best there) and
  ``unmask_confidence_gap`` (under the reference's confidences, how far
  the least confident position the program unmasked lies below the most
  confident one it left masked, in log-probability).  Both are MEANS, over
  the compared tokens and over the compared states that had a choice: the
  largest single gap of a run is printed beside each and compared with
  nothing (over some 380 tokens it has a heavy tail: sound runs read
  0.008-0.094 and the fp8 control 0.20-0.36, which leaves no limit with
  room on both sides; PERF.md section 2).

A traced run also reduces the trace once per kernel class of the family
(``KERNEL_CLASSES``) for the per-class rooflines.
"""
from __future__ import annotations

import math
import os
import shutil
import time

import numpy as np

import common
import trace as trace_mod
import traffic as traffic_mod
import work_moe
from serve_loop import COMPARES, Rec  # noqa: F401  (COMPARES: run.py reads it)

STATES_PER_REQUEST = 32     # at least; the last block's come on top
WIDTH_STEP = 512            # reference forwards are padded to these widths


def _block_start(x, emitted, block):
    """Cache length at the block that holds output token ``emitted``."""
    return (x.prompt_len + emitted) // block * block


def _work(recs, start, cfg):
    """(output tokens, lane-forwards' attended positions, forward FLOPs)
    between the reading named ``start`` and the close: each token emitted
    is charged its share of its block's forwards (``work_moe.token_work``),
    a request admitted in the span its prompt's whole blocks."""
    block = int(cfg["serve"]["block_diffusion"]["block_length"])
    since = {"at_open": 1, "at_trace": 2}[start]
    out = 0
    pos = att = head = lane_att = 0.0
    for x in recs:
        if x.req is None:
            continue
        if x.admitted_after is not None and x.admitted_after >= since:
            p = x.prompt_len // block * block
            pos += p
            att += work_moe.block_causal_attended(p, block)
        e0, e1 = getattr(x, start), x.at_close
        out += max(e1 - e0, 0)
        for e in range(e0, e1):
            f, a, h = work_moe.token_work(cfg, _block_start(x, e, block))
            pos, att, head = pos + f, att + a, head + h
            lane_att += a / block       # a lane-forward serves B tokens
    return out, lane_att, work_moe.forward_flops(cfg, pos, att, head)


class DRec(Rec):
    """``Rec`` with the phase in which the request was admitted (0 before
    the open, 1 in the window, 2 in its traced part; None: not yet), for
    the prefill's share of the work."""
    __slots__ = ("admitted_after",)

    def __init__(self, *a):
        super().__init__(*a)
        self.admitted_after = None


def fill_weights(r, model) -> None:
    """Weights from ``--seed``, made on the device in the type they are
    served in, the top leaves and then a layer at a time: each layer's
    seeded weights replace the model's own before the next are made."""
    import jax
    import jax.numpy as jnp
    fam, ref, cfg = r.family, r.ref, r.cfg
    dtype = jnp.dtype(cfg["dtype"])
    k_top, k_layers = ref.param_keys(cfg, ref.seed_key(r.seed))
    fam.set_weights(model, jax.jit(lambda k: fam.top_layout(
        ref.top_params(cfg, k, dtype)))(k_top), part=True)
    make = jax.jit(lambda k: ref.layer_params(cfg, k, dtype))
    for i, k in enumerate(k_layers):
        fam.set_weights(model, fam.layer_layout(i, make(k)), part=True)


def trace_stop(r) -> None:
    """``Run.trace_stop`` with one more reduction per kernel class: the
    seconds of each class inside the ``step`` program go to
    ``r.records["kernel_class_s"]``."""
    import jax
    if r._tracing is None:
        return
    d, ann, t0 = r._tracing
    ann.__exit__(None, None, None)
    r.records["trace_host_s"] = time.monotonic() - t0
    jax.profiler.stop_trace()
    fam = r.family
    r.reduced, planes = trace_mod.reduce_trace(d, r.spans.names,
                                               fam.KERNEL_OP)
    per_class = {}
    for name, pattern in getattr(fam, "KERNEL_CLASSES", {}).items():
        prog = trace_mod.reduce_planes(planes, kernel_op=pattern).program(
            fam.PROGRAMS["decode_step"])
        if prog and sum(prog["kernel_s"]) > 0:
            per_class[name] = sum(prog["kernel_s"])
    r.records["kernel_class_s"] = per_class
    common.log(f"trace reduced: window {r.reduced.window_s:.3f} s, busy "
               f"{r.reduced.busy_s:.3f} s, kernel classes {per_class}")
    keep = os.environ.get("BENCH_KEEP_TRACE")
    if keep:   # by hand only: a copy of the reduction's input
        os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
        trace_mod.save_planes(planes, keep)
    shutil.rmtree(d, ignore_errors=True)
    r._tracing = None


def run(r) -> None:
    fam, ref, cfg, tr = r.family, r.ref, r.cfg, r.traffic
    span = r.spans.span
    gen = cfg["serve"]["generation"]
    vocab = int(tr.get("id_limit", cfg["vocab_size"]))
    reqs = traffic_mod.Requests(tr, vocab, r.seed)

    def at(what):
        common.log(f"{what} at {time.monotonic() - r.t_proc:.1f} s")

    at("driver start")
    model, make_engine = fam.build_engine(cfg)
    at("model built")
    fill_weights(r, model)
    at("weights set")
    engine = make_engine()
    at("engine up")
    mon = fam.monitor()
    mon.enable()
    rng = np.random.default_rng([int(r.seed), 3])
    warm = [fam.submit(engine, rng.integers(
        0, vocab, b, dtype=np.int64).astype(np.int32), 9)
        for b in gen["prefill_buckets"]]
    while not all(w.done() for w in warm):
        t = time.monotonic()
        engine.step()
        if time.monotonic() - t > 0.5:
            at(f"a warm-up engine.step() of {time.monotonic() - t:.1f} s done")
    at("warm-up traffic done")

    recs, waiting = [], []
    next_i = 0
    target = int(tr.get("queue_target", 0))
    t_start = time.monotonic()
    t_open = t_start + float(tr["ramp_s"])
    t_close = None
    opened = None
    traced_at = None
    counters = ("jit.compile{cause=new_shape}", "gen.diffusion.forwards",
                "gen.diffusion.unmasked", "gen.diffusion.commits",
                "moe.rows", "moe.expert_rows_max")

    def submit(prompt, budget):
        try:
            req = fam.submit(engine, prompt, int(budget))
        except Exception as e:          # QueueFull: refused, counts failed
            common.log(f"submit refused: {type(e).__name__}: {e}")
            req = None
        now = time.monotonic()
        rec = DRec(prompt.size, budget, now, now, req)
        recs.append(rec)
        if req is not None:
            waiting.append(rec)

    def emitted(name):
        """Fence, then note under ``name`` what every request has emitted."""
        lanes = fam.lane_progress(engine)
        for x in recs:
            if x.req is not None:
                setattr(x, name, int(x.req.n_emitted) if x.req.done()
                        else lanes.get(x.req.id, 0))
        return {"t": time.monotonic(), **engine.stats,
                "health": engine.health(),
                "counters": {c: fam.counter(c) for c in counters}}

    pump_s = []
    phase = [0]     # 0 before the open, 1 in the window, 2 in the trace

    def pump(now):
        with span("engine.step"):
            engine.step()
        pump_s.append(time.monotonic() - now)
        for rec in [w for w in waiting if w.req.admitted_at is not None
                    or w.req.done()]:
            rec.admit_call_t = now
            rec.admitted_after = phase[0]
            waiting.remove(rec)

    for p, b in zip(reqs.start_prompts, reqs.start_output_len):
        submit(p, b)
    while True:
        now = time.monotonic()
        if opened is None and now >= t_open:
            opened = emitted("at_open")
            phase[0] = 1
            t_close = opened["t"] + r.seconds
            now = opened["t"]
        if opened is not None and now >= t_close:
            break
        if opened is not None and r.trace_due(opened["t"], now):
            traced_at = emitted("at_trace")
            phase[0] = 2
            r.trace_start()
        with span("generator"):
            while len(waiting) < target:
                submit(reqs.prompt(next_i),
                       reqs.output_len[next_i % len(reqs)])
                next_i += 1
        pump(now)
    with span("fence"):
        closed = emitted("at_close")
    t_end = closed["t"]
    trace_stop(r)
    r.window_s = t_end - opened["t"]
    r.setup_s = opened["t"] - r.t_proc

    def delta(name, since=opened):
        return closed["counters"][name] - since["counters"][name]

    r.counters["compiles_in_window"] = delta("jit.compile{cause=new_shape}")
    for c in counters[1:]:
        r.counters[c] = delta(c)

    # ---- what the scheduler and the device did in the window
    produced, _, flops = _work(recs, "at_open", cfg)
    s0, s1 = opened["decode_steps"], closed["decode_steps"]
    r.records.update(
        decode_steps_in_window=s1 - s0, decode_tokens_in_window=produced,
        forward_flops_in_window=flops,
        prefills_in_window=closed["prefills"] - opened["prefills"],
        submitted=len(recs))
    if traced_at is not None:
        tr_tok, tr_att, _ = _work(recs, "at_trace", cfg)
        r.records.update(
            steps_in_trace=s1 - traced_at["decode_steps"],
            lane_attended_in_trace=tr_att, tokens_in_trace=tr_tok,
            counters_in_trace={c: delta(c, traced_at)
                               for c in counters[1:]})

    # ---- end-to-end numbers (as serve_loop: every token emitted inside
    # the window counts; tpot over the requests finished in it)
    finished = [x for x in recs if x.req is not None and fam.completed(x.req)
                and opened["t"] <= x.req.finished_at <= t_end]
    out_tokens = sum(int(x.req.tokens.size) for x in finished)
    r.e2e["serve_tokens_per_s"] = produced / r.window_s
    tpot = [(x.req.finished_at - x.req.first_token_at) * 1e3
            / (x.req.tokens.size - 1) for x in finished
            if x.req.tokens.size > 1]
    if tpot:
        r.e2e["tpot_p95_ms"] = common.percentile(tpot, 95)
    mask = int(cfg["serve"]["block_diffusion"]["mask_token_id"])
    failed = {id(x) for x in recs if x.req is None or (
        x.req.done() and not fam.completed(x.req))}
    failed |= {id(x) for x in recs if x.req is not None and x.req.done()
               and (x.req.tokens.size != x.budget
                    or (x.req.tokens == mask).any()
                    or x.req.unmask_steps is None
                    or x.req.unmask_steps.size != x.budget
                    or (x.req.unmask_steps < 0).any())}
    r.attempted = len(finished) + len(failed)
    r.failed = len(failed)

    def state(s):
        h = s["health"]
        return {"in_flight": h["slots_busy"], "queued": h["queue_depth"],
                "cache_tokens_held": h["capacity_tokens"] - h["free_tokens"],
                "cache_tokens": h["capacity_tokens"]}

    r.notes.update(
        engine_step_ms={"p50": common.percentile(pump_s, 50) * 1e3,
                        "largest": [x * 1e3 for x in sorted(pump_s)[-3:]]},
        finished_in_window=len(finished), output_tokens=produced,
        finished_tokens=out_tokens, submitted=len(recs),
        at_open=state(opened), at_close=state(closed),
        tpot_ms={"p50": common.percentile(tpot, 50) if tpot else None,
                 "n": len(tpot)},
        diffusion={c: r.counters[c] for c in counters[1:]})
    common.log(f"window {r.window_s:.3f} s: {produced} tokens emitted, "
               f"{len(finished)} requests finished with {out_tokens}, "
               f"{s1 - s0} steps, {len(recs)} submitted, in flight "
               f"{r.notes['at_open']['in_flight']} at the open and "
               f"{r.notes['at_close']['in_flight']} at the close")

    # ---- memory: allocator's peak plus the largest program's scratch
    progs = fam.engine_programs(engine)
    r.read_memory(max([common.temp_bytes(e) for e in progs.values()] + [0]))
    try:
        r.notes["kernels"] = {k: e.as_text().count("tpu_custom_call")
                              for k, e in progs.items()
                              if "step" in k or k.startswith("prefill")}
    except Exception as e:      # a loaded executable may carry no text
        r.notes["kernels"] = f"unavailable: {type(e).__name__}"
    mon.disable()

    # ---- the sample that is compared: drawn from the seed among the
    # requests the window finished, the longest always in it
    k = int(tr["checked_requests"])
    pick = sorted(finished, key=lambda x: -(x.req.prompt.size
                                            + x.req.tokens.size))[:1]
    rest = [x for x in finished if x not in pick]
    srng = np.random.default_rng([int(r.seed), 4])
    pick += [rest[j] for j in srng.permutation(len(rest))[:k - 1]]
    samples = [(np.asarray(x.req.prompt), np.asarray(x.req.tokens),
                np.asarray(x.req.unmask_steps)) for x in pick]
    del progs, engine, model, warm, recs, finished, pick, rest
    del opened, closed, traced_at
    freed = common.free_device()
    t_ref = time.monotonic()
    r.compared, notes, control = compare_diffusion(
        ref, cfg, r.seed, samples,
        cfg["control_precision"] if r.control else None)
    if r.control:
        r.stand_ins["control"] = control
    r.notes.update(notes, reference_s=round(time.monotonic() - t_ref, 2),
                   freed_bytes=freed)


# ------------------------------------------------------------ comparison

def sample_states(ref, prompt, tokens, usteps, gen, rng):
    """At least STATES_PER_REQUEST (block start, step) states of one
    request, the last block's always among them."""
    states = ref.request_states(prompt.size, tokens.size, usteps, gen)
    last = states[-1][0]
    keep = [s for s in states if s[0] == last]
    rest = [s for s in states if s[0] != last]
    more = max(STATES_PER_REQUEST - len(keep), 0)
    keep += [rest[j] for j in rng.permutation(len(rest))[:more]]
    return sorted(keep)


def _left_out_gap(conf, picked, left, gen):
    """How far, under confidences ``conf`` (log-probabilities by absolute
    position), the unmasking of ``picked`` while ``left`` stayed masked
    departs from the rule; 0 when it follows it."""
    if gen["remasking"] == "low_confidence_static":
        if not len(left) or not len(picked):
            return 0.0
        return max(float(conf[left].max() - conf[picked].min()), 0.0)
    tau = math.log(float(gen["confidence_threshold"]))
    best = max(np.concatenate([picked, left]), key=lambda j: conf[j])
    over = [conf[j] - tau for j in left if conf[j] > tau]
    under = [tau - conf[j] for j in picked if j != best and conf[j] <= tau]
    return max([0.0] + over + under)


def compare_diffusion(ref, cfg, seed, samples, quant=None):
    """({number: value}, notes, the control's numbers or None)."""
    import jax
    import jax.numpy as jnp
    if not samples:
        return {}, {"compared_requests": 0}, None
    gen = cfg["serve"]["block_diffusion"]
    block = int(gen["block_length"])
    dtype = jnp.dtype(cfg["dtype"])
    # weights in a program of their own, so that they are the rounded
    # values the program was given
    params = jax.jit(lambda k: ref.make_params(cfg, k, dtype))(
        ref.seed_key(seed))
    prefix = jax.jit(lambda p, ids, q: ref.prefix_kv(p, ids, cfg, q, block),
                     static_argnums=2)
    on_top = jax.jit(lambda p, kv, ids, start, q: ref.block_logits(
        p, kv, ids, start, cfg, q), static_argnums=4)
    rng = np.random.default_rng([int(seed), 5])
    gaps, conf_gaps = [], []            # per token; per state with a choice
    c_gaps, c_conf_gaps = [], []        # the control's
    n_states = agree = 0
    for prompt, tokens, usteps in samples:
        total = prompt.size + tokens.size
        ids = np.zeros(-(-total // WIDTH_STEP) * WIDTH_STEP, np.int32)
        ids[:total] = np.concatenate([prompt, tokens])
        kv = prefix(params, jnp.asarray(ids), None)
        kv_low = prefix(params, jnp.asarray(ids), quant) if quant else None
        for start, step in sample_states(ref, prompt, tokens, usteps, gen,
                                         rng):
            seq, masked = ref.rebuild_state(prompt, tokens, usteps, start,
                                            step, gen)
            at = np.arange(start, start + block)
            state = jnp.asarray(seq[start:].astype(np.int32))
            logits = np.asarray(on_top(params, kv, state, start, None))
            conf = np.full(start + block, -np.inf)
            conf[at] = logits.max(-1) - _lse(logits)
            when = usteps[masked - prompt.size]
            picked, left = masked[when == step], masked[when > step]
            served = tokens[picked - prompt.size]
            rows = picked - start
            gaps += list(logits[rows].max(-1) - logits[rows, served])
            agree += int((logits[rows].argmax(-1) == served).sum())
            if len(left) and len(picked):
                conf_gaps.append(_left_out_gap(conf, picked, left, gen))
            n_states += 1
            if quant:
                # what the lower precision would have served from the same
                # state: its argmax at the positions it would unmask
                lo = np.asarray(on_top(params, kv_low, state, start, quant))
                lconf = np.full(start + block, -np.inf)
                lconf[at] = lo.max(-1) - _lse(lo)
                cand = np.zeros(start + block, bool)
                cand[masked] = True
                mine = ref.choose(lconf[at], cand[at], gen)
                mine, rest = at[mine], at[cand[at] & ~mine]
                c_gaps += list(logits[mine - start].max(-1) - logits[
                    mine - start, lo[mine - start].argmax(-1)])
                if len(rest) and len(mine):
                    c_conf_gaps.append(_left_out_gap(conf, mine, rest, gen))

    def numbers(g, c):
        return {"served_logit_gap": float(np.mean(g)) if g else 0.0,
                "unmask_confidence_gap": float(np.mean(c)) if c else 0.0,
                "served_logit_gap_max": float(np.max(g)) if g else 0.0,
                "unmask_confidence_gap_max": float(np.max(c)) if c else 0.0}

    notes = {"compared_requests": len(samples), "compared_states": n_states,
             "compared_choices": len(conf_gaps),
             "compared_tokens": len(gaps),
             "greedy_agree": agree / max(len(gaps), 1),
             "longest_compared": int(max(p.size + t.size
                                         for p, t, _ in samples))}
    return numbers(gaps, conf_gaps), notes, \
        (numbers(c_gaps, c_conf_gaps) if quant else None)


def _lse(x):
    m = x.max(-1)
    return m + np.log(np.exp(x - m[..., None]).sum(-1))
