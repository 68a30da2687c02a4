"""The serving loop shared by ``closed_loop`` and ``open_loop``: one thread
feeds ``ServingEngine.submit()`` and pumps ``ServingEngine.step()``.

The traffic's ``in_flight_at_start`` requests go in first, each met at a
uniform point of its life, then ``ramp_s`` of the traffic itself (both are
set-up), then the window opens.  Everything the window reports is taken
between its open and its close on ``time.monotonic``, the clock the
program stamps its requests with.  Output tokens are counted as observed:
what each request had emitted at the close less what it had at the open,
read from the device's per-lane counters behind a fence at both ends.
"""
from __future__ import annotations

import time

import numpy as np

import check
import common
import traffic as traffic_mod
import work

COMPARES = "serve"      # limits/<config>.serve.json
LATE_GRACE_S = 60.0     # how long past the close a due request is awaited
FAILED_MS = 60000.0     # the latency a failed or refused request is given


class Rec:
    """One request as the harness saw it; ``at_open`` / ``at_trace`` /
    ``at_close`` are the output tokens it had emitted at those moments."""
    __slots__ = ("prompt_len", "budget", "due", "submitted", "req",
                 "admit_call_t", "at_open", "at_trace", "at_close")

    def __init__(self, prompt_len, budget, due, submitted, req):
        self.prompt_len, self.budget = int(prompt_len), int(budget)
        self.due, self.submitted, self.req = due, submitted, req
        self.admit_call_t = None
        self.at_open = self.at_trace = self.at_close = 0


def _work(recs, start, cfg):
    """(output tokens, decode tokens, positions the decode tokens attended,
    forward FLOPs) between the reading named ``start`` and the close.
    Output token j of a request (the prefill's is 0) attends its prompt
    and the j tokens before it."""
    out = dec = att = 0
    flops = 0.0
    for x in recs:
        e0, e1 = getattr(x, start), x.at_close
        if e1 <= e0:
            continue
        out += e1 - e0
        if e0 == 0:         # its prefill ran in this span
            p = x.prompt_len
            flops += work.forward_flops(cfg, p, p * (p + 1) / 2.0, 1)
        a = max(e0, 1)
        n = e1 - a
        dec += n
        att += n * x.prompt_len + (a + e1 - 1) * n // 2
    flops += work.forward_flops(cfg, dec, att, dec)
    return out, dec, att, flops


def run(r, open_loop: bool) -> None:
    fam, ref, cfg, tr = r.family, r.ref, r.cfg, r.traffic
    span = r.spans.span
    gen = cfg["serve"]["generation"]
    reqs = traffic_mod.Requests(tr, cfg["vocab_size"], r.seed)

    # ---- set-up: weights from the seed, then the engine (it snapshots
    # them and warms every program it can dispatch)
    def at(what):
        common.log(f"{what} at {time.monotonic() - r.t_proc:.1f} s")

    at("driver start")
    model, make_engine = fam.build_engine(cfg)
    at("model built")
    r.fill_weights(model)
    at("weights set")
    engine = make_engine()
    at("engine up")
    mon = fam.monitor()
    mon.enable()
    # one real request through every prefill bucket and the decode step
    rng = np.random.default_rng([int(r.seed), 3])
    warm = [fam.submit(engine, rng.integers(
        0, cfg["vocab_size"], b, dtype=np.int64).astype(np.int32), 3)
        for b in gen["prefill_buckets"]]
    at("warm-up traffic submitted")
    while not all(w.done() for w in warm):
        t = time.monotonic()
        engine.step()
        if time.monotonic() - t > 0.5:
            at(f"a warm-up engine.step() of {time.monotonic() - t:.1f} s done")
    at("warm-up traffic done")

    # ---- the population in flight, the ramp, then the window, in one loop
    recs, waiting = [], []
    next_i = 0
    target = int(tr.get("queue_target", 0))
    t_start = time.monotonic()
    t_open = t_start + float(tr["ramp_s"])
    t_close = None
    opened = None           # stats at the open
    trace_step0 = None

    def submit(prompt, budget, due):
        try:
            req = fam.submit(engine, prompt, int(budget))
        except Exception as e:          # QueueFull: refused, counts failed
            common.log(f"submit refused: {type(e).__name__}: {e}")
            req = None
        rec = Rec(prompt.size, budget, due, time.monotonic(), req)
        recs.append(rec)
        if req is not None:
            waiting.append(rec)

    def next_due():
        i = next_i % len(reqs)
        return t_start + reqs.due[i] + (next_i // len(reqs)) * reqs.due[-1]

    def submit_next(now):
        nonlocal next_i
        i = next_i % len(reqs)
        submit(reqs.prompt(next_i), reqs.output_len[i],
               next_due() if open_loop else now)
        next_i += 1

    def emitted(name):
        """Fence, then note under ``name`` what every request has emitted."""
        lanes = fam.lane_progress(engine)
        for x in recs:
            if x.req is not None:
                setattr(x, name, int(x.req.n_emitted) if x.req.done()
                        else lanes.get(x.req.id, 0))
        return {"t": time.monotonic(), **engine.stats,
                "health": engine.health()}

    pump_s = []      # how long each scheduler iteration held the thread

    def pump(now):
        with span("engine.step"):
            engine.step()
        pump_s.append(time.monotonic() - now)
        for rec in [w for w in waiting if w.req.admitted_at is not None
                    or w.req.done()]:
            rec.admit_call_t = now
            waiting.remove(rec)

    for p, b in zip(reqs.start_prompts, reqs.start_output_len):
        submit(p, b, t_start)
    while True:
        now = time.monotonic()
        if opened is None and now >= t_open:
            opened = emitted("at_open")
            opened["compiles"] = fam.counter("jit.compile{cause=new_shape}")
            t_close = opened["t"] + r.seconds
            now = opened["t"]
        if opened is not None and now >= t_close:
            break
        if opened is not None and r.trace_due(opened["t"], now):
            trace_step0 = emitted("at_trace")["decode_steps"]
            r.trace_start()
        with span("generator"):
            if open_loop:
                while next_due() <= now:
                    submit_next(now)
            else:
                while len(waiting) < target:
                    submit_next(now)
        if not engine.busy:
            with span("wait.arrival"):
                time.sleep(max(0.0, min(next_due(), t_close or t_open)
                               - time.monotonic()))
            continue
        pump(now)
    with span("fence"):
        closed = emitted("at_close")
    t_end = closed["t"]
    r.trace_stop()
    r.window_s = t_end - opened["t"]
    r.setup_s = opened["t"] - r.t_proc
    r.counters["compiles_in_window"] = \
        fam.counter("jit.compile{cause=new_shape}") - opened["compiles"]

    # ---- every request due in the window is submitted and awaited past
    # its close: one that comes late is late, not wrong
    while open_loop and next_due() < t_end:
        submit_next(time.monotonic())
    in_window = [x for x in recs if opened["t"] <= x.due < t_end]
    t_grace = t_end + LATE_GRACE_S
    if open_loop:
        while time.monotonic() < t_grace and any(
                x.req is not None and x.req.first_token_at is None
                and not x.req.done() for x in in_window):
            pump(time.monotonic())

    # ---- what the scheduler and the device did in the window
    produced, dec_tok, _, flops = _work(recs, "at_open", cfg)
    s0, s1 = opened["decode_steps"], closed["decode_steps"]
    r.records.update(
        decode_steps_in_window=s1 - s0, decode_tokens_in_window=dec_tok,
        forward_flops_in_window=flops,
        prefills_in_window=closed["prefills"] - opened["prefills"])
    if trace_step0 is not None:
        _, tr_tok, tr_att, _ = _work(recs, "at_trace", cfg)
        r.records.update(steps_in_trace=s1 - trace_step0,
                         attended_in_trace=tr_att, tokens_in_trace=tr_tok)
    # ---- end-to-end numbers
    finished = [x for x in recs if x.req is not None and fam.completed(x.req)
                and opened["t"] <= x.req.finished_at <= t_end]
    # every output token emitted inside the window counts, also those of
    # requests still in flight at its close.  Requests last as long as the
    # window, so "finished in the window" would follow the seed's order of
    # long and short ones, not the system (PERF.md).
    out_tokens = sum(int(x.req.tokens.size) for x in finished)
    r.e2e["serve_tokens_per_s"] = produced / r.window_s
    tpot = [(x.req.finished_at - x.req.first_token_at) * 1e3
            / (x.req.tokens.size - 1) for x in finished
            if x.req.tokens.size > 1]
    if tpot:
        r.e2e["tpot_p95_ms"] = common.percentile(tpot, 95)
    late = [(x.submitted - x.due) * 1e3 for x in in_window]
    failed = {id(x) for x in recs if x.req is None or (
        x.req.done() and not fam.completed(x.req))}
    failed |= {id(x) for x in recs if x.req is not None and x.req.done()
               and x.req.tokens.size != x.budget}
    if open_loop and in_window:
        ttft = [FAILED_MS if (id(x) in failed
                             or x.req.first_token_at is None)
                else (x.req.first_token_at - x.due) * 1e3
                for x in in_window]
        r.e2e["ttft_p95_ms"] = common.percentile(ttft, 95)
        r.records["queue_waits_ms"] = [
            FAILED_MS if x.admit_call_t is None
            else max(x.admit_call_t - x.due, 0.0) * 1e3 for x in in_window]
        r.notes["ttft_ms"] = {"p50": common.percentile(ttft, 50),
                              "p95": r.e2e["ttft_p95_ms"], "n": len(ttft)}
        r.notes["generator_late_ms"] = {
            "p95": common.percentile(late, 95), "max": max(late)}
    r.attempted = len(in_window) if open_loop else len(finished) + len(failed)
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
        finished_tokens=out_tokens,
        at_open=state(opened), at_close=state(closed),
        tpot_ms={"p50": common.percentile(tpot, 50) if tpot else None,
                 "n": len(tpot)})

    common.log(f"window {r.window_s:.3f} s: {produced} tokens emitted, "
               f"{len(finished)} requests finished with {out_tokens}, "
               f"{s1 - s0} decode steps, in flight "
               f"{r.notes['at_open']['in_flight']} at the open and "
               f"{r.notes['at_close']['in_flight']} at the close")

    # ---- memory: allocator's peak plus the largest program's scratch
    progs = fam.engine_programs(engine)
    r.read_memory(max([common.temp_bytes(e) for e in progs.values()] + [0]))
    try:
        r.notes["kernels"] = {k: e.as_text().count("tpu_custom_call")
                              for k, e in progs.items()
                              if k == "step" or k.startswith("prefill")}
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
    samples = [(np.asarray(x.req.prompt), np.asarray(x.req.tokens))
               for x in pick]
    olen = tr["output_len"]
    width = int(tr["prompt_len"]["max"]) + int(olen.get("cap", olen["max"]))
    del progs, engine, model, warm, recs, finished, in_window, pick, rest
    del opened, closed
    freed = common.free_device()
    t_ref = time.monotonic()
    r.compared, notes = compare_serve(
        ref, cfg, r.seed, samples, width,
        cfg["control_precision"] if r.control else None)
    if r.control:
        r.stand_ins["control"] = {
            "served_logit_gap": notes.pop("control_logit_gap")}
    r.notes.update(notes, reference_s=round(time.monotonic() - t_ref, 2),
                   freed_bytes=freed)


def pack(samples, width):
    """(ids, next_ids, mask) [K, width]: each row is prompt + served tokens;
    position p-1+j is followed by served token j."""
    k = len(samples)
    ids = np.zeros((k, width), np.int32)
    nxt = np.zeros((k, width), np.int32)
    mask = np.zeros((k, width), bool)
    for row, (prompt, toks) in enumerate(samples):
        seq = np.concatenate([prompt, toks]).astype(np.int32)
        ids[row, :seq.size] = seq
        nxt[row, :seq.size - 1] = seq[1:]
        mask[row, prompt.size - 1:seq.size - 1] = True
    return ids, nxt, mask


def compare_serve(ref, cfg, seed, samples, width, quant=None):
    if not samples:
        return {}, {"compared_requests": 0}
    ids, nxt, mask = pack(samples, width)
    got = check.serve_gaps(ref, cfg, ref.seed_key(seed), ids, nxt, mask,
                           quant)
    out = {"served_logit_gap": float(got["served_gap"].max())}
    notes = {"compared_requests": len(samples),
             "compared_tokens": int(mask.sum()),
             "greedy_agree": got["greedy_agree"],
             "longest_compared": int(max(p.size + t.size
                                         for p, t in samples))}
    if quant is not None:
        notes["control_logit_gap"] = float(got["control_gap"].max())
    return out, notes
