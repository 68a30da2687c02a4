"""Driver loop for ``kind: train_steps``: one donated ``TrainStep`` per
iteration, dispatched without a per-step fence."""
from __future__ import annotations

import collections
import math
import time

import jax
import jax.numpy as jnp

import check
import common
import traffic as traffic_mod

COMPARES = "train"      # limits/<config>.train.json
WARM_STEPS = 2          # after the checked steps, before the window


def run(r) -> None:
    fam, ref, cfg, tr = r.family, r.ref, r.cfg, r.traffic
    span = r.spans.span
    dtype = jnp.dtype(cfg["dtype"])
    batches = traffic_mod.train_batches(tr, cfg["vocab_size"], r.seed)
    key = ref.seed_key(r.seed)

    # ---- set-up: the step with its state, weights from the seed
    model, opt, step = fam.build_trainer(cfg, tr["seq_len"])
    r.fill_weights(model)
    feed = [fam.batch_tensors(b) for b in batches]
    mon = fam.monitor()
    mon.enable()
    norms = jax.jit(lambda t: ref.leaf_norms(fam.to_canonical(t, cfg)))
    # the start point is made in a program of its own: inside a larger one
    # the TPU compiler may drop the f32 -> bf16 -> f32 round trip, and the
    # weights' rounding would be read as a change
    start = jax.jit(lambda k: ref.make_params(cfg, k, dtype))
    change = jax.jit(lambda t, s: ref.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        fam.to_canonical(t, cfg), s)))

    # ---- the first steps, through the window's own call and feed: they
    # compile, warm up, and give the numbers the reference is held to
    checked = int(tr["checked_steps"])
    prog = {"loss": []}
    for i in range(checked):
        with span("train.dispatch"):
            loss = step(*feed[i % len(feed)])
        prog["loss"].append(float(loss))
        if i == 0:
            m, _ = fam.train_state(step, cfg)
            scale = 1.0 / (1.0 - cfg["train"]["beta1"])
            prog["grad_norms"] = {k: v * scale for k, v in
                                  jax.device_get(norms(m)).items()}
            del m
    _, w = fam.train_state(step, cfg)
    prog["delta_norms"] = jax.device_get(change(w, start(key)))
    del w
    for i in range(WARM_STEPS):
        float(step(*feed[(checked + i) % len(feed)]))
    done = checked + WARM_STEPS

    # ---- the window
    compiles0 = fam.counter("jit.compile{cause=new_shape}")
    run_ahead = int(tr["run_ahead"])
    pending, losses, done_at = collections.deque(), [], []
    t0 = time.monotonic()
    r.setup_s = t0 - r.t_proc
    n = 0
    while True:
        now = time.monotonic()
        if now - t0 >= r.seconds:
            break
        if r.trace_due(t0, now):
            r.trace_start()
            r.records["trace_first_step"] = n
        with span("train.dispatch"):
            pending.append(step(*feed[(done + n) % len(feed)]))
        n += 1
        if len(pending) > run_ahead:
            with span("fence"):
                losses.append(float(pending.popleft()))
            done_at.append(time.monotonic())
    with span("fence"):
        for x in pending:
            losses.append(float(x))
            done_at.append(time.monotonic())
    t1 = time.monotonic()
    r.trace_stop()
    r.window_s = t1 - t0
    tokens = n * tr["batch"] * tr["seq_len"]
    r.e2e["train_tokens_per_s"] = tokens / r.window_s
    r.attempted = n
    r.failed = sum(not math.isfinite(x) for x in losses)
    r.counters["compiles_in_window"] = \
        fam.counter("jit.compile{cause=new_shape}") - compiles0
    r.records.update(steps=n, tokens=tokens, batch=tr["batch"],
                     seq_len=tr["seq_len"])
    gaps = sorted(b - a for a, b in zip([t0] + done_at, done_at))
    r.notes["step_gap_ms"] = {"median": gaps[len(gaps) // 2] * 1e3,
                              "largest": [g * 1e3 for g in gaps[-3:]]}
    common.log(f"window {r.window_s:.3f} s, {n} steps, last losses "
               f"{[round(x, 4) for x in losses[-3:]]}")

    # ---- memory: the allocator's peak plus the step program's scratch
    compiled = step.lower(*feed[0]).compile()
    r.read_memory(common.temp_bytes(compiled))
    r.notes["kernels_in_step"] = compiled.as_text().count("tpu_custom_call")
    mon.disable()

    # ---- free the program's state, then the reference
    del compiled, step, opt, model, feed, pending
    freed = common.free_device()
    key = ref.seed_key(r.seed)      # the old one went with the rest
    t_ref = time.monotonic()
    want = check.reference_train(
        ref, cfg, key, batches, checked,
        int(tr["reference_rows_per_block"]))
    r.compared, notes = check.compare_train(prog, want)
    if r.control:   # by hand: the control and the faults in the program's place
        block = int(tr["reference_rows_per_block"])
        for name, kw in (("control", {"quant": cfg["control_precision"]}),
                         ("half_batch", {"fault": "half_batch"}),
                         ("no_update", {"fault": "no_update"})):
            got = check.reference_train(ref, cfg, key, batches, checked,
                                        block, **kw)
            r.stand_ins[name] = check.compare_train(got, want)[0]
    r.notes.update(notes, reference_s=round(time.monotonic() - t_ref, 2),
                   freed_bytes=freed, loss_program=prog["loss"],
                   loss_reference=want["loss"])
