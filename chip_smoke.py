#!/usr/bin/env python3
"""Smoke run of the main path on the attached TPU: GPT-2 small, full
width and depth, random weights from ``--seed``.

    python chip_smoke.py               # one chip: train + serve
    python chip_smoke.py --four-chips  # four chips: the fleet step only

One process, no child that needs JAX. It refuses to start unless JAX's
first device is a TPU; there is no CPU branch and no smaller model.

One chip runs two phases through the entry points a user would call:

* ``train``: ``paddle.jit.TrainStep`` on ``gpt("gpt2-small")`` at b16
  s1024, bf16 weights with fp32 master weights, fused LM loss, AdamW
  (the configuration of ``bench_gpt2`` and ``examples/gpt2_pretrain.py``).
  Pass: every loss finite, the last below the first, and the compiled
  step holds the flash kernel (``tpu_custom_call``).
* ``serve.<kind>``: ``inference.Config`` -> ``ServingEngine`` -> ragged
  ``submit()`` -> ``result()``, for each engine kind that owns a kernel
  (dense, paged, int8 dense, int8 paged, chunked prefill over a bf16 and
  over an int8 cache). Pass: every request completes with the tokens it
  asked for; greedy output equals sequential ``Predictor.generate()``
  under the same cache dtype, or, where the streams part, both tokens at
  the first difference sit within the bounded-logit gate of
  ``tests/test_quant_cache.py::test_int8_logit_error_bounded`` of the
  top logit; no compile after ``warmup()``; the decode program holds
  the Pallas kernel; the page free-list is conserved at drain.

``--four-chips`` runs ``fleet.DistributedTrainStep`` (mp=2 x sharding=2,
ZeRO stage 2) against single-device ``TrainStep`` on the same seed and
batch, and nothing else.

Every phase prints one JSON line. Every phase runs even after an earlier
one failed, and any failure makes the exit code non-zero. The last line
of standard output is ``{"ok": ..., "device": {...}}`` with the device
as JAX reports it. None of the times printed here is a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback

import numpy as np

MODEL = "gpt2-small"
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 16, 1024, 5
#: |loss_fleet - loss_single| <= this * loss_single at every step. bf16
#: keeps 8 bits of mantissa (eps 2**-8 = 0.39%); the tensor-parallel
#: all-reduce and the sharded update change the order of bf16 sums, not
#: the math, so the two runs may differ by a few eps and no more.
FLEET_LOSS_RTOL = 1e-2

# serve traffic: ragged prompts, some shorter than the small bucket, two
# in the 512 bucket (one exactly 512, so prefill takes the flash path
# with no padding), more requests than slots so admission happens
# mid-decode
SERVE_BUCKETS = (64, 512)
SERVE_MAX_NEW = 16
SERVE_MAX_BATCH = 4
SERVE_PROMPT_LENS = (9, 40, 64, 300, 512, 23)
SERVE_BUDGETS = (16, 8, 16, 12, 16, 5)
SERVE_CHUNK = 128
ENGINE_KINDS = {
    "dense": {},
    "paged": {"paged": True, "kv_page_size": 128},
    "int8": {"kv_cache_dtype": "int8"},
    "int8_paged": {"kv_cache_dtype": "int8", "paged": True,
                   "kv_page_size": 128},
    "chunked": {"prefill_chunk_tokens": SERVE_CHUNK},
    "chunked_int8": {"prefill_chunk_tokens": SERVE_CHUNK,
                     "kv_cache_dtype": "int8"},
}

KERNEL = "tpu_custom_call"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter")


def emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


class CacheEvents:
    """JAX's own persistent-cache events: a hit is an executable read
    from the cache dir, a miss is one compiled and written to it."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses}


def memory(dev) -> dict:
    """Allocator counters of one device; a TPU that reports none is a
    failure, not something to estimate."""
    stats = dev.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        raise RuntimeError(f"{dev} reports no memory_stats(): {stats!r}")
    return {"bytes_in_use": int(stats["bytes_in_use"]),
            "peak_bytes_in_use": int(stats["peak_bytes_in_use"])}


def program_bytes(compiled) -> dict:
    """What the compiler planned for one program: the allocator's peak
    can be read against it."""
    plan = compiled.memory_analysis()
    return {"argument": int(plan.argument_size_in_bytes),
            "output": int(plan.output_size_in_bytes),
            "temp": int(plan.temp_size_in_bytes),
            "alias": int(plan.alias_size_in_bytes)}


def counter(name: str) -> int:
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot().get(name)
    return int(snap["value"]) if snap else 0


def token_batch(vocab: int, batch: int, seq: int, seed: int):
    import paddle_tpu as paddle
    ids = np.random.RandomState(seed).randint(
        0, vocab, (batch, seq)).astype(np.int32)
    return paddle.to_tensor(ids), paddle.to_tensor(ids.astype(np.int64))


def build_trainer(model_name: str, seq: int, seed: int, fleet=None):
    """(model, step): the bench_gpt2 configuration — bf16 weights, fp32
    master weights, fused LM loss over the whole sequence, AdamW.
    ``fleet`` given: the hybrid-parallel step on the active mesh."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.gpt import gpt

    paddle.seed(seed)
    model = gpt(model_name, max_position_embeddings=seq,
                fused_lm_loss=True, lm_loss_chunk=seq)
    model.bfloat16()
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          multi_precision=True)

    def loss_fn(out, labels):
        return model.loss(out, labels)

    if fleet is None:
        return model, paddle.jit.TrainStep(model, opt, loss_fn)
    model = fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    return model, fleet.DistributedTrainStep(model, opt, loss_fn)


def run_steps(step, x, y, n: int):
    """n steps fenced by float(loss); (losses, first_step_s, rest_s).
    The first step includes the compile."""
    t0 = time.perf_counter()
    losses = [float(step(x, y))]
    t1 = time.perf_counter()
    losses += [float(step(x, y)) for _ in range(n - 1)]
    return losses, t1 - t0, time.perf_counter() - t1


def check_losses(losses) -> list:
    bad = []
    if not all(np.isfinite(losses)):
        bad.append(f"non-finite loss in {losses}")
    elif not losses[-1] < losses[0]:
        bad.append(f"loss did not fall: {losses}")
    return bad


# ------------------------------------------------------------- one chip

def phase_train(dev, cache, seed, model_name=MODEL, batch=TRAIN_BATCH,
                seq=TRAIN_SEQ, steps=TRAIN_STEPS) -> dict:
    model, step = build_trainer(model_name, seq, seed)
    x, y = token_batch(model.cfg.vocab_size, batch, seq, seed)
    losses, compile_s, run_s = run_steps(step, x, y, steps)
    # the text of the program the steps above ran (same jit, same
    # operand avals: jax hands back the executable it already built)
    compiled = step.lower(x, y).compile()
    text = compiled.as_text()
    failures = check_losses(losses)
    if KERNEL not in text:
        failures.append("no Pallas kernel in the compiled train step: "
                        "attention took the XLA path")
    return {"phase": "train", "ok": not failures, "failures": failures,
            "model": model_name, "batch": batch, "seq": seq,
            "params": int(model.num_params()),
            "compile_s": round(compile_s, 2), "run_s": round(run_s, 2),
            "losses": [round(v, 4) for v in losses],
            "kernels": {"train_step": text.count(KERNEL)},
            "program_bytes": program_bytes(compiled),
            "memory": memory(dev), "jax_cache": cache.snapshot()}


def generation_config(model, max_batch, kv_cache_dtype):
    """The README's generation set-up; the engine adds
    ``enable_serving()`` to it, the reference builds a Predictor."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config
    spec = [paddle.to_tensor(np.zeros((1, SERVE_BUCKETS[0]), np.int32))]
    return (Config().from_layer(model, spec)
            .enable_tpu("bfloat16")
            .enable_generation(max_new_tokens=SERVE_MAX_NEW,
                               prefill_buckets=SERVE_BUCKETS,
                               max_batch=max_batch,
                               kv_cache_dtype=kv_cache_dtype))


def sequential_reference(model, prompts, budgets, kv_cache_dtype):
    """Greedy tokens of ``Predictor.generate()``, one prompt at a time
    at batch 1, under the given cache dtype."""
    from paddle_tpu.inference import create_predictor
    pred = create_predictor(generation_config(model, 1, kv_cache_dtype))
    return [pred.generate([p], max_new_tokens=b)[0]
            for p, b in zip(prompts, budgets)]


def first_difference(model, prompt, want, got):
    """Where two greedy streams part, and whether that is a near-tie:
    under a teacher-forced forward of the common prefix, both tokens'
    logits are within 1% of the logit scale of the top one (the bound
    of test_int8_logit_error_bounded)."""
    from paddle_tpu.core.tensor import Tensor
    n = min(len(want), len(got))
    at = next((i for i in range(n) if want[i] != got[i]), n)
    if at == n:
        return {"at": at, "near_tie": False,
                "why": f"lengths {len(want)} != {len(got)}"}
    ctx = np.concatenate([prompt, want[:at]]).astype(np.int32)[None]
    logits = np.asarray(model(Tensor(ctx))._data[0, -1], np.float32)
    bound = 0.01 * max(1.0, float(np.abs(logits).max()))
    gap = float(logits.max() - min(logits[want[at]], logits[got[at]]))
    return {"at": at, "gap": round(gap, 5), "bound": round(bound, 5),
            "near_tie": gap <= bound}


def phase_serve_engine(dev, cache, model, kind, serving_kw, prompts,
                       budgets, references) -> dict:
    """``references`` holds the sequential tokens by cache dtype, built
    by the first engine kind that needs them."""
    from paddle_tpu.core import monitor
    from paddle_tpu.serving import (RequestParams, RequestStatus,
                                    ServingEngine)
    kv = serving_kw.get("kv_cache_dtype")
    if kv not in references:
        references[kv] = sequential_reference(model, prompts, budgets, kv)
    reference = references[kv]
    t0 = time.perf_counter()
    engine = ServingEngine(             # the constructor warms up
        generation_config(model, SERVE_MAX_BATCH, kv)
        .enable_serving(max_queue=64, **serving_kw))
    compile_s = time.perf_counter() - t0

    monitor.enable()
    try:
        new_shape0 = counter("jit.compile{cause=new_shape}")
        t0 = time.perf_counter()
        handles = [engine.submit(p, RequestParams(max_new_tokens=b))
                   for p, b in zip(prompts, budgets)]
        outs = [np.asarray(h.result()) for h in handles]
        run_s = time.perf_counter() - t0
        new_shape = counter("jit.compile{cause=new_shape}") - new_shape0
    finally:
        monitor.disable()

    failures = []
    for i, (h, out, b) in enumerate(zip(handles, outs, budgets)):
        if h.status is not RequestStatus.COMPLETED or len(out) != b:
            failures.append(f"request {i}: status {h.status}, "
                            f"{len(out)} of {b} tokens")
    parted = {}
    for i, (p, want, got) in enumerate(zip(prompts, reference, outs)):
        if not np.array_equal(want, got):
            parted[i] = first_difference(model, p, want, got)
            if not parted[i]["near_tie"]:
                failures.append(f"request {i} differs from sequential "
                                f"generate: {parted[i]}")
    if new_shape:
        failures.append(f"{new_shape} compile(s) after warmup")
    # the engine's warm programs, by scheduler key
    kernels = {".".join(str(k) for k in key): exe.as_text().count(KERNEL)
               for key, exe in engine._exes.items()}
    need = ["step"] + ([f"chunk.{SERVE_CHUNK}",
                        f"chunk_final.{SERVE_CHUNK}"]
                       if "prefill_chunk_tokens" in serving_kw else [])
    for name in need + [f"prefill.{SERVE_BUCKETS[-1]}"]:
        if not kernels.get(name):
            failures.append(f"no Pallas kernel in program {name!r}")
    pages = None
    if serving_kw.get("paged"):
        engine._alloc.assert_conserved()
        health = engine.health()
        pages = {"free": health["free_pages"],
                 "total": health["total_pages"]}
        if pages["free"] != pages["total"]:
            failures.append(f"pages not returned at drain: {pages}")
    engine.shutdown()
    return {"phase": f"serve.{kind}", "ok": not failures,
            "failures": failures, "compile_s": round(compile_s, 2),
            "run_s": round(run_s, 2),
            "requests": len(handles),
            "tokens": int(sum(len(o) for o in outs)),
            "bitwise_equal": len(prompts) - len(parted),
            "parted": parted, "compiles_after_warmup": new_shape,
            "kernels": kernels, "pages": pages,
            "cache_len": engine.max_len,
            "memory": memory(dev), "jax_cache": cache.snapshot()}


def run_one_chip(dev, cache, seed) -> bool:
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt

    ok = run_phase("train", phase_train, dev, cache, seed)
    gc.collect()   # the trainer's params and moments leave the device

    paddle.seed(seed)
    model = gpt(MODEL)
    model.bfloat16()
    model.eval()
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, model.cfg.vocab_size, n).astype(np.int32)
               for n in SERVE_PROMPT_LENS]
    references = {}
    for kind, serving_kw in ENGINE_KINDS.items():
        ok = run_phase(f"serve.{kind}", phase_serve_engine, dev, cache,
                       model, kind, serving_kw, prompts, SERVE_BUDGETS,
                       references) and ok
        gc.collect()
    return ok


# ----------------------------------------------------------- four chips

def spread(step, devices) -> dict:
    """Where the step's parameters and optimizer state live."""
    import jax
    leaves = [p._data for p in step._params] \
        + jax.tree_util.tree_leaves(step._opt_state_tree)
    holders = set()
    split = 0
    for a in leaves:
        shards = a.addressable_shards
        holders.update(s.device for s in shards)
        split += any(s.data.shape != a.shape for s in shards)
    in_use = [memory(d)["bytes_in_use"] for d in devices]
    return {"devices_holding_shards": len(holders),
            "arrays": len(leaves), "arrays_split": split,
            "bytes_in_use": in_use}


def phase_fleet(dev, cache, seed, model_name=MODEL, batch=TRAIN_BATCH,
                seq=TRAIN_SEQ, steps=TRAIN_STEPS) -> dict:
    import jax
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet

    devices = jax.devices()
    # what it is compared with: single-device TrainStep, same seed,
    # same batch; its buffers leave the device before the mesh is built
    model, step = build_trainer(model_name, seq, seed)
    x, y = token_batch(model.cfg.vocab_size, batch, seq, seed)
    single, single_compile_s, single_run_s = run_steps(step, x, y, steps)
    del model, step
    gc.collect()

    fleet.init(strategy=fleet.DistributedStrategy(
        hybrid_configs={"mp_degree": 2, "sharding_degree": 2},
        sharding=True, sharding_configs={"stage": 2}))
    try:
        model, step = build_trainer(model_name, seq, seed, fleet=fleet)
        losses, compile_s, run_s = run_steps(step, x, y, steps)
        compiled = step.lower(x, y).compile()
        text = compiled.as_text()
        where = spread(step, devices)
        mesh = {k: int(v) for k, v in step.mesh.shape.items()}
    finally:
        dist.set_hybrid_communicate_group(None)

    failures = check_losses(losses) + check_losses(single)
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses, single))
    if not worst <= FLEET_LOSS_RTOL:
        failures.append(f"fleet and single-device losses differ by "
                        f"{worst:.2e} > {FLEET_LOSS_RTOL}")
    if where["devices_holding_shards"] != len(devices) \
            or not where["arrays_split"]:
        failures.append(f"state not spread over the mesh: {where}")
    if max(where["bytes_in_use"]) > 2 * min(where["bytes_in_use"]):
        failures.append(f"uneven bytes_in_use: {where['bytes_in_use']}")
    found = {c: text.count(c + "(") + text.count(c + "-start(")
             for c in COLLECTIVES}
    if not found["all-reduce"] or not (found["all-gather"]
                                       or found["reduce-scatter"]):
        failures.append(f"collectives missing from the step: {found}")
    return {"phase": "fleet", "ok": not failures, "failures": failures,
            "model": model_name, "batch": batch, "seq": seq,
            "mesh": mesh, "zero_stage": 2,
            "losses": [round(v, 4) for v in losses],
            "single_device_losses": [round(v, 4) for v in single],
            "max_rel_diff": float(f"{worst:.3e}"),
            "rtol": FLEET_LOSS_RTOL,
            "compile_s": round(compile_s, 2), "run_s": round(run_s, 2),
            "single_compile_s": round(single_compile_s, 2),
            "single_run_s": round(single_run_s, 2),
            "spread": where, "collectives": found,
            "kernels": {"fleet_step": text.count(KERNEL)},
            "program_bytes": program_bytes(compiled),
            "memory": [memory(d) for d in devices],
            "jax_cache": cache.snapshot()}


# ----------------------------------------------------------------- main

def run_phase(name, fn, *args) -> bool:
    """Run one phase and print its line. A phase that raises is a
    failed phase: the run goes on, and ends non-zero."""
    try:
        row = fn(*args)
    except Exception as e:
        traceback.print_exc()
        row = {"phase": name, "ok": False,
               "failures": [f"{type(e).__name__}: {e}"[:2000]]}
    emit(row)
    return bool(row["ok"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet step on four chips and the "
                         "single-device step it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, token ids and prompts")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's first device is "
                 f"platform {dev.platform!r} ({dev.device_kind}); "
                 "nothing was run")
    need = 4 if args.four_chips else 1
    if jax.device_count() < need:
        sys.exit(f"chip_smoke: needs {need} chips, JAX sees "
                 f"{jax.device_count()}; nothing was run")

    from paddle_tpu import native
    from paddle_tpu.jit import compile_cache, enable_compile_cache
    enable_compile_cache()
    cache = CacheEvents()
    emit({"phase": "setup", "ok": True, "jax": jax.__version__,
          "compile_cache_dir": compile_cache.cache_dir(),
          "memory_stats": dev.memory_stats(),
          "native_lib": "built" if native.available()
          else "python fallback"})

    if args.four_chips:
        ok = run_phase("fleet", phase_fleet, dev, cache, args.seed)
    else:
        ok = run_one_chip(dev, cache, args.seed)
    emit({"ok": ok, "device": {"platform": dev.platform,
                               "kind": dev.device_kind,
                               "count": jax.device_count()}})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
