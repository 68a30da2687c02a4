"""Benchmarks for the BASELINE.md progression configs.

Default (`python bench.py`): the flagship GPT-2 small pretraining step —
prints ONE JSON line {"metric", "value", "unit", "vs_baseline"} with
vs_baseline = achieved MFU / 0.40 (the ERNIE-3.0 north-star target).

Other configs (BASELINE configs #2-#5; `python bench.py <name>`):
  resnet50      ResNet-50 train step, images/sec (conv/layout path)
  ernie-base    ERNIE-3.0-Base masked-LM step (sharding-family model)
  bert-large    BERT-large masked-LM step
  gpt6.7b-layer one GPT-3-6.7B transformer block (single-chip microbench
                of the hybrid config; full model needs the 8-way mesh —
                see __graft_entry__.dryrun_multichip)
  vit-l         ViT-L/16 train step
  warmstart     relaunch-to-first-token / relaunch-to-first-step, cold
                vs warm through the jit.compile_cache executable store
                (ISSUE-9 gate: warm >= 5x faster on test-tiny)
  all           every config; one JSON line each on stderr, flagship on
                stdout last

MFU for the non-GPT configs uses XLA's own cost model for the compiled
step (TrainStep.cost_analysis) instead of hand formulas.

Shape overrides reproduce the BASELINE.md sweep rows on the flagship,
e.g. the long-context sweep: BENCH_SEQ=4096 BENCH_BATCH=4,
BENCH_SEQ=8192 BENCH_BATCH=2, BENCH_SEQ=16384 BENCH_BATCH=1.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

PEAKS_BF16 = {  # dense bf16 TFLOP/s per chip
    "TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v4": 275e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12, "TPU v5p": 459e12,
}


def peak_flops(device) -> float:
    """Published peak of ``device``; a device not in the table is an
    error, never a default."""
    kind = getattr(device, "device_kind", "") or ""
    for name, val in PEAKS_BF16.items():
        if name.lower() in kind.lower():
            return val
    raise ValueError(
        f"no published peak for device kind {kind!r} (platform "
        f"{device.platform}); known: {sorted(PEAKS_BF16)}")


def _setup(configure_cache: bool = True):
    import jax
    if configure_cache:
        # the shared process-global setup (jit/compile_cache.py owns the
        # jax cache dir and the rule that places it); warmstart mode
        # skips this so its COLD phase really is cold
        from paddle_tpu.jit import enable_compile_cache
        enable_compile_cache(min_compile_time_secs=1.0)
    dev = jax.devices()[0]
    return dev, dev.platform == "tpu"


def _time_steps(step, x, y, iters, profile_dir=None):
    # warmup (compile). Sync via host transfer of the loss: donated
    # param buffers alias inputs, so float() of the step's own output
    # is the fence.
    loss = step(x, y)
    float(loss)
    prof = None
    if profile_dir:
        # BENCH_PROFILE=1: drop ONE Perfetto trace of a few mid-run
        # steps so host/device overlap is visually auditable (host spans
        # + metric counter tracks; open in ui.perfetto.dev). The
        # recording window adds host overhead — the tokens/sec printed
        # from a profiled run is NOT a benchmark number.
        from paddle_tpu import profiler as _profiler
        prof = _profiler.Profiler(
            scheduler=(1, min(1 + 4, iters)),
            on_trace_ready=_profiler.export_chrome_tracing(
                profile_dir, "bench"))
        prof.start()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(x, y)
        if prof is not None:
            prof.step()
    final = float(loss)
    if prof is not None:
        prof.stop()
        print(f"BENCH_PROFILE: Perfetto trace in {profile_dir}/",
              file=sys.stderr)
    return time.perf_counter() - t0, final


def bench_gpt2(dev, on_tpu):
    import os
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.gpt import gpt

    if on_tpu:
        name, batch, seq = "gpt2-small", 16, 1024
    else:  # CPU smoke config
        name, batch, seq = "test-tiny", 2, 64
    # HBM-pressure sweeps (BASELINE.md): override shape/remat/offload
    batch = int(os.environ.get("BENCH_BATCH", batch))
    seq = int(os.environ.get("BENCH_SEQ", seq))
    remat = os.environ.get("BENCH_REMAT", "")  # ""/selective/full
    offload = os.environ.get("BENCH_OFFLOAD", "") == "1"
    # chunked fused LM-head+CE is the default: it never materializes
    # the [B, S, vocab] logits and wins ~10% MFU at s1024, ~16% at
    # s2048 (see BASELINE.md sweeps). BENCH_FUSED=0 opts out.
    fused = os.environ.get("BENCH_FUSED", "1") == "1"
    # fused-loss chunk: when the whole fp32 [B, S, vocab] logits fit in
    # ~4 GB HBM alongside the step, a single un-rematerialized chunk is
    # fastest (b16-s1024: MFU 0.499 -> 0.529 measured r4 — saving the
    # logits beats recomputing the vocab matmul); beyond that, scan
    # chunks of ~8192 logit rows with per-chunk remat (b32 chunk 256,
    # s2048 chunk 512 — the [batch*chunk, vocab] live buffer matters)
    from paddle_tpu.models.gpt import CONFIGS
    base_cfg = CONFIGS[name]
    logit_bytes = batch * (seq - 1) * base_cfg.vocab_size * 4
    chunk = int(os.environ.get("BENCH_CHUNK", 0)) or \
        (seq if logit_bytes <= base_cfg.lm_loss_save_logits_budget
         else max(8192 // batch, 128))

    paddle.seed(0)
    model = gpt(name, max_position_embeddings=seq,
                use_recompute=bool(remat),
                recompute_granularity=remat or "selective",
                fused_lm_loss=fused, lm_loss_chunk=chunk)
    model.bfloat16() if on_tpu else None
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          multi_precision=on_tpu)
    step = paddle.jit.TrainStep(
        model, opt, lambda logits, labels: model.loss(logits, labels),
        offload_opt_state=offload)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, model.cfg.vocab_size, (batch, seq)).astype(np.int32)
    x = paddle.to_tensor(ids)
    y = paddle.to_tensor(ids.astype(np.int64))

    iters = 20 if on_tpu else 3
    profile_dir = "bench_trace" \
        if os.environ.get("BENCH_PROFILE", "") == "1" else None
    dt, loss = _time_steps(step, x, y, iters, profile_dir=profile_dir)

    tokens_per_sec = batch * seq * iters / dt
    mfu = tokens_per_sec * model.flops_per_token(seq) / peak_flops(dev)
    extra = (f", remat={remat}" if remat else "") + \
        (", offload" if offload else "") + \
        (", fused_loss" if fused else "")
    return {
        "metric": f"{name} train tokens/sec/chip (b{batch} s{seq}, "
                  f"MFU={mfu:.3f}, loss={loss:.3f}{extra}, "
                  f"device={dev.device_kind})",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.40, 4),
    }


def _mlm_bench(dev, on_tpu, cfg_name, batch, seq, iters=20):
    """ERNIE/BERT masked-LM + sentence-order pretraining step."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.ernie import ernie

    import os
    fused = os.environ.get("BENCH_FUSED", "1") == "1"
    paddle.seed(0)
    # fused MLM loss: only the (<= max_predictions) masked positions
    # run the vocab projection — the dense [B, S, vocab] logits never
    # materialize (BENCH_FUSED=0 opts out)
    model = ernie(cfg_name if on_tpu else "test-tiny",
                  fused_mlm_loss=fused,
                  max_predictions=max(int(seq * 0.19), 8))
    model.bfloat16() if on_tpu else None
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          multi_precision=on_tpu)
    step = paddle.jit.TrainStep(
        model, opt, lambda out, labels: model.loss(out, labels))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, model.cfg.vocab_size,
                      (batch, seq)).astype(np.int32)
    x = paddle.to_tensor(ids)
    mlm = ids.astype(np.int64)
    mlm[rng.rand(*mlm.shape) > 0.15] = -100  # only masked positions score
    y = (paddle.to_tensor(mlm),
         paddle.to_tensor(rng.randint(0, 2, (batch,)).astype(np.int64)))
    xla_flops = float(step.cost_analysis(x, y).get("flops", 0.0))
    n = iters if on_tpu else 2
    dt, loss = _time_steps(step, x, y, n)
    tokens_per_sec = batch * seq * n / dt
    mfu = (xla_flops * n / dt) / peak_flops(dev)
    return {
        "metric": f"{cfg_name} train tokens/sec/chip (b{batch} "
                  f"s{seq}, MFU={mfu:.3f}, loss={loss:.3f}, "
                  f"device={dev.device_kind})",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.40, 4),
    }


def bench_ernie_base(dev, on_tpu):
    b, s = (32, 512) if on_tpu else (2, 32)
    return _mlm_bench(dev, on_tpu, "ernie-3.0-base", b, s)


def bench_bert_large(dev, on_tpu):
    b, s = (16, 512) if on_tpu else (2, 32)
    return _mlm_bench(dev, on_tpu, "bert-large", b, s)


def bench_gpt67_layer(dev, on_tpu):
    """One transformer block of the GPT-3-6.7B config (BASELINE #4's
    building block; the full model runs on the 8-way mesh in
    dryrun_multichip)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.models.gpt import CONFIGS, GPTBlock
    import dataclasses

    cfg = CONFIGS["gpt3-6.7b" if on_tpu else "test-tiny"]
    paddle.seed(0)

    class OneBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.block = GPTBlock(cfg)

        def forward(self, x):
            return self.block(x)

    model = OneBlock()
    model.bfloat16() if on_tpu else None
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          multi_precision=on_tpu)
    loss_fn = lambda out, labels: (out.astype("float32") ** 2).mean()
    step = paddle.jit.TrainStep(model, opt, loss_fn)
    b, s = (8, 2048) if on_tpu else (2, 32)
    rng = np.random.RandomState(0)
    h = rng.randn(b, s, cfg.hidden_size).astype(np.float32)
    x = paddle.to_tensor(h).astype("bfloat16" if on_tpu else "float32")
    y = paddle.zeros([1])
    xla_flops = float(step.cost_analysis(x, y).get("flops", 0.0))
    iters = 30 if on_tpu else 2
    dt, loss = _time_steps(step, x, y, iters)
    tokens_per_sec = b * s * iters / dt
    mfu = (xla_flops * iters / dt) / peak_flops(dev)
    return {
        "metric": f"gpt3-6.7b single-layer train tokens/sec/chip "
                  f"(b{b} s{s}, MFU={mfu:.3f}, "
                  f"device={dev.device_kind})",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.40, 4),
    }


def bench_resnet50(dev, on_tpu):
    import os
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.models.resnet import resnet50

    layout = os.environ.get("BENCH_LAYOUT", "NHWC")
    s2d = os.environ.get("BENCH_S2D", "1") == "1"
    # fused conv+BN training kernels (kernels/fused_resnet.py) measured
    # SLOWER end-to-end than XLA's own fusion (61.5 -> 103 ms/step, see
    # BASELINE.md r4 negative result): default OFF; BENCH_FUSED_BN=1
    # opts in. NB: MFU from XLA cost analysis is bogus when Pallas
    # custom calls carry the flops.
    fused_bn = os.environ.get("BENCH_FUSED_BN", "0") == "1" and \
        layout == "NHWC"
    paddle.seed(0)
    model = resnet50(num_classes=1000, data_format=layout,
                     stem_space_to_depth=s2d, fused_bn=fused_bn)
    model.bfloat16() if on_tpu else None
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters(),
                             multi_precision=on_tpu)
    ce = nn.CrossEntropyLoss()

    def loss_fn(logits, labels):
        return ce(logits.astype("float32"), labels)

    step = paddle.jit.TrainStep(model, opt, loss_fn)
    b, hw = (128, 224) if on_tpu else (2, 32)
    rng = np.random.RandomState(0)
    img = rng.randn(b, 3, hw, hw).astype(np.float32)
    x = paddle.to_tensor(img).astype("bfloat16" if on_tpu else "float32")
    y = paddle.to_tensor(rng.randint(0, 1000, (b,)).astype(np.int64))
    xla_flops = float(step.cost_analysis(x, y).get("flops", 0.0))
    iters = 20 if on_tpu else 2
    dt, loss = _time_steps(step, x, y, iters)
    imgs_per_sec = b * iters / dt
    mfu = (xla_flops * iters / dt) / peak_flops(dev)
    return {
        "metric": f"resnet50 train images/sec/chip (b{b} {hw}x{hw}, "
                  f"{layout}{', s2d-stem' if s2d else ''}"
                  f"{', fused-bn' if fused_bn else ''}, "
                  f"MFU={mfu:.3f}, loss={loss:.3f}, "
                  f"device={dev.device_kind})",
        "value": round(imgs_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(mfu / 0.40, 4),
    }


def bench_vit_l(dev, on_tpu):
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.models.vit import vit

    paddle.seed(0)
    model = vit("vit-l-16" if on_tpu else "test-tiny")
    model.bfloat16() if on_tpu else None
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          multi_precision=on_tpu)
    ce = nn.CrossEntropyLoss()

    def loss_fn(logits, labels):
        return ce(logits.astype("float32"), labels)

    step = paddle.jit.TrainStep(model, opt, loss_fn)
    b = 64 if on_tpu else 2
    hw = model.cfg.image_size
    rng = np.random.RandomState(0)
    img = rng.randn(b, 3, hw, hw).astype(np.float32)
    x = paddle.to_tensor(img).astype("bfloat16" if on_tpu else "float32")
    y = paddle.to_tensor(rng.randint(0, model.cfg.num_classes,
                                     (b,)).astype(np.int64))
    xla_flops = float(step.cost_analysis(x, y).get("flops", 0.0))
    iters = 20 if on_tpu else 2
    dt, loss = _time_steps(step, x, y, iters)
    imgs_per_sec = b * iters / dt
    mfu = (xla_flops * iters / dt) / peak_flops(dev)
    return {
        "metric": f"vit-l-16 train images/sec/chip (b{b} {hw}x{hw}, "
                  f"MFU={mfu:.3f}, loss={loss:.3f}, "
                  f"device={dev.device_kind})",
        "value": round(imgs_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(mfu / 0.40, 4),
    }


def bench_moe_block(dev, on_tpu):
    """Single-chip MoE transformer block (EP correctness lives in the
    dryrun/tests; this is the expert-compute perf leg — BASELINE.md
    r3 MoE row). 8 local experts, gshard gate."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.models.gpt import CONFIGS, GPTBlock, GPTConfig
    import dataclasses

    base = CONFIGS["gpt2-small" if on_tpu else "test-tiny"]
    cfg = dataclasses.replace(base, moe_num_experts=8,
                              moe_capacity_factor=1.25)
    paddle.seed(0)

    class OneBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.block = GPTBlock(cfg)

        def forward(self, x):
            return self.block(x)

    model = OneBlock()
    model.bfloat16() if on_tpu else None
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          multi_precision=on_tpu)
    from paddle_tpu.distributed.parallel.moe import aux_loss
    loss_fn = lambda out, labels: \
        (out.astype("float32") ** 2).mean() + aux_loss(model)
    step = paddle.jit.TrainStep(model, opt, loss_fn)
    b, s = (16, 1024) if on_tpu else (2, 32)
    rng = np.random.RandomState(0)
    h = rng.randn(b, s, cfg.hidden_size).astype(np.float32)
    x = paddle.to_tensor(h).astype("bfloat16" if on_tpu else "float32")
    y = paddle.zeros([1])
    xla_flops = float(step.cost_analysis(x, y).get("flops", 0.0))
    iters = 30 if on_tpu else 2
    dt, loss = _time_steps(step, x, y, iters)
    tokens_per_sec = b * s * iters / dt
    mfu = (xla_flops * iters / dt) / peak_flops(dev)
    return {
        "metric": f"moe block (8 experts, gshard, h={cfg.hidden_size}) "
                  f"train tokens/sec/chip (b{b} s{s}, MFU={mfu:.3f}, "
                  f"device={dev.device_kind})",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.40, 4),
    }


def _metric_counter(name):
    """Current value of one registry counter (0 when never recorded) —
    the delta reader behind every PR-10 counters sub-dict."""
    from paddle_tpu.profiler import metrics as _metrics
    snap = _metrics.snapshot().get(name)
    return int(snap["value"]) if snap else 0


def _tree_bytes(tree):
    import jax
    return sum(
        int(np.prod(l.shape, dtype=np.int64)) * np.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(tree) if hasattr(l, "shape"))


def _mem_sub_dict(plan, measure_fn, held, pool_bytes):
    """The ISSUE-14 "mem" row: the static planner's predicted peak vs
    a MEASURED peak (live-byte delta around exactly one dispatch of the
    same program, inputs in ``held`` kept referenced) plus the KV pool
    bytes. The plan upper-bounds the resident set, so
    predicted_over_measured >= 1.0 is the healthy regime; the tier-1
    predicted-vs-measured test pins its slack band."""
    import jax
    from paddle_tpu import device
    device.reset_peak_memory_stats()
    m0 = device.memory_allocated()
    out = measure_fn()
    jax.block_until_ready(out)
    measured = _tree_bytes(held) + max(
        0, device.max_memory_allocated() - m0)
    return {
        "predicted_peak_bytes": int(plan.peak_bytes),
        "measured_peak_bytes": int(measured),
        "pool_bytes": int(pool_bytes),
        "predicted_over_measured": round(plan.peak_bytes / measured, 2),
    }


def _bench_spec_rows(model, draft, on_tpu, new_tokens):
    """Speculative-decode comparison rows (ISSUE-11): batch-1 greedy
    decode — the latency-bound regime speculation targets — off vs
    self-speculative (prompt-lookup) vs draft-model, on a prompt with
    the input-grounded repetition prompt-lookup exists for (a repeated
    motif: the summarization/code-edit/RAG shape). Each variant reports
    decode tokens/sec, accept_rate from the gen.spec.* counters, and
    its own post-warmup retrace counters — the PR-10 sub-dict proving
    the timed pass dispatched warm executables only."""
    rng = np.random.RandomState(0)
    motif = rng.randint(0, model.cfg.vocab_size, 16)
    ids = np.tile(motif, 32)[None, :512].astype(np.int32)  # batch 1
    counter = _metric_counter

    def run(label, **kw):
        model.generate(ids, max_new_tokens=new_tokens, **kw)  # warmup
        before = {k: counter(k) for k in
                  ("jit.compile.total", "jit.compile{cause=new_shape}",
                   "gen.spec.proposed", "gen.spec.accepted")}
        t0 = time.perf_counter()
        model.generate(ids, max_new_tokens=new_tokens, **kw)
        dt = time.perf_counter() - t0
        prop = counter("gen.spec.proposed") - before["gen.spec.proposed"]
        acc = counter("gen.spec.accepted") - before["gen.spec.accepted"]
        return {
            "tokens_per_sec": round(new_tokens / dt, 1),
            **({"accept_rate": round(acc / prop, 3)} if prop else {}),
            "counters": {
                "jit.compile.total":
                    counter("jit.compile.total")
                    - before["jit.compile.total"],
                "jit.compile{cause=new_shape}":
                    counter("jit.compile{cause=new_shape}")
                    - before["jit.compile{cause=new_shape}"],
            },
        }

    rows = {"batch": 1, "prompt": "16-token motif x32 (prompt-lookup "
                                  "regime)", "new_tokens": new_tokens}
    rows["off"] = run("off")
    rows["ngram"] = run("ngram", speculative="ngram")
    rows["draft"] = run("draft", speculative="draft", draft_model=draft)
    off = rows["off"]["tokens_per_sec"]
    for v in ("ngram", "draft"):
        rows[v]["speedup_vs_off"] = round(
            rows[v]["tokens_per_sec"] / off, 2)
    return rows


def _bench_precision_rows(model, on_tpu, ids, new_tokens):
    """Per-precision decode rows (ISSUE-13): the same prompt batch
    decoded with the full-width cache, the int8 KV cache (fused
    in-kernel dequant), and the int8-cache + int4-weight serving
    engine (the only surface that owns a weight path). Each row
    carries decode tokens/sec and the PR-10 counters sub-dict proving
    the timed pass dispatched warm programs only."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config
    from paddle_tpu.inference.config import PrecisionType
    from paddle_tpu.serving import RequestParams, ServingEngine

    b = ids.shape[0]
    counter = _metric_counter

    def timed(fn, tokens):
        fn()  # warmup (compiles once)
        before = {k: counter(k) for k in
                  ("jit.compile.total", "jit.compile{cause=new_shape}")}
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        return {
            "tokens_per_sec": round(tokens / dt, 1),
            "counters": {k: counter(k) - before[k] for k in before},
        }

    wide = "bfloat16" if on_tpu else "float32"
    rows = {"batch": b, "new_tokens": new_tokens, "wide_dtype": wide}
    rows[wide] = timed(
        lambda: model.generate(ids, max_new_tokens=new_tokens),
        b * new_tokens)
    rows["int8-kv"] = timed(
        lambda: model.generate(ids, max_new_tokens=new_tokens,
                               kv_cache_dtype="int8"),
        b * new_tokens)

    # int8-kv + int4 weight-only: through the engine (weights pack two
    # nibbles per byte, dequant in-trace; cache int8, dequant in-kernel)
    bucket = ids.shape[1]
    spec = [paddle.to_tensor(np.zeros((b, 64), np.int32))]
    cfg = (Config().from_layer(model, spec)
           .enable_generation(max_new_tokens=new_tokens,
                              prefill_buckets=(bucket,), max_batch=b,
                              kv_cache_dtype="int8")
           .enable_serving(max_queue=2 * b, weight_bits=4))
    cfg.precision = PrecisionType.Int8
    engine = ServingEngine(cfg, poll_every=4)

    def engine_pass():
        hs = [engine.submit(ids[i], RequestParams(
            max_new_tokens=new_tokens)) for i in range(b)]
        while engine.busy:
            engine.step()
        assert all(h.status.value == "completed" for h in hs)

    rows["int8-kv+int4-w"] = timed(engine_pass, b * new_tokens)
    engine.shutdown()
    for label in (wide, "int8-kv", "int8-kv+int4-w"):
        rows[label]["speedup_vs_wide"] = round(
            rows[label]["tokens_per_sec"] / rows[wide]["tokens_per_sec"],
            2)
    return rows


def bench_decode(dev, on_tpu):
    """Serving-trajectory bench: prefill 512 + decode 128 on test-tiny
    GPT (ISSUE-6 decode mode). Reports decode tokens/sec (pipelined
    host loop, no per-token sync) plus p50/p95 per-token latency from a
    second, per-step-synced pass, the ISSUE-11 speculative rows
    (off / self-spec / draft-model at batch 1) as the "spec" sub-dict,
    and the ISSUE-13 per-precision rows (wide / int8-kv /
    int8-kv+int4-w) as the "precision" sub-dict.
    vs_baseline is 1.0 by definition — this row DEFINES the decode
    baseline from this revision on."""
    import os
    import paddle_tpu as paddle
    from paddle_tpu.generation import GenerationConfig, GenerationSession
    from paddle_tpu.generation.api import _round_up
    from paddle_tpu.models.gpt import gpt
    import jax
    import jax.numpy as jnp

    prefill_len, new_tokens = 512, 128
    b = int(os.environ.get("BENCH_DECODE_BATCH", 8 if on_tpu else 2))
    paddle.seed(0)
    model = gpt("test-tiny", max_position_embeddings=1024)
    model.bfloat16() if on_tpu else None
    rng = np.random.RandomState(0)
    ids = rng.randint(0, model.cfg.vocab_size,
                      (b, prefill_len)).astype(np.int32)

    cfg = GenerationConfig()
    cache_len = _round_up(prefill_len + new_tokens)
    sess = GenerationSession(model)
    state = sess.state_values()
    key = jax.random.PRNGKey(0)
    plen = jnp.full((b,), prefill_len, jnp.int32)

    def run(sync_each_step):
        tok, cache, k, fin = sess.prefill(state, jnp.asarray(ids), plen,
                                          key, cfg, cache_len)
        tok.block_until_ready()  # decode timer must NOT include the
        #                          async prefill-512 device time
        times = []
        t0 = time.perf_counter()
        for _ in range(new_tokens - 1):
            s0 = time.perf_counter()
            tok, _, cache, k, fin = sess.decode(state, tok, cache, k,
                                                fin, cfg)
            if sync_each_step:
                tok.block_until_ready()
                times.append(time.perf_counter() - s0)
        tok.block_until_ready()
        return time.perf_counter() - t0, times

    run(False)  # warmup: compiles prefill + decode
    dt, _ = run(False)                          # throughput pass
    _, per_step = run(True)                     # latency pass
    decode_tps = b * (new_tokens - 1) / dt
    p50 = float(np.percentile(per_step, 50) * 1e3)
    p95 = float(np.percentile(per_step, 95) * 1e3)
    paddle.seed(7)
    draft = gpt("test-tiny-draft", max_position_embeddings=1024)
    draft.bfloat16() if on_tpu else None
    spec = _bench_spec_rows(model, draft, on_tpu, new_tokens)
    precision = _bench_precision_rows(model, on_tpu, ids, new_tokens)
    wide = precision["wide_dtype"]

    # ISSUE-14 "mem" sub-dict: the decode program's static MemoryPlan
    # vs one measured dispatch (same donation the backend dispatches)
    from paddle_tpu import analysis
    tok, cache, k2, fin = sess.prefill(state, jnp.asarray(ids), plen,
                                       key, cfg, cache_len)
    tok.block_until_ready()
    margs = (state, tok, cache, k2, fin)
    mem_plan = analysis.plan_memory(
        sess._decode_fn, *margs, cfg, static_argnums=(5,),
        donate=sess._decode_donate, name="bench.decode")
    mem = _mem_sub_dict(mem_plan, lambda: sess.decode(*margs, cfg),
                        margs, _tree_bytes((cache,)))
    return {
        "metric": f"test-tiny decode tokens/sec/chip (b{b} "
                  f"prefill{prefill_len}+decode{new_tokens}, "
                  f"p50={p50:.2f}ms, p95={p95:.2f}ms per token, "
                  f"spec b1 off={spec['off']['tokens_per_sec']} "
                  f"ngram={spec['ngram']['tokens_per_sec']} "
                  f"({spec['ngram']['speedup_vs_off']}x, accept "
                  f"{spec['ngram'].get('accept_rate', 0)}), "
                  f"int8-kv {precision['int8-kv']['speedup_vs_wide']}x "
                  f"vs {wide}, "
                  f"device={dev.device_kind})",
        "value": round(decode_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": 1.0,
        "spec": spec,
        "precision": precision,
        "mem": mem,
    }


def bench_serve_shared_prefix(dev, on_tpu):
    """`bench.py serve --shared-prefix` (ISSUE-12): the capacity-at-
    equal-HBM gate for the paged KV cache. Poisson arrivals over K
    distinct LONG system prompts x short user suffixes — the traffic
    shape that dominates real fleets — served twice at the SAME cache
    HBM byte budget:

      dense:  max_batch slots x max_len ring rows   (the PR-8 engine)
      paged:  4x the slots over a page pool of the dense cache's exact
              token footprint (shared prefixes are stored once and
              reference-counted; each request's pages cover only ITS
              prompt + budget)

    The row's value is the ratio of peak concurrent in-flight requests
    (paged / dense); the acceptance gate is > 2x, so vs_baseline =
    ratio / 2. prefix_hits > 0 and page conservation at drain are
    asserted, and the PR-10 counters sub-dict rides along to show zero
    post-warmup retraces (`jit.compile{cause=new_shape}` == 0)."""
    import os
    import threading
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config
    from paddle_tpu.models.gpt import gpt
    from paddle_tpu.serving import RequestParams, ServingEngine

    from paddle_tpu.generation.api import _round_up

    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS",
                               96 if on_tpu else 48))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 256.0))  # req/sec
    dense_batch = int(os.environ.get("BENCH_SERVE_BATCH",
                                     8 if on_tpu else 4))
    paged_batch = 4 * dense_batch
    max_new = int(os.environ.get("BENCH_SERVE_NEW_TOKENS", 16))
    page = int(os.environ.get("PADDLE_KV_PAGE_SIZE",
                              128 if on_tpu else 16))
    # system prompts span 6 FULL pages whatever the page size (sharing
    # is page-granular — a sys prompt shorter than one page would never
    # produce a prefix key, and the gate below would be vacuous): 96
    # tokens at the CPU page 16, 768 at the TPU page 128
    sys_len = 6 * page
    bucket = _round_up(sys_len + 32)
    paddle.seed(0)
    model = gpt("test-tiny", max_position_embeddings=1024)
    model.bfloat16() if on_tpu else None
    assert bucket + max_new <= model.cfg.max_position_embeddings

    rng = np.random.RandomState(0)
    n_sys = 4
    sys_prompts = [rng.randint(0, model.cfg.vocab_size, sys_len)
                   .astype(np.int32) for _ in range(n_sys)]
    prompts = [np.concatenate([sys_prompts[i % n_sys],
                               rng.randint(0, model.cfg.vocab_size,
                                           rng.randint(8, 17))
                               .astype(np.int32)])
               for i in range(n_req)]
    budgets = rng.randint(max(4, max_new // 2), max_new + 1, size=n_req)
    gaps = rng.exponential(1.0 / rate, size=n_req)

    def run(paged, kv_dtype=None, slots=None, kv_pages=None):
        spec = [paddle.to_tensor(np.zeros((dense_batch, 64), np.int32))]
        cfg = (Config().from_layer(model, spec)
               .enable_generation(max_new_tokens=max_new,
                                  prefill_buckets=(bucket,),
                                  max_batch=slots if slots else (
                                      paged_batch if paged
                                      else dense_batch),
                                  kv_cache_dtype=kv_dtype))
        if paged:
            # EQUAL cache HBM: the pool holds exactly the dense
            # engine's dense_batch * max_len tokens (plus the reserved
            # null page); 4x the decode slots share it. An int8 run
            # passes its own kv_pages (the same BYTE budget buys ~2x
            # bf16 / ~3.6x fp32 the pages) + a wider slot set.
            max_len = _round_up(bucket + max_new)
            cfg.enable_serving(
                max_queue=n_req, paged=True, kv_page_size=page,
                kv_pages=kv_pages if kv_pages
                else dense_batch * max_len // page + 1)
        else:
            cfg.enable_serving(max_queue=n_req)
        engine = ServingEngine(cfg, poll_every=2)
        handles = []

        def feeder():
            for p, b, g in zip(prompts, budgets, gaps):
                time.sleep(g)
                handles.append(engine.submit(
                    p, RequestParams(max_new_tokens=int(b))))

        peak = 0
        busy_sum = steps = 0
        t0 = time.perf_counter()
        th = threading.Thread(target=feeder, daemon=True)
        th.start()
        while th.is_alive() or engine.busy:
            if engine.busy:
                engine.step()
                n_busy = sum(s is not None for s in engine._slots)
                peak = max(peak, n_busy)
                busy_sum += n_busy
                steps += 1
            else:
                time.sleep(0.0002)
        dt = time.perf_counter() - t0
        th.join()
        assert len(handles) == n_req and \
            all(h.status.value == "completed" for h in handles)
        stats = dict(engine._alloc.stats) if engine._alloc else {}
        if engine._alloc is not None:
            engine.drain()
            engine._alloc.assert_conserved()   # no leaked/double-freed
        return dict(peak=peak, mean_busy=round(busy_sum / max(1, steps), 2),
                    qps=round(n_req / dt, 1), **stats)

    dense = run(paged=False)
    paged_r = run(paged=True)
    assert paged_r["prefix_hits"] > 0, "shared-prefix traffic never hit"
    ratio = paged_r["peak"] / dense["peak"]
    max_len = _round_up(bucket + max_new)

    # ISSUE-13 equal-HBM int8 row: the SAME cache byte budget spent on
    # int8 pages (values 1 byte + bf16 scale per (position, head))
    # instead of wide ones buys ~2x (bf16) / ~3.6x (fp32) the pages —
    # the acceptance gate is >= 1.8x the wide-paged concurrent
    # capacity. Slots widen with the pages so the page capacity, not
    # the lane count, is what saturates first.
    h = model.cfg.num_heads
    d = model.cfg.hidden_size // h
    wide_itemsize = 2 if on_tpu else 4
    tok_wide = 2 * h * d * wide_itemsize          # k+v bytes/token
    tok_int8 = 2 * (h * d + h * 2)                # + bf16 scales
    hbm_budget = dense_batch * max_len * tok_wide
    int8_pages = hbm_budget // (page * tok_int8)
    int8_r = run(paged=True, kv_dtype="int8", slots=2 * paged_batch,
                 kv_pages=int(int8_pages) + 1)
    assert int8_r["prefix_hits"] > 0
    int8_vs_wide = int8_r["peak"] / paged_r["peak"]

    return {
        "metric": f"test-tiny paged-KV capacity at equal HBM "
                  f"({dense_batch * max_len} cache tokens, page {page}, "
                  f"{n_sys} shared {sys_len}-tok system prompts, "
                  f"poisson@{rate:g}/s): peak {paged_r['peak']} vs "
                  f"{dense['peak']} concurrent; int8 pages "
                  f"{int8_r['peak']} = {int8_vs_wide:.2f}x wide pages "
                  f"(device={dev.device_kind})",
        "value": round(ratio, 2),
        "unit": "x concurrent capacity",
        "vs_baseline": round(ratio / 2.0, 2),   # gate: > 2x -> >= 1.0
        "paged": {"dense": dense, "paged": paged_r,
                  "hbm_cache_tokens": dense_batch * max_len,
                  "page_size": page, "conserved": True},
        "int8": {**int8_r, "pages": int(int8_pages),
                 "wide_pages": dense_batch * max_len // page,
                 "vs_wide_pages": round(int8_vs_wide, 2),
                 "gate_1_8x": round(int8_vs_wide / 1.8, 2)},
    }



def bench_serve(dev, on_tpu):
    """Serving-engine bench (ISSUE-8 serve mode): synthetic Poisson
    arrivals of ragged prompts/budgets against the continuous-batching
    ServingEngine on test-tiny GPT. A feeder thread submits with
    exponential inter-arrival gaps (live traffic — requests land
    mid-decode and are admitted into freed slots); the main thread
    pumps the scheduler. Reports sustained QPS plus the SLA percentiles
    the serve.* metrics family tracks — TTFT and per-token latency
    p50/p95/p99 — as the BENCH_r06 row shape (the flat metric/value
    keys stay BENCH-schema compatible; the new "sla" sub-dict carries
    the percentile table). vs_baseline is 1.0 by definition — this row
    DEFINES the serving baseline from this revision on."""
    import os
    import threading
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config
    from paddle_tpu.models.gpt import gpt
    from paddle_tpu.serving import RequestParams, ServingEngine

    from paddle_tpu.inference.config import PrecisionType

    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS",
                               96 if on_tpu else 32))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 64.0))  # req/sec
    max_batch = int(os.environ.get("BENCH_SERVE_BATCH",
                                   8 if on_tpu else 4))
    max_new = int(os.environ.get("BENCH_SERVE_NEW_TOKENS", 32))
    paddle.seed(0)
    model = gpt("test-tiny", max_position_embeddings=1024)
    model.bfloat16() if on_tpu else None
    spec = [paddle.to_tensor(np.zeros((max_batch, 64), np.int32))]

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, model.cfg.vocab_size,
                           rng.randint(4, 128)).astype(np.int32)
               for _ in range(n_req)]
    budgets = rng.randint(max(4, max_new // 4), max_new + 1,
                          size=n_req)
    gaps = rng.exponential(1.0 / rate, size=n_req)

    counter = _metric_counter

    def traffic(engine):
        """One Poisson pass of the shared request set; returns
        (qps, handles, counters-delta)."""
        handles = []

        def feeder():
            for p, b, g in zip(prompts, budgets, gaps):
                time.sleep(g)
                handles.append(engine.submit(
                    p, RequestParams(max_new_tokens=int(b))))

        before = {k: counter(k) for k in
                  ("jit.compile.total", "jit.compile{cause=new_shape}")}
        t0 = time.perf_counter()
        th = threading.Thread(target=feeder, daemon=True)
        th.start()
        while th.is_alive() or engine.busy:
            if engine.busy:
                engine.step()
            else:
                time.sleep(0.0002)
        dt = time.perf_counter() - t0
        th.join()
        assert len(handles) == n_req and \
            all(h.status.value == "completed" for h in handles)
        return n_req / dt, handles, \
            {k: counter(k) - before[k] for k in before}

    def build(kv_dtype=None, weight_bits=None):
        cfg = (Config().from_layer(model, spec)
               .enable_generation(max_new_tokens=max_new,
                                  prefill_buckets=(32, 64, 128),
                                  max_batch=max_batch,
                                  kv_cache_dtype=kv_dtype)
               .enable_serving(max_queue=n_req,
                               weight_bits=weight_bits))
        if weight_bits:
            cfg.precision = PrecisionType.Int8
        return ServingEngine(cfg, poll_every=2)  # warmup compiles here

    engine = build()
    # ISSUE-17 "slo" sub-dict scaffolding: bracket the flagship pass
    # with two snapshots in a PRIVATE time-series ring, so the default
    # TTFT SLO can be evaluated over exactly that window (the later
    # precision passes re-drive the same metrics and must not leak in)
    from paddle_tpu.core import slo as slo_mod
    from paddle_tpu.core import timeseries as ts_mod
    slo_ring = ts_mod.TimeSeriesRing(period_s=1.0, retention=4)
    slo_ring.sample(now=0.0)
    t_slo0 = time.perf_counter()
    qps, handles, _ = traffic(engine)
    slo_span = time.perf_counter() - t_slo0
    slo_ring.sample(now=slo_span)
    # ISSUE-15 "goodput" sub-dict: the serve-side wall-time ledger
    # after the first (flagship) pass — buckets sum to wall, compute
    # fraction is the replica's goodput under this traffic shape
    gp = engine.goodput()
    goodput_row = {
        "wall_s": round(gp["wall_s"], 3),
        "goodput_fraction": round(gp["goodput_fraction"], 4),
        "buckets_s": {k: round(v, 3)
                      for k, v in gp["buckets"].items() if v > 0},
    }

    # ISSUE-13 per-precision rows: the SAME traffic against the int8-KV
    # engine and the int8-KV + int4-weight engine (counters prove the
    # timed pass ran warm)
    wide = "bfloat16" if on_tpu else "float32"
    precision = {"wide_dtype": wide}
    for label, kw in ((wide, {}),
                      ("int8-kv", dict(kv_dtype="int8")),
                      ("int8-kv+int4-w",
                       dict(kv_dtype="int8", weight_bits=4))):
        eng = engine if not kw else build(**kw)
        q2, _, ctr = traffic(eng)
        precision[label] = {"qps": round(q2, 1), "counters": ctr}
        if kw:
            eng.shutdown()
    for label in (wide, "int8-kv", "int8-kv+int4-w"):
        precision[label]["vs_wide"] = round(
            precision[label]["qps"] / precision[wide]["qps"], 2)
    ttft = np.array([h.ttft for h in handles]) * 1e3        # ms
    per_tok = np.array([h.per_token_latency for h in handles
                        if h.per_token_latency is not None]) * 1e3
    pct = lambda a, q: float(np.percentile(a, q))  # noqa: E731
    sla = {
        "qps": round(qps, 1),
        "requests": n_req,
        "ttft_ms": {q: round(pct(ttft, q), 2) for q in (50, 95, 99)},
        "token_ms": {q: round(pct(per_tok, q), 2)
                     for q in (50, 95, 99)},
        "slots_reused": engine.stats["slots_reused"],
        "decode_steps": engine.stats["decode_steps"],
    }
    # ISSUE-17 "slo" sub-dict: the default serve TTFT SLO evaluated
    # over the flagship pass — objective, measured p99 off the ring's
    # histogram delta, and the burn rate at end of run (burn > 1 means
    # this traffic shape would eat error budget in production)
    ttft_slo = next((s for s in slo_mod.default_slos()
                     if s.name == "serve-ttft-p99"), None)
    if ttft_slo is None:   # PADDLE_SLO_TTFT_P99=off
        ttft_slo = slo_mod.SLO("serve-ttft-p99", "latency",
                               "serve.ttft", 0.5)
    measured = ttft_slo.measure(slo_ring, slo_span)
    slo_row = {"slo": ttft_slo.name,
               "objective_s": ttft_slo.objective,
               "percentile": ttft_slo.percentile,
               "window_s": round(slo_span, 3)}
    if measured is not None:
        m, bad = measured
        slo_row["measured_s"] = round(m, 4)
        slo_row["burn_rate"] = round(ttft_slo.burn(bad), 3)
        slo_row["within_objective"] = bool(m <= ttft_slo.objective)
    # ISSUE-14 "mem" sub-dict: the engine's static HBM plan vs one
    # measured slot-decode dispatch, plus the KV pool bytes. Runs LAST:
    # on TPU the direct step dispatch donates the engine's state
    # buffers, so the engine serves no traffic after this.
    mp = engine.memory_plan()
    step = engine._programs[("step",)]
    margs = (engine._state, engine._cache, engine._lanes, engine._key)
    mem_plan = step.plan("bench.serve.decode")
    mem = _mem_sub_dict(
        mem_plan, lambda: step.jit(*margs, engine._cfg),
        margs, mp["kv_cache_bytes"])
    mem["predicted_engine_peak_bytes"] = mp["predicted_peak_bytes"]
    return {
        "metric": f"test-tiny serving QPS (continuous batching b{max_batch} "
                  f"poisson@{rate:g}/s, ttft p50={sla['ttft_ms'][50]}ms "
                  f"p99={sla['ttft_ms'][99]}ms, token p50="
                  f"{sla['token_ms'][50]}ms p99={sla['token_ms'][99]}ms, "
                  f"int8-kv {precision['int8-kv']['vs_wide']}x vs "
                  f"{wide}, device={dev.device_kind})",
        "value": round(qps, 1),
        "unit": "req/sec",
        "vs_baseline": 1.0,
        "sla": sla,
        "slo": slo_row,
        "precision": precision,
        "mem": mem,
        "goodput": goodput_row,
    }


def bench_serve_adversarial(dev, on_tpu):
    """Head-of-line-blocking bench (ISSUE-20 `serve --adversarial`
    mode): Poisson traffic of SHORT, TTFT-sensitive requests with a
    long prompt injected every few arrivals — the adversarial pattern
    where an inline long prefill parks the device for a whole
    monolithic dispatch while every short request behind it eats that
    wall into its TTFT. The same schedule runs twice at equal engine
    HBM (identical buckets/batch/cache; the only delta is the
    ``prefill_chunk_tokens`` knob): INLINE (chunking off) vs CHUNKED
    (page-aligned chunks interleaved with decode). Reports short-
    request TTFT p50/p95/p99 per mode plus each pass's serve.goodput
    compute fraction; the headline value is the p99 ratio
    (inline/chunked — higher is better), vs_baseline = ratio / 3 (the
    ISSUE-20 acceptance floor is 3x, so >= 1.0 means the gate holds)."""
    import os
    import threading
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config
    from paddle_tpu.models.gpt import gpt
    from paddle_tpu.serving import RequestParams, ServingEngine

    n_req = int(os.environ.get("BENCH_ADV_REQUESTS",
                               80 if on_tpu else 40))
    rate = float(os.environ.get("BENCH_ADV_RATE", 64.0))   # req/sec
    every = int(os.environ.get("BENCH_ADV_LONG_EVERY", 4))
    max_batch = int(os.environ.get("BENCH_SERVE_BATCH",
                                   8 if on_tpu else 4))
    max_new = int(os.environ.get("BENCH_ADV_NEW_TOKENS", 16))
    chunk = int(os.environ.get("BENCH_ADV_CHUNK_TOKENS", 32))
    paddle.seed(0)
    model = gpt("test-tiny", max_position_embeddings=1024)
    model.bfloat16() if on_tpu else None
    spec = [paddle.to_tensor(np.zeros((max_batch, 64), np.int32))]

    rng = np.random.RandomState(0)
    is_long = np.array([(i % every) == every - 1 for i in range(n_req)])
    prompts = [rng.randint(0, model.cfg.vocab_size,
                           rng.randint(400, 512) if lng
                           else rng.randint(4, 24)).astype(np.int32)
               for lng in is_long]
    budgets = rng.randint(4, max_new + 1, size=n_req)
    gaps = rng.exponential(1.0 / rate, size=n_req)

    counter = _metric_counter

    def run(prefill_chunk_tokens):
        cfg = (Config().from_layer(model, spec)
               .enable_generation(max_new_tokens=max_new,
                                  prefill_buckets=(32, 512),
                                  max_batch=max_batch)
               .enable_serving(max_queue=n_req,
                               prefill_chunk_tokens=prefill_chunk_tokens))
        engine = ServingEngine(cfg, poll_every=2)  # warmup compiles here
        before = {k: counter(k) for k in
                  ("jit.compile.total", "jit.compile{cause=new_shape}")}
        handles = []

        def feeder():
            for p, b, g in zip(prompts, budgets, gaps):
                time.sleep(g)
                handles.append(engine.submit(
                    p, RequestParams(max_new_tokens=int(b))))

        t0 = time.perf_counter()
        th = threading.Thread(target=feeder, daemon=True)
        th.start()
        while th.is_alive() or engine.busy:
            if engine.busy:
                engine.step()
            else:
                time.sleep(0.0002)
        dt = time.perf_counter() - t0
        th.join()
        assert len(handles) == n_req and \
            all(h.status.value == "completed" for h in handles)
        short_ttft = np.array([h.ttft for h, lng in zip(handles, is_long)
                               if not lng]) * 1e3           # ms
        gp = engine.goodput()
        row = {
            "qps": round(n_req / dt, 1),
            "short_ttft_ms": {q: round(float(np.percentile(short_ttft,
                                                           q)), 2)
                              for q in (50, 95, 99)},
            "long_requests": int(is_long.sum()),
            "goodput_fraction": round(gp["goodput_fraction"], 4),
            "counters": {k: counter(k) - before[k] for k in before},
        }
        if prefill_chunk_tokens:
            row["prefill_chunks"] = engine.stats["prefill_chunks"]
        engine.shutdown()
        return row

    inline = run(None)
    chunked = run(chunk)
    ratio = inline["short_ttft_ms"][99] / \
        max(chunked["short_ttft_ms"][99], 1e-9)
    return {
        "metric": f"test-tiny adversarial serving: short-request TTFT "
                  f"p99 {inline['short_ttft_ms'][99]}ms inline vs "
                  f"{chunked['short_ttft_ms'][99]}ms chunked@{chunk} "
                  f"(1 long per {every} arrivals, poisson@{rate:g}/s "
                  f"b{max_batch}, goodput {inline['goodput_fraction']} "
                  f"vs {chunked['goodput_fraction']}, "
                  f"device={dev.device_kind})",
        "value": round(ratio, 2),
        "unit": "x short-request TTFT p99 (inline/chunked)",
        "vs_baseline": round(ratio / 3.0, 2),   # gate: >= 3x -> >= 1.0
        "inline": inline,
        "chunked": chunked,
        "chunk_tokens": chunk,
    }


def bench_serve_router(dev, on_tpu):
    """Fleet-router bench (ISSUE-19 `serve --router` mode): the SAME
    Poisson traffic shape as the serve row, but fanned over a 3-replica
    in-process fleet behind the FleetRouter — with a zero-drop rolling
    deploy of one replica MID-RUN. Reports routed QPS (the headline:
    what the fleet sustains while losing and regaining a replica),
    the router's re-route/re-home accounting, and the rejoin's
    ExecutableStore counters (hits == program count, misses == 0: the
    relaunch paid zero XLA compiles). vs_baseline is 1.0 — this row
    defines the routed-serving baseline."""
    import os
    import shutil
    import threading
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config
    from paddle_tpu.jit.compile_cache import ExecutableStore, cache_root
    from paddle_tpu.models.gpt import gpt
    from paddle_tpu.serving import InProcessFleet, RequestParams

    n_req = int(os.environ.get("BENCH_ROUTER_REQUESTS",
                               96 if on_tpu else 24))
    rate = float(os.environ.get("BENCH_ROUTER_RATE", 64.0))  # req/sec
    n_rep = int(os.environ.get("BENCH_ROUTER_REPLICAS", 3))
    max_batch = int(os.environ.get("BENCH_SERVE_BATCH",
                                   8 if on_tpu else 2))
    max_new = int(os.environ.get("BENCH_SERVE_NEW_TOKENS", 32))
    paddle.seed(0)
    model = gpt("test-tiny", max_position_embeddings=1024)
    model.bfloat16() if on_tpu else None
    spec = [paddle.to_tensor(np.zeros((max_batch, 64), np.int32))]

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, model.cfg.vocab_size,
                           rng.randint(4, 128)).astype(np.int32)
               for _ in range(n_req)]
    budgets = rng.randint(max(4, max_new // 4), max_new + 1,
                          size=n_req)
    gaps = rng.exponential(1.0 / rate, size=n_req)

    store_dir = os.path.join(cache_root(), "bench-router")
    shutil.rmtree(store_dir, ignore_errors=True)
    store = ExecutableStore(store_dir)

    def factory(name):
        from paddle_tpu.serving import ServingEngine
        cfg = (Config().from_layer(model, spec)
               .enable_generation(max_new_tokens=max_new,
                                  prefill_buckets=(32, 64, 128),
                                  max_batch=max_batch)
               .enable_serving(max_queue=n_req, drain_timeout_s=120.0))
        return ServingEngine(cfg, poll_every=2, executable_store=store)

    fleet = InProcessFleet(factory, n=n_rep)   # warmup compiles here
    router = fleet.router
    handles = []

    def feeder():
        for p, b, g in zip(prompts, budgets, gaps):
            time.sleep(g)
            handles.append(router.submit(
                p, RequestParams(max_new_tokens=int(b))))

    t0 = time.perf_counter()
    th = threading.Thread(target=feeder, daemon=True)
    th.start()
    deployed = rejoin = None
    while True:
        engines = router.engines()
        busy = [e for e in engines.values() if e.busy]
        if deployed is None and len(handles) >= n_req // 2:
            # the gate move: drain + relaunch one replica while the
            # fleet's queues are live (its queued work re-homes)
            victim = sorted(engines)[-1]
            h0, m0 = store.stats["hits"], store.stats["misses"]
            fresh = fleet.rolling_deploy(victim)
            deployed = victim
            rejoin = {"replica": victim,
                      "programs": len(fresh._exes),
                      "store_hits": store.stats["hits"] - h0,
                      "store_misses": store.stats["misses"] - m0}
            continue
        if not busy and not th.is_alive():
            break
        for e in busy:
            e.step()
        if not busy:
            time.sleep(0.0002)
    outs = [h.result(timeout=600) for h in handles]
    dt = time.perf_counter() - t0
    th.join()
    assert len(outs) == n_req and \
        all(h.status.value == "completed" for h in handles)
    assert rejoin is not None and rejoin["store_misses"] == 0
    qps = n_req / dt
    stats = router.stats
    homes = {}
    for h in handles:
        homes[h.replica] = homes.get(h.replica, 0) + 1
    fleet.shutdown()
    return {
        "metric": f"test-tiny ROUTED serving QPS ({n_rep} replicas b"
                  f"{max_batch} poisson@{rate:g}/s, rolling deploy of "
                  f"{deployed} mid-run: {stats['rehomed']} re-homed, "
                  f"rejoin {rejoin['store_hits']}/{rejoin['programs']} "
                  f"programs warm, device={dev.device_kind})",
        "value": round(qps, 1),
        "unit": "req/sec",
        "vs_baseline": 1.0,
        "router": {
            "replicas": n_rep,
            "requests": n_req,
            "admissions": stats["admissions"],
            "reroutes": stats["reroutes"],
            "rehomed": stats["rehomed"],
            "rejected": stats["rejected"],
            "breaker_trips": stats["breaker_trips"],
            "placements": homes,
        },
        "deploy": rejoin,
    }


def bench_warmstart(dev, on_tpu):
    """Warm-restart bench (ISSUE-9 warmstart mode): relaunch-to-first-
    token (serving engine build + warmup + one request) and relaunch-
    to-first-step (fused TrainStep build + one step) on test-tiny,
    COLD (empty executable store — every program traces and
    XLA-compiles) vs WARM (same store — every program deserializes off
    the traceless manifest; `jax.clear_caches()` between phases drops
    all in-memory trace/compile state, so the warm phase sees exactly
    what a relaunched process sees: only the store persists).

    The timed window starts at MODEL-IN-MEMORY: a relauncher pays
    python import + module construction + checkpoint restore
    identically cold and warm — that cost is what `bench.py gpt2`-style
    rows already track — while THIS row isolates the window the
    executable store actually owns: build-the-programs-and-produce-the-
    first-output. vs_baseline is speedup / 5 (the ISSUE-9 acceptance
    floor is 5x, so >= 1.0 means the gate holds); the "warmstart"
    sub-dict carries cold_s/warm_s/speedup plus the store's hit/miss
    counters per mode."""
    import os
    import shutil
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.inference import Config
    from paddle_tpu.jit.compile_cache import ExecutableStore, cache_root
    from paddle_tpu.models.gpt import gpt
    from paddle_tpu.serving import RequestParams, ServingEngine

    root = os.environ.get("BENCH_WARMSTART_DIR", "")
    keep = bool(root)
    if not keep:
        # the COLD phase must start empty: empty one fixed directory
        root = os.path.join(cache_root(), "bench-warmstart")
        shutil.rmtree(root, ignore_errors=True)
    b, s, max_new = 2, 64, 16

    def serve_relaunch(store):
        """One serving relaunch, model already in memory: engine build
        + warmup (compiles or loads every program) + one request to its
        first token."""
        paddle.seed(0)
        model = gpt("test-tiny")
        spec = [paddle.to_tensor(np.zeros((2, 12), np.int32))]
        t0 = time.perf_counter()
        cfg = (Config().from_layer(model, spec)
               .enable_generation(max_new_tokens=max_new,
                                  prefill_buckets=(16, 32, 64),
                                  max_batch=2))
        engine = ServingEngine(cfg, poll_every=1,
                               executable_store=store)
        handle = engine.submit(np.arange(1, 9, dtype=np.int32),
                               RequestParams(max_new_tokens=1))
        toks = handle.result()
        return time.perf_counter() - t0, np.asarray(toks)

    def train_relaunch(store):
        """One training relaunch, model already in memory: warm-started
        TrainStep build + its first completed step."""
        paddle.seed(0)
        model = gpt("test-tiny", max_position_embeddings=s)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        ids = np.random.RandomState(0).randint(
            0, model.cfg.vocab_size, (b, s)).astype(np.int32)
        x = paddle.to_tensor(ids)
        y = paddle.to_tensor(ids.astype(np.int64))
        t0 = time.perf_counter()
        step = paddle.jit.TrainStep(
            model, opt,
            lambda logits, labels: model.loss(logits, labels))
        step.enable_warm_start(store)
        loss = float(step(x, y))
        return time.perf_counter() - t0, loss

    results = {}
    for mode, relaunch in (("serve", serve_relaunch),
                           ("train", train_relaunch)):
        store_root = os.path.join(root, mode)
        cold_store = ExecutableStore(store_root)
        cold_s, cold_out = relaunch(cold_store)
        jax.clear_caches()  # relaunch: no in-memory jit/trace state
        warm_store = ExecutableStore(store_root)
        warm_s, warm_out = relaunch(warm_store)
        assert warm_store.stats["hits"] > 0 and \
            warm_store.stats["misses"] == 0, warm_store.stats
        assert np.array_equal(np.asarray(cold_out),
                              np.asarray(warm_out)), \
            "warm relaunch must reproduce the cold outputs bitwise"
        results[mode] = {
            "cold_s": round(cold_s, 3), "warm_s": round(warm_s, 3),
            "speedup": round(cold_s / max(warm_s, 1e-9), 2),
            "cold_hits": cold_store.stats["hits"],
            "cold_misses": cold_store.stats["misses"],
            "warm_hits": warm_store.stats["hits"],
            "warm_misses": warm_store.stats["misses"],
        }
    if not keep:
        shutil.rmtree(root, ignore_errors=True)
    sv, tr = results["serve"], results["train"]
    speedup = min(sv["speedup"], tr["speedup"])
    return {
        "metric": f"test-tiny warm restart (serve {sv['speedup']}x: "
                  f"{sv['cold_s']}s->{sv['warm_s']}s to first token, "
                  f"train {tr['speedup']}x: {tr['cold_s']}s->"
                  f"{tr['warm_s']}s to first step, "
                  f"device={dev.device_kind})",
        "value": round(speedup, 2),
        "unit": "x cold/warm",
        "vs_baseline": round(speedup / 5.0, 4),
        "warmstart": results,
    }


# counter families attached to every BENCH row (flat keys always
# present so the row schema is stable; the labeled cause/... breakdown
# rides along when nonzero)
_COUNTER_KEYS = ("jit.compile.total", "jit.compile_cache.hits",
                 "jit.compile_cache.misses", "train.host_syncs",
                 "train.loss_fetches")
_COUNTER_PREFIXES = ("jit.compile{", "jit.compile_cache.misses{")


def _counter_values():
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot()
    out = {k: int(snap[k]["value"]) if k in snap else 0
           for k in _COUNTER_KEYS}
    for name, d in snap.items():
        if d["kind"] == "counter" and \
                any(name.startswith(p) for p in _COUNTER_PREFIXES):
            out[name] = int(d["value"])
    return out


def _with_counters(fn, dev, on_tpu):
    """Run one bench with the metrics registry on and attach the
    counter deltas as the row's "counters" sub-dict — a perf
    regression's first triage question ("did it retrace? miss the
    executable store? stall on host syncs?") answers itself from the
    BENCH json."""
    from paddle_tpu.profiler import metrics
    was = metrics.is_enabled()
    metrics.enable()
    before = _counter_values()
    try:
        row = fn(dev, on_tpu)
    finally:
        if not was:
            metrics.disable()
    after = _counter_values()
    row["counters"] = {k: after[k] - before.get(k, 0)
                       for k in sorted(after)
                       if k in _COUNTER_KEYS
                       or after[k] - before.get(k, 0)}
    return row


BENCHES = {
    "gpt2": bench_gpt2,
    "decode": bench_decode,
    "serve": bench_serve,
    "serve-prefix": bench_serve_shared_prefix,
    "serve-router": bench_serve_router,
    "serve-adversarial": bench_serve_adversarial,
    "warmstart": bench_warmstart,
    "moe-block": bench_moe_block,
    "resnet50": bench_resnet50,
    "ernie-base": bench_ernie_base,
    "bert-large": bench_bert_large,
    "gpt6.7b-layer": bench_gpt67_layer,
    "vit-l": bench_vit_l,
}


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "gpt2"
    # `bench.py serve --shared-prefix`: the paged-KV capacity gate
    # (ISSUE-12) instead of the PR-8 SLA row
    if which == "serve" and "--shared-prefix" in sys.argv[2:]:
        which = "serve-prefix"
    # `bench.py serve --router`: the ISSUE-19 fleet-router row (3
    # replicas + mid-run rolling deploy) instead of the PR-8 SLA row
    if which == "serve" and "--router" in sys.argv[2:]:
        which = "serve-router"
    # `bench.py serve --adversarial`: the ISSUE-20 head-of-line row
    # (short Poisson traffic + long-prompt injections, inline vs
    # chunked prefill at equal HBM) instead of the PR-8 SLA row
    if which == "serve" and "--adversarial" in sys.argv[2:]:
        which = "serve-adversarial"
    # warmstart measures COLD compiles: it must not inherit a populated
    # process-global cache (it anchors its own fresh store per phase)
    dev, on_tpu = _setup(configure_cache=(which != "warmstart"))
    if which == "all":
        failed = []
        for name, fn in BENCHES.items():
            if name == "gpt2":
                continue
            if name == "warmstart":
                # its COLD phase must not inherit the .jax_cache the
                # other benches just configured/populated (clear_caches
                # drops only in-memory state): run it standalone
                print(json.dumps({"metric": "warmstart SKIPPED in "
                                  "'all' (needs a cold process: run "
                                  "`python bench.py warmstart`)"}),
                      file=sys.stderr)
                continue
            try:
                print(json.dumps(_with_counters(fn, dev, on_tpu)),
                      file=sys.stderr)
            except Exception as e:
                # one failing config must not silence the flagship
                # line; it makes the exit code non-zero after it
                failed.append(name)
                print(json.dumps({"metric": f"{name} FAILED: {e}"}),
                      file=sys.stderr)
        print(json.dumps(_with_counters(bench_gpt2, dev, on_tpu)))
        if failed:
            raise SystemExit(f"benches failed: {failed}")
        return
    if which not in BENCHES:
        raise SystemExit(f"unknown bench {which!r}; one of "
                         f"{sorted(BENCHES)} or 'all'")
    print(json.dumps(_with_counters(BENCHES[which], dev, on_tpu)))


if __name__ == "__main__":
    main()
