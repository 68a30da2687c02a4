"""Adapter between the harness and the program for the ``gpt`` family.

The only file of the benchmark that knows the program's names: how to
build its model, trainer and engine from a configuration file, how the
reference's weight layout maps onto the program's parameters, and what
its compiled programs are called in a device trace.  It reaches the
program through its public entry points only.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

#: device-trace module names of the programs the windows drive
PROGRAMS = {"train_step": "jit_step_fn", "decode_step": "jit_step_fn",
            "prefill": "jit_prefill_fn", "admit": "jit_admit_fn"}
#: device ops that are Pallas (Mosaic) kernels, by (shortened) trace
#: event name: the custom-call target, not an operand that mentions one
KERNEL_OP = r"tpu_custom_call"

_PER_LAYER = {  # program leaf -> reference leaf (qkv handled apart)
    "ln1.weight": "ln1_g", "ln1.bias": "ln1_b",
    "attn.out_proj.weight": "wo", "attn.out_proj.bias": "bo",
    "ln2.weight": "ln2_g", "ln2.bias": "ln2_b",
    "mlp.fc1.weight": "w1", "mlp.fc1.bias": "b1",
    "mlp.fc2.weight": "w2", "mlp.fc2.bias": "b2"}
_TOP = {"gpt.embed.wte.weight": "wte", "gpt.embed.wpe.weight": "wpe",
        "gpt.ln_f.weight": "lnf_g", "gpt.ln_f.bias": "lnf_b"}


def program_layout(canon: dict, cfg: dict) -> dict:
    """Reference-layout weights -> {program parameter name: array}.
    Traceable (runs inside the one jitted weight program)."""
    out = {name: canon[k] for name, k in _TOP.items()}
    lay = canon["layers"]
    for i in range(cfg["num_layers"]):
        pre = f"gpt.blocks.{i}."
        out[pre + "attn.qkv_proj.weight"] = jnp.concatenate(
            [lay["wq"][i], lay["wk"][i], lay["wv"][i]], axis=-1)
        out[pre + "attn.qkv_proj.bias"] = jnp.concatenate(
            [lay["bq"][i], lay["bk"][i], lay["bv"][i]], axis=-1)
        for leaf, k in _PER_LAYER.items():
            out[pre + leaf] = lay[k][i]
    return out


def to_canonical(named: dict, cfg: dict) -> dict:
    """{program parameter name: array} -> reference layout (inverse of
    :func:`program_layout`; the fused qkv leaf is split into q, k, v so
    that each is compared as a leaf of its own).  Traceable."""
    out = {k: named[name] for name, k in _TOP.items()}
    n, h = cfg["num_layers"], cfg["hidden_size"]
    lay = {k: [] for k in _PER_LAYER.values()}
    for k in ("wq", "wk", "wv", "bq", "bk", "bv"):
        lay[k] = []
    for i in range(n):
        pre = f"gpt.blocks.{i}."
        w = named[pre + "attn.qkv_proj.weight"]
        b = named[pre + "attn.qkv_proj.bias"]
        for j, (kw, kb) in enumerate((("wq", "bq"), ("wk", "bk"),
                                      ("wv", "bv"))):
            lay[kw].append(w[..., j * h:(j + 1) * h])
            lay[kb].append(b[j * h:(j + 1) * h])
        for leaf, k in _PER_LAYER.items():
            lay[k].append(named[pre + leaf])
    out["layers"] = {k: jnp.stack(v) for k, v in lay.items()}
    return out


def _model(cfg: dict, **overrides):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    # the program's own initialisers draw from its global key; the
    # harness overwrites every weight from --seed right after
    paddle.seed(0)
    gcfg = GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        tie_word_embeddings=cfg["tie_word_embeddings"], **overrides)
    return GPTForCausalLM(gcfg)


def parameters(model) -> dict:
    return dict(model.named_parameters())


def set_weights(model, named: dict) -> None:
    params = parameters(model)
    missing = set(params) ^ set(named)
    if missing:
        raise ValueError(f"weight names do not match the model: {missing}")
    for name, p in params.items():
        if tuple(p.shape) != tuple(named[name].shape):
            raise ValueError(f"{name}: {p.shape} vs {named[name].shape}")
        p.set_value(named[name])


def build_trainer(cfg: dict, seq_len: int):
    """(model, optimizer, step): chip_smoke.py's ``build_trainer`` — bf16
    weights, fp32 master weights, fused LM head + loss over the whole
    sequence, AdamW — with every number taken from the configuration."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    t = cfg["train"]
    if seq_len > cfg["max_position_embeddings"]:
        raise ValueError("traffic seq_len exceeds the configuration's "
                         "max_position_embeddings")
    model = _model(cfg, fused_lm_loss=bool(t["fused_lm_loss"]),
                   lm_loss_chunk=seq_len)
    if cfg["dtype"] == "bfloat16":
        model.bfloat16()
    opt = optimizer.AdamW(
        learning_rate=t["learning_rate"], beta1=t["beta1"],
        beta2=t["beta2"], epsilon=t["epsilon"],
        weight_decay=t["weight_decay"], parameters=model.parameters(),
        multi_precision=t["master_weights"] == "float32")

    def loss_fn(out, labels):
        return model.loss(out, labels)

    return model, opt, paddle.jit.TrainStep(model, opt, loss_fn)


def batch_tensors(ids: np.ndarray):
    import paddle_tpu as paddle
    return (paddle.to_tensor(ids.astype(np.int32)),
            paddle.to_tensor(ids.astype(np.int64)))


def train_state(step, cfg: dict):
    """What the optimizer holds after a step, by program parameter name:
    (first moments, float32 master weights).  Read from the step's own
    state lists; the program offers no public reader that stays on the
    device (PERF.md, Open questions)."""
    names = list(step._param_names)
    m = {n: st["moment1"] for n, st in zip(names, step._opt_state_tree)}
    w = {n: st.get("master", p._data) for n, st, p in
         zip(names, step._opt_state_tree, step._params_cache)}
    return m, w


def build_engine(cfg: dict):
    """A warm ``ServingEngine`` over a fresh model with the options the
    configuration file states; (model, engine-factory) so that weights
    go in before the engine snapshots them."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config
    from paddle_tpu.serving import ServingEngine
    s = cfg["serve"]
    model = _model(cfg)
    if cfg["dtype"] == "bfloat16":
        model.bfloat16()
    model.eval()

    def make():
        gen = s["generation"]
        spec = [paddle.to_tensor(
            np.zeros((1, gen["prefill_buckets"][0]), np.int32))]
        conf = (Config().from_layer(model, spec)
                .enable_tpu(s["precision"])
                .enable_generation(
                    max_new_tokens=gen["max_new_tokens"],
                    prefill_buckets=tuple(gen["prefill_buckets"]),
                    max_batch=gen["max_batch"],
                    do_sample=bool(s["do_sample"]))
                .enable_serving(**s["serving"]))
        return ServingEngine(conf)

    return model, make


def submit(engine, prompt: np.ndarray, max_new_tokens: int):
    from paddle_tpu.serving import RequestParams
    return engine.submit(prompt, RequestParams(max_new_tokens=max_new_tokens))


def completed(req) -> bool:
    from paddle_tpu.serving import RequestStatus
    return req.status is RequestStatus.COMPLETED


def lane_progress(engine) -> dict:
    """{request id: output tokens emitted so far} of the requests that hold
    a decode lane, read from the device's own per-lane counters (the ones
    the engine's poll reads; the prefill's token counts).  It waits for
    every dispatched step.  The engine offers no public reader of a
    request's progress before it finishes (PERF.md, Open questions).  A
    lane whose request has no first token yet (a chunked prefill under
    way) still shows its last holder's count and is left out."""
    steps = np.asarray(engine._steps)
    return {req.id: int(steps[i]) for i, req in enumerate(engine._slots)
            if req is not None and req.first_token_at is not None}


def engine_programs(engine) -> dict:
    """{program key: compiled executable} of the engine's warm programs
    (for ``memory_analysis`` and the kernel count)."""
    return {".".join(str(k) for k in key): exe
            for key, exe in engine._exes.items()}


def counter(name: str) -> int:
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot().get(name)
    return int(snap["value"]) if snap else 0


def monitor():
    from paddle_tpu.core import monitor as m
    return m


def enable_compile_cache(path):
    from paddle_tpu.jit import enable_compile_cache as enable
    return enable(path)
