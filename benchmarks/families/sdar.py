"""Adapter between the harness and the program for the ``sdar`` family
(an SDAR-MoE decoder served by block diffusion).

Like ``families/gpt.py`` it is the one file of the benchmark that knows
the program's names for this family: how to build its model and engine
from a configuration file, how the reference's weight layout maps onto
the program's parameters, and what its compiled programs and kernels are
called in a device trace.  It reaches the program through its public
entry points only (the two readers of engine internals say why).
"""
from __future__ import annotations

import numpy as np

#: device-trace module names of the programs the window drives
PROGRAMS = {"decode_step": "jit_block_step_fn",
            "prefill": "jit_prefill_fn", "admit": "jit_block_admit_fn"}
#: device ops that are kernels, by (shortened) trace event name: the
#: Mosaic kernels and XLA's own grouped-product kernel are all
#: ``tpu_custom_call``s
KERNEL_OP = r"tpu_custom_call"
#: the two kernel classes whose rooflines are reported apart, by the
#: name their ops carry in the trace: the experts' grouped products are
#: ``jax.lax.ragged_dot`` (XLA prints them ``%ragged-dot...``), the block
#: attention is the paged decode kernel under ``window_causal=False``
KERNEL_CLASSES = {"moe_expert": r"ragged-dot",
                  "block_attn": r"flash_decode_paged"}

_PER_LAYER = {  # program leaf -> reference leaf
    "norm1.weight": "g1", "attn.q_proj.weight": "wq",
    "attn.k_proj.weight": "wk", "attn.v_proj.weight": "wv",
    "attn.q_norm": "gq", "attn.k_norm": "gk", "attn.o_proj.weight": "wo",
    "norm2.weight": "g2", "mlp.router": "wr", "mlp.gate_up": "wgu",
    "mlp.down": "wd"}
_TOP = {"model.embed.weight": "embed", "model.norm.weight": "gf",
        "lm_head.weight": "head"}


def top_layout(top: dict) -> dict:
    return {name: top[k] for name, k in _TOP.items()}


def layer_layout(i: int, lp: dict) -> dict:
    return {f"model.blocks.{i}.{leaf}": lp[k]
            for leaf, k in _PER_LAYER.items()}


def program_layout(canon: dict, cfg: dict) -> dict:
    """Reference-layout weights -> {program parameter name: array}: a
    renaming, no leaf is reshaped or copied.  Traceable."""
    out = top_layout(canon)
    for i, lp in enumerate(canon["layers"]):
        out.update(layer_layout(i, lp))
    return out


def model_config(cfg: dict):
    from paddle_tpu.models.sdar import SDARConfig
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "rms_norm_eps", "rope_theta",
            "max_position_embeddings")
    # built in the served type: 4.4 B float32 parameters pass one chip
    return SDARConfig(dtype=cfg["dtype"], **{k: cfg[k] for k in keys})


def _model(cfg: dict):
    import paddle_tpu as paddle
    from paddle_tpu.models.sdar import SDARForCausalLM
    # the program's own initialisers draw from its global key; the
    # harness overwrites every weight from --seed right after
    paddle.seed(0)
    return SDARForCausalLM(model_config(cfg))


def parameters(model) -> dict:
    return dict(model.named_parameters())


def set_weights(model, named: dict, part: bool = False) -> None:
    """Overwrite the model's parameters; ``part``: only those named (the
    driver fills the model a layer at a time)."""
    params = parameters(model)
    missing = set(named) - set(params) if part else set(params) ^ set(named)
    if missing:
        raise ValueError(f"weight names do not match the model: {missing}")
    for name, value in named.items():
        p = params[name]
        if tuple(p.shape) != tuple(value.shape) or p.dtype != value.dtype:
            raise ValueError(f"{name}: {p.shape} {p.dtype} vs "
                             f"{value.shape} {value.dtype}")
        p.set_value(value)


def build_engine(cfg: dict):
    """(model, engine-factory): weights go in before the engine snapshots
    them; every option comes from the configuration file."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config
    from paddle_tpu.serving import ServingEngine
    s = cfg["serve"]
    model = _model(cfg)
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
                    do_sample=bool(s["do_sample"]),
                    block_diffusion=dict(s["block_diffusion"]))
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
    """{request id: output tokens unmasked so far} of the requests that
    hold a lane, from the device's own per-lane counters (under block
    diffusion ``_steps`` counts tokens, not steps).  It waits for every
    dispatched step.  The engine offers no public reader of a request's
    progress before it finishes (PERF.md, Open questions)."""
    steps = np.asarray(engine._steps)
    return {req.id: int(steps[i]) for i, req in enumerate(engine._slots)
            if req is not None and req.first_token_at is not None}


def engine_programs(engine) -> dict:
    """{program key: compiled executable} of the engine's warm programs."""
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
