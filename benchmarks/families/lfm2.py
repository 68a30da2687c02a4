"""Adapter between the harness and the program for the ``lfm2`` family
(an LFM2-MoE decoder: short-convolution and attention layers, dense and
expert MLPs, served autoregressively).

Like ``families/gpt.py`` it is the one file of the benchmark that knows
the program's names for this family: how to build its model and engine
from a configuration file, how the reference's weight layout maps onto
the program's parameters, and what its compiled programs and kernels are
called in a device trace.  It reaches the program through its public
entry points only (the two readers of engine internals say why).
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np


def _sibling(name: str):
    """Another family's adapter, by path (adapters are loaded so)."""
    spec = importlib.util.spec_from_file_location(
        "family_" + name, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# what does not depend on the model is ``families/sdar.py``'s: weights
# by name (whole or a part), requests in and out, the two readers of
# engine internals, the program's counters, monitor and compile cache
_shared = _sibling("sdar")
parameters, set_weights = _shared.parameters, _shared.set_weights
submit, completed = _shared.submit, _shared.completed
lane_progress, engine_programs = _shared.lane_progress, \
    _shared.engine_programs
counter, monitor = _shared.counter, _shared.monitor
enable_compile_cache = _shared.enable_compile_cache

#: device-trace module names of the programs the window drives
PROGRAMS = {"decode_step": "jit_step_fn", "prefill": "jit_prefill_fn",
            "admit": "jit_admit_fn"}
#: device ops that are kernels, by (shortened) trace event name: the
#: Mosaic kernels and XLA's own grouped-product kernel are all
#: ``tpu_custom_call``s
KERNEL_OP = r"tpu_custom_call"
#: the two kernel classes whose rooflines are reported apart, by the
#: name their ops carry in the trace: the experts' grouped products are
#: ``jax.lax.ragged_dot`` (XLA prints them ``%ragged-dot...``), the
#: decode attention is the paged decode kernel
KERNEL_CLASSES = {"moe_expert": r"ragged-dot",
                  "decode_attn": r"flash_decode_paged"}

_PER_LAYER = {  # reference leaf -> program leaf
    "g1": "norm1.weight", "g2": "norm2.weight",
    "w_in": "attn.in_proj.weight", "taps": "attn.conv",
    "w_out": "attn.out_proj.weight",
    "wq": "attn.q_proj.weight", "wk": "attn.k_proj.weight",
    "wv": "attn.v_proj.weight", "gq": "attn.q_norm", "gk": "attn.k_norm",
    "wo": "attn.o_proj.weight",
    "w1": "mlp.gate_proj.weight", "w3": "mlp.up_proj.weight",
    "w2": "mlp.down_proj.weight",
    "wr": "mlp.router", "bias": "mlp.select_bias", "wgu": "mlp.gate_up",
    "wd": "mlp.down"}
_TOP = {"model.embed.weight": "embed", "model.norm.weight": "gf"}


def top_layout(top: dict) -> dict:
    return {name: top[k] for name, k in _TOP.items()}


def layer_layout(i: int, lp: dict) -> dict:
    """Layer ``i``'s leaves, whatever kinds it is of, under the
    program's names."""
    return {f"model.blocks.{i}.{_PER_LAYER[k]}": v for k, v in lp.items()}


def program_layout(canon: dict, cfg: dict) -> dict:
    """Reference-layout weights -> {program parameter name: array}: a
    renaming, no leaf is reshaped or copied.  Traceable."""
    out = top_layout(canon)
    for i, lp in enumerate(canon["layers"]):
        out.update(layer_layout(i, lp))
    return out


def model_config(cfg: dict):
    from paddle_tpu.models.lfm2 import LFM2Config
    keys = ("vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
            "num_attention_heads", "num_key_value_heads", "conv_L_cache",
            "conv_bias", "intermediate_size", "num_dense_layers",
            "moe_intermediate_size", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "use_expert_bias",
            "norm_eps", "rope_theta", "max_position_embeddings")
    # built in the served type: 4.7 B float32 parameters pass one chip
    return LFM2Config(dtype=cfg["dtype"], **{k: cfg[k] for k in keys})


def _model(cfg: dict):
    import paddle_tpu as paddle
    from paddle_tpu.models.lfm2 import LFM2ForCausalLM
    # the program's own initialisers draw from its global key; the
    # harness overwrites every weight from --seed right after
    paddle.seed(0)
    return LFM2ForCausalLM(model_config(cfg))


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
                    do_sample=bool(s["do_sample"]))
                .enable_serving(**s["serving"]))
        return ServingEngine(conf)

    return model, make
