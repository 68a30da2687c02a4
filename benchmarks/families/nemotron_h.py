"""Adapter between the harness and the program for the ``nemotron_h``
family (a Nemotron-H decoder: single-mixer blocks of Mamba-2, attention
and expert layers, served autoregressively as one chip's share).

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
#: the kernel classes whose rooflines are reported apart, by the name
#: their ops carry in the trace: the experts' grouped products (the
#: repo's kernel, or XLA's ``ragged_dot`` where it falls back), the paged
#: decode attention, the state-space mixers' one-step update
KERNEL_CLASSES = {"moe_expert": r"grouped_matmul|ragged-dot",
                  "decode_attn": r"flash_decode_paged",
                  "ssm_update": r"ssm_update"}

_PER_LAYER = {  # reference leaf -> program leaf, by the layer's kind
    "M": {"g": "norm1.weight", "w_in": "attn.in_proj.weight",
          "conv_w": "attn.conv", "conv_b": "attn.conv_bias",
          "a_log": "attn.A_log", "dt_bias": "attn.dt_bias", "d": "attn.D",
          "gn": "attn.norm", "w_out": "attn.out_proj.weight"},
    "*": {"g": "norm1.weight", "wq": "attn.q_proj.weight",
          "wk": "attn.k_proj.weight", "wv": "attn.v_proj.weight",
          "wo": "attn.o_proj.weight"},
    "E": {"g": "norm2.weight", "wr": "mlp.routed.router",
          "bias": "mlp.routed.select_bias", "wu": "mlp.routed.up",
          "wd": "mlp.routed.down", "ws_up": "mlp.shared.up_proj.weight",
          "ws_down": "mlp.shared.down_proj.weight"}}
_TOP = {"model.embed.weight": "embed", "model.norm.weight": "gf",
        "lm_head.weight": "head"}
#: the experts' width as the program stores it: whole lane tiles
#: (``NemotronHConfig.expert_pad_to``), columns of zeros past the
#: published width
PAD_TO = 128


def top_layout(top: dict) -> dict:
    return {name: top[k] for name, k in _TOP.items()}


def _stored(leaf: str, v):
    """An expert leaf padded with zeros to the stored width (exact:
    ``relu(0)^2 = 0``, and a zero row of ``wd`` adds nothing)."""
    import jax.numpy as jnp
    axis = {"wu": 2, "wd": 1}.get(leaf)
    if axis is None or v.shape[axis] % PAD_TO == 0:
        return v
    pad = [(0, 0)] * v.ndim
    pad[axis] = (0, -v.shape[axis] % PAD_TO)
    return jnp.pad(v, pad)


def layer_layout(i: int, lp: dict) -> dict:
    """Layer ``i``'s leaves under the program's names (the kind is told
    by a leaf only it has)."""
    kind = "M" if "w_in" in lp else "*" if "wq" in lp else "E"
    return {f"model.blocks.{i}.{_PER_LAYER[kind][k]}": _stored(k, v)
            for k, v in lp.items()}


def program_layout(canon: dict, cfg: dict) -> dict:
    """Reference-layout weights -> {program parameter name: array}: a
    renaming, and the experts' zero padding.  Traceable."""
    out = top_layout(canon)
    for i, lp in enumerate(canon["layers"]):
        out.update(layer_layout(i, lp))
    return out


def model_config(cfg: dict):
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
            "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
            "use_conv_bias", "time_step_min", "time_step_max",
            "time_step_floor", "num_attention_heads",
            "num_key_value_heads", "head_dim", "moe_intermediate_size",
            "moe_shared_expert_intermediate_size", "n_shared_experts",
            "n_routed_experts", "router_experts", "first_expert",
            "num_experts_per_tok", "n_group", "topk_group",
            "norm_topk_prob", "routed_scaling_factor",
            "layer_norm_epsilon", "max_position_embeddings")
    # built in the served type: 3.9 B float32 parameters pass one chip.
    # rope_theta is NOT handed on: nemotron_h attention applies no
    # position embedding (the configuration's ``assumed`` says why)
    return NemotronHConfig(dtype=cfg["dtype"], expert_pad_to=PAD_TO,
                           **{k: cfg[k] for k in keys})


def _model(cfg: dict):
    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM
    # the program's own initialisers draw from its global key; the
    # harness overwrites every weight from --seed right after
    paddle.seed(0)
    return NemotronHForCausalLM(model_config(cfg))


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
