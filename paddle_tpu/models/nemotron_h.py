"""Nemotron-H decoder (NVIDIA Nemotron-3-Nano-30B-A3B, ``model_type:
nemotron_h``): a PATTERN of single-mixer blocks, ``h = h + mixer_l(
RMSNorm_l(h))``, one letter of ``hybrid_override_pattern`` a block:

* ``M`` :class:`~.decoder.Mamba2Mixer`: ``mamba_num_heads`` heads of
  ``mamba_head_dim`` (``d_inner`` is their product, NOT ``expand x
  hidden``: the published parameter count needs 64 x 64 = 4096),
  ``n_groups`` groups of ``B`` / ``C``, state size ``ssm_state_size``, a
  convolution of ``conv_kernel`` taps with a bias, the gated group norm
  with the gate before the norm;
* ``*`` :class:`~.decoder.RotaryGQAttention` with NO position embedding
  (``rope_theta`` / ``partial_rotary_factor`` in the config are read by
  nothing: the Mamba layers carry position), no q/k norms, no bias;
* ``E`` :class:`SharedPlusRoutedExperts`: ``shared(u) + routed(u)``, the
  shared expert an ungated relu^2 MLP of
  ``moe_shared_expert_intermediate_size`` every token passes, the routed
  ones ``distributed.parallel.moe.DroplessMoE`` (ungated relu^2 experts
  of ``moe_intermediate_size``; scores ``sigmoid(W_g u)`` over all
  ``router_experts``; the ``num_experts_per_tok`` largest of score +
  ``e_score_correction_bias`` chosen, ``n_group`` = ``topk_group`` = 1
  making the group step the identity; their weights the scores without
  the bias over (their sum + 1e-20), times ``routed_scaling_factor``).

After the last block the final RMSNorm and an untied head.

**The share.** ``n_routed_experts`` counts the experts HELD here, from
``first_expert`` on, of the ``router_experts`` the router ranks (the
chip's share under expert parallelism: the layer computes its own
experts' part of the result, and the shared expert whole); a sliced
vocabulary is simply a smaller ``vocab_size``.

**The states.** The cache the model hands the serving surfaces has a KV
layer for each ``*`` block and, for each ``M`` block, two states a lane
beside it (``generation.hybrid_cache.HybridCache``): the convolution's
window ``[conv_kernel - 1, d_inner + 2 G N]`` in the activations' type and
the state matrices ``[heads, head_dim, N]`` in ``ssm_state_dtype``
(float32). A prefill at a padded bucket hands on both at ``prompt_len``
(padded positions get ``dt = 0``).

Departures from the published code, each on purpose:

* the router's product runs in float32 at ``Precision.HIGHEST``
  (``dropless_moe`` does for every router kind);
* the softplus, ``exp(dt A)``, the recurrence, the convolution's taps and
  the group norm's sums are float32 whatever the activations' type; the
  state is kept in float32 between steps;
* the prefill is a chunked scan of ``chunk_size`` positions
  (``decoder.ssm_scan``), algebraically the recurrence, its products at
  ``Precision.HIGHEST``; a decode step is the one-step update;
* logits come off the float32 accumulator of the head's product;
* the experts' width is stored in whole lane tiles (``expert_pad_to``
  128: 1856 -> 1920, columns of zeros; exact, ``relu(0)^2 = 0``) so that
  both grouped products take ``kernels/grouped_matmul.py``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import dispatch
from ..distributed.parallel.moe import DroplessMoE
from ..distributed.parallel.mp_layers import sharded_constraint
from ..nn.layer import Layer
from ._common import spec_linear
from .decoder import (DecoderBlock, DecoderTrunk, Mamba2Mixer, Relu2MLP,
                      RotaryGQAttention, gather_last, residual_std)

#: ``hybrid_override_pattern`` of Nemotron-3-Nano-30B-A3B as published
NEMOTRON_3_NANO_PATTERN = \
    "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass
class NemotronHConfig:
    """Sizes as config.json names them, plus the share."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = NEMOTRON_3_NANO_PATTERN
    # M
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    ssm_state_dtype: str = "float32"
    # *
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    #: None: no position embedding (module docstring)
    rope_theta: Optional[float] = None
    # E
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    #: experts HELD here, from ``first_expert`` on, of ``router_experts``
    n_routed_experts: int = 128
    router_experts: Optional[int] = None
    first_expert: int = 0
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    expert_pad_to: int = 128
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    #: the type the parameters are created in (the experts directly, the
    #: rest by a cast): "bfloat16" to serve 3.9 B parameters on one chip
    dtype: str = "float32"

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers:
            raise ValueError(f"a pattern of {len(pattern)} blocks for "
                             f"{self.num_hidden_layers} layers")
        if set(pattern) - set("ME*"):
            raise ValueError(f"unknown block kinds in {pattern!r}: one of "
                             "M (Mamba-2), E (experts), * (attention)")
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        if self.first_expert + self.n_routed_experts > self.router_experts:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert}+"
                f"{self.n_routed_experts} outside the router's "
                f"{self.router_experts}")
        if (self.n_group, self.topk_group) != (1, 1):
            raise NotImplementedError("group-limited routing: the "
                                      "published model has one group")
        if self.n_shared_experts != 1 or not self.use_conv_bias:
            raise NotImplementedError("the published model has one shared "
                                      "expert and a convolution bias")

    @property
    def held(self):
        """(first, count) of the router's experts this chip holds; None
        where it holds them all."""
        if self.n_routed_experts == self.router_experts:
            return None
        return self.first_expert, self.n_routed_experts


class SharedPlusRoutedExperts(Layer):
    """``shared(u) + routed(u)``: the shared expert whole, every token;
    the routed layer's (share of the) result beside it."""

    def __init__(self, shared: Layer, routed: Layer):
        super().__init__()
        self.shared, self.routed = shared, routed

    def forward(self, x):
        with jax.named_scope("moe_shared"):
            y = self.shared(x)
        return y + self.routed(x)


class NemotronHForCausalLM(Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        std, eps = cfg.initializer_range, cfg.layer_norm_epsilon
        out_std = residual_std(std, cfg.num_hidden_layers)
        h = cfg.hidden_size

        def block(kind):
            if kind == "M":
                return DecoderBlock(h, eps, attn=Mamba2Mixer(
                    h, cfg.mamba_num_heads, cfg.mamba_head_dim,
                    cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel,
                    cfg.chunk_size, eps, std=std, out_std=out_std,
                    state_dtype=cfg.ssm_state_dtype,
                    dt_range=(cfg.time_step_min, cfg.time_step_max),
                    dt_floor=cfg.time_step_floor))
            if kind == "*":
                return DecoderBlock(h, eps, attn=RotaryGQAttention(
                    h, cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim, cfg.rope_theta, eps, qk_norm=False,
                    std=std, out_std=out_std))
            return DecoderBlock(h, eps, mlp=SharedPlusRoutedExperts(
                Relu2MLP(h, cfg.moe_shared_expert_intermediate_size,
                         std=std, out_std=out_std),
                DroplessMoE(
                    h, cfg.moe_intermediate_size, cfg.router_experts,
                    cfg.num_experts_per_tok, cfg.norm_topk_prob, std=std,
                    down_std=out_std, dtype=cfg.dtype, router="sigmoid",
                    select_bias=True, scaling=cfg.routed_scaling_factor,
                    gated=False, held=cfg.held, norm_eps=1e-20,
                    pad_to=cfg.expert_pad_to)))

        self.model = DecoderTrunk(
            cfg.vocab_size, h, eps,
            [block(k) for k in cfg.hybrid_override_pattern],
            cfg.num_key_value_heads, cfg.head_dim,
            cfg.max_position_embeddings, std=std)
        self.lm_head = spec_linear(h, cfg.vocab_size, std, P(None, "mp"),
                                   has_bias=False)
        if cfg.dtype != "float32":
            self.to(dtype=cfg.dtype)

    @jax.named_scope("lm_head")
    def _logits(self, h):
        """float32 logits off the head's float32 accumulator (rounded to
        bfloat16 first, greedy ties would go by rounding)."""
        logits = dispatch(
            "lm_head_f32",
            lambda h_, w_: jnp.einsum(
                "bsh,hv->bsv", h_, w_.astype(h_.dtype),
                preferred_element_type=jnp.float32),
            (h, self.lm_head.weight), {})
        return sharded_constraint(logits, P(("dp", "sharding"), None, "mp"))

    def forward(self, input_ids, cache=None, use_cache=False,
                prompt_len=None, cache_max_len=None, cache_dtype=None):
        """No cache: logits [b, s, vocab] of the causal forward. KV-cache
        protocol (``use_cache`` / ``cache``): (logits, cache); prefill
        returns the logits at each row's last real position
        ([b, 1, vocab]), a decode window the logits of all its
        positions."""
        if cache is None and not use_cache:
            return self._logits(self.model(input_ids))
        decode = cache is not None
        kv0 = cache.kv_len if decode else None
        h, cache = self.model(
            input_ids, cache=cache, use_cache=True, prompt_len=prompt_len,
            cache_max_len=cache_max_len, cache_dtype=cache_dtype)
        if prompt_len is not None:
            h = gather_last(h, prompt_len, kv0)
        elif not decode:
            h = h[:, -1:]
        return self._logits(h), cache
