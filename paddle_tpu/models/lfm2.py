"""LFM2-MoE decoder (LiquidAI LFM2-8B-A1B, ``model_type: lfm2_moe``): a
hybrid of gated short-convolution layers and grouped-query attention
layers, a dense gated MLP in the leading layers and dropless experts
behind a sigmoid router with a selection bias in the rest, the head tied
to the embedding — assembled from ``models/decoder.py``'s pieces and
``distributed.parallel.moe.DroplessMoE``.

Block ``l``: ``h = h + mixer_l(RMSNorm(h))``, ``h = h + ffn_l(RMSNorm(h))``.
``mixer_l`` is :class:`~.decoder.GatedShortConv` (``conv_L_cache`` taps,
no bias) or :class:`~.decoder.RotaryGQAttention` (per-head q/k RMSNorm
BEFORE rotary, ``rotate_half`` over the whole head, no bias) by
``layer_types[l]``; only attention layers see positions. ``ffn_l`` is
:class:`~.decoder.GatedMLP` of ``intermediate_size`` for
``l < num_dense_layers``, else ``num_experts`` experts of
``moe_intermediate_size``, ``num_experts_per_tok`` a token: scores
``sigmoid(W_g u)``, the experts chosen by ``score + expert_bias``, their
weights the scores WITHOUT the bias over (their sum + 1e-6), times
``routed_scaling_factor``.

The cache the model hands the serving surfaces has a KV layer for each
attention layer only and a ``[conv layers, lanes, L - 1, hidden]`` state
beside it (``generation.hybrid_cache.HybridCache``); a prefill at a
padded bucket hands on the state at ``prompt_len``.

Departures from the published code, each on purpose:

* the router's product runs in float32 at ``Precision.HIGHEST``
  (``dropless_moe`` does for every router kind): the 4th and 5th biased
  scores lie close, and which is chosen changes the output;
* the convolution's ``L`` multiply-adds a channel accumulate in float32;
* logits come off the float32 accumulator of the tied head's product;
* ``config.json`` has no key for the tied head: the published 8.3 B
  parameter count needs it (untied it would be 8.47 B).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import dispatch
from ..distributed.parallel.moe import DroplessMoE
from ..distributed.parallel.mp_layers import sharded_constraint
from ..nn.layer import Layer
from .decoder import (DecoderBlock, DecoderTrunk, GatedMLP, GatedShortConv,
                      RotaryGQAttention, gather_last, residual_std)

#: ``layer_types`` of LFM2-8B-A1B as published (24 layers)
LFM2_8B_A1B_LAYERS = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclass
class LFM2Config:
    """Sizes as config.json names them."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = LFM2_8B_A1B_LAYERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    intermediate_size: int = 7168
    num_dense_layers: int = 2
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    #: the type the parameters are created in (the experts directly, the
    #: rest by a cast): "bfloat16" to serve 4.7 B parameters on one chip
    dtype: str = "float32"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.conv_bias:
            raise NotImplementedError("conv_bias: the published model "
                                      "has none")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


class LFM2ForCausalLM(Layer):
    def __init__(self, cfg: LFM2Config):
        super().__init__()
        self.cfg = cfg
        std = cfg.initializer_range
        out_std = residual_std(std, cfg.num_hidden_layers)

        def mixer(kind):
            if kind == "conv":
                return GatedShortConv(cfg.hidden_size, cfg.conv_L_cache,
                                      std=std, out_std=out_std)
            return RotaryGQAttention(
                cfg.hidden_size, cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim, cfg.rope_theta,
                cfg.norm_eps, qk_norm=True, std=std, out_std=out_std)

        def ffn(i):
            if i < cfg.num_dense_layers:
                return GatedMLP(cfg.hidden_size, cfg.intermediate_size,
                                std=std, out_std=out_std)
            return DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.num_experts, cfg.num_experts_per_tok,
                cfg.norm_topk_prob, std=std, down_std=out_std,
                dtype=cfg.dtype, router="sigmoid",
                select_bias=cfg.use_expert_bias,
                scaling=cfg.routed_scaling_factor)

        blocks = [DecoderBlock(cfg.hidden_size, cfg.norm_eps, mixer(kind),
                               ffn(i))
                  for i, kind in enumerate(cfg.layer_types)]
        self.model = DecoderTrunk(
            cfg.vocab_size, cfg.hidden_size, cfg.norm_eps, blocks,
            cfg.num_key_value_heads, cfg.head_dim,
            cfg.max_position_embeddings, std=std)
        if cfg.dtype != "float32":
            self.to(dtype=cfg.dtype)

    @jax.named_scope("lm_head")
    def _logits(self, h):
        """The head tied to the embedding; float32 logits off the
        product's float32 accumulator (rounded to bfloat16 first, logits
        near 6 would lie 0.03 apart, and greedy ties go by rounding)."""
        logits = dispatch(
            "tied_lm_head_f32",
            lambda h_, w_: jnp.einsum(
                "bsh,vh->bsv", h_, w_.astype(h_.dtype),
                preferred_element_type=jnp.float32),
            (h, self.model.embed.weight), {})
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
