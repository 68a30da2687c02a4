"""SDAR-MoE decoder (JetLM SDAR-30B-A3B-Chat, ``model_type: sdar_moe``):
RMSNorm, rotary positions, grouped-query attention with per-head q/k
norms, dropless SiLU-gated experts in every layer, an untied head —
assembled from ``models/decoder.py``'s pieces and
``distributed.parallel.moe.DroplessMoE``.

The model is generated from by DIFFUSION OVER BLOCKS
(``generation/block_diffusion.py``): the serving surfaces pass
``block_length=B`` through the KV-cache protocol, under which prefill
attends block-causally, a decode window is one block of B positions that
all see each other, and logits are returned for every position of the
window (they predict the token AT a position: no shift). Without
``block_length`` the forward is plain causal.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import dispatch
from ..distributed.parallel.moe import DroplessMoE
from ..distributed.parallel.mp_layers import sharded_constraint
from ..nn.layer import Layer
from ._common import spec_linear
from .decoder import (DecoderBlock, DecoderTrunk, RotaryGQAttention,
                      gather_last, residual_std)


@dataclass
class SDARConfig:
    """Sizes as config.json names them (``num_hidden_layers`` etc.)."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    qk_norm: bool = True
    initializer_range: float = 0.02
    #: the type the parameters are created in (the experts directly, the
    #: rest by a cast): "bfloat16" to serve 4.4 B parameters on one chip
    dtype: str = "float32"


class SDARForCausalLM(Layer):
    def __init__(self, cfg: SDARConfig):
        super().__init__()
        self.cfg = cfg
        std = cfg.initializer_range
        out_std = residual_std(std, cfg.num_hidden_layers)
        blocks = [DecoderBlock(
            cfg.hidden_size, cfg.rms_norm_eps,
            RotaryGQAttention(
                cfg.hidden_size, cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim, cfg.rope_theta,
                cfg.rms_norm_eps, qk_norm=cfg.qk_norm, std=std,
                out_std=out_std),
            DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.num_experts, cfg.num_experts_per_tok,
                cfg.norm_topk_prob, std=std, down_std=out_std,
                dtype=cfg.dtype))
            for _ in range(cfg.num_hidden_layers)]
        self.model = DecoderTrunk(
            cfg.vocab_size, cfg.hidden_size, cfg.rms_norm_eps, blocks,
            cfg.num_key_value_heads, cfg.head_dim,
            cfg.max_position_embeddings, std=std)
        self.lm_head = spec_linear(cfg.hidden_size, cfg.vocab_size, std,
                                   P(None, "mp"), has_bias=False)
        if cfg.dtype != "float32":
            self.to(dtype=cfg.dtype)

    @jax.named_scope("lm_head")
    def _logits(self, h):
        """float32 logits off the bf16 product's float32 accumulator:
        rounded to bfloat16 first, logits near 6 would lie 0.03 apart,
        and block diffusion ranks positions by their confidence."""
        logits = dispatch(
            "lm_head_f32",
            lambda h_, w_: jnp.matmul(h_, w_.astype(h_.dtype),
                                      preferred_element_type=jnp.float32),
            (h, self.lm_head.weight), {})
        return sharded_constraint(logits, P(("dp", "sharding"), None, "mp"))

    def forward(self, input_ids, cache=None, use_cache=False,
                prompt_len=None, cache_max_len=None, cache_dtype=None,
                block_length=None):
        """No cache: logits [b, s, vocab] of the (block-)causal forward.
        KV-cache protocol (``use_cache`` / ``cache``): (logits, cache);
        prefill returns the logits at each row's last real position
        ([b, 1, vocab]), a decode window the logits of all its
        positions."""
        if cache is None and not use_cache:
            return self._logits(self.model(input_ids, block=block_length))
        decode = cache is not None
        kv0 = cache.kv_len if decode else None
        h, cache = self.model(
            input_ids, cache=cache, use_cache=True, prompt_len=prompt_len,
            cache_max_len=cache_max_len, cache_dtype=cache_dtype,
            block=block_length)
        if prompt_len is not None:
            h = gather_last(h, prompt_len, kv0)
        elif not decode:
            h = h[:, -1:]
        return self._logits(h), cache
