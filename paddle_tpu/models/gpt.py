"""GPT decoder-only transformer — the flagship pretraining model
(BASELINE.json config #4; capability analog of the reference's
auto_parallel_gpt_model.py test fixture and PaddleNLP GPT).

TPU-first: every weight carries a PartitionSpec (mp on qkv/ffn out-dims,
vocab on embedding) so the SAME model runs single-chip or hybrid
dp×mp×sharding under DistributedTrainStep; attention goes through
F.scaled_dot_product_attention (Pallas flash kernel for long seq);
bf16-friendly throughout (fp32 layernorm accumulation)."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from ..distributed.parallel.mp_layers import sharded_constraint
from ..distributed.parallel.recompute import recompute
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..nn.layers_common import Dropout, Embedding, LayerNorm, Linear


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None   # default 4*hidden
    max_position_embeddings: int = 1024
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    use_recompute: bool = False
    #: remat policy when use_recompute: "selective" saves matmul
    #: outputs (save_dots_no_batch — cheap backward, moderate memory),
    #: "full" saves nothing (max memory relief, ~1.3x trunk FLOPs).
    #: ≈ the reference's recompute_granularity (full/core_attn); also
    #: accepts the fleet.utils.RecomputeConfig policy names
    #: (dots_saveable / nothing_saveable / dots_with_no_batch_dims_saveable)
    recompute_granularity: str = "selective"
    #: fuse the LM head into the loss, scanned over sequence chunks so
    #: the [B, S, vocab] logits are never materialized — the dominant
    #: HBM cost at long seq (B16 s2048 logits alone are 3.3 GB bf16).
    #: forward() then returns the final hidden states; loss() applies
    #: the chunked head+CE (rematerialized per chunk in backward)
    fused_lm_loss: bool = False
    lm_loss_chunk: int = 256
    #: when a single chunk covers the whole sequence AND its fp32
    #: logits fit this many bytes, skip the per-chunk remat and save
    #: the logits for backward instead (measured faster: 35.3 vs
    #: 40.8 ms on the b16-s1024 head — experiments/lm_loss_head_probe
    #: .py); above the budget the remat scan keeps peak HBM at
    #: chunk*vocab regardless of batch
    lm_loss_save_logits_budget: int = 4 << 30
    tie_word_embeddings: bool = True
    sequence_parallel: bool = False   # shard seq dim over 'sp' +
    # ring attention (NEW vs the reference — SURVEY §5 long-context story)
    moe_num_experts: int = 0          # >0: MoE FFN over the 'ep' axis
    moe_gate: str = "gshard"
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size


from ._common import spec_linear as _linear

#: recompute_granularity -> distributed.parallel.recompute policy name.
#: Keys cover both the reference's granularities (selective/core_attn/
#: full) and fleet.utils.RecomputeConfig's jax-named policies, so one
#: vocabulary works across model configs and train-step configs.
_REMAT_POLICY = {
    "selective": "save_dots_no_batch",
    "dots_with_no_batch_dims_saveable": "save_dots_no_batch",
    "core_attn": "save_dots",
    "dots_saveable": "save_dots",
    "full": "full",
    "nothing_saveable": "full",
}


def _remat_policy(granularity: str) -> str:
    """Resolve a recompute_granularity to the parallel.recompute policy
    name; a typo'd granularity ERRORS (silently training with a default
    policy would quietly ignore the user's memory/FLOPs intent)."""
    try:
        return _REMAT_POLICY[granularity]
    except KeyError:
        raise ValueError(
            f"unknown recompute_granularity {granularity!r}; one of "
            f"{sorted(_REMAT_POLICY)}") from None


class GPTAttention(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h, nh = cfg.hidden_size, cfg.num_heads
        self.num_heads = nh
        self.head_dim = h // nh
        std = cfg.initializer_range
        # fused qkv, out-dim mp-sharded (column parallel)
        self.qkv_proj = _linear(h, 3 * h, std, P(None, "mp"), P("mp"))
        # out proj, in-dim mp-sharded (row parallel)
        self.out_proj = _linear(h, h, std / math.sqrt(2 * cfg.num_layers),
                                P("mp", None), P())
        self.dropout_p = cfg.dropout
        self.sequence_parallel = cfg.sequence_parallel

    def forward(self, x, attn_mask=None, cache=None, layer_idx=0,
                decode=False):
        b, s, h = x.shape
        seq = "sp" if self.sequence_parallel else None
        qkv = self.qkv_proj(x)
        qkv = sharded_constraint(qkv, P(("dp", "sharding"), seq, "mp"))
        qkv = qkv.reshape([b, s, 3, self.num_heads, self.head_dim])
        q, k, v = qkv.unbind(axis=2)
        if cache is not None:
            # generation path (eval) — shared cache choreography in
            # generation/attention.py; GPT attends causally on prefill
            if self.sequence_parallel:
                raise NotImplementedError(
                    "KV-cache generation under sequence_parallel ring "
                    "attention is not supported")
            from ..generation.attention import cached_attention
            out, cache = cached_attention(
                q, k, v, cache, layer_idx, decode=decode, causal=True,
                attn_mask=attn_mask)
            return self.out_proj(out.reshape([b, s, h])), cache
        if self.sequence_parallel:
            if attn_mask is not None:
                raise ValueError(
                    "sequence_parallel ring attention does not support an "
                    "explicit attn_mask (causal only)")
            if self.dropout_p > 0.0 and self.training:
                raise ValueError(
                    "sequence_parallel ring attention does not support "
                    "attention dropout; set cfg.dropout = 0")
            from ..core.tensor import dispatch
            from ..distributed.parallel.context_parallel import \
                ring_attention
            out = dispatch(
                "ring_attention",
                lambda q_, k_, v_: ring_attention(q_, k_, v_, causal=True),
                (q, k, v), {})
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=True,
                dropout_p=self.dropout_p, training=self.training)
        out = out.reshape([b, s, h])
        return self.out_proj(out)


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        std = cfg.initializer_range
        self.fc1 = _linear(cfg.hidden_size, cfg.ffn_size, std,
                           P(None, "mp"), P("mp"))
        self.fc2 = _linear(cfg.ffn_size, cfg.hidden_size,
                           std / math.sqrt(2 * cfg.num_layers),
                           P("mp", None), P())
        self.dropout = Dropout(cfg.dropout)

    def forward(self, x):
        return self.dropout(self.fc2(F.gelu(self.fc1(x), approximate=True)))


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.attn = GPTAttention(cfg)
        self.ln2 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        if cfg.moe_num_experts > 0:
            from ..distributed.parallel.moe import MoEMLP
            self.mlp = MoEMLP(cfg.hidden_size, cfg.ffn_size,
                              num_experts=cfg.moe_num_experts,
                              gate=cfg.moe_gate,
                              capacity_factor=cfg.moe_capacity_factor)
        else:
            self.mlp = GPTMLP(cfg)

    def forward(self, x, attn_mask=None, cache=None, layer_idx=0,
                decode=False):
        # the regions a device trace (and a per-region roofline) tells
        # apart: embed / attn / mlp / lm_head
        if cache is not None:
            with jax.named_scope("attn"):
                a, cache = self.attn(self.ln1(x), attn_mask, cache=cache,
                                     layer_idx=layer_idx, decode=decode)
                x = x + a
            with jax.named_scope("mlp"):
                x = x + self.mlp(self.ln2(x))
            return x, cache
        with jax.named_scope("attn"):
            x = x + self.attn(self.ln1(x), attn_mask)
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.ln2(x))
        return x


class GPTEmbeddings(Layer):
    """Token + position embedding (+ dropout). Shared by the serial model
    and the pipeline 'pre' segment (≈ PaddleNLP GPTEmbeddings)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        std = cfg.initializer_range
        self.wte = Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.ParamAttr(initializer=I.Normal(0.0, std)))
        self.wte.weight.spec = P("mp", None)  # vocab-parallel
        self.wpe = Embedding(
            cfg.max_position_embeddings, cfg.hidden_size,
            weight_attr=I.ParamAttr(initializer=I.Normal(0.0, std)))
        self.wpe.weight.spec = P()
        self.drop = Dropout(cfg.dropout)
        self.sequence_parallel = cfg.sequence_parallel

    @jax.named_scope("embed")
    def forward(self, input_ids, pos=None):
        b, s = input_ids.shape
        from .. import ops
        if pos is None:
            pos = ops.creation.arange(s, dtype="int32")
        elif not isinstance(pos, Tensor):
            pos = Tensor(pos)  # decode: [b, s] offsets from the KV cache
        x = self.wte(input_ids) + self.wpe(pos)
        seq = "sp" if getattr(self, "sequence_parallel", False) else None
        x = sharded_constraint(x, P(("dp", "sharding"), seq, None))
        return self.drop(x)


@jax.named_scope("lm_head")
def _lm_logits(x, head, wte_weight):
    """Final head dispatch (tied vs separate), with the output constraint.
    Shared by GPTForCausalLM and GPTHeadPipe."""
    if head is not None:
        logits = head(x)
    else:
        logits = F.linear(x, _transpose(wte_weight))
    return sharded_constraint(logits, P(("dp", "sharding"), None, "mp"))


class _AuxBlock(Layer):
    """Adapter returning (x, moe_aux) so the aux loss crosses the
    jax.checkpoint boundary as a RETURN VALUE (an attribute set inside
    the remat scope would leak its tracer)."""

    def __init__(self, block: "GPTBlock"):
        super().__init__()
        self.block = block

    def forward(self, x, attn_mask=None):
        out = self.block(x, attn_mask)
        # MoEMLP.forward always sets l_aux to a scalar Tensor
        return out, self.block.mlp.l_aux


class GPTModel(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = GPTEmbeddings(cfg)
        self.blocks = LayerList([GPTBlock(cfg)
                                 for _ in range(cfg.num_layers)])
        self.ln_f = LayerNorm(cfg.hidden_size,
                              epsilon=cfg.layer_norm_epsilon)
        if cfg.moe_num_experts > 0:
            # plain list, NOT a LayerList: the adapters wrap blocks that
            # are already registered via self.blocks — registering them
            # again would duplicate every parameter in state_dict
            self._aux_blocks = [_AuxBlock(b) for b in self.blocks]
        #: total MoE aux loss of the last recompute-mode forward (same
        #: trace); None when the plain path ran (read l_aux attrs then)
        self._moe_aux = None

    def forward(self, input_ids, attn_mask=None, cache=None,
                use_cache=False, prompt_len=None, cache_max_len=None,
                cache_dtype=None):
        if cache is not None or use_cache:
            return self._forward_cached(input_ids, attn_mask, cache,
                                        prompt_len, cache_max_len,
                                        cache_dtype)
        x = self.embed(input_ids)
        self._moe_aux = None
        moe = self.cfg.moe_num_experts > 0
        if self.cfg.use_recompute and self.training:
            policy = _remat_policy(self.cfg.recompute_granularity)
            aux_total = None
            for i, block in enumerate(self.blocks):
                if moe:
                    x, aux = recompute(self._aux_blocks[i], x, attn_mask,
                                       policy=policy)
                    aux_total = aux if aux_total is None \
                        else aux_total + aux
                else:
                    x = recompute(block, x, attn_mask,
                                  policy=policy)
            self._moe_aux = aux_total
        else:
            for block in self.blocks:
                x = block(x, attn_mask)
        return self.ln_f(x)

    def _forward_cached(self, input_ids, attn_mask, cache, prompt_len,
                        cache_max_len, cache_dtype=None):
        """Generation forward (eval only): prefill creates + fills the
        KV cache (``cache=None``), decode consumes one. Returns
        (hidden, cache). ``prompt_len`` [b] marks each row's true
        length in a right-padded prompt; kv_len advances to it so the
        pad tail is invisible to (and overwritten by) decode steps.
        ``cache_dtype="int8"`` creates the quantized cache (values
        quantize in-trace at every write; decode dequantizes inside
        the kernel). Decode + ``prompt_len`` is the chunked-prefill
        window: s cache-writing positions whose tail may overhang the
        row's true length (the final padded chunk), so kv_len clamps
        to ``prompt_len`` — the overhang stays invisible to (and is
        overwritten by) later decode steps, exactly like prefill's pad
        tail."""
        from ..generation.kv_cache import KVCache
        import jax.numpy as jnp
        b, s = input_ids.shape
        decode = cache is not None
        if decode:
            x = self.embed(input_ids, pos=cache.positions(s))
        else:
            x = self.embed(input_ids)
            max_len = int(cache_max_len
                          or self.cfg.max_position_embeddings)
            cache = KVCache.create(
                self.cfg.num_layers, b, max_len, self.cfg.num_heads,
                self.cfg.hidden_size // self.cfg.num_heads,
                dtype=x._data.dtype, cache_dtype=cache_dtype)
        for i, block in enumerate(self.blocks):
            x, cache = block(x, attn_mask, cache=cache, layer_idx=i,
                             decode=decode)
        if decode:
            new_len = cache.kv_len + s
            if prompt_len is not None:
                plen = jnp.asarray(
                    prompt_len._data if isinstance(prompt_len, Tensor)
                    else prompt_len, jnp.int32)
                new_len = jnp.minimum(new_len, plen)
            cache = cache.with_kv_len(new_len)
        else:
            cache = cache.with_kv_len(
                s if prompt_len is None else prompt_len)
        return self.ln_f(x), cache


class GPTForCausalLM(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size,
                                   cfg.initializer_range, P(None, "mp"),
                                   has_bias=False)
        else:
            self.lm_head = None

    def forward(self, input_ids, attn_mask=None, cache=None,
                use_cache=False, prompt_len=None, cache_max_len=None,
                cache_dtype=None):
        if cache is not None or use_cache:
            return self._forward_cached(input_ids, attn_mask, cache,
                                        prompt_len, cache_max_len,
                                        cache_dtype)
        h = self.gpt(input_ids, attn_mask)
        if self.cfg.fused_lm_loss:
            # ship the head weight WITH the output (cloned while any
            # functional_call binding is live) so loss() sees the
            # traced/current value — reading self...weight there would
            # bake a stale constant into compiled train steps and drop
            # the head-weight gradient
            w = self.lm_head.weight if self.lm_head is not None \
                else self.gpt.embed.wte.weight
            return h, w.clone()
        return _lm_logits(h, self.lm_head,
                          self.gpt.embed.wte.weight)

    def _forward_cached(self, input_ids, attn_mask, cache, prompt_len,
                        cache_max_len, cache_dtype=None):
        """Generation forward: returns (logits, cache). Prefill returns
        next-token logits only ([b, 1, vocab], gathered at each row's
        last REAL position — the [b, s, vocab] prompt logits are never
        materialized); decode returns logits for all (1..8) new
        positions. Always the real LM head, even under fused_lm_loss
        (generation samples from logits, not a loss)."""
        import jax.numpy as jnp
        decode = cache is not None
        kv0 = cache.kv_len if decode else None
        h, cache = self.gpt(input_ids, attn_mask, cache=cache,
                            use_cache=True, prompt_len=prompt_len,
                            cache_max_len=cache_max_len,
                            cache_dtype=cache_dtype)
        if decode and prompt_len is not None:
            # chunked-prefill final window: gather each row's hidden at
            # its last REAL prompt position (global prompt_len - 1 ==
            # window-local prompt_len - 1 - kv_len-at-entry; the padded
            # tail past it is never sampled) → [b, 1, vocab], same
            # shape as a decode step's single-token logits
            from ..core.tensor import dispatch
            plen = jnp.asarray(
                prompt_len._data if isinstance(prompt_len, Tensor)
                else prompt_len, jnp.int32)
            idx = plen - 1 - kv0.astype(jnp.int32)
            h = dispatch(
                "gather_last_hidden",
                lambda hr, ir: jnp.take_along_axis(
                    hr, ir[:, None, None], axis=1),
                (h, idx), {}, differentiable=False)
        elif not decode:
            from ..core.tensor import dispatch
            b, s = input_ids.shape
            if prompt_len is None:
                h = h[:, s - 1:s]
            else:
                idx = jnp.asarray(
                    prompt_len._data if isinstance(prompt_len, Tensor)
                    else prompt_len, jnp.int32) - 1
                h = dispatch(
                    "gather_last_hidden",
                    lambda hr, ir: jnp.take_along_axis(
                        hr, ir[:, None, None], axis=1),
                    (h, idx), {}, differentiable=False)
        logits = _lm_logits(h, self.lm_head, self.gpt.embed.wte.weight)
        return logits, cache

    def generate(self, input_ids, max_new_tokens: int = 32, **kwargs):
        """Autoregressive decoding with the KV cache — see
        ``paddle_tpu.generation.generate`` for sampling options."""
        from ..generation.api import generate as _generate
        return _generate(self, input_ids, max_new_tokens, **kwargs)

    @jax.named_scope("lm_head")
    def _fused_loss(self, hidden, labels, w):
        """Chunked LM-head + cross-entropy: scan sequence chunks, each
        chunk's logits live only inside its (rematerialized) scan step.
        HBM for logits drops from S*V to chunk*V per microbatch.
        `w` is the head weight ([in, V] untied / [V, in] tied wte),
        passed as a traced operand so its gradient flows."""
        import jax

        h = hidden
        y = labels
        tied = self.lm_head is None
        hs = h[:, :-1, :]
        ys = y[:, 1:]
        b, s1, hd = hs.shape
        chunk = min(self.cfg.lm_loss_chunk, s1)
        n_chunks = -(-s1 // chunk)

        def chunk_ce(hc, yc):
            wmat = w.T if tied else w
            logits = (hc @ wmat.astype(hc.dtype)).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            yc_safe = jnp.maximum(yc, 0)
            gold = jnp.take_along_axis(
                logits, yc_safe[..., None], axis=-1)[..., 0]
            valid = (yc >= 0).astype(jnp.float32)
            return jnp.sum((lse - gold) * valid), jnp.sum(valid)

        vocab = w.shape[0] if tied else w.shape[-1]
        budget = self.cfg.lm_loss_save_logits_budget
        if n_chunks == 1 and b * s1 * vocab * 4 <= budget:
            # single chunk within the HBM budget: skip the scan AND the
            # remat — saving the logits for backward beats recomputing
            # the vocab matmul (measured: 35.3 vs 40.8 ms for the
            # b16-s1024 head, experiments/lm_loss_head_probe.py)
            total, count = chunk_ce(hs, ys)
            return total / jnp.maximum(count, 1.0)
        pad = n_chunks * chunk - s1
        hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
        ys = jnp.pad(ys, ((0, 0), (0, pad)), constant_values=-1)
        hs = hs.reshape(b, n_chunks, chunk, hd).transpose(1, 0, 2, 3)
        ys = ys.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

        # NOTE r4: a middle tier (explicit bf16-logit residuals via
        # custom_vjp — see experiments/fused_ce_probe.py) wins the
        # isolated head by ~22% at b32/s2048 but LOSES end-to-end
        # (b32 MFU 0.468 -> 0.440, s2048 0.452 -> 0.428): the ~3.3 GB
        # of residuals resident across the trunk backward cost more in
        # scheduling/spill than the saved vocab-matmul remat. Measured
        # and reverted — over-budget configs keep the remat scan.
        def body(carry, xs):
            hc, yc = xs
            ssum, cnt = jax.checkpoint(chunk_ce)(hc, yc)
            return (carry[0] + ssum, carry[1] + cnt), None

        (total, count), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (hs, ys))
        return total / jnp.maximum(count, 1.0)

    def loss(self, logits, labels):
        """Shifted LM loss (mean over non-shifted tokens) + MoE aux loss
        when experts are active (read in the same trace as forward)."""
        fused = (self is not None
                 and getattr(self, "cfg", None) is not None
                 and self.cfg.fused_lm_loss)
        if fused:
            from ..core.tensor import dispatch
            hidden, w = logits  # forward returned (hidden, head_weight)
            # routed through dispatch so the eager tape records it and
            # the head weight is a differentiable operand
            ce = dispatch("fused_lm_loss",
                          lambda h, y, wv: self._fused_loss(h, y, wv),
                          (hidden, labels, w), {})
        else:
            shifted = logits[:, :-1, :]
            targets = labels[:, 1:]
            ce = F.cross_entropy(
                shifted.reshape([-1, shifted.shape[-1]]),
                targets.reshape([-1]))
        if self is not None and getattr(self, "cfg", None) is not None \
                and self.cfg.moe_num_experts > 0:
            carried = getattr(self.gpt, "_moe_aux", None)
            if carried is not None:  # recompute path: aux was returned
                aux = carried
            else:
                from ..distributed.parallel.moe import aux_loss
                aux = aux_loss(self)
            ce = ce + self.cfg.moe_aux_weight * aux
        return ce

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len: int) -> float:
        """Approximate training FLOPs/token (6N + attention term)."""
        n = self.num_params()
        att = 12 * self.cfg.num_layers * self.cfg.hidden_size * seq_len
        return 6 * n + att


def _transpose(w):
    from .. import ops
    return ops.linalg.t(w)


# convenience configs (≈ PaddleNLP gpt2 sizes; 6.7B = BASELINE config #4)
CONFIGS = {
    "gpt2-small": GPTConfig(hidden_size=768, num_layers=12, num_heads=12),
    "gpt2-medium": GPTConfig(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt2-large": GPTConfig(hidden_size=1280, num_layers=36, num_heads=20),
    "gpt3-6.7b": GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                           max_position_embeddings=2048),
    "test-tiny": GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                           num_heads=4, max_position_embeddings=128),
    # draft companion for speculative decoding tests/bench: same vocab
    # and position table as test-tiny (a draft LM must share both), a
    # quarter of the compute — the KVCache layout class is identical
    "test-tiny-draft": GPTConfig(vocab_size=512, hidden_size=32,
                                 num_layers=1, num_heads=2,
                                 max_position_embeddings=128),
}


def gpt(name: str = "gpt2-small", **overrides) -> GPTForCausalLM:
    import dataclasses
    cfg = dataclasses.replace(CONFIGS[name], **overrides)
    return GPTForCausalLM(cfg)


# ---------------------------------------------------------------- pipeline
GPTEmbeddingPipe = GPTEmbeddings  # the 'pre' segment IS the embedding


class GPTHeadPipe(Layer):
    """'post' segment: final norm + (tied) LM head. Holds an unregistered
    reference to the embedding for weight tying (the SharedLayerDesc
    analog — values flow through the embedding's own name under
    functional_call)."""

    def __init__(self, cfg: GPTConfig, embed: Optional[GPTEmbeddings]):
        super().__init__()
        self.ln_f = LayerNorm(cfg.hidden_size,
                              epsilon=cfg.layer_norm_epsilon)
        self._embed_ref = [embed]
        if embed is None:
            self.head = _linear(cfg.hidden_size, cfg.vocab_size,
                                cfg.initializer_range, P(None, "mp"),
                                has_bias=False)
        else:
            self.head = None

    def forward(self, x):
        x = self.ln_f(x)
        wte = self._embed_ref[0].wte.weight if self.head is None else None
        return _lm_logits(x, self.head, wte)


def gpt_pipe(name: str = "gpt2-small", num_stages: Optional[int] = None,
             num_microbatches: Optional[int] = None, interleave: int = 1,
             seg_sizes=None, **overrides):
    """Pipeline-parallel GPT: [embed | blocks... | norm+head] as a
    PipelineLayer over the 'pp' mesh axis (≈ GPTForCausalLMPipe)."""
    import dataclasses
    from ..distributed.parallel.pipeline import PipelineLayer
    cfg = dataclasses.replace(CONFIGS[name], **overrides)
    if cfg.moe_num_experts > 0:
        # per-stage aux-loss collection across the pp shard_map stages is
        # not wired yet; fail loudly rather than silently dropping the
        # load-balancing loss
        raise NotImplementedError(
            "MoE inside the pipeline-parallel GPT is not supported yet; "
            "use the serial gpt() model with ep/dp/mp axes instead")
    embed = GPTEmbeddingPipe(cfg)
    layers = ([embed] + [GPTBlock(cfg) for _ in range(cfg.num_layers)]
              + [GPTHeadPipe(cfg, embed if cfg.tie_word_embeddings
                             else None)])
    model = PipelineLayer(
        layers, num_stages=num_stages,
        num_microbatches=num_microbatches,
        use_recompute=cfg.use_recompute, interleave=interleave,
        seg_sizes=seg_sizes,
        loss_fn=lambda logits, labels: GPTForCausalLM.loss(
            None, logits, labels))
    model.cfg = cfg
    return model
