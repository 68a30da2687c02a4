"""Shared cached-attention step for model wiring.

GPT and ERNIE attention layers run the identical cache choreography —
write the fresh k/v into the ring at each row's ``kv_len``, then either
attend the cached prefix through the decode flash kernel (decode) or
run ordinary self-attention over the fresh window (prefill). One
implementation here so a fix (GQA cache heads, sharded creation, mask
semantics) can never silently diverge between models; only the
``causal`` flag differs.
"""
from __future__ import annotations


def cached_attention(q, k, v, cache, layer_idx, *, decode: bool,
                     causal: bool, attn_mask=None):
    """Write ``k``/``v`` ([b, s, heads, head_dim] Tensors) into
    ``cache`` at layer ``layer_idx`` and attend. Returns (out, cache);
    ``out`` is [b, s, heads, head_dim]. Decode reads the cached prefix
    via ``kernels.flash_attention_decode`` with per-row ragged masking
    at ``kv_len + s``; prefill is plain self-attention over the fresh
    window (``causal`` per model family, ``attn_mask`` honored)."""
    from ..core.tensor import dispatch
    from ..nn import functional as F
    cache = cache.update(layer_idx, k, v, cache.kv_len)
    if decode:
        s = q.shape[1]
        mask_len = cache.kv_len + s  # includes the new rows
        quant = getattr(cache, "k_scale", None) is not None
        if getattr(cache, "page_table", None) is not None:
            # paged cache: attend the pooled pages through the row's
            # page table (index-map indirection on TPU, gather+mask
            # off it — bitwise-equal either way). The kernel takes the
            # STACKED pools (and scale sidecars) and picks the layer in
            # its index map: slicing cache.k[layer_idx] here would copy
            # a whole layer's pool every step
            from ..kernels.flash_attention import \
                flash_attention_decode_paged
            scales = (cache.k_scale, cache.v_scale) if quant else ()
            out = dispatch(
                "flash_attention_decode_paged",
                lambda q_, kp, vp, pt, kl, *sc:
                    flash_attention_decode_paged(
                        q_, kp, vp, pt, kl, layer_idx,
                        **(dict(k_scale=sc[0], v_scale=sc[1])
                           if sc else {})),
                (q, cache.k, cache.v, cache.page_table, mask_len)
                + scales, {}, differentiable=False)
            return out, cache
        # int8 cache (QuantKVCache): the layer's scale sidecars ride as
        # two extra operands — dequant fuses in-register, the wide
        # cache is never materialized
        scales = (cache.k_scale[layer_idx], cache.v_scale[layer_idx]) \
            if quant else ()
        from ..kernels.flash_attention import (
            MAX_DECODE_QLEN, flash_attention_chunk,
            flash_attention_decode)
        if s > MAX_DECODE_QLEN:
            # chunk-prefill window (serving's chunked admission): a
            # C-token slice of a long prompt attends the cache written
            # by the earlier chunks — decode-shaped ragged masking,
            # q-tiled kernel (dense cache only; the engine's chunk
            # side-cache is never paged)
            out = dispatch(
                "flash_attention_chunk",
                lambda q_, kc, vc, kl, *sc: flash_attention_chunk(
                    q_, kc, vc, kl,
                    **(dict(k_scale=sc[0], v_scale=sc[1])
                       if sc else {})),
                (q, cache.k[layer_idx], cache.v[layer_idx], mask_len)
                + scales, {}, differentiable=False)
            return out, cache
        out = dispatch(
            "flash_attention_decode",
            lambda q_, kc, vc, kl, *sc: flash_attention_decode(
                q_, kc, vc, kl,
                **(dict(k_scale=sc[0], v_scale=sc[1]) if sc else {})),
            (q, cache.k[layer_idx], cache.v[layer_idx], mask_len)
            + scales, {}, differentiable=False)
    else:
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=causal,
            dropout_p=0.0, training=False)
    return out, cache
