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


def block_causal_attention(q, k, v, block: int):
    """Prefill self-attention under the BLOCK-causal mask of generation
    by diffusion over blocks (query i sees key j iff
    ``j // block <= i // block``), q/k/v raw [b, s, heads, head_dim]
    with grouped kv heads. The flash kernel (``block=``) where it
    applies, else the XLA softmax under an explicit mask."""
    import jax
    import jax.numpy as jnp
    from ..nn.functional.attention import _FLASH_MIN_SEQ, _sdpa_xla
    s, d = q.shape[1], q.shape[-1]
    if jax.default_backend() == "tpu" and s >= _FLASH_MIN_SEQ \
            and s % 128 == 0 and d in (64, 128, 256):
        from ..kernels.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=True, block=block)
    g = q.shape[2] // k.shape[2]
    if g > 1:       # query head i reads kv head i // g
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    blk = jnp.arange(s, dtype=jnp.int32) // block
    return _sdpa_xla(q, k, v, mask=blk[None, :] <= blk[:, None])


def cached_attention(q, k, v, cache, layer_idx, *, decode: bool,
                     causal: bool, attn_mask=None, block=None):
    """Write ``k``/``v`` ([b, s, heads, head_dim] Tensors) into
    ``cache`` at layer ``layer_idx`` and attend. Returns (out, cache);
    ``out`` is [b, s, heads, head_dim]. Decode reads the cached prefix
    via ``kernels.flash_attention_decode`` with per-row ragged masking
    at ``kv_len + s``; prefill is plain self-attention over the fresh
    window (``causal`` per model family, ``attn_mask`` honored).

    ``block`` (generation by diffusion over blocks): prefill attends
    block-causally, and a decode window is one block whose ``s``
    positions all see each other as well as the cached prefix
    (``window_causal=False``)."""
    if block is not None:
        import jax
        # the region a device trace tells apart from causal attention
        with jax.named_scope("block_attn"):
            return _cached_attention(q, k, v, cache, layer_idx, decode,
                                     causal, attn_mask, block)
    return _cached_attention(q, k, v, cache, layer_idx, decode, causal,
                             attn_mask, None)


def _cached_attention(q, k, v, cache, layer_idx, decode, causal, attn_mask,
                      block):
    from ..core.tensor import dispatch
    from ..nn import functional as F
    cache = cache.update(layer_idx, k, v, cache.kv_len)
    wc = {} if block is None else {"window_causal": False}
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
            import jax.numpy as jnp

            from ..kernels.flash_attention import \
                flash_attention_decode_paged
            scales = (cache.k_scale, cache.v_scale) if quant else ()
            # a row that holds nothing is idle (the pool's contract: its
            # write above went to the null page): it attends nothing,
            # so the kernel walks no page for it
            mask_len = jnp.where(cache.kv_len > 0, mask_len, 0)
            out = dispatch(
                "flash_attention_decode_paged",
                lambda q_, kp, vp, pt, kl, *sc:
                    flash_attention_decode_paged(
                        q_, kp, vp, pt, kl, layer_idx, **wc,
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
            if block is not None:
                raise NotImplementedError(
                    f"a block of {s} positions exceeds the decode "
                    f"kernels' window of {MAX_DECODE_QLEN}")
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
                q_, kc, vc, kl, **wc,
                **(dict(k_scale=sc[0], v_scale=sc[1]) if sc else {})),
            (q, cache.k[layer_idx], cache.v[layer_idx], mask_len)
            + scales, {}, differentiable=False)
    elif block is not None or (causal and attn_mask is None
                               and k.shape[2] != q.shape[2]):
        # grouped kv heads under the plain causal mask take the same
        # path: a block of 1 is causal, and it repeats the kv heads (or
        # hands them to the flash kernel, which does)
        out = dispatch(
            "block_causal_attention",
            lambda q_, k_, v_: block_causal_attention(q_, k_, v_,
                                                      block or 1),
            (q, k, v), {}, differentiable=False)
    else:
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=causal,
            dropout_p=0.0, training=False)
    return out, cache
