"""Flash attention (forward + backward) as Pallas TPU kernels.

Online-softmax tiling keeps the full [S, S] score matrix out of HBM: per
(batch*head, q-block) the kernel streams k/v blocks through VMEM, keeping a
running row-max `m`, normalizer `l`, and fp32 accumulator. The backward pass
recomputes probabilities from the saved logsumexp (no O(S^2) residuals).

Base-2 softmax (r5): the kernels work in log2 space throughout — the
query is pre-scaled by `scale * log2(e)` once ([B*H, S, D] elementwise,
fused by XLA into the layout transpose), scores feed `exp2` directly,
and the saved logsumexp is in base-2 units. exp(x) on the TPU VPU is
exp2(x * log2e) under the hood, so this removes one [bq, bk] multiply
per score per exp pass; folding the softmax scale out of the score tile
and the dq/dk tiles (post-scaling the [bq, d] results instead) removes
three more. Net: 5 full-score-tile VPU multiplies eliminated per
fwd+bwd step vs the r4 kernels, with identical math (exp(s·scale - lse)
== exp2(s·scale·log2e - lse2)).

Reference analog: paddle/fluid/operators/fused/fused_attention_op.cu fuses
QKV+softmax+dropout by hand in CUDA; on TPU the same memory-bound problem is
solved with a Pallas online-softmax kernel feeding the MXU with
[block_q, block_k] tiles.

Layout convention at this layer is [B, H, S, D]; the public wrapper accepts
the framework's [B, S, H, D] and transposes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 1024  # 1024/1024 measured fastest on v5e (s1024:
DEFAULT_BLOCK_K = 1024  # -17%, s2048: -24% vs 512/512); 2048 OOMs VMEM
_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() exact zero
_LANES = 128      # TPU vector lane count; m/l scratch pads to this
_LSE_LANES = 8    # lse/delta HBM rows: 8 lanes (min sublane tile), not
                  # 128 — a 16x HBM-traffic cut on the saved softmax stats
_LOG2E = 1.4426950408889634  # log2(e): q pre-scale folds softmax scale
_LN2 = 0.6931471805599453    # ln(2): dk post-scale undoing the q pre-scale
_CAUSAL_SPLITS = 4  # max causal prefix buckets (see kernels); blocks are
# only ever halved to create buckets — 4-way via bq/4 was measured WORSE
# (flagship 0.584 -> 0.554: grid-step overhead beats the extra skipping)
_WHOLE_K_MAX_SK = 4096  # scratch-free fwd kernel limit ([bq,sk] f32 tile)


def _causal_split_plan(sq, bq):
    """(bq', n_splits) for causal self-attention prefix bucketing: halve
    the q-block at most once (smaller blocks measured net-negative),
    then use as many buckets as the resulting q-block count supports,
    capped at _CAUSAL_SPLITS. n_splits always divides nq, so every
    bucket's key prefix lands on a q-block boundary."""
    bq = _pick_block(sq, min(bq, max(sq // 2, 128)))
    nq = sq // bq
    n = _CAUSAL_SPLITS
    while n > 1 and nq % n:
        n //= 2
    return bq, n


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_block(seq: int, preferred: int) -> int:
    block = min(preferred, seq)
    while seq % block:
        block //= 2
    return max(block, 1)


# ---------------------------------------------------------------- forward

def _causal_rows(rows, block):
    """The last column a query row may see under the (block-)causal
    mask: itself, or with ``block`` B > 1 the end of its block of B
    (``j // B <= i // B``; B divides every kernel block, so the k-block
    skips and prefix buckets of the causal kernels hold unchanged)."""
    return rows if block == 1 else rows // block * block + (block - 1)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, causal, offset,
                block_q, block_k, num_kblocks, kv_len=None, block=1):
    # q_ref holds q * (scale * log2e); scores are base-2 logits
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip k-blocks strictly above the diagonal band of this q-block
    # (offset = sk - sq aligns the diagonal bottom-right for cross lengths)
    q_last = (iq + 1) * block_q - 1 + offset
    needed = jnp.logical_or(not causal, ik * block_k <= q_last)

    @pl.when(needed)
    def _compute():
        q = q_ref[0]  # [block_q, D], pre-scaled
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk] base-2
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + iq * block_q + offset
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                + ik * block_k
            s = jnp.where(_causal_rows(rows, block) >= cols, s, _NEG_INF)
        if kv_len is not None:  # padded keys: mask cols beyond kv_len
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                + ik * block_k
            s = jnp.where(cols < kv_len, s, _NEG_INF)
        m_prev = m_scr[:, 0:1]                      # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)   # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp2(m_prev - m_new)            # [bq, 1]
        p = jnp.exp2(s - m_new)                     # [bq, bk] fp32
        l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == num_kblocks - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, 0:1] + jnp.log2(l_safe)      # base-2 lse
        lse_ref[0] = jnp.broadcast_to(lse, (lse.shape[0], _LSE_LANES))


def _whole_k_attn(q, k, v, iq, block_q, offset, causal, kv_len, out_dtype,
                  block=1):
    """One-shot softmax-attention over a q-block against the given K/V
    columns (assumed to start at col 0). Returns (o, lse) values."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bq, sk] base-2
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
            + iq * block_q + offset
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(_causal_rows(rows, block) >= cols, s, _NEG_INF)
    if kv_len is not None:
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, _NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)                # [bq, 1]
    p = jnp.exp2(s - m)                                  # [bq, sk]
    l = jnp.sum(p, axis=1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    acc = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bq, D]
    # fully-masked rows (causal sq > sk): every s is _NEG_INF, so
    # m = _NEG_INF and p = exp2(0) = 1 everywhere — emit zeros and the
    # lse = _NEG_INF sentinel the backward kernels key off, matching
    # the multi-block kernel's never-accumulated behavior
    dead = m <= _NEG_INF * 0.5                           # [bq, 1]
    o = jnp.where(dead, 0.0, acc / l_safe).astype(out_dtype)
    lse = jnp.where(dead, _NEG_INF, m + jnp.log2(l_safe))
    return o, lse


def _fwd_kernel_whole_k(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                        causal, offset, block_q, num_qblocks,
                        causal_splits=1, kv_len=None, block=1):
    """Single-k-block forward: the whole K/V is one block, so the online
    rescale machinery (m/l/acc scratch, alpha corrections) degenerates —
    this variant drops it entirely. This IS the hot path for the
    flagship/ERNIE/BERT configs (s ≤ block_k = 1024): one exp2 pass,
    one max, one sum, straight out.

    causal_splits > 1 (causal self-attention, offset == 0): q-blocks in
    the j-th quantile of the sequence can only attend to keys below
    (j+1)/n_splits · sk, so they run the whole pipeline — score matmul,
    exp2, pv matmul — on that K prefix only. The strictly-masked
    upper-right region of the score matrix is never computed instead of
    computed-then-masked: 25% (2 splits) / 37.5% (4 splits) of the
    forward score work gone with no extra grid steps."""
    iq = pl.program_id(1)

    if causal_splits > 1:
        sk = k_ref.shape[1]
        bucket = iq * causal_splits // num_qblocks
        for j in range(causal_splits):
            prefix = (j + 1) * sk // causal_splits

            @pl.when(bucket == j)
            def _branch(prefix=prefix):
                o, lse = _whole_k_attn(
                    q_ref[0], k_ref[0, :prefix], v_ref[0, :prefix], iq,
                    block_q, offset, causal, kv_len, o_ref.dtype, block)
                o_ref[0] = o
                lse_ref[0] = jnp.broadcast_to(
                    lse, (lse.shape[0], _LSE_LANES))
    else:
        o, lse = _whole_k_attn(
            q_ref[0], k_ref[0], v_ref[0], iq, block_q,
            offset, causal, kv_len, o_ref.dtype, block)
        o_ref[0] = o
        lse_ref[0] = jnp.broadcast_to(lse, (lse.shape[0], _LSE_LANES))


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, kv_len=None,
               block=1):
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    nq, nk = sq // bq, sk // bk
    # base-2 fold: one [B*H, S, D] multiply XLA fuses into the producing
    # transpose, replacing a [bq, bk] multiply per score tile in-kernel
    q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    cost = pl.CostEstimate(
        flops=4 * bh * sq * sk * d // (2 if causal else 1),
        bytes_accessed=2 * bh * (sq + 2 * sk) * d,
        transcendentals=bh * sq * sk)
    # the scratch-free whole-K kernel engages past the block_k limit by
    # shrinking the q-block so the [bq, sk] fp32 score tile stays ~4 MB
    # (sk 2048 -> bq 512); beyond _WHOLE_K_MAX_SK VMEM forces the
    # online-rescale multi-block kernel
    if nk > 1 and sk <= _WHOLE_K_MAX_SK:
        # power-of-two floor: a raw (1 << 20) // sk quotient for
        # non-power-of-two sk never divides sq, collapsing _pick_block
        # to degenerate 1-3-row q-blocks
        cap = 1 << (((1 << 20) // sk).bit_length() - 1)
        bq = _pick_block(sq, min(bq, cap))
        bk, nk, nq = sk, 1, sq // bq
    if nk == 1:
        # causal self-attention: split q-blocks into prefix buckets so
        # most never touch the strictly-masked upper key range. n_splits
        # must divide nq so every bucket's prefix lands on a q-block
        # boundary (the bucket's last row stays below its prefix).
        n_splits = 1
        if causal and sq == sk and sq >= 256:
            bq, n_splits = _causal_split_plan(sq, bq)
            nq = sq // bq
        kernel = functools.partial(
            _fwd_kernel_whole_k, causal=causal, offset=sk - sq,
            block_q=bq, num_qblocks=nq, causal_splits=n_splits,
            kv_len=kv_len, block=block)
        grid = (bh, nq)
        out, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, sq, _LSE_LANES), jnp.float32),
            ],
            cost_estimate=cost,
            interpret=_interpret(),
            name="flash_fwd_whole_k",
        )(q, k, v)
        return out, lse
    kernel = functools.partial(
        _fwd_kernel, causal=causal, offset=sk - sq,
        block_q=bq, block_k=bk, num_kblocks=nk, kv_len=kv_len,
        block=block)
    grid = (bh, nq, nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        cost_estimate=cost,
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------- backward
#
# All backward kernels receive the PRE-SCALED query (q * scale * log2e)
# and the base-2 lse, so the score recompute is a bare matmul feeding
# exp2. The per-score `* scale` on ds is gone: dq/dk accumulate the
# unscaled ds matmuls and the [*, D]-sized finalize applies
#   dq = (ds @ k) * scale
#   dk = (ds^T @ q_pre) * ln2        (q_pre carries scale*log2e already)
# which is exact: scale / (scale * log2e) = ln 2.

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, offset, block_q, block_k,
                   num_kblocks, kv_len=None):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_last = (iq + 1) * block_q - 1 + offset
    needed = jnp.logical_or(not causal, ik * block_k <= q_last)

    @pl.when(needed)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]       # [bq, 1] base-2
        delta = delta_ref[0][:, 0:1]   # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.exp2(s - lse)                                  # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + iq * block_q + offset
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                + ik * block_k
            # explicit zero: fully-masked rows carry lse = _NEG_INF, so
            # exp2(masked_s - lse) = 1 would inject phantom gradients
            p = jnp.where(rows >= cols, p, 0.0)
        if kv_len is not None:
            cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) \
                + ik * block_k
            p = jnp.where(cols < kv_len, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bq, bk]
        ds = p * (dp - delta)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == num_kblocks - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, causal,
                    offset, block_q, block_k, num_qblocks, kv_len=None):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_last = (iq + 1) * block_q - 1 + offset
    needed = jnp.logical_or(not causal, ik * block_k <= q_last)

    @pl.when(needed)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bq, bk]
        p = jnp.exp2(s - lse)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + iq * block_q + offset
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                + ik * block_k
            # explicit zero: fully-masked rows carry lse = _NEG_INF, so
            # exp2(masked_s - lse) = 1 would inject phantom gradients
            p = jnp.where(rows >= cols, p, 0.0)
        if kv_len is not None:
            cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) \
                + ik * block_k
            p = jnp.where(cols < kv_len, p, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                                  # [bq, bk]
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, D]

    @pl.when(iq == num_qblocks - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * _LN2).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _whole_k_bwd(q, k, v, do, lse, delta, iq, block_q, offset, causal,
                 kv_len):
    """Shared fused-backward block math against the given K/V columns
    (assumed to start at col 0). Returns (dq_unscaled, dk_contrib,
    dv_contrib) — the caller applies the base-2 post-scales."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [bq, sk]
    p = jnp.exp2(s - lse)                                    # ONE exp pass
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
            + iq * block_q + offset
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # explicit zero (NOT exp of masked s): a fully-masked row has
        # lse = _NEG_INF from the forward, so exp2(s - lse) would be
        # exp2(0) = 1 on its masked entries — phantom gradients
        p = jnp.where(rows >= cols, p, 0.0)
    if kv_len is not None:
        cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
        p = jnp.where(cols < kv_len, p, 0.0)
    dv_c = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [sk, D]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [bq, sk]
    ds = p * (dp - delta)
    dq = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [bq, D]
    dk_c = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [sk, D]
    return dq, dk_c, dv_c


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale,
                      causal, offset, block_q, num_qblocks,
                      causal_splits=1, kv_len=None):
    """Single-k-block backward: the whole K/V stays resident, so s, p,
    dp, ds are computed ONCE and all three grads come out of the same
    pass — 5 matmuls + 1 exp pass vs the split kernels' 7 + 2. Engaged
    when sk <= _FUSED_BWD_MAX_SK and head_dim <= 128 (the flagship
    s1024 / ERNIE / BERT s512 / long-seq s2048-4096 configs); measured
    end-to-end in BASELINE.md r4.

    causal_splits > 1 (causal self-attention, offset == 0): q-blocks in
    the j-th sequence quantile run all five matmuls and the exp2
    against their K prefix only — the strictly-masked upper-right
    region of the score/grad tiles is never touched. 25% (2 splits) /
    37.5% (4 splits) of the backward score work gone, same grid."""
    iq = pl.program_id(1)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if causal_splits > 1:
        sk = k_ref.shape[1]
        bucket = iq * causal_splits // num_qblocks
        for j in range(causal_splits):
            prefix = (j + 1) * sk // causal_splits

            @pl.when(bucket == j)
            def _branch(prefix=prefix):
                dq, dk_c, dv_c = _whole_k_bwd(
                    q_ref[0], k_ref[0, :prefix], v_ref[0, :prefix],
                    do_ref[0], lse_ref[0][:, 0:1], delta_ref[0][:, 0:1],
                    iq, block_q, offset, causal, kv_len)
                dq_ref[0] = (dq * scale).astype(dq_ref.dtype)
                dk_scr[:prefix] += dk_c
                dv_scr[:prefix] += dv_c
    else:
        dq, dk_c, dv_c = _whole_k_bwd(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0],
            lse_ref[0][:, 0:1], delta_ref[0][:, 0:1], iq, block_q,
            offset, causal, kv_len)
        dq_ref[0] = (dq * scale).astype(dq_ref.dtype)
        dk_scr[:] += dk_c
        dv_scr[:] += dv_c

    @pl.when(iq == num_qblocks - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * _LN2).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


_FUSED_BWD_MAX_SK = 4096  # whole-K resident limit: [bq, sk] fp32
# score/softmax/grad tiles bound VMEM, so bq shrinks as sk grows
# (sk<=1024 -> bq 512, sk<=2048 -> bq 256; ~3x2 MB tiles either way).
# Gate placement measured r5: forcing the k-tiled kernel below this
# limit LOSES (s2048 0.525 -> 0.516, s4096 0.582 -> 0.564 MFU) —
# whole-K residency beats tile streaming whenever it fits

_TILED_BWD_K_CHUNK = 1024   # in-body k-tile for the long-context kernel
_TILED_BWD_MAX_D = 128   # head-dim cap for the tiled fused backward
_TILED_BWD_DQ_CAP = 1 << 19  # sq*d cap per call: the [sq, d] fp32 dq
# accumulator (2 MB at the cap) plus tile scratch must fit VMEM;
# longer sequences recurse by halving the q range (the causal low half
# also drops the strictly-masked high keys)


def _bwd_fused_tiled_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                            delta_ref, dq_ref, dk_ref, dv_ref,
                            dq_scr, dk_scr, dv_scr, *, scale, causal,
                            offset, block_q, block_k, num_qblocks,
                            num_kblocks, kv_len=None):
    """Long-context fused backward (sk > _FUSED_BWD_MAX_SK): same
    5-matmul/1-exp structure as _bwd_fused_kernel, but neither the
    [bq, sk] score tiles nor whole-K residency fit the 16 MB VMEM, so
    the grid streams (k-tile OUTER, q-block inner):

    - dk/dv accumulate across the inner q sweep in per-TILE fp32
      scratch and flush to their HBM tile once per k-tile — the only
      grid order where each output block is written exactly once;
    - dq, which needs contributions from every k-tile, accumulates in a
      full-length [sq, D] fp32 scratch (sq*d*4 bytes — the small side
      of the problem) and is written once at the final grid step.

    Causal q-blocks strictly above a k-tile skip the whole body, so the
    upper triangle is pruned at (bq x block_k) granularity."""
    jk = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(jnp.logical_and(jk == 0, iq == 0))
    def _init_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(iq == 0)
    def _init_tile():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_last = (iq + 1) * block_q - 1 + offset
    needed = jnp.logical_or(not causal, jk * block_k <= q_last)

    @pl.when(needed)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        p = jnp.exp2(s - lse)                            # ONE exp pass
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + iq * block_q + offset
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                + jk * block_k
            # explicit zero: see _whole_k_bwd
            p = jnp.where(rows >= cols, p, 0.0)
        if kv_len is not None:
            cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) \
                + jk * block_k
            p = jnp.where(cols < kv_len, p, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]
        dq_scr[pl.ds(iq * block_q, block_q)] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == num_qblocks - 1)
    def _flush_tile():
        dk_ref[0] = (dk_scr[:] * _LN2).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(jk == num_kblocks - 1,
                             iq == num_qblocks - 1))
    def _flush_dq():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_tiled_dispatch(q, k, v, lse_b, delta_b, do, scale, causal,
                              kv_len=None, diag_offset=None):
    """Route to the tiled fused backward, halving the q range while the
    [sq, d] fp32 dq accumulator exceeds its VMEM budget. The diagonal
    offset is threaded explicitly so any recursion depth and any
    cross-length shape keeps the right causal alignment: the low half
    keeps the parent offset, the high half shifts it by the split
    point. A causal low half whose visible key prefix lands on the 128
    grid only receives that prefix of K/V (pruning score work as well
    as memory); dk/dv halves recombine in fp32."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    if diag_offset is None:
        diag_offset = sk - sq
    if sq * d <= _TILED_BWD_DQ_CAP:
        return _flash_bwd_fused_tiled(q, k, v, lse_b, delta_b, do, scale,
                                      causal, kv_len=kv_len,
                                      diag_offset=diag_offset)
    h = sq // 2
    klen_lo = h + diag_offset  # keys visible to the causal low half
    lo_k = causal and 0 < klen_lo < sk and klen_lo % 128 == 0
    kA, vA = (k[:, :klen_lo], v[:, :klen_lo]) if lo_k else (k, v)
    dqA, dkA, dvA = _flash_bwd_tiled_dispatch(
        q[:, :h], kA, vA, lse_b[:, :h], delta_b[:, :h], do[:, :h],
        scale, causal, kv_len=kv_len, diag_offset=diag_offset)
    dqB, dkB, dvB = _flash_bwd_tiled_dispatch(
        q[:, h:], k, v, lse_b[:, h:], delta_b[:, h:], do[:, h:],
        scale, causal, kv_len=kv_len, diag_offset=diag_offset + h)
    dq = jnp.concatenate([dqA, dqB], axis=1)
    dkB32, dvB32 = dkB.astype(jnp.float32), dvB.astype(jnp.float32)
    if lo_k:
        dk = dkB32.at[:, :klen_lo].add(dkA.astype(jnp.float32))
        dv = dvB32.at[:, :klen_lo].add(dvA.astype(jnp.float32))
    else:
        dk = dkB32 + dkA.astype(jnp.float32)
        dv = dvB32 + dvA.astype(jnp.float32)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_bwd_fused_tiled(q, k, v, lse_b, delta_b, do, scale, causal,
                           kv_len=None, diag_offset=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    if diag_offset is None:
        diag_offset = sk - sq
    # [bq, bk] fp32 score/grad tiles + the [sq, d] dq accumulator share
    # VMEM: shrink the q-block when the accumulator is at its 4 MB cap
    bq = _pick_block(sq, 256 if sq * d * 4 >= (1 << 22) else 512)
    bk = _pick_block(sk, _TILED_BWD_K_CHUNK)
    nq, nk = sq // bq, sk // bk
    stat = pl.BlockSpec((1, bq, _LSE_LANES), lambda b, j, i: (b, i, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_tiled_kernel, scale=scale,
                          causal=causal, offset=diag_offset, block_q=bq,
                          block_k=bk, num_qblocks=nq, num_kblocks=nk,
                          kv_len=kv_len),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # k tile
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # v tile
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),  # do
            stat, stat,
        ],
        out_specs=[
            pl.BlockSpec((1, sq, d), lambda b, j, i: (b, 0, 0)),  # dq
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # dk
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # dv
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((sq, d), jnp.float32),   # dq accumulator
            pltpu.VMEM((bk, d), jnp.float32),   # dk tile accumulator
            pltpu.VMEM((bk, d), jnp.float32),   # dv tile accumulator
        ],
        interpret=_interpret(),
        name="flash_bwd_tiled",
    )(q, k, v, do, lse_b, delta_b)
    return dq, dk, dv


def _flash_bwd_fused(q, k, v, lse_b, delta_b, do, scale, causal,
                     kv_len=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(sq, 512 if sk <= 1024 else (256 if sk <= 2048 else 128))
    n_splits = 1
    if causal and sq == sk and sq >= 256:
        bq, n_splits = _causal_split_plan(sq, bq)
    nq = sq // bq
    stat = pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i: (b, i, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          offset=sk - sq, block_q=bq, num_qblocks=nq,
                          causal_splits=n_splits, kv_len=kv_len),
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),   # q
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),   # k (whole)
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),   # do
            stat, stat,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((sk, d), jnp.float32),
            pltpu.VMEM((sk, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd",
    )(q, k, v, do, lse_b, delta_b)
    return dq, dk, dv


def _flash_bwd(q, k, v, out, lse, do, scale, causal, block_q, block_k,
               kv_len=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    nq, nk = sq // bq, sk // bk

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                    # [bh, sq]
    delta_b = jnp.broadcast_to(delta[:, :, None], (bh, sq, _LSE_LANES))
    lse_b = lse  # already [bh, sq, _LSE_LANES] base-2 from the forward
    # same base-2 fold as the forward: kernels see q * (scale * log2e)
    q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)

    # fused single-pass backward: whole K/V + [bq, sk] fp32 score tiles
    # + sk*d fp32 dk/dv scratch must fit VMEM — bounded by capping sk
    # and head_dim (d=256 at s4096 would need ~20 MB; the tiled split
    # path below stays the fallback there and beyond _FUSED_BWD_MAX_SK)
    if sk <= _FUSED_BWD_MAX_SK and d <= 128:
        return _flash_bwd_fused(q, k, v, lse_b, delta_b, do, scale, causal,
                                kv_len=kv_len)
    # long-context: the k-tiled fused kernel keeps the 5-matmul/1-exp
    # structure for any sk (K streams through tile-grid blocks); big q
    # ranges recurse by halving (see _flash_bwd_tiled_dispatch)
    if d <= _TILED_BWD_MAX_D:
        return _flash_bwd_tiled_dispatch(q, k, v, lse_b, delta_b, do,
                                         scale, causal, kv_len=kv_len)

    row_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),      # q
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),      # k
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),      # v
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),      # do
        pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i, j: (b, i, 0)),
    ]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          offset=sk - sq, block_q=bq, block_k=bk,
                          num_kblocks=nk, kv_len=kv_len),
        grid=(bh, nq, nk),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, do, lse_b, delta_b)

    col_specs = [
        pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),      # q
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),      # k
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),      # v
        pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),      # do
        pl.BlockSpec((1, bq, _LSE_LANES), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, bq, _LSE_LANES), lambda b, j, i: (b, i, 0)),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal,
                          offset=sk - sq, block_q=bq, block_k=bk,
                          num_qblocks=nq, kv_len=kv_len),
        grid=(bh, nk, nq),
        in_specs=col_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, do, lse_b, delta_b)
    return dq, dk, dv


# ------------------------------------------------------------- public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k, kv_len=None):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        kv_len=kv_len)
    return out


def _flash_bhsd_fwd(q, k, v, scale, causal, block_q, block_k, kv_len=None):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          kv_len=kv_len)
    return out, (q, k, v, out, lse)


def _flash_bhsd_bwd(scale, causal, block_q, block_k, kv_len, res, do):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, do, scale, causal,
                      block_q, block_k, kv_len=kv_len)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


# ------------------------------------------------------ decode forward
#
# Single-query ("decode-shaped") attention: q-len 1..8 new tokens per
# row against a long cached K/V with a PER-ROW valid length. This is
# the serving hot loop — one call per generated token — so the kernel
# is forward-only (no vjp) and streams the cache through VMEM with the
# same base-2 online softmax as the training kernels. The ragged
# column masking generalizes `_fwd_kernel`'s scalar `kv_len` to a
# per-row length read from SMEM, and k-blocks entirely past a row's
# valid prefix skip their compute via `pl.when` (their DMA still runs;
# the grid is static).

_DECODE_QPAD = 8          # min fp32 sublane tile: q rows pad to this
#: public cap on the decode kernel's query window (the 8-row fp32
#: sublane tile): a speculative verify window of K draft tokens + 1
#: needs K + 1 <= this — generation.speculative validates against it
#: at the config boundary so the limit fails fast with its name, not
#: as a padding-path fallthrough deep in a trace.
MAX_DECODE_QLEN = _DECODE_QPAD
_DECODE_BLOCK_K = 512


def _decode_scratch(rows, d, lead=()):
    """m / l / acc scratch of the decode-family online softmax
    (``lead``: the batch dims of a kernel that attends several heads
    at once)."""
    return [pltpu.VMEM(lead + (rows, _LANES), jnp.float32),
            pltpu.VMEM(lead + (rows, _LANES), jnp.float32),
            pltpu.VMEM(lead + (rows, d), jnp.float32)]


def _scale_row_specs(bk, index_map):
    """BlockSpecs for the k/v per-column scale rows of an int8 cache.
    The row rides a singleton second-to-last dim ([B, 1, T] blocked
    (1, 1, bk)): a 1-row block of a taller 2-D array is off the
    (8, 128) tiling, a block equal to the array's own dim is on it."""
    spec = pl.BlockSpec((1, 1, bk), index_map)
    return [spec, spec]


def _decode_init(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _decode_accumulate(q, k, v, col_base, kv_len, sq,
                       m_scr, l_scr, acc_scr, ks=None, vs=None,
                       window_causal=True, row_pos=None):
    """One k-block of the decode online softmax — the ONE copy of the
    accumulate math shared by the dense, chunk and paged decode kernels,
    so their numerics can never silently diverge (the paged/dense
    bitwise-parity gate depends on them staying locked together).

    Operands are [rows, D] x [bk, D] (dense, chunk), or carry leading
    batch dims ([heads, rows, D] x [heads, bk, D]: the paged kernel
    attends all kv heads of a page in one pair of batched products);
    the scratch has the operands' rank.

    Query row i sits at global position kv_len - sq + i: it may attend
    keys at cols <= kv_len - sq + i (ragged causal; ``col_base`` is
    this block's first logical column). Rows past sq-1 are padding;
    their outputs are sliced off outside. ``row_pos`` (int32, shaped to
    broadcast against the score tile) replaces the row's own index as
    its position i in the window, for a caller that stacks several
    query heads into the rows. With ``window_causal=False``
    every row attends every valid column (``cols < kv_len``): the
    denoise window of block diffusion, whose positions all see each
    other — the mask then does not depend on the row.

    ``ks``/``vs`` ([1, bk] per-column dequant scales, or
    [heads, 1, bk]) switch on the
    int8-cache mode: k/v arrive int8 and the dequant FUSES into the
    score tile instead of ever widening the cache block —
    ``s[i,j] = (q[i] . k_int8[j]) * ks[j]`` (scaling score columns ==
    scaling K rows) and ``acc += (p * vs) @ v_int8`` (scaling the
    softmax weights == scaling V rows). Both multiplies ride the
    [qpad, bk] tile as lane-aligned row-vector broadcasts — no
    transposes, no materialized wide K/V, HBM traffic stays int8."""
    quant = ks is not None
    lead = tuple(range(q.ndim - 2))           # batch dims: the heads
    last = q.ndim - 1
    qk_dims = (((last,), (last,)), (lead, lead))
    pv_dims = (((last,), (last - 1,)), (lead, lead))
    if quant:
        # int8 -> f32 in-register is exact (|v| <= 127); the matmul
        # runs at f32 either way (preferred_element_type)
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32), qk_dims,
            preferred_element_type=jnp.float32)      # [qpad, bk] base-2
        s = s * ks.astype(jnp.float32)               # fused K dequant
    else:
        s = jax.lax.dot_general(
            q, k, qk_dims,
            preferred_element_type=jnp.float32)      # [qpad, bk] base-2
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, last) + col_base
    if window_causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, last - 1) \
            if row_pos is None else row_pos
        s = jnp.where(cols - rows <= kv_len - sq, s, _NEG_INF)
    else:
        s = jnp.where(cols < kv_len, s, _NEG_INF)
    m_prev = m_scr[..., 0:1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp2(m_prev - m_new)
    p = jnp.exp2(s - m_new)
    l_new = l_scr[..., 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if quant:
        # fused V dequant: fold the per-column scale into the softmax
        # weights (l stays the sum of the UNSCALED p — v's scale
        # belongs to the values, not the normalizer)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p * vs.astype(jnp.float32), v.astype(jnp.float32), pv_dims,
            preferred_element_type=jnp.float32)
    else:
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, pv_dims,
            preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _decode_result(l_scr, acc_scr, dtype):
    l = l_scr[..., 0:1]
    l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros
    return (acc_scr[...] / l_safe).astype(dtype)


def _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, *rest, sq, block_k,
                   num_kblocks, group, quant=False, window_causal=True):
    # q_ref holds q * (scale * log2e); scores are base-2 logits. In
    # quant mode two per-column bf16 scale rows ([1, 1, bk], same index
    # map as k/v) follow the caches, and the shared accumulate body
    # fuses the dequant into the score tile. kvlen_ref is the
    # scalar-prefetched [B*Hk] length vector, whole in SMEM.
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        _decode_init(m_scr, l_scr, acc_scr)

    # this row's valid cache length (incl. the sq new positions,
    # already written)
    kv_len = kvlen_ref[pl.program_id(0) // group]

    # skip k-blocks entirely past the valid prefix
    @pl.when(ik * block_k < kv_len)
    def _compute():
        _decode_accumulate(q_ref[0], k_ref[0], v_ref[0], ik * block_k,
                           kv_len, sq, m_scr, l_scr, acc_scr,
                           ks=ks_ref[0] if quant else None,
                           vs=vs_ref[0] if quant else None,
                           window_causal=window_causal)

    @pl.when(ik == num_kblocks - 1)
    def _finalize():
        o_ref[0] = _decode_result(l_scr, acc_scr, o_ref.dtype)


def _window_qpad(sq: int) -> int:
    """Query rows padded to whole fp32 sublane tiles (8 for a causal
    window of <= MAX_DECODE_QLEN; more where a full window stacks the
    query heads of one kv head)."""
    return -(-sq // _DECODE_QPAD) * _DECODE_QPAD


def _decode_pallas(q, k_cache, v_cache, kv_len, scale,
                   block_k=_DECODE_BLOCK_K, group=1,
                   k_scale=None, v_scale=None, window_causal=True):
    """q: [B*Hq, sq<=8, D] (unscaled), caches [B*Hk, T, D], kv_len
    [B*Hk]. GQA/MQA (``group`` = Hq//Hk > 1) maps each query head to
    its kv head via the k/v BlockSpec index maps (grid row b reads
    cache row b // group): the hk-sized caches are streamed as-is, no
    repeated copy is ever materialized. ``k_scale``/``v_scale``
    ([B*Hk, T] bf16) switch on the int8-cache mode — the scale rows
    stream through the SAME b//group index maps as the caches and the
    dequant fuses in-register (see ``_decode_accumulate``). kv_len is
    scalar-prefetched: a per-row (1, 1) SMEM block is off the TPU
    tiling and the front end refuses it."""
    bh, sq, d = q.shape
    t = k_cache.shape[1]
    quant = k_scale is not None
    qpad = _window_qpad(sq)
    q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    if sq < qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad - sq), (0, 0)))
    bk = _pick_block(t, block_k)
    nk = t // bk
    kv_bytes = k_cache.dtype.itemsize * t * d \
        + (k_scale.dtype.itemsize * t if quant else 0)
    in_specs = [
        pl.BlockSpec((1, qpad, d), lambda b, j, kl: (b, 0, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, kl: (b // group, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, kl: (b // group, j, 0)),
    ]
    operands = [q, k_cache, v_cache]
    if quant:
        in_specs += _scale_row_specs(
            bk, lambda b, j, kl: (b // group, 0, j))
        operands += [k_scale[:, None], v_scale[:, None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, qpad, d), lambda b, j, kl: (b, 0, 0)),
        scratch_shapes=_decode_scratch(qpad, d),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, sq=sq, block_k=bk,
                          num_kblocks=nk, group=group, quant=quant,
                          **({} if window_causal
                             else {"window_causal": False})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, qpad, d), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * qpad * t * d,
            bytes_accessed=bh * (qpad * d * q.dtype.itemsize
                                 + 2 * kv_bytes),
            transcendentals=bh * qpad * t),
        interpret=_interpret(),
        name="flash_decode",
    )(kv_len.astype(jnp.int32), *operands)
    return out[:, :sq]


def _decode_xla(q, k_cache, v_cache, kv_len, scale, group=1,
                ks=None, vs=None, window_causal=True):
    """Fallback decode attention (CPU/interpret, or cache lengths off
    the 128 grid): fp32 masked softmax over [B*Hk, group, sq, T]
    scores — fine at decode sizes, never used for training shapes.
    GQA/MQA query heads fold into the ``group`` dim so the hk-sized
    caches broadcast in the einsum (head-index mapping, no repeat).
    ``ks``/``vs`` ([B*Hk, T]) run the int8-cache mode with the SAME
    fused-dequant structure as the Pallas kernel (score columns
    scaled, softmax weights scaled) — the paged/dense parity contract
    extends to the quantized path."""
    bhq, sq, d = q.shape
    t = k_cache.shape[1]
    q4 = q.reshape(k_cache.shape[0], group, sq, d)
    s = jnp.einsum("bgqd,bkd->bgqk", q4.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    if ks is not None:
        s = s * ks.astype(jnp.float32)[:, None, None, :]
    rows = jnp.arange(sq, dtype=jnp.int32)[None, None, :, None]
    cols = jnp.arange(t, dtype=jnp.int32)[None, None, None, :]
    kl = kv_len.astype(jnp.int32)[:, None, None, None]
    valid = (cols - rows <= kl - sq) if window_causal \
        else jnp.broadcast_to(cols < kl, s.shape)
    s = jnp.where(valid, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    if vs is not None:
        out = jnp.einsum(
            "bgqk,bkd->bgqd", p * vs.astype(jnp.float32)[:, None, None, :],
            v_cache.astype(jnp.float32)).astype(q.dtype)
    else:
        out = jnp.einsum("bgqk,bkd->bgqd", p.astype(v_cache.dtype),
                         v_cache).astype(q.dtype)
    return out.reshape(bhq, sq, d)


def _fold_group(query, hk: int):
    """[b, sq, hq, d] -> [b, hk, group * sq, d]: the query heads of one
    kv head stacked into the rows of ONE decode-kernel tile (the window
    position minor), so its K/V pages stream once for the group
    instead of once a query head. The dense kernel's mask goes by the
    row, so only a row-independent one (``window_causal=False``) lets
    it fold; the paged kernel tells a row's position by ``row % sq``."""
    b, sq, hq, d = query.shape
    g = hq // hk
    return query.reshape(b, sq, hk, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b, hk, g * sq, d)


def _unfold_group(out, sq: int):
    """Inverse of :func:`_fold_group` on the kernel's output."""
    b, hk, rows, d = out.shape
    g = rows // sq
    return out.reshape(b, hk, g, sq, d).transpose(0, 3, 1, 2, 4) \
        .reshape(b, sq, hk * g, d)


def flash_attention_decode(query, key_cache, value_cache, kv_len,
                           scale=None, block_k=_DECODE_BLOCK_K,
                           k_scale=None, v_scale=None,
                           window_causal=True):
    """Decode-shaped attention: 1..8 new query tokens per row against a
    cached K/V with per-row valid lengths.

    Int8 cache mode: with ``key_cache``/``value_cache`` int8 pass
    ``k_scale``/``v_scale`` ([batch, max_len, num_kv_heads], the
    ``QuantKVCache`` sidecars) — dequantization fuses INSIDE the
    kernel (per-column scale on the score tile / softmax weights; see
    ``_decode_accumulate``), so HBM streams half the bytes and a wide
    cache is never materialized.

    query: [batch, q_len<=8, num_heads, head_dim] (framework layout).
    key_cache/value_cache: [batch, max_len, num_kv_heads, head_dim] —
    one layer's slice of a ``generation.KVCache`` (new tokens already
    written). kv_len: [batch] int32 — valid entries per row INCLUDING
    the q_len new positions; query row i attends cache columns
    ``<= kv_len - q_len + i`` (ragged causal), or with
    ``window_causal=False`` every column ``< kv_len`` (the denoise
    window of block diffusion: its positions all see each other; the
    query heads of a kv head then share one grid row). GQA/MQA (kv heads
    dividing q heads) attends by HEAD-INDEX MAPPING: query head h reads
    cache head ``h // (hq//hk)`` directly — the kernel's k/v BlockSpecs
    (and the fallback's grouped einsum) index the hk-sized caches, so
    decode HBM traffic stays at the cache's true size; no repeated
    copies are materialized.

    TPU runs the Pallas kernel; other backends (and cache lengths not
    on the 128 grid) take the XLA fallback — identical math.
    """
    b, sq, hq, d = query.shape
    t, hk = key_cache.shape[1], key_cache.shape[2]
    if sq > _DECODE_QPAD:
        raise ValueError(
            f"flash_attention_decode: q_len {sq} > MAX_DECODE_QLEN "
            f"({_DECODE_QPAD}, the fp32 sublane tile); use "
            "flash_attention/prefill for longer query windows, or cap "
            "the speculative verify window at draft_k <= "
            f"{_DECODE_QPAD - 1}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    assert hq % hk == 0, f"q heads {hq} not divisible by kv heads {hk}"
    group = hq // hk
    quant = key_cache.dtype == jnp.int8
    if quant and (k_scale is None or v_scale is None):
        raise ValueError(
            "flash_attention_decode: int8 caches need k_scale/v_scale "
            "([batch, max_len, kv_heads] — the QuantKVCache sidecars); "
            "an unscaled int8 cache cannot be dequantized")
    # query rows [b, h] flatten so that row i's kv row is i // group
    # (b*hq = (b*hk)*group, batch-major): the group-size broadcast is
    # pure indexing, never a materialized repeat of the caches
    if window_causal:
        qt = jnp.swapaxes(query, 1, 2).reshape(b * hq, sq, d)
    else:
        qt, group = _fold_group(query, hk).reshape(b * hk, -1, d), 1
    kt = jnp.swapaxes(key_cache, 1, 2).reshape(b * hk, t, d)
    vt = jnp.swapaxes(value_cache, 1, 2).reshape(b * hk, t, d)
    kst = vst = None
    if quant:
        kst = jnp.swapaxes(k_scale, 1, 2).reshape(b * hk, t)
        vst = jnp.swapaxes(v_scale, 1, 2).reshape(b * hk, t)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    kl = jnp.repeat(kv_len, hk)                       # [B*Hk] int32
    use_pallas = (jax.default_backend() == "tpu"
                  and t % 128 == 0 and d in (64, 128, 256))
    wc = {} if window_causal else {"window_causal": False}
    if use_pallas:
        out = _decode_pallas(qt, kt, vt, kl, float(scale), block_k,
                             group=group, k_scale=kst, v_scale=vst, **wc)
    else:
        out = _decode_xla(qt, kt, vt, kl, float(scale), group=group,
                          ks=kst, vs=vst, **wc)
    if not window_causal:
        return _unfold_group(out.reshape(b, hk, -1, d), sq)
    return jnp.swapaxes(out.reshape(b, hq, sq, d), 1, 2)


# ------------------------------------------------ chunk prefill forward
#
# "Chunk-shaped" attention: a WINDOW of new query tokens (tens to
# hundreds — a prefill chunk) per row against the same cached K/V the
# decode kernel reads, with the same per-row ragged valid length. This
# is decode attention generalized along the query axis: query row i of
# the window sits at global position kv_len - sq + i and attends cache
# columns <= that position, so the serving engine can fill a long
# prompt's cache C tokens at a time between decode polls instead of
# monopolizing the device with one inline prefill. The kernel q-tiles
# the decode kernel rather than forking it: each q-tile re-enters
# _decode_accumulate with an ADJUSTED sq (sq_total - iq*block_q), which
# shifts the shared ``cols - rows <= kv_len - sq`` mask to exactly the
# tile's causal window — the accumulate math stays the single shared
# copy, so chunked numerics can never drift from decode numerics.

_CHUNK_BLOCK_Q = 128


def _chunk_kernel(kvlen_ref, q_ref, k_ref, v_ref, *rest, sq_total,
                  block_q, block_k, num_kblocks, group, quant=False):
    # q_ref holds q * (scale * log2e); scores are base-2 logits.
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        _decode_init(m_scr, l_scr, acc_scr)

    # valid cache length incl. the sq_total new positions (already
    # written), from the scalar-prefetched [B*Hk] vector
    kv_len = kvlen_ref[pl.program_id(0) // group]
    # local row r of q-tile iq is global query iq*block_q + r, so the
    # shared mask with sq := sq_total - iq*block_q is exactly this
    # tile's causal window
    sq_tile = sq_total - iq * block_q
    # skip k-blocks entirely past the LAST row of this q-tile's window
    # (col limit kv_len - sq_tile + block_q - 1, also capped by kv_len
    # for padded tail tiles whose rows overhang sq_total)
    limit = jnp.minimum(kv_len, kv_len - sq_tile + block_q)

    @pl.when(ik * block_k < limit)
    def _compute():
        _decode_accumulate(q_ref[0], k_ref[0], v_ref[0], ik * block_k,
                           kv_len, sq_tile, m_scr, l_scr, acc_scr,
                           ks=ks_ref[0] if quant else None,
                           vs=vs_ref[0] if quant else None)

    @pl.when(ik == num_kblocks - 1)
    def _finalize():
        o_ref[0] = _decode_result(l_scr, acc_scr, o_ref.dtype)


def _chunk_pallas(q, k_cache, v_cache, kv_len, scale,
                  block_k=_DECODE_BLOCK_K, group=1,
                  k_scale=None, v_scale=None):
    """q: [B*Hq, sq, D] (unscaled, sq arbitrary), caches [B*Hk, T, D],
    kv_len [B*Hk]. Same GQA head-index streaming and fused int8
    dequant as ``_decode_pallas``; the grid gains a q-tile axis."""
    bh, sq, d = q.shape
    t = k_cache.shape[1]
    quant = k_scale is not None
    sq_pad = -(-sq // _DECODE_QPAD) * _DECODE_QPAD
    bq = _pick_block(sq_pad, _CHUNK_BLOCK_Q)
    q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    if sq < sq_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0)))
    nq = sq_pad // bq
    bk = _pick_block(t, block_k)
    nk = t // bk
    kv_bytes = k_cache.dtype.itemsize * t * d \
        + (k_scale.dtype.itemsize * t if quant else 0)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j, kl: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j, kl: (b // group, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j, kl: (b // group, j, 0)),
    ]
    operands = [q, k_cache, v_cache]
    if quant:
        in_specs += _scale_row_specs(
            bk, lambda b, i, j, kl: (b // group, 0, j))
        operands += [k_scale[:, None], v_scale[:, None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, d),
                               lambda b, i, j, kl: (b, i, 0)),
        scratch_shapes=_decode_scratch(bq, d),
    )
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, sq_total=sq, block_q=bq,
                          block_k=bk, num_kblocks=nk, group=group,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * sq_pad * t * d,
            bytes_accessed=bh * (sq_pad * d * q.dtype.itemsize
                                 + 2 * kv_bytes),
            transcendentals=bh * sq_pad * t),
        interpret=_interpret(),
        name="flash_chunk",
    )(kv_len.astype(jnp.int32), *operands)
    return out[:, :sq]


def flash_attention_chunk(query, key_cache, value_cache, kv_len,
                          scale=None, block_k=_DECODE_BLOCK_K,
                          k_scale=None, v_scale=None):
    """Chunk-prefill attention: an arbitrary-length window of new query
    tokens per row against a cached K/V with per-row valid lengths —
    ``flash_attention_decode`` without the 8-row cap, for the serving
    engine's chunked prefill (a C-token slice of a long prompt attends
    the cache the earlier chunks wrote).

    Same contract as ``flash_attention_decode``: query [batch, q_len,
    num_heads, head_dim]; caches [batch, max_len, num_kv_heads,
    head_dim] with the new tokens already written; kv_len [batch] int32
    INCLUDING the q_len new positions (query row i attends columns
    ``<= kv_len - q_len + i``); int8 caches take the QuantKVCache
    ``k_scale``/``v_scale`` sidecars with the dequant fused in-kernel;
    GQA attends by head-index mapping. TPU runs the q-tiled Pallas
    kernel; other backends (and off-grid cache lengths) take the same
    XLA fallback as decode, which is already generic in q_len.
    """
    b, sq, hq, d = query.shape
    t, hk = key_cache.shape[1], key_cache.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    assert hq % hk == 0, f"q heads {hq} not divisible by kv heads {hk}"
    group = hq // hk
    quant = key_cache.dtype == jnp.int8
    if quant and (k_scale is None or v_scale is None):
        raise ValueError(
            "flash_attention_chunk: int8 caches need k_scale/v_scale "
            "([batch, max_len, kv_heads] — the QuantKVCache sidecars); "
            "an unscaled int8 cache cannot be dequantized")
    qt = jnp.swapaxes(query, 1, 2).reshape(b * hq, sq, d)
    kt = jnp.swapaxes(key_cache, 1, 2).reshape(b * hk, t, d)
    vt = jnp.swapaxes(value_cache, 1, 2).reshape(b * hk, t, d)
    kst = vst = None
    if quant:
        kst = jnp.swapaxes(k_scale, 1, 2).reshape(b * hk, t)
        vst = jnp.swapaxes(v_scale, 1, 2).reshape(b * hk, t)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    kl = jnp.repeat(kv_len, hk)                       # [B*Hk] int32
    use_pallas = (jax.default_backend() == "tpu"
                  and t % 128 == 0 and d in (64, 128, 256))
    if use_pallas:
        out = _chunk_pallas(qt, kt, vt, kl, float(scale), block_k,
                            group=group, k_scale=kst, v_scale=vst)
    else:
        out = _decode_xla(qt, kt, vt, kl, float(scale), group=group,
                          ks=kst, vs=vst)
    return jnp.swapaxes(out.reshape(b, hq, sq, d), 1, 2)


# ------------------------------------------------ paged decode forward
#
# Decode attention over the block-table paged KV cache
# (generation.paged_cache.PagedKVCache): K/V live in a shared pool of
# fixed-size pages and each batch row (a lane) names its pages in an
# int32 page table. The pool is [layers, pages, kv heads, page, D], so
# a page of ALL kv heads of a layer is one contiguous block. The kernel
# walks the VALID pages only: the lanes' ``cdiv(kv_len, page)`` first
# table entries are listed lane after lane (``_paged_work_list``), the
# grid has one step per listed page (its bound is the list's length, a
# value of the call, not of the trace), and a step's K and V block is
# that page with all its kv heads, resolved from the scalar-prefetched
# list and table in the index map and copied where it lies by the
# pipeline, which has the next page (the first page of the next lane
# too) in flight while this one is attended. The heads are the batch
# dimension of the two products. Table slots past ``kv_len`` cost
# nothing: no grid step, no copy; a lane that holds nothing is never
# visited. Off-TPU (and for page sizes off the 128 grid) an XLA gather
# fallback materializes the gathered rows with IDENTICAL math to the
# dense _decode_xla path — the bitwise-parity gate between paged and
# dense serving rests on that.

_PAGED_COPY_BYTES = 1 << 20     # most K (or V) bytes one step copies


def _paged_heads(hk, page, d, itemsize):
    """kv heads a grid step copies and attends: as many as keep a page's
    copy within ``_PAGED_COPY_BYTES`` (a divisor of ``hk``; all of them
    at the widths served today: 32 heads of 128 are 1 MB a page in bf16,
    4 heads 128 KB, 12 heads of 64 192 KB). K and V blocks are double
    buffered: 4 MB of VMEM at the limit. More heads than that walk the
    list once a block of heads."""
    per_head = page * d * itemsize
    return max(h for h in range(1, hk + 1) if hk % h == 0
               and (h == 1 or h * per_head <= _PAGED_COPY_BYTES))


def _paged_work_list(kv_len, page, num_slots):
    """(lane, slot, count): the valid (lane, table slot) pairs in lane
    order, padded to the static ``lanes * num_slots``, and how many
    there are. A few small fusions on [lanes * slots, lanes] ints,
    the same for every layer of a step, so XLA computes them once."""
    lanes = kv_len.shape[0]
    pages = jnp.clip(-(-kv_len // page), 0, num_slots)
    ends = jnp.cumsum(pages)
    item = jnp.arange(lanes * num_slots, dtype=jnp.int32)
    lane = jnp.minimum(
        jnp.sum(item[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        lanes - 1)
    return lane, item - (ends - pages)[lane], ends[-1]


def _paged_decode_kernel(lane_ref, slot_ref, table_ref, kvlen_ref,
                         q_ref, k_ref, v_ref, *rest, sq, group, page_size,
                         quant=False, window_causal=True):
    # q_ref holds q * (scale * log2e) as [heads, rows, D]: the rows of
    # a kv head are its ``group`` query heads x ``sq`` window positions
    # (padded to the sublane tile); scores are base-2 logits. The
    # accumulate body is the SAME _decode_accumulate as the dense
    # kernel, run with the kv heads as a batch dimension — only the
    # k-block addressing differs (the listed pages through the
    # scalar-prefetched table vs contiguous blocks). Quant mode adds
    # the page's scale rows ([heads, page], same index map as the
    # pools) and fuses the dequant in the shared body.
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    item = pl.program_id(1)
    lane, j = lane_ref[item], slot_ref[item]

    @pl.when(j == 0)
    def _init():
        _decode_init(m_scr, l_scr, acc_scr)

    kv_len = kvlen_ref[lane]       # this lane's valid cache length
    row_pos = None
    if window_causal and group > 1:
        # rows are (query head of the group, window position), the
        # position minor: row r of a kv head sits at position r % sq
        row_pos = jax.lax.rem(jax.lax.broadcasted_iota(
            jnp.int32, (1, q_ref.shape[1], 1), 1), sq) if sq > 1 else 0
    _decode_accumulate(q_ref[...], k_ref[...], v_ref[...],
                       j * page_size, kv_len, sq, m_scr, l_scr, acc_scr,
                       ks=ks_ref[...][:, None] if quant else None,
                       vs=vs_ref[...][:, None] if quant else None,
                       window_causal=window_causal, row_pos=row_pos)

    @pl.when((j + 1) * page_size >= kv_len)    # the lane's last page
    def _finalize():
        o_ref[...] = _decode_result(l_scr, acc_scr, o_ref.dtype)


def _paged_decode_pallas(q, k_pool, v_pool, page_table, kv_len, scale,
                         layer, sq, interpret=None, k_scale=None,
                         v_scale=None, window_causal=True):
    """q: [B, Hk, group * sq, D] (unscaled; the query heads of a kv
    head stacked into its rows, the window position minor), pools
    [L, n_pages, Hk, page, D] (the STACKED pool of every layer, where
    it lies), page_table [B, P] int32, kv_len [B], ``layer`` static.
    The grid is (blocks of kv heads, listed pages); the k/v index map
    resolves (layer, page id, head block) from the scalar-prefetched
    work list and table, so no layer is ever sliced out of the pool and
    nothing past ``kv_len`` is read. ``k_scale``/``v_scale``
    ([L, n_pages, Hk, page] bf16) run the int8-pool mode: a page's
    scales of all heads are one block through the SAME index map,
    dequant fused in the shared accumulate body."""
    b, hk, rows, d = q.shape
    page = k_pool.shape[3]
    num_slots = page_table.shape[1]
    quant = k_scale is not None
    qpad = _window_qpad(rows)
    q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    if rows < qpad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, qpad - rows), (0, 0)))
    heads = _paged_heads(hk, page, d, k_pool.dtype.itemsize)
    kvl = kv_len.astype(jnp.int32)
    lane, slot, count = _paged_work_list(kvl, page, num_slots)

    def q_index(h, i, lane, slot, tbl, kl):
        return (lane[i], h, 0, 0)

    def page_index(h, i, lane, slot, tbl, kl):
        return (layer, tbl[lane[i] * num_slots + slot[i]], h, 0, 0)

    # layer and page dims are squeezed: the kernel sees a page of
    # ``heads`` kv heads, [heads, page, d], and their query rows
    q_spec = pl.BlockSpec((None, heads, qpad, d), q_index)
    kv_spec = pl.BlockSpec((None, None, heads, page, d), page_index)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k_pool, v_pool]
    if quant:
        scale_spec = pl.BlockSpec((None, None, heads, page),
                                  lambda *a: page_index(*a)[:4])
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(hk // heads, count),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=_decode_scratch(qpad, d, lead=(heads,)),
    )
    kv_bytes = k_pool.dtype.itemsize * num_slots * page * d \
        + (k_scale.dtype.itemsize * num_slots * page if quant else 0)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, sq=sq, group=rows // sq,
                          page_size=page, quant=quant,
                          **({} if window_causal
                             else {"window_causal": False})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * hk * qpad * num_slots * page * d,
            bytes_accessed=b * hk * (qpad * d * q.dtype.itemsize
                                     + 2 * kv_bytes),
            transcendentals=b * hk * qpad * num_slots * page),
        interpret=_interpret() if interpret is None else interpret,
        name="flash_decode_paged",
    )(lane, slot, page_table.astype(jnp.int32).reshape(-1), kvl, *operands)
    # a lane that holds nothing is never visited and its block never
    # written: it attends nothing, so it is zeros
    out = jnp.where((kvl > 0)[:, None, None, None], out, 0)
    return out[:, :, :rows]


def flash_attention_decode_paged(query, key_pool, value_pool,
                                 page_table, kv_len, layer, scale=None,
                                 k_scale=None, v_scale=None,
                                 window_causal=True):
    """Decode-shaped attention over a PAGED KV cache: 1..8 new query
    tokens per row against K/V stored in a shared page pool addressed
    through per-row page tables.

    Int8 pool mode: with int8 pools pass ``k_scale``/``v_scale``
    ([layers, n_pages, num_kv_heads, page_size], the
    ``QuantPagedKVCache`` sidecars) — the scale pages resolve through
    the same scalar-prefetched table and the dequant fuses in-kernel,
    so the pool streams at half the HBM bytes.

    query: [batch, q_len<=8, num_heads, head_dim] (framework layout).
    key_pool/value_pool: [layers, n_pages, num_kv_heads, page_size,
    head_dim] — the whole stacked pool of a ``generation.PagedKVCache``
    (new tokens already written through the table); ``layer`` (static)
    names the layer to attend, and is resolved where the page id is, in
    the kernel's index map: the pool is read where it lies.
    page_table: [batch, pages_per_row] int32 (entry 0 = the reserved
    null page). kv_len: [batch] int32 — valid entries per row INCLUDING
    the q_len new positions; masking is identical to
    ``flash_attention_decode``, ``window_causal=False`` (every row sees
    the whole window) included. A row with ``kv_len`` 0 attends nothing
    and gives zeros.

    TPU with a lane-aligned page size runs the Pallas kernel: it walks
    the rows' valid pages alone (``cdiv(kv_len, page_size)`` table
    entries a row; entries past them are never read, whatever they
    name), a page of all kv heads in one copy, the query heads of a kv
    head stacked in the rows of its tile — no gather ever materializes
    the logical row. Other backends gather the row's pages and run the
    dense XLA decode math bit-for-bit (garbage in pages past kv_len is
    masked to exact zeros, so paged results are bitwise-equal to the
    dense cache)."""
    b, sq, hq, d = query.shape
    hk, ps = key_pool.shape[2], key_pool.shape[3]
    num_slots = page_table.shape[1]
    if sq > _DECODE_QPAD:
        raise ValueError(
            f"flash_attention_decode_paged: q_len {sq} > "
            f"MAX_DECODE_QLEN ({_DECODE_QPAD}); same contract as "
            "flash_attention_decode")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    assert hq % hk == 0, f"q heads {hq} not divisible by kv heads {hk}"
    group = hq // hk
    quant = key_pool.dtype == jnp.int8
    if quant and (k_scale is None or v_scale is None):
        raise ValueError(
            "flash_attention_decode_paged: int8 pools need "
            "k_scale/v_scale ([layers, n_pages, kv_heads, page_size] — "
            "the QuantPagedKVCache sidecars); an unscaled int8 pool "
            "cannot be dequantized")
    kv_len = jnp.asarray(kv_len, jnp.int32)
    wc = {} if window_causal else {"window_causal": False}
    use_pallas = (jax.default_backend() == "tpu"
                  and ps % 128 == 0 and d in (64, 128, 256))
    # a pool wider than the heads (``paged_cache.pool_head_dim``: heads
    # of 64 stored in 128 lanes, the rest zero): the kernel runs at the
    # pool's width on a query padded with zeros, which adds nothing to a
    # score, and the lanes of the result past ``d`` are V's zeros
    wide = key_pool.shape[-1] - d
    if use_pallas:
        if wide:
            query = jnp.pad(query, ((0, 0),) * 3 + ((0, wide),))
        # the query heads of a kv head ride the rows of its tile in
        # both windows: the causal one tells them apart by row % sq
        return _unfold_group(_paged_decode_pallas(
            _fold_group(query, hk), key_pool, value_pool, page_table,
            kv_len, float(scale), layer, sq, k_scale=k_scale,
            v_scale=v_scale, **wc), sq)[..., :d]
    if window_causal:
        qt = jnp.swapaxes(query, 1, 2).reshape(b * hq, sq, d)
    else:
        qt, group = _fold_group(query, hk).reshape(b * hk, -1, d), 1

    def unflatten(out):
        if not window_causal:
            return _unfold_group(out.reshape(b, hk, -1, d), sq)
        return jnp.swapaxes(out.reshape(b, hq, sq, d), 1, 2)

    # XLA fallback: gather the row's pages ([b, slots, hk, ps, d]) into
    # the logical per-head rows [b * hk, pages_per_row * page_size, d]
    # and run the exact dense decode math — t equals the dense cache's
    # max_len, so the reduction order (and thus every bit) matches the
    # dense engine
    t = num_slots * ps

    def rows(pool):  # [b, slots, hk, ps, ...] -> [b * hk, t, ...]
        g = jnp.swapaxes(pool[layer, page_table], 1, 2)
        if wide and pool.ndim == 5:     # the heads' own lanes alone
            g = g[..., :d]
        return g.reshape((b * hk, t) + g.shape[4:])

    return unflatten(_decode_xla(
        qt, rows(key_pool), rows(value_pool), jnp.repeat(kv_len, hk),
        float(scale), group=group, ks=rows(k_scale) if quant else None,
        vs=rows(v_scale) if quant else None, **wc))


def flash_attention(query, key, value, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    block=1):
    """Flash attention over [batch, seq, num_heads, head_dim] inputs
    (framework layout; matches F.scaled_dot_product_attention).

    Supports self- and cross-attention (different kv length), causal
    masking, grouped-query attention (kv heads dividing q heads), and
    gradients via the Pallas backward kernels.

    ``block`` B > 1 with ``causal`` widens the mask to BLOCK-causal
    (query i sees key j iff ``j // B <= i // B``: the prefill mask of
    generation by diffusion over blocks). B must divide 128, so every
    kernel block holds whole mask blocks. Forward only: the backward
    kernels know the plain causal mask alone.
    """
    b, sq, hq, d = query.shape
    hk = key.shape[2]
    sk = key.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if hk != hq:  # GQA/MQA: repeat kv heads
        assert hq % hk == 0, f"q heads {hq} not divisible by kv heads {hk}"
        key = jnp.repeat(key, hq // hk, axis=2)
        value = jnp.repeat(value, hq // hk, axis=2)
    qt = jnp.swapaxes(query, 1, 2).reshape(b * hq, sq, d)
    kt = jnp.swapaxes(key, 1, 2).reshape(b * hq, sk, d)
    vt = jnp.swapaxes(value, 1, 2).reshape(b * hq, sk, d)
    q_pad = (-sq) % 128
    k_pad = (-sk) % 128
    block = int(block)
    if block != 1:
        if not causal or block < 1 or 128 % block or sq != sk \
                or q_pad:
            raise NotImplementedError(
                f"flash_attention: block={block} needs causal=True, a "
                "block length dividing 128 and equal q/k lengths on the "
                f"128 grid (got causal={causal}, q={sq}, k={sk})")
        out, _ = _flash_fwd(qt, kt, vt, float(scale), True, int(block_q),
                            int(block_k), block=block)
        return jnp.swapaxes(out.reshape(b, hq, sq, d), 1, 2)
    if (q_pad or k_pad) and causal:
        # the diagonal offset under asymmetric padding is not worth the
        # complexity; fail clearly so scaled_dot_product_attention's
        # fallback takes the XLA path instead of a degenerate block
        # size crashing deep inside Mosaic
        raise NotImplementedError(
            "flash_attention: causal attention requires sequence "
            f"lengths divisible by 128, got q={sq} k={sk}; use the XLA "
            "attention path for ragged causal shapes")
    if q_pad or k_pad:
        # ragged sequence (e.g. ViT's 197 patches): pad to the 128-lane
        # grid and mask the phantom key columns inside the kernels.
        # Padded q rows produce discarded outputs and zero cotangents
        # (the pad/slice live in the autodiff graph), so only the key
        # side needs in-kernel masking.
        qt = jnp.pad(qt, ((0, 0), (0, q_pad), (0, 0)))
        kt = jnp.pad(kt, ((0, 0), (0, k_pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, k_pad), (0, 0)))
        out = _flash_bhsd(qt, kt, vt, float(scale), False,
                          int(block_q), int(block_k), int(sk))
        out = out[:, :sq]
    else:
        out = _flash_bhsd(qt, kt, vt, float(scale), bool(causal),
                          int(block_q), int(block_k))
    return jnp.swapaxes(out.reshape(b, hq, sq, d), 1, 2)
