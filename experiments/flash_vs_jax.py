"""Compare the in-tree flash attention kernel vs jax's reference TPU
flash-attention Pallas kernel, fwd+bwd, at the bench model shapes —
in-program scan repeats so the host dispatch cost is amortized.

Usage: python experiments/flash_vs_jax.py
"""
from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import flash_attention as ours

from jax.experimental.pallas.ops.tpu.flash_attention import (
    flash_attention as jax_fa, BlockSizes)

REPS = 10


def bench_scan(grad_fn, q, k, v):
    """Chain REPS grad evaluations (dq feeds the next q) so XLA cannot
    hoist them; one device program, one fence."""

    def prog(q, k, v):
        def f(carry, _):
            dq, dk, dv = grad_fn(carry, k, v)
            upd = (dq + dk + dv).astype(carry.dtype)  # keep all 3 live
            return carry + upd * 1e-6, None
        out, _ = jax.lax.scan(f, q, None, length=REPS)
        return out

    fn = jax.jit(prog)
    out = fn(q, k, v)
    float(jnp.sum(out.astype(jnp.float32)))
    t0 = time.perf_counter()
    out = fn(q, k, v)
    float(jnp.sum(out.astype(jnp.float32)))
    return (time.perf_counter() - t0) / REPS


def run(tag, b, h, s, d, causal):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)  # ours layout
    k = jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, h, d), jnp.bfloat16)
    qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))  # jax layout

    def loss_ours(q, k, v):
        return ours(q, k, v, causal=causal).astype(jnp.float32).sum()

    def make_loss_jax(bq, bkmaj, bk):
        bs = BlockSizes(
            block_q=bq, block_k_major=bkmaj, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bkmaj,
            block_k_dkv=bk, block_q_dkv=bq,
            block_k_major_dq=bkmaj, block_k_dq=bk, block_q_dq=bq)

        def loss(q, k, v):
            return jax_fa(q, k, v, causal=causal, sm_scale=1.0 / d ** 0.5,
                          block_sizes=bs).astype(jnp.float32).sum()
        return loss

    print(f"{tag}: b{b} h{h} s{s} d{d} causal={causal}")
    t = bench_scan(jax.grad(loss_ours, argnums=(0, 1, 2)), q, k, v)
    print(f"  {'ours':>18}: {t * 1e3:8.2f} ms")
    for bq in (256, 512, 1024):
        for bk in (256, 512, 1024):
            if bq > s or bk > s:
                continue
            try:
                t = bench_scan(
                    jax.grad(make_loss_jax(bq, bk, bk), argnums=(0, 1, 2)),
                    qt, kt, vt)
                print(f"  jax({bq}/{bk})".rjust(20) + f": {t * 1e3:8.2f} ms")
            except Exception as e:  # noqa: BLE001
                print(f"  jax {bq}/{bk} failed: {type(e).__name__}: {e}")


if __name__ == "__main__":
    print("devices:", jax.devices())
    run("ernie-s512", 32, 12, 512, 64, False)
    run("gpt2-s1024", 16, 12, 1024, 64, True)
