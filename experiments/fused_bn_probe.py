"""Probe: where does the fused conv+BN path lose time vs XLA?

Times, on the real chip (host-transfer fenced, in-program scan repeats
to amortize the host dispatch):
  1. Pallas matmul_bn_stats vs XLA (1x1 conv + separate stats) — fwd
  2. the same, fwd+bwd through the stats consumers
  3. one layer1 bottleneck block fwd+bwd, fused vs unfused
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

REPS = 10


def timeit(fn, *args):
    fn_j = jax.jit(fn)
    out = fn_j(*args)
    jax.tree_util.tree_map(
        lambda a: np.asarray(jax.device_get(a)).ravel()[:1], out)
    t0 = time.perf_counter()
    out = fn_j(*args)
    s = jax.tree_util.tree_leaves(out)[0]
    float(jnp.sum(s))  # host fence
    return (time.perf_counter() - t0)


def scan_rep(body, x):
    """Run body REPS times inside the program; returns summed output."""
    def f(carry, _):
        return carry, jnp.sum(body(x))
    _, ys = jax.lax.scan(f, 0, None, length=REPS)
    return ys


def main():
    from paddle_tpu.kernels.fused_resnet import (matmul_bn_stats,
                                                 bn_relu_matmul_bn_stats)
    rng = np.random.RandomState(0)
    # layer1 conv3 shape: M=401408, K=64, N=256
    M, K, N = 128 * 56 * 56, 64, 256
    x = jax.device_put(jnp.asarray(
        rng.randn(M, K).astype(np.float32), ), jax.devices()[0]).astype(jnp.bfloat16)
    w = jax.device_put(jnp.asarray(
        rng.randn(K, N).astype(np.float32))).astype(jnp.bfloat16)
    scale = jnp.ones((K,), jnp.float32)
    shift = jnp.zeros((K,), jnp.float32)

    def pallas_fwd(x):
        y, m, v = matmul_bn_stats(x, w)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(m) + jnp.sum(v)

    def xla_fwd(x):
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        yb = y.astype(jnp.bfloat16)
        yf = yb.astype(jnp.float32)
        m = jnp.mean(yf, axis=0)
        v = jnp.mean(yf * yf, axis=0) - m * m
        return jnp.sum(yf) + jnp.sum(m) + jnp.sum(v)

    def pallas_prologue_fwd(x):
        y, m, v = bn_relu_matmul_bn_stats(x, scale, shift, w)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(m) + jnp.sum(v)

    def xla_prologue_fwd(x):
        a = jnp.maximum(x.astype(jnp.float32) * scale + shift, 0.0)
        y = jnp.dot(a.astype(jnp.bfloat16), w,
                    preferred_element_type=jnp.float32)
        yb = y.astype(jnp.bfloat16).astype(jnp.float32)
        m = jnp.mean(yb, axis=0)
        v = jnp.mean(yb * yb, axis=0) - m * m
        return jnp.sum(yb) + jnp.sum(m) + jnp.sum(v)

    for name, f in [("pallas_fwd", pallas_fwd), ("xla_fwd", xla_fwd),
                    ("pallas_pro_fwd", pallas_prologue_fwd),
                    ("xla_pro_fwd", xla_prologue_fwd)]:
        dt = timeit(lambda x: scan_rep(f, x), x)
        print(f"{name:18s} {dt / REPS * 1e3:8.3f} ms")

    for name, f in [("pallas_fwdbwd", pallas_fwd), ("xla_fwdbwd", xla_fwd),
                    ("pallas_pro_fb", pallas_prologue_fwd),
                    ("xla_pro_fb", xla_prologue_fwd)]:
        g = jax.grad(f)
        dt = timeit(lambda x: scan_rep(lambda x: jnp.sum(g(x)), x), x)
        print(f"{name:18s} {dt / REPS * 1e3:8.3f} ms")


if __name__ == "__main__":
    main()
