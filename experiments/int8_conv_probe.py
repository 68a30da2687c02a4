"""Is the 'XLA:TPU upcasts int8 convolutions' wall real? (VERDICT r3
Weak #7 — the documented limitation in quantization/int8_compute.py
had no in-tree measurement.)

Three timings on the real chip, in-program scan repeats (host
dispatch amortized), device-resident operands:
  1. bf16 conv_general_dilated        (the production path)
  2. int8-input conv_general_dilated with preferred int32 accumulation
     (what XLA does with it is the question)
  3. int8 1x1 conv recast as the known-good int8 MXU matmul
     (the escape hatch: a 1x1 conv IS a matmul)
Shapes: ResNet layer3-ish 1x1 conv (b128 14x14x1024 -> 256) where the
MXU is the binding resource.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

REPS = 30


def timed_chain(step, x0, w):
    """Dependent chain: carry the activation, so no iteration can be
    hoisted/CSE'd out of the scan."""

    def prog(x, wv):
        def f(carry, _):
            return step(carry, wv), None
        out, _ = jax.lax.scan(f, x, None, length=REPS)
        return out

    fn = jax.jit(prog)
    out = fn(x0, w)
    float(jnp.sum(out.astype(jnp.float32)))       # compile + fence
    t0 = time.perf_counter()
    out = fn(x0, w)
    float(jnp.sum(out.astype(jnp.float32)))
    return (time.perf_counter() - t0) / REPS


def main():
    rng = np.random.RandomState(0)
    n, h, w_, c = 128, 14, 14, 1024
    xf = jax.device_put(jnp.asarray(
        rng.randn(n, h, w_, c).astype(np.float32))).astype(jnp.bfloat16)
    wf = jax.device_put(jnp.asarray(
        (rng.randn(1, 1, c, c) * 0.03).astype(np.float32))
    ).astype(jnp.bfloat16)
    xi = jax.device_put(jnp.asarray(
        rng.randint(-127, 127, (n, h, w_, c)).astype(np.int8)))
    wi = jax.device_put(jnp.asarray(
        rng.randint(-127, 127, (1, 1, c, c)).astype(np.int8)))

    def conv_bf16(x, wv):
        y = jax.lax.conv_general_dilated(
            x, wv, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
        return y.astype(jnp.bfloat16)

    def conv_int8(x, wv):
        y = jax.lax.conv_general_dilated(
            x, wv, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.int32)
        # requantize back to int8 (shift approximates the scale)
        return (y >> 8).astype(jnp.int8)

    def mm_int8(x, wv):
        x2 = x.reshape(-1, c)
        w2 = wv.reshape(c, c)
        y = jax.lax.dot_general(
            x2, w2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return ((y >> 8).astype(jnp.int8)).reshape(x.shape)

    flops = 2.0 * n * h * w_ * c * c
    for name, f, a, b in [("conv bf16", conv_bf16, xf, wf),
                          ("conv int8->int32", conv_int8, xi, wi),
                          ("1x1-as-int8-matmul", mm_int8, xi, wi)]:
        try:
            dt = timed_chain(f, a, b)
            print(f"{name:22s} {dt * 1e6:9.1f} us   "
                  f"{flops / dt / 1e12:7.1f} T(op|flop)/s", flush=True)
        except Exception as e:
            print(f"{name:22s} FAILED: {type(e).__name__}: "
                  f"{str(e)[:120]}", flush=True)
    # what does XLA actually emit for the int8 conv? look for
    # a convert before the convolution
    hlo = jax.jit(conv_int8).lower(xi, wi).compile().as_text()
    upcast = "convert" in hlo.split("convolution")[0][-600:] \
        if "convolution" in hlo else None
    print(f"int8 conv HLO: {'upcast convert before conv' if upcast else 'direct int8 convolution'}")


if __name__ == "__main__":
    main()
