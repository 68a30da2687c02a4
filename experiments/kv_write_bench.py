"""The new positions' K and V rows into the page pool at the five serving
cells' real shapes (and a few between them): XLA's row scatters (``paged_cache._scatter_tokens``,
one for K and one for V a layer) against ``kernels/paged_write.py`` (one
aliased write a layer), each as ONE jitted pass over all layers with the
stacked pools donated.  Microseconds a layer (K and V together) and the
pools compared bit for bit off the null page: every page a lane writes,
gathered, and a checksum of the whole pool.  The kernel is called
whatever ``paged_write.supports`` says: this table is what sets its floor
on the rows a lane (``MIN_ROWS_A_LANE``).

Needs the chip (a time from the CPU is no device number):

    chiprun -- python experiments/kv_write_bench.py

Writes ``chiprun_out/kv_write_bench.jsonl`` (one line a shape).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.generation.paged_cache import (PagedKVCache, _scatter_tokens,
                                               _to_pool_width, pool_head_dim)
from paddle_tpu.kernels import paged_write as pw

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, SLOTS = 128, 16
CALLS, REPEATS = 20, 3
# a jitted pass holds about this many layers' writes, so that the host's
# dispatch (0.2 ms a call) stays under the device's time
WRITES_A_CALL = 24
# cell -> (attention layers, pages, kv heads, head_dim, lanes, positions
# a lane and step, live lanes)
SHAPES = {
    "gpt3l8-offline": (8, 512, 32, 128, 64, 1, 64),
    "gpt3l8-chat": (8, 512, 32, 128, 64, 1, 4),
    "sdar-l6-offline": (6, 1024, 4, 128, 128, 4, 128),
    "lfm2-l14-offline": (3, 1024, 8, 64, 128, 1, 128),
    "nemotron3n-l13-offline": (2, 2048, 2, 128, 256, 1, 256),
}
# between the cells' 2, 8, 16 and 32 rows a lane: where the floor lies
SHAPES.update({f"floor-sweep-{heads}x{s}": (3, 1024, heads, 128, 128, s, 128)
               for heads, s in ((1, 1), (4, 1), (2, 4), (16, 1), (2, 8))})


def fill(shape, salt):
    """A pool whose every element depends on where it lies, made in one
    fusion (no second copy of gigabytes)."""
    at = [jax.lax.broadcasted_iota(jnp.int32, shape, i) for i in range(5)]
    mixed = (at[0] * 7 + at[1] * 13 + at[2] * 3 + at[3] * 5 + at[4] + salt)
    return (mixed % 509 - 254).astype(jnp.bfloat16)


def passes(layers):
    """The layers a jitted call writes, in order: every layer, over and
    over."""
    return list(range(layers)) * max(1, WRITES_A_CALL // layers)


def scatter_pass(k, v, page, off, k_new, v_new):
    for layer in passes(k.shape[0]):
        k = _scatter_tokens(k, layer, page, off, k_new)
        v = _scatter_tokens(v, layer, page, off, v_new)
    return k, v


def kernel_pass(k, v, page, off, k_new, v_new):
    k_new, v_new = _to_pool_width(k_new, k), _to_pool_width(v_new, v)
    for layer in passes(k.shape[0]):
        k, v = pw.paged_kv_write(k, v, layer, page, off, k_new, v_new)
    return k, v


@jax.jit
def witness(k, v, pages):
    """What a pass left: the pages the lanes write (every layer of them)
    and a checksum of everything off the null page."""
    def bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    return (k[:, pages], v[:, pages],
            jnp.sum(bits(k[:, 1:])), jnp.sum(bits(v[:, 1:])))


def same_bits(a, b):
    if a.dtype == jnp.bfloat16:
        a, b = (jax.lax.bitcast_convert_type(x, jnp.uint16) for x in (a, b))
    return bool(jnp.array_equal(a, b))


def bench(shapes, out):
    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    make = jax.jit(fill, static_argnums=(0,))
    with open(out, "w") as f:
        for cell, (layers, pages, heads, d, lanes, s, live) in shapes.items():
            shape = (layers, pages, heads, PAGE, pool_head_dim(d))
            held = (pages - 1) // lanes         # pages a lane owns
            table = np.zeros((lanes, SLOTS), np.int32)
            table[:, :held] = 1 + rng.permutation(lanes * held).reshape(
                lanes, held)
            kv_len = rng.integers(1, held * PAGE - s, lanes)
            kv_len[rng.permutation(lanes)[live:]] = 0    # idle lanes
            pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
            cache = PagedKVCache(pool, pool, jnp.asarray(table),
                                 jnp.asarray(kv_len, jnp.int32))
            page, off = cache._token_dest(cache.kv_len, lanes, s)
            # the pages written, the null page left out
            some = page.max()
            written = jnp.unique(jnp.where(page == 0, some, page),
                                 size=min(pages, 2 * lanes), fill_value=some)
            keys = jax.random.split(jax.random.PRNGKey(1), 2)
            k_new, v_new = (jax.random.normal(
                key, (lanes, s, heads, d), jnp.float32).astype(jnp.bfloat16)
                for key in keys)
            row = {"cell": cell, "pool": list(shape), "lanes": lanes,
                   "live_lanes": live, "positions": s,
                   "rows_a_lane": heads * s, "device": dev.device_kind}
            left = {}
            for name, fn in (("scatter", scatter_pass),
                             ("kernel", kernel_pass)):
                run = jax.jit(fn, donate_argnums=(0, 1))
                k, v = run(make(shape, 0), make(shape, 1), page, off,
                           k_new, v_new)
                left[name] = witness(k, v, written)
                best = float("inf")
                for _ in range(REPEATS):
                    t = time.perf_counter()
                    for _ in range(CALLS):
                        k, v = run(k, v, page, off, k_new, v_new)
                    jax.block_until_ready((k, v))
                    best = min(best, (time.perf_counter() - t) / CALLS)
                row[f"{name}_us_a_layer"] = best * 1e6 / len(passes(layers))
                del k, v
            row["bit_equal"] = all(
                same_bits(a, b)
                for a, b in zip(left["scatter"], left["kernel"]))
            del left
            row["kernel_wins"] = (row["kernel_us_a_layer"]
                                  < row["scatter_us_a_layer"])
            row["supports"] = pw.supports(shape, jnp.bfloat16, s)
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"kv_write_bench: needs a TPU; JAX's first device is "
                 f"{dev.platform!r}; nothing run")
    out = os.path.join(REPO, "chiprun_out", "kv_write_bench.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    bench(SHAPES, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
