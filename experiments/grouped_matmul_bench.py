"""The experts' grouped product at the serving cells' real shapes: XLA's
``ragged_dot``, ``ragged_dot`` on row chunks of 1,024, jax's
``megablox.gmm`` at its default tiling and at this repo's, and
``kernels/grouped_matmul.py``. Milliseconds a call and the share of the
least time the chip allows, ``max(bytes / 819 GB/s, FLOPs / 197
TFLOP/s)``: the weights of every group that holds a row once, ``lhs``
once, the float32 result once; ``2 M K N`` FLOPs.

Needs the chip (a time from the CPU is no device number):

    chiprun -- python experiments/grouped_matmul_bench.py
    ... --products sdar_gate_up,lfm2_gate_up --rows 4096,512 --tiles

Writes ``chiprun_out/grouped_matmul_bench.jsonl`` (one line a timing)
and prints the table PERF.md section 5 holds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

from paddle_tpu.kernels import grouped_matmul as gm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmarks", "peaks.json")) as _f:
    PEAKS = json.load(_f)       # by device_kind; one not listed raises
# name -> (experts, K, N): the two products of each MoE cell's expert
# layer (benchmarks/configs/sdar-30b-a3b-l6.json, lfm2-8b-a1b-l14.json)
PRODUCTS = {
    "sdar_gate_up": (128, 2048, 1536),
    "sdar_down": (128, 768, 2048),
    "lfm2_gate_up": (32, 2048, 3584),
    "lfm2_down": (32, 1792, 2048),
}
ROWS = (512, 1024, 2048, 4096, 8192)
IMBALANCE = 2.2             # expert_load_imbalance.serve reads 2.1-2.3
CHUNK = 1024
CALLS, REPEATS = 20, 3


def group_sizes(m: int, e: int, pattern: str, seed: int = 0):
    """``m`` rows over ``e`` groups. ``skewed``: a multinomial whose
    log-probabilities are normal, their spread chosen so that the
    busiest group holds ``IMBALANCE`` times the mean; ``quarter``: the
    same over a seeded quarter of the groups, the others empty."""
    rng = np.random.default_rng(seed)
    live = e if pattern == "skewed" else e // 4
    noise = rng.standard_normal(live)
    best = None
    for spread in np.linspace(0.0, 2.0, 41):
        p = np.exp(spread * noise)
        sizes = np.random.default_rng(seed + 1).multinomial(m, p / p.sum())
        off = abs(sizes.max() * live / m - IMBALANCE)
        if best is None or off < best[0]:
            best = (off, sizes)
    out = np.zeros(e, np.int32)
    out[rng.permutation(e)[:live] if live < e else np.arange(e)] = best[1]
    return out


def least_seconds(sizes, m, k, n, peak):
    hit = int((sizes > 0).sum())
    nbytes = hit * k * n * 2 + m * k * 2 + m * n * 4
    return max(nbytes / peak["hbm_bytes_per_s"],
               2 * m * k * n / peak["flops_per_s"]["bfloat16"])


def ragged_dot(lhs, rhs, sizes):
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)


def ragged_dot_chunked(lhs, rhs, sizes):
    """XLA's own op on chunks of ``CHUNK`` rows, each with the group
    sizes clipped to the rows the chunk holds."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    out = []
    for lo in range(0, lhs.shape[0], CHUNK):
        hi = min(lo + CHUNK, lhs.shape[0])
        clipped = jnp.clip(ends, lo, hi) - jnp.clip(starts, lo, hi)
        out.append(ragged_dot(lhs[lo:hi], rhs, clipped))
    return jnp.concatenate(out) if len(out) > 1 else out[0]


def implementations(k, n, tiles):
    # megablox sets no VMEM limit of its own: a weight block of 4 MB,
    # double buffered, is what the default 16 MB leaves room for
    tn = gm.col_tile(k, n, limit=4 << 20)
    impls = {
        "ragged_dot": ragged_dot,
        "ragged_dot_chunks": ragged_dot_chunked,
        "megablox_default": lambda a, b, s: megablox_gmm(a, b, s),
        "megablox_tiled": lambda a, b, s: megablox_gmm(
            a, b, s, tiling=(min(128, a.shape[0]), k, tn)),
        "grouped_matmul": gm.grouped_matmul,
    }
    for tm, cols in tiles:
        if n % cols:
            continue
        impls[f"grouped_matmul_tm{tm}_tn{cols}"] = (
            lambda a, b, s, tm=tm, cols=cols: gm._pallas(
                a, b, s, tiles=(tm, cols)))
    return impls


def time_call(fn, *args):
    fn(*args).block_until_ready()            # compile, warm
    best = None
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / CALLS
        best = dt if best is None else min(best, dt)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--products", default=",".join(PRODUCTS))
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--patterns", default="skewed,quarter")
    ap.add_argument("--only", default="", help="implementations, by name")
    ap.add_argument("--tiles", default="",
                    help="further (row tile x column tile) variants of "
                         "the kernel, e.g. 64x1536,256x768")
    ap.add_argument("--out", default="chiprun_out/grouped_matmul_bench.jsonl")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU: the first device is {dev.platform}")
    tiles = [tuple(map(int, t.split("x"))) for t in args.tiles.split(",")
             if t]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    lines = []
    for name in args.products.split(","):
        e, k, n = PRODUCTS[name]
        rhs = jax.random.normal(jax.random.PRNGKey(1), (e, k, n),
                                jnp.bfloat16) * 0.02
        for m in map(int, args.rows.split(",")):
            lhs = jax.random.normal(jax.random.PRNGKey(2), (m, k),
                                    jnp.bfloat16)
            for pattern in args.patterns.split(","):
                sizes = group_sizes(m, e, pattern)
                least = least_seconds(sizes, m, k, n,
                                      PEAKS[dev.device_kind])
                dsizes = jnp.asarray(sizes)
                want = None
                for impl, fn in implementations(k, n, tiles).items():
                    if args.only and impl not in args.only.split(","):
                        continue
                    if "_tm" in impl and m % int(
                            impl.split("_tm")[1].split("_")[0]):
                        continue
                    line = {"product": name, "experts": e, "k": k, "n": n,
                            "rows": m, "pattern": pattern, "impl": impl,
                            "groups_hit": int((sizes > 0).sum()),
                            "imbalance": round(float(
                                sizes.max() * (sizes > 0).sum() / m), 3),
                            "least_ms": least * 1e3,
                            "device": dev.device_kind}
                    try:
                        jitted = jax.jit(fn)
                        got = jitted(lhs, rhs, dsizes)
                        if want is None:
                            want = got
                        line["max_abs_diff"] = float(
                            jnp.max(jnp.abs(got - want)))
                        seconds = time_call(jitted, lhs, rhs, dsizes)
                        line["ms"] = seconds * 1e3
                        line["roofline_share"] = least / seconds
                    except Exception as err:  # a tiling the chip refuses
                        line["error"] = f"{type(err).__name__}: " \
                            f"{str(err)[:300]}"
                    lines.append(line)
                    with open(args.out, "a") as f:
                        f.write(json.dumps(line) + "\n")
                    print(json.dumps(line), flush=True)
    print(table(lines))


def table(lines):
    """One row a (product, rows, pattern), one column an implementation:
    ``ms (share of the least time)``."""
    impls = list(dict.fromkeys(ln["impl"] for ln in lines))
    rows = ["| product | rows | groups | least ms | "
            + " | ".join(impls) + " |",
            "|---|---|---|---|" + "---|" * len(impls)]
    keys = dict.fromkeys((ln["product"], ln["rows"], ln["pattern"])
                         for ln in lines)
    for key in keys:
        cells = {ln["impl"]: ln for ln in lines
                 if (ln["product"], ln["rows"], ln["pattern"]) == key}
        any_ = next(iter(cells.values()))
        row = [key[0], str(key[1]),
               f"{key[2]} ({any_['groups_hit']} of {any_['experts']})",
               f"{any_['least_ms']:.3f}"]
        for impl in impls:
            ln = cells.get(impl)
            row.append("-" if ln is None else "refused" if "ms" not in ln
                       else f"{ln['ms']:.3f} "
                            f"({100 * ln['roofline_share']:.0f}%)")
        rows.append("| " + " | ".join(row) + " |")
    return "\n".join(rows)


if __name__ == "__main__":
    main()
