"""The decode-time selective state update at the cell's real shapes
(``nemotron3n-l13-offline``: 6 layers x 256 lanes x 64 heads x 64 x 128
float32 = 3.2 GB of state): ``kernels/ssm_update.py`` against XLA's
fusion of the same arithmetic (``ssm_update_reference`` written back with
``.at[layer].set``, the path everywhere but on a TPU), each as ONE jitted
pass over the six layers with the stacked state donated.  Milliseconds a
layer and the share of the least time the chip allows (the live lanes'
state read and written once at 819 GB/s), at every lane live and at half
of them idle.

Needs the chip (a time from the CPU is no device number):

    chiprun -- python experiments/ssm_update_bench.py

Writes ``chiprun_out/ssm_update_bench.jsonl`` (one line a timing).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import ssm_update as su

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmarks", "peaks.json")) as _f:
    PEAKS = json.load(_f)       # by device_kind; one not listed raises
LAYERS, LANES, HEADS, P, N, GROUPS = 6, 256, 64, 64, 128, 8
CALLS, REPEATS = 20, 3


def kernel_pass(state, x, dt, a, b, c, live):
    ys = []
    for layer in range(LAYERS):
        y, state = su.ssm_update(state, layer, x, dt, a, b, c, live)
        ys.append(y)
    return sum(ys), state


def fusion_pass(state, x, dt, a, b, c, live):
    ys = []
    dt = jnp.where(live[:, None], dt, 0.0)
    for layer in range(LAYERS):
        y, new = su.ssm_update_reference(state[layer], x, dt, a, b, c)
        state = state.at[layer].set(new)
        ys.append(y)
    return sum(ys), state


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"ssm_update_bench: needs a TPU; JAX's first device is "
                 f"{dev.platform!r}; nothing run")
    bw = PEAKS[dev.device_kind]["hbm_bytes_per_s"]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[1], (LANES, HEADS, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (LANES, HEADS)) - 3.0)
    a = -jnp.exp(jax.random.normal(ks[3], (HEADS,)))
    b = jax.random.normal(ks[4], (LANES, GROUPS, N))
    c = jax.random.normal(ks[5], (LANES, GROUPS, N))
    out = os.path.join(REPO, "chiprun_out", "ssm_update_bench.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        for lanes_live in (LANES, LANES // 2):
            live = (jnp.arange(LANES) % (LANES // lanes_live)) == 0
            least = 2.0 * lanes_live * HEADS * P * N * 4 / bw
            for name, fn in (("kernel", kernel_pass),
                             ("xla_fusion", fusion_pass)):
                run = jax.jit(fn, donate_argnums=(0,))
                state = jax.random.normal(ks[0], (LAYERS, LANES, HEADS, P,
                                                  N))
                y, state = run(state, x, dt, a, b, c, live)
                jax.block_until_ready(state)
                best = float("inf")
                for _ in range(REPEATS):
                    t = time.perf_counter()
                    for _ in range(CALLS):
                        y, state = run(state, x, dt, a, b, c, live)
                    jax.block_until_ready((y, state))
                    best = min(best, (time.perf_counter() - t) / CALLS)
                row = {"path": name, "live_lanes": lanes_live,
                       "ms_a_layer": best * 1e3 / LAYERS,
                       "least_ms_a_layer": least * 1e3,
                       "roofline_pct": 100.0 * least * LAYERS / best,
                       "device": dev.device_kind}
                print(json.dumps(row), flush=True)
                f.write(json.dumps(row) + "\n")
                del state
    return 0


if __name__ == "__main__":
    sys.exit(main())
