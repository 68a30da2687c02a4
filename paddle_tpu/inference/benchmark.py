"""Inference benchmarking utilities — the analog of the reference's
paddle/fluid/inference/utils/benchmark.h (Benchmark: name/batch_size/
latency bookkeeping + report) plus a TPU-specific device-time
extractor.

Wall-clocking pred.run() counts the host's dispatch and transfer with
the predictor. `device_time_per_run` leaves them out: it compiles ONE
program that runs the predict function
N times in a dependent lax.scan chain (each iteration's input is tied
to the previous output so XLA cannot collapse the loop), times the
single dispatch at two different N, and takes the slope — the fixed
dispatch/transfer cost cancels exactly, leaving pure device time per
inference."""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Benchmark", "device_time_per_run"]


def device_time_per_run(predictor, inputs: Sequence[np.ndarray],
                        iters: Sequence[int] = (8, 40),
                        repeats: int = 3) -> float:
    """Seconds of DEVICE time per predictor.run(inputs), measured by
    the two-point scan-slope method described in the module docstring.
    Works with any Predictor (layer- or artifact-built): the traced
    body goes through the same _run_fn the serving path executes."""
    feeds = tuple(jnp.asarray(a) for a in inputs)
    if not any(jnp.issubdtype(f.dtype, jnp.floating) for f in feeds):
        raise ValueError("device_time_per_run needs at least one "
                         "floating input to carry the loop dependency")

    def body(carry, _):
        outs = predictor._run_fn(list(carry))
        tie = sum(jnp.sum(o).astype(jnp.float32)
                  for o in outs
                  if jnp.issubdtype(jnp.asarray(o).dtype, jnp.floating))
        new = []
        tied = False
        for f in carry:
            if not tied and jnp.issubdtype(f.dtype, jnp.floating):
                new.append(f * (1 + 0 * tie).astype(f.dtype))
                tied = True
            else:
                new.append(f)
        return tuple(new), ()

    times = {}
    for n in iters:
        fn = jax.jit(lambda f, n=n: jax.lax.scan(
            body, f, None, length=n)[0])
        out = fn(feeds)  # compile + warm
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = fn(feeds)
            jax.tree_util.tree_map(
                lambda x: x.block_until_ready(), out)
            best = min(best, time.perf_counter() - t0)
        times[n] = best
    n_lo, n_hi = min(iters), max(iters)
    if n_hi == n_lo:
        raise ValueError("need two distinct iteration counts")
    return max((times[n_hi] - times[n_lo]) / (n_hi - n_lo), 0.0)


class Benchmark:
    """Latency/QPS bookkeeping, mirroring the reference Benchmark
    (inference/utils/benchmark.h:1): set name/batch_size, record
    latency, emit a one-line report."""

    def __init__(self, name: str = "", batch_size: int = 1):
        self.name = name
        self.batch_size = batch_size
        self.latency_ms: Optional[float] = None
        self._records: List[float] = []

    def set_name(self, name: str):
        self.name = name

    def set_batch_size(self, batch_size: int):
        self.batch_size = batch_size

    def record(self, seconds: float):
        self._records.append(seconds)
        self.latency_ms = float(np.mean(self._records)) * 1e3

    def measure(self, predictor, inputs, **kw):
        """Record the device-time-per-run of a predictor."""
        self.record(device_time_per_run(predictor, inputs, **kw))
        return self.latency_ms

    @property
    def qps(self) -> Optional[float]:
        if not self.latency_ms:
            return None
        return self.batch_size / (self.latency_ms / 1e3)

    def report(self) -> str:
        lat = f"{self.latency_ms:.3f} ms" if self.latency_ms else "n/a"
        qps = f"{self.qps:.1f}" if self.qps else "n/a"
        line = (f"[benchmark] name={self.name} batch={self.batch_size} "
                f"latency={lat} qps={qps}")
        print(line)
        return line
