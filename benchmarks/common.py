"""Small shared pieces of the harness: files, clocks, spans, device."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (metric readers have dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def log(*a) -> None:
    """Progress and diagnostics go to standard error; standard output
    carries the result line and nothing after it."""
    print(*a, file=sys.stderr, flush=True)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty list (q in 0..100)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


class Spans:
    """The harness's own host spans: each is a
    ``jax.profiler.TraceAnnotation`` (so it lands in the device trace's
    clock) and a (name, start, end) record on ``time.monotonic``."""

    def __init__(self):
        self.records = []
        self.names = set()

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        self.names.add(name)
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.monotonic()))


def device_memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return {}
    return {"bytes_in_use": int(stats["bytes_in_use"]),
            "peak_bytes_in_use": int(stats["peak_bytes_in_use"])}


def temp_bytes(compiled) -> int:
    """Scratch the compiler planned for one program.  The allocator's
    ``peak_bytes_in_use`` counts live buffers and leaves this out (PERF.md,
    Findings, PR 21), so a cell's peak is the allocator's peak plus the
    largest program's scratch."""
    try:
        return int(compiled.memory_analysis().temp_size_in_bytes)
    except Exception as e:  # a loaded executable may not carry the plan
        log(f"memory_analysis unavailable: {type(e).__name__}: {e}")
        return 0


def free_device() -> int:
    """Delete every live device array: the program's state goes before the
    reference runs.  Returns the bytes that were live."""
    import gc
    import jax
    gc.collect()
    n = 0
    for a in jax.live_arrays():
        try:
            n += a.nbytes
            a.delete()
        except Exception:
            pass
    gc.collect()
    return n
