#!/usr/bin/env python3
"""What the flight recorder's pieces cost on this host, in µs a call
(ISSUE 36, PERF.md section 6): a span on and off, the compare and store
of the boundary stamp, the watcher's look at one thread, a ``gc``
callback pair.  The least of many short batches.  Touches no device.

    python experiments/recorder_cost.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from paddle_tpu.core import flight_recorder as fr  # noqa: E402


def cpu_us(fn, n=2000, batches=50):
    """The least of many short batches on the wall clock (the chip
    machine's thread CPU clock ticks too coarsely to time 2,000 calls)."""
    best = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    return best


def span():
    with fr.span("serve.sync", site="poll", steps_queued=3) as sp:
        sp.set(emitted=1)


def main():
    out = {}
    fr.configure(capacity=fr.DEFAULT_CAPACITY, on=True)
    with fr.span("serve.step"):         # arms the watcher and the callback
        pass
    out["span_on_us"] = cpu_us(span)
    st = fr._tls.st
    now_ns, stall_ns = fr.now_ns, fr.STALL_NS

    def stamp_only():
        now_ns()

    def stamp_compare_store():
        t = now_ns()
        if t - st.stamp > stall_ns:
            pass
        st.stamp = t

    out["boundary_compare_and_store_us"] = \
        cpu_us(stamp_compare_store) - cpu_us(stamp_only)
    watch = fr._watch
    with fr.span("serve.step"):
        # a thread inside an iteration, not silent: what a wake-up reads
        out["watcher_look_us"] = cpu_us(
            lambda: watch._look(st, now_ns(), 0))
    info = {"generation": 0, "collected": 0, "uncollectable": 0}

    def pair():
        fr._on_gc("start", info)
        fr._on_gc("stop", info)

    out["gc_callback_pair_us"] = cpu_us(pair)
    fr.disable()
    out["span_off_us"] = cpu_us(span)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
