"""One run of one cell: the record the drivers fill and the metric readers
read, the traced sub-window, and the result line."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

import common
import trace as trace_mod

TRACE_SECONDS = 5.0     # the traced part of a --trace 1 window, at its end


@dataclass
class Run:
    cell: dict                  # the workloads entry
    cfg: dict                   # configs/<config>.json
    traffic: dict               # traffic/<traffic>.json
    limits: dict                # limits/<config>.<compares>.json
    peaks: dict                 # peaks.json[device_kind]
    family: object              # families/<family>.py
    ref: object                 # reference/<family>.py
    seed: int
    seconds: float
    traced: bool
    t_proc: float               # time.monotonic() at process start
    scratch: str                # a directory inside the checkout
    rehearsal: bool = False
    control: bool = False       # by hand: read the control as well
    spans: common.Spans = field(default_factory=common.Spans)
    # ---- filled by the driver
    setup_s: float = 0.0
    window_s: float = 0.0
    e2e: dict = field(default_factory=dict)       # name -> value
    counters: dict = field(default_factory=dict)  # deltas over the window
    records: dict = field(default_factory=dict)   # driver-specific facts
    attempted: int = 0
    failed: int = 0
    compared: dict = field(default_factory=dict)  # name -> value
    # --control 1: {"control" | fault: {name -> value}} of what was put in
    # the program's place
    stand_ins: dict = field(default_factory=dict)
    notes: dict = field(default_factory=dict)
    memory: dict = field(default_factory=dict)
    reduced: object = None                        # trace.Reduced
    work: object = None                           # work.py, for readers
    _tracing: object = None

    # ------------------------------------------------------- shared steps
    def fill_weights(self, model) -> None:
        """Weights from ``--seed``, made on the device in one jitted call in
        the type they are served in, laid out as the program wants them."""
        import jax
        import jax.numpy as jnp
        fam, ref, cfg = self.family, self.ref, self.cfg
        dtype = jnp.dtype(cfg["dtype"])
        fam.set_weights(model, jax.jit(lambda k: fam.program_layout(
            ref.make_params(cfg, k, dtype), cfg))(ref.seed_key(self.seed)))

    def read_memory(self, program_temp_bytes: int) -> None:
        """The cell's peak: the allocator's peak plus the largest program's
        scratch, which the allocator leaves out (PERF.md, PR 21)."""
        import jax
        mem = common.device_memory(jax.devices()[0])
        if mem:
            self.memory = {
                "memory_peak_bytes":
                    mem["peak_bytes_in_use"] + program_temp_bytes,
                "allocator_peak_bytes": mem["peak_bytes_in_use"],
                "program_temp_bytes": program_temp_bytes}

    # ---------------------------------------------- for the metric readers
    def program_median_ms(self, which: str):
        """Median device duration (ms) of one execution of the program the
        family calls ``which``; None where the trace has none."""
        import statistics
        prog = self.reduced and self.reduced.program(
            self.family.PROGRAMS[which])
        if not prog or not prog["durations_s"]:
            return None
        return statistics.median(prog["durations_s"]) * 1e3

    def idle_share_pct(self):
        red = self.reduced
        if red is None or not red.devices or red.window_s <= 0:
            return None
        return 100.0 * (1.0 - red.busy_s / red.window_s)

    # ------------------------------------------------------------ tracing
    def trace_due(self, t_open: float, now: float) -> bool:
        """True once, when a traced run reaches the last TRACE_SECONDS of
        its window: the caller then calls :meth:`trace_start`."""
        return (self.traced and self._tracing is None
                and now >= t_open + self.seconds
                - min(self.seconds, TRACE_SECONDS))

    def trace_start(self) -> None:
        import jax
        d = os.path.join(self.scratch, "trace")
        shutil.rmtree(d, ignore_errors=True)
        jax.profiler.start_trace(d)
        ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        ann.__enter__()
        self._tracing = (d, ann, time.monotonic())

    def trace_stop(self) -> None:
        """Close the traced window, reduce the trace, delete it."""
        import jax
        if self._tracing is None:
            return
        d, ann, t0 = self._tracing
        ann.__exit__(None, None, None)
        self.records["trace_host_s"] = time.monotonic() - t0
        jax.profiler.stop_trace()
        t1 = time.monotonic()
        self.reduced, planes = trace_mod.reduce_trace(
            d, self.spans.names, self.family.KERNEL_OP)
        common.log(f"trace reduced in {time.monotonic() - t1:.1f} s: "
                   f"window {self.reduced.window_s:.3f} s, busy "
                   f"{self.reduced.busy_s:.3f} s, programs "
                   f"{ {k: len(v['durations_s']) for k, v in self.reduced.programs.items()} }")
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:   # by hand only: a copy of the reduction's input
            os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
            trace_mod.save_planes(planes, keep)
            trace_mod.dump_structure(trace_mod.find_xplane(d),
                                     keep + ".structure.txt")
        shutil.rmtree(d, ignore_errors=True)
        self._tracing = None


def device_block(run: Run, dev, count: int) -> dict:
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": count}
    out.update(run.memory)
    if run.reduced is not None and run.reduced.devices:
        out["busy_s"] = run.reduced.busy_s
        out["window_s"] = run.reduced.window_s
    return out


def result_line(run: Run, metrics: dict, correct: bool, rows: dict,
                dev, count: int) -> str:
    line = {"correct": bool(correct), "attempted": int(run.attempted),
            "failed": int(run.failed), "metrics": metrics,
            "device": device_block(run, dev, count)}
    if run.reduced is not None and run.reduced.devices:
        line["breakdown"] = {"device_ops": run.reduced.device_ops,
                             "idle_gaps": run.reduced.idle_gaps}
    line["notes"] = run.notes
    line["compared"] = rows          # last: each number beside its limit
    return json.dumps(line)


def print_compared(rows: dict, correct: bool) -> None:
    for name, row in rows.items():
        print(f"compared {name} value={row['value']!r} "
              f"limit={row['limit']!r}", file=sys.stderr)
    print(f"correct={correct}", file=sys.stderr, flush=True)
