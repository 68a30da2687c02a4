#!/usr/bin/env python3
"""Hunt the scheduler's stall (ISSUE 36): run serving cells of the
benchmark untraced, one process a run, and keep of each run what the
flight recorder can say about its longest iteration.

    chiprun --timeout 3600 -- python experiments/stall_hunt.py \
        --runs sdar-l6-offline:9,gpt3l8-chat:9 --cold 2 --seed0 2147483900

``--runs cell:n,...`` makes n runs of each cell, each with a seed of its
own; the first ``--cold`` runs of a cell get an empty
``JAX_COMPILATION_CACHE_DIR`` each (they compile in-process), the others
share the checkout's cache.  The parent never touches JAX.  Each run
(``--one``) calls ``benchmarks/run.py``'s ``main`` in-process, then
writes ``chiprun_out/hunt/<cell>.<seed>.json``: the result line's
end-to-end numbers, the span readers that need no device trace, every
``serve.stall`` event with where it fell (set-up, window, grace), the
tree of the iteration it lay in, and the ring's event count by name.

Who else stood still?  A canary thread in the run's process and one in
the parent (which holds no chip and shares only the machine) each sleep
10 ms at a time and note every oversleep past 30 ms on CLOCK_MONOTONIC:
a stall the run's canary slept through as well held the interpreter or
the process, one the parent's slept through too held the machine or its
CPU allowance.  The run also notes what the kernel charged it across
itself: the cgroup's throttled time, the machine's steal and the CPU
pressure stall.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
OUT = os.path.join(REPO, "chiprun_out", "hunt")
READERS = ("longest_silence_ms.serve", "host_gc_ms_per_step.serve",
           "telemetry_ms_per_step.serve", "admit_host_ms.serve",
           "sched_host_ms_per_step.serve")


class Canary:
    """Sleeps 10 ms at a time; keeps (when it should have woken in ns on
    CLOCK_MONOTONIC, ms overslept) of every oversleep past 30 ms."""

    def __init__(self):
        self.late = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="hunt-canary")
        self._thread.start()

    def _loop(self):
        while not self._stop.is_set():
            t0 = time.monotonic_ns()
            time.sleep(0.01)
            over = (time.monotonic_ns() - t0) / 1e6 - 10.0
            if over > 30.0:
                self.late.append((t0 + 10_000_000, round(over, 1)))

    def stop(self):
        self._stop.set()
        self._thread.join(2.0)
        return self.late


def kernel_counters() -> dict:
    """What the kernel has charged this cgroup and machine so far."""
    out = {}
    for path, keys in (("/sys/fs/cgroup/cpu.stat",
                        ("nr_throttled", "throttled_usec", "usage_usec")),
                       ("/sys/fs/cgroup/cpu/cpu.stat",
                        ("nr_throttled", "throttled_time")),
                       ("/sys/fs/cgroup/cpu,cpuacct/cpu.stat",
                        ("nr_throttled", "throttled_time"))):
        try:
            with open(path) as f:
                for line in f:
                    k, _, v = line.partition(" ")
                    if k in keys:
                        out[k] = int(v)
        except OSError:
            pass
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        out["steal_jiffies"] = int(cpu[8])
    except (OSError, IndexError, ValueError):
        pass
    try:
        with open("/proc/pressure/cpu") as f:
            for line in f:
                kind, *rest = line.split()
                out[f"psi_cpu_{kind}_total_us"] = int(
                    dict(r.split("=") for r in rest)["total"])
    except (OSError, KeyError, ValueError):
        pass
    return out


def time_telemetry() -> dict:
    """Wrap each statement of the poll's telemetry in a clock (one run,
    by hand: ROADMAP Speed 11 wants to know which of them costs)."""
    from paddle_tpu.core import goodput, monitor, slo
    from paddle_tpu.serving import engine
    spent = {}

    def timed(owner, name):
        fn = getattr(owner, name)

        def wrapper(*a, **kw):
            t0 = time.perf_counter_ns()
            try:
                return fn(*a, **kw)
            finally:
                rec = spent.setdefault(name, [0, 0])
                rec[0] += 1
                rec[1] += time.perf_counter_ns() - t0
        setattr(owner, name, wrapper)

    for owner, names in (
            (monitor, ("record_serve_token_latency",
                       "record_serve_slot_occupancy",
                       "record_cache_occupancy")),
            (goodput.GoodputLedger, ("charge", "flush")),
            (engine.ServingEngine, ("_charge_window", "_drain_page_stats",
                                    "_drain_quant_stats")),
            (slo, ("tick",))):
        for name in names:
            timed(owner, name)
    return spent


def one(cell: str, seed: int, seconds: float, tag: str, spec,
        telemetry: bool = False) -> int:
    sys.path.insert(0, BENCH)
    import run as bench_run
    spent = time_telemetry() if telemetry else None
    canary, kernel0 = Canary(), kernel_counters()
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0"]
                        + (["--spec", spec] if spec else []))
    from paddle_tpu.core import flight_recorder as fr
    import common
    late, kernel1 = canary.stop(), kernel_counters()
    run = bench_run.main.last["run"]
    t_open = int((run.t_proc + run.setup_s) * 1e9)
    t_close = t_open + int(run.window_s * 1e9)
    events = fr.events()
    counts = {}
    for _, kind, f in events:
        name = f["name"] if kind == "span" and f else kind
        if name.startswith("req"):          # a sampled request's segments
            name = "req*." + name.split(".", 1)[-1]
        counts[name] = counts.get(name, 0) + 1
    spans = fr.spans_between(0, 2 ** 62)
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)

    def tree(s, depth=0):
        out = [{"depth": depth, "name": s.name,
                "start_ms": (s.start_ns - t_open) / 1e6,
                "ms": (s.end_ns - s.start_ns) / 1e6, **s.fields}]
        for c in sorted(kids.get(s.id, ()), key=lambda c: c.start_ns):
            out += tree(c, depth + 1)
        return out

    stalls = []
    for t, kind, f in events:
        if kind != "serve.stall":
            continue
        t0 = t - int(f["ms"] * 1e6)
        part = "setup" if t0 < t_open else \
            "window" if t0 < t_close else "grace"
        # the iteration it lay in: the serve.step that covers it
        step = next((s for s in spans if s.name == "serve.step"
                     and s.start_ns <= t0 and s.end_ns >= t), None)
        stalls.append({"part": part, "at_s": (t0 - t_open) / 1e9,
                       "t0_ns": t0, "t1_ns": t, **f,
                       # the run's own canary: asleep through it too?
                       "canary_late_ms": [ms for due, ms in late
                                          if t0 - 50e6 < due < t + 50e6],
                       "iteration": tree(step) if step is not None
                       and part != "setup" else None})
    metrics = {}
    for name in READERS:
        reader = common.load_module(
            os.path.join(BENCH, "metrics", name + ".py"),
            "metric_" + name.replace(".", "_"))
        metrics[name] = reader.read(run)
    os.makedirs(OUT, exist_ok=True)
    rec = {"cell": cell, "seed": seed, "tag": tag, "rc": rc,
           "correct": bench_run.main.last["correct"],
           "e2e": run.e2e, "setup_s": run.setup_s,
           "engine_step_ms": run.notes.get("engine_step_ms"),
           "metrics": metrics, "stalls": stalls,
           "t_open_ns": t_open,
           # calls and ms of each telemetry statement, whole process
           "telemetry_statements": spent and {
               k: [n, ns / 1e6] for k, (n, ns) in spent.items()},
           "canary_late": [((due - t_open) / 1e9, ms) for due, ms in late
                           if due >= t_open],
           "kernel": {k: kernel1[k] - kernel0.get(k, 0) for k in kernel1},
           "events": len(events), "capacity": fr.capacity(),
           "dropped_since_t_proc": fr.dropped_since(int(run.t_proc * 1e9)),
           "events_by_name": dict(sorted(counts.items(),
                                         key=lambda kv: -kv[1]))}
    with open(os.path.join(OUT, f"{cell}.{seed}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    window = [s for s in stalls if s["part"] == "window"]
    print(f"hunt {cell} seed {seed} {tag}: e2e {run.e2e} "
          f"longest_silence {metrics['longest_silence_ms.serve']} "
          f"events {len(events)} window stalls "
          f"{[(s['ms'], s['span'], s.get('site'), s.get('program'), s['gc_ms'], s.get('samples'), s.get('late_ms'), s.get('cpu_ms'), s.get('thread_cpu_ms'), s['canary_late_ms'], s.get('top')) for s in window]} "
          f"kernel {rec['kernel']}",
          flush=True)
    return rc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", default="sdar-l6-offline:1")
    ap.add_argument("--cold", type=int, default=0)
    ap.add_argument("--seed0", type=int, default=2147483900)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--one", default=None, help="cell: run it here")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tag", default="warm")
    ap.add_argument("--time-telemetry", type=int, default=0,
                    help="1: the LAST run of each cell times each "
                         "telemetry statement")
    ap.add_argument("--spec", default=None,
                    help="benchmarks/rehearsal.json: rehearse on the CPU")
    args = ap.parse_args()
    if args.one:
        return one(args.one, args.seed, args.seconds, args.tag, args.spec,
                   bool(args.time_telemetry))
    os.makedirs(OUT, exist_ok=True)
    seed, rcs = args.seed0, []
    for item in args.runs.split(","):
        cell, n = item.rsplit(":", 1)
        for i in range(int(n)):
            env = dict(os.environ)
            env.pop("BENCH_RUN", None)
            tag = "warm"
            if i < args.cold:
                tag = "cold"
                d = os.path.join(REPO, ".bench_scratch",
                                 f"cold_cache_{cell}_{seed}")
                os.makedirs(d, exist_ok=True)
                env["JAX_COMPILATION_CACHE_DIR"] = d
            t0 = time.monotonic()
            canary = Canary()
            err = os.path.join(OUT, f"{cell}.{seed}.err")
            with open(err, "w") as ferr:
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), "--one",
                     cell, "--seed", str(seed), "--seconds",
                     str(args.seconds), "--tag", tag]
                    + (["--spec", args.spec] if args.spec else [])
                    + (["--time-telemetry", "1"] if args.time_telemetry
                       and i == int(n) - 1 else []),
                    cwd=REPO, env=env, stdout=subprocess.PIPE,
                    stderr=ferr, text=True, timeout=900)
            lines = proc.stdout.strip().splitlines()
            print("\n".join(l for l in lines if l.startswith("hunt "))
                  or f"hunt {cell} seed {seed}: rc {proc.returncode}, "
                  "no line", flush=True)
            # keep the end of standard error: the stall's log line, the
            # dump's path, the readers' notes
            with open(err) as f:
                tail = f.readlines()[-120:]
            with open(err, "w") as f:
                f.writelines(tail)
            # the parent's canary against the run's stalls
            late = canary.stop()
            out = os.path.join(OUT, f"{cell}.{seed}.json")
            if os.path.exists(out):
                with open(out) as f:
                    rec = json.load(f)
                rec["parent_canary_late"] = [
                    ((due - rec["t_open_ns"]) / 1e9, ms)
                    for due, ms in late if due >= rec["t_open_ns"]]
                for st in rec["stalls"]:
                    st["parent_canary_late_ms"] = [
                        ms for due, ms in late
                        if st["t0_ns"] - 50e6 < due < st["t1_ns"] + 50e6]
                with open(out, "w") as f:
                    json.dump(rec, f, indent=1)
                hits = [(s["ms"], s["parent_canary_late_ms"])
                        for s in rec["stalls"] if s["part"] == "window"]
                if hits:
                    print(f"  parent's canary through the window's "
                          f"stalls: {hits}", flush=True)
            print(f"  rc {proc.returncode} in "
                  f"{time.monotonic() - t0:.0f} s", flush=True)
            rcs.append(proc.returncode)
            seed += 1
    return int(any(rcs))


if __name__ == "__main__":
    sys.exit(main())
