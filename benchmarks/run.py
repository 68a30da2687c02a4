#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell: weights on the device from ``--seed``, warm-up of
that cell's shapes, a window of ``--seconds``, the comparison with the
plain reference, one JSON line last on standard output.  Everything about
the cell is data found by name: ``BENCHMARK.json``'s ``workloads`` entry
names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the traffic's ``kind`` names the driver
(``drivers/<kind>.py``); the configuration's ``family`` names the adapter
to the program (``families/<family>.py``) and the plain reference
(``reference/<family>.py``); each per-layer metric is read by
``metrics/<name>.py``; the limits of ``correct`` are
``limits/<config>.<what the driver compares>.json``.
No chip, too few chips, or a device that ``peaks.json`` does not list:
exit non-zero, print no result.
"""
from __future__ import annotations

import time
T_PROC = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "drivers"))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import common  # noqa: E402
import check  # noqa: E402
import harness  # noqa: E402


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    sys.exit(f"run.py: no {what} named {name!r}")


def applies(metric: dict, cell: dict) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


def main(argv=None, patch=None) -> int:
    """``patch(family)`` is for the fault tests under benchmarks/tests: it
    breaks the timed path underneath an otherwise whole run."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="by hand: also put the lower-precision control "
                         "(and, in training, each planted fault) in the "
                         "program's place on this run's inputs and hold "
                         "it to the cell's limits (control_correct)")
    ap.add_argument("--rate", type=float, default=None,
                    help="by hand: override an open loop's rate_per_s "
                         "(the sweep that finds the knee)")
    ap.add_argument("--in-flight", type=int, default=None,
                    help="by hand, with --rate: override the traffic's "
                         "in_flight_at_start")
    ap.add_argument("--spec", default=os.path.join(common.REPO,
                                                   "BENCHMARK.json"),
                    help="the cells file; benchmarks/rehearsal.json runs "
                         "the CPU rehearsal cells")
    args = ap.parse_args(argv)
    spec = common.load_json(args.spec)
    rehearsal = bool(spec.get("rehearsal"))
    cell = find(spec["workloads"], args.workload, "workload")
    conf = find(spec["configs"], cell["config"], "configuration")
    cfg = common.load_json(common.REPO, conf["file"])
    tr = common.load_json(HERE, "traffic", cell["traffic"] + ".json")
    if args.rate is not None:
        tr["rate_per_s"] = args.rate
    if args.in_flight is not None:
        tr["in_flight_at_start"] = args.in_flight
    seconds = float(args.seconds if args.seconds is not None
                    else spec["run_seconds"])

    # ---- the compile cache: where JAX_COMPILATION_CACHE_DIR says, else a
    # fixed directory inside the checkout (never /tmp, never a temp name)
    scratch = os.path.join(common.REPO, ".bench_scratch")
    os.makedirs(scratch, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    fam = common.load_module(
        os.path.join(HERE, "families", cfg["family"] + ".py"),
        "family_" + cfg["family"])
    ref = common.load_module(
        os.path.join(HERE, "reference", cfg["family"] + ".py"),
        "reference_" + cfg["family"])
    if patch is not None:
        patch(fam)
    fam.enable_compile_cache(None)   # the env var's, else <checkout>/.jax_cache

    # ---- the device: a chip the peaks table knows, and enough of them
    devs = jax.devices()
    dev = devs[0]
    peaks_all = common.load_json(HERE, "peaks.json")
    if rehearsal:
        peaks = peaks_all.get(dev.device_kind) or peaks_all["TPU v5e"]
    else:
        if dev.platform != "tpu":
            sys.exit(f"run.py: needs a TPU; JAX's first device is "
                     f"{dev.platform!r} ({dev.device_kind}); nothing run")
        if len(devs) < int(cell["chips"]):
            sys.exit(f"run.py: cell {cell['name']} needs {cell['chips']} "
                     f"chips, JAX sees {len(devs)}; nothing run")
        if dev.device_kind not in peaks_all:
            sys.exit(f"run.py: device kind {dev.device_kind!r} is not in "
                     "benchmarks/peaks.json; nothing run")
        peaks = peaks_all[dev.device_kind]

    driver = common.load_module(
        os.path.join(HERE, "drivers", tr["kind"] + ".py"),
        "driver_" + tr["kind"])
    lim = common.load_json(HERE, "limits",
                           f"{cell['config']}.{driver.COMPARES}.json")
    limits = lim["limits"]
    run = harness.Run(cell=cell, cfg=cfg, traffic=tr, limits=limits,
                      peaks=peaks, family=fam, ref=ref, seed=args.seed,
                      seconds=seconds, traced=bool(args.trace),
                      t_proc=T_PROC, scratch=scratch, rehearsal=rehearsal,
                      control=bool(args.control))
    driver.run(run)
    run.e2e["setup_s"] = run.setup_s

    # ---- the metrics this cell reports
    metrics = {}
    if args.trace:
        import work
        run.work = work
        for m in spec["per_layer"]:
            if not applies(m, cell):
                continue
            reader = common.load_module(
                os.path.join(HERE, "metrics", m["name"] + ".py"),
                "metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            if applies(m, cell) and m["name"] in run.e2e:
                metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                      "unit": m["unit"]}
    skip = lim.get("not_compared", ())
    rows, correct = check.verdict(run.compared, limits, skip)
    correct = correct and bool(run.compared)
    # by hand (--control 1): whatever was put in the program's place is
    # held to the same limits, and has to come out as not correct
    stand_ins = {name: check.verdict(numbers, limits, skip)
                 for name, numbers in run.stand_ins.items()}
    if stand_ins:
        run.notes["stand_ins"] = {k: v[0] for k, v in stand_ins.items()}
        run.notes["control_correct"] = {k: v[1]
                                        for k, v in stand_ins.items()}
    line = harness.result_line(run, metrics, correct, rows, dev,
                               int(cell["chips"]) if not rehearsal
                               else len(devs))
    harness.print_compared(rows, correct)
    print(line, flush=True)
    main.last = {"correct": correct, "rows": rows, "run": run,
                 "control_correct": run.notes.get("control_correct")}
    return 0


if __name__ == "__main__":
    sys.exit(main())
