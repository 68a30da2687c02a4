"""Roofline share of the experts' grouped products: the least time the
chip could take for every expert layer of every step of the traced window
(``work_moe.expert_layer``: the weights of every expert hit read once and
6 * rows * H * F FLOPs, rows from the program's ``moe.rows`` counter over
the traced part) over the summed device time of the grouped-product
kernels inside the ``step`` program (the family's ``KERNEL_CLASSES``,
reduced by the driver), in percent.  The bound is printed on standard
error."""
import sys

import work_moe


def read(run):
    kernel_s = (run.records.get("kernel_class_s") or {}).get("moe_expert")
    seen = run.records.get("counters_in_trace") or {}
    red = run.reduced
    prog = red and red.program(run.family.PROGRAMS["decode_step"])
    if not kernel_s or not seen.get("moe.rows") or not prog:
        return None
    cfg = run.cfg
    # rows of one layer of one step; the trace may hold a step more or
    # fewer than the host counted, so scale by the steps it holds
    steps = max(run.records["steps_in_trace"], 1)
    layers = cfg["num_hidden_layers"]
    rows = seen["moe.rows"] / steps / layers
    flops, nbytes = work_moe.expert_layer(int(round(rows)), cfg)
    n = len(prog["durations_s"]) * layers
    least, bound = run.work.roofline_seconds(flops * n, nbytes * n,
                                             run.peaks, cfg["dtype"])
    print(f"moe_expert_roofline.serve: bound by {bound}, least "
          f"{least * 1e3:.2f} ms over {len(prog['durations_s'])} steps "
          f"({rows:.0f} rows a layer), kernels {kernel_s * 1e3:.2f} ms",
          file=sys.stderr)
    return 100.0 * least / kernel_s
