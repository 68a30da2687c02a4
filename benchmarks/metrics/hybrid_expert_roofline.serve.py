"""Roofline share of the experts' grouped products in a model whose layers
are not all expert layers: the least time the chip could take for every
EXPERT layer of every step of the traced window
(``work_<family>.expert_layer``: the weights of every expert hit read
once and 6 * rows * H * F FLOPs; rows from the program's ``moe.rows``
counter over the traced part, spread over the layers that have experts,
``layer_counts(cfg)["moe"]``) over the summed device time of the
grouped-product kernels (``ragged-dot``) inside the ``step`` program (the
family's ``KERNEL_CLASSES``, reduced by the driver), in percent.  The
bound is printed on standard error."""
import sys


def read(run):
    kernel_s = (run.records.get("kernel_class_s") or {}).get("moe_expert")
    seen = run.records.get("counters_in_trace") or {}
    red = run.reduced
    prog = red and red.program(run.family.PROGRAMS["decode_step"])
    work = getattr(run, "family_work", None)    # the driver's, by family
    if not kernel_s or not seen.get("moe.rows") or not prog or not work:
        return None
    cfg = run.cfg
    # rows of one expert layer of one step; the trace may hold a step
    # more or fewer than the host counted, so scale by the steps it holds
    steps = max(run.records["steps_in_trace"], 1)
    layers = work.layer_counts(cfg)["moe"]
    rows = seen["moe.rows"] / steps / layers
    flops, nbytes = work.expert_layer(int(round(rows)), cfg)
    n = len(prog["durations_s"]) * layers
    least, bound = run.work.roofline_seconds(flops * n, nbytes * n,
                                             run.peaks, cfg["dtype"])
    print(f"hybrid_expert_roofline.serve: bound by {bound}, least "
          f"{least * 1e3:.2f} ms over {len(prog['durations_s'])} steps "
          f"({rows:.0f} rows in each of {layers} expert layers), kernels "
          f"{kernel_s * 1e3:.2f} ms", file=sys.stderr)
    return 100.0 * least / kernel_s
