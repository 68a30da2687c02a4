"""Roofline share of the paged decode-attention kernel: the least time the
chip could take to read the K and V actually valid in every row and do
4*kv_len*hidden FLOPs per row and layer (``work.paged_decode_row``), summed
over the decode steps of the traced window, over the summed device time of
the Mosaic custom calls inside those executions of the ``step`` program,
in percent.  The bound (expected: HBM) is printed on standard error."""


import sys


def read(run):
    red = run.reduced
    prog = red and red.program(run.family.PROGRAMS["decode_step"])
    attended = run.records.get("attended_in_trace")
    if not prog or not attended:
        return None
    kernel_s = sum(prog["kernel_s"])
    if kernel_s <= 0:
        return None
    cfg = run.cfg
    flops, nbytes = run.work.paged_decode_row(attended, cfg["hidden_size"])
    # the trace may hold one step more or fewer than the host counted
    scale = len(prog["durations_s"]) / max(run.records["steps_in_trace"], 1)
    least, bound = run.work.roofline_seconds(
        flops * cfg["num_layers"] * scale, nbytes * cfg["num_layers"] * scale,
        run.peaks, cfg["dtype"])
    print(f"decode_attn_roofline.serve: bound by {bound}, least "
          f"{least * 1e3:.2f} ms over {len(prog['durations_s'])} steps, "
          f"kernels {kernel_s * 1e3:.2f} ms", file=sys.stderr)
    return 100.0 * least / kernel_s
