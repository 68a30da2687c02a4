"""Roofline share of the block attention (the paged decode kernel under
``window_causal=False``): the least time the chip could take to read the
valid K and V of the kv heads once a lane and do 4 * B * kv_len * (query
heads x head_dim) FLOPs a lane and layer (``work_moe.block_attention``),
over the lane-forwards of the traced window, over the summed device time
of that kernel inside the ``step`` program, in percent.  The bound
(expected: HBM) is printed on standard error."""
import sys

import work_moe


def read(run):
    kernel_s = (run.records.get("kernel_class_s") or {}).get("block_attn")
    attended = run.records.get("lane_attended_in_trace")
    red = run.reduced
    prog = red and red.program(run.family.PROGRAMS["decode_step"])
    if not kernel_s or not attended or not prog:
        return None
    cfg = run.cfg
    scale = len(prog["durations_s"]) / max(run.records["steps_in_trace"], 1)
    flops, nbytes = work_moe.block_attention(attended * scale, cfg)
    layers = cfg["num_hidden_layers"]
    least, bound = run.work.roofline_seconds(
        flops * layers, nbytes * layers, run.peaks, cfg["dtype"])
    print(f"block_attn_roofline.serve: bound by {bound}, least "
          f"{least * 1e3:.2f} ms over {len(prog['durations_s'])} steps, "
          f"kernels {kernel_s * 1e3:.2f} ms", file=sys.stderr)
    return 100.0 * least / kernel_s
