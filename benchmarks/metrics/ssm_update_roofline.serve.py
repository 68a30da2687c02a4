"""Roofline share of the state-space mixers' one-step update: the least
time the chip could take to read and write the SSM state of every LIVE
lane once (``work_<family>.ssm_update``: a ``[heads, P, N]`` float32
matrix set a lane, read and written, and 5 FLOPs an element, held against
the chip's published matrix peak for want of a vector one: the bytes
bound it sixty times over) in each
state-space layer (``layer_counts(cfg)["ssm"]``) of every decode step of
the traced window, over the summed device time of the ``ssm_update``
kernels inside the ``step`` program (the family's ``KERNEL_CLASSES``,
reduced by the driver), in percent.  Live lanes: the tokens the decode
steps of the traced part emitted (a finished or empty lane is skipped by
the kernel and emits nothing).  The bound (expected: HBM) is printed on
standard error."""
import sys


def read(run):
    kernel_s = (run.records.get("kernel_class_s") or {}).get("ssm_update")
    lane_steps = run.records.get("tokens_in_trace")
    red = run.reduced
    prog = red and red.program(run.family.PROGRAMS["decode_step"])
    work = getattr(run, "family_work", None)    # the driver's, by family
    if not kernel_s or not lane_steps or not prog or not work \
            or not hasattr(work, "ssm_update"):
        return None
    cfg = run.cfg
    # the trace may hold one step more or fewer than the host counted
    scale = len(prog["durations_s"]) / max(run.records["steps_in_trace"], 1)
    flops, nbytes = work.ssm_update(lane_steps * scale, cfg)
    layers = work.layer_counts(cfg)["ssm"]
    least, bound = run.work.roofline_seconds(
        flops * layers, nbytes * layers, run.peaks, cfg["dtype"])
    print(f"ssm_update_roofline.serve: bound by {bound}, least "
          f"{least * 1e3:.2f} ms over {len(prog['durations_s'])} steps in "
          f"{layers} state-space layers ({lane_steps * scale:.0f} lane "
          f"steps), kernels {kernel_s * 1e3:.2f} ms", file=sys.stderr)
    return 100.0 * least / kernel_s
