"""Roofline share of the flash-attention kernels in the train step: the least
time the chip could take for forward + backward attention of every layer
(ops and bytes from shapes, ``work.flash_forward`` / ``flash_backward``)
over the summed device time of the Mosaic custom calls inside one
execution of the train-step program (median execution), in percent.  The
bound that sets the least time is printed on standard error."""


import statistics
import sys


def read(run):
    red = run.reduced
    prog = red and red.program(run.family.PROGRAMS["train_step"])
    if not prog:
        return None
    kernel_s = [k for k in prog["kernel_s"] if k > 0]
    if not kernel_s:
        return None
    cfg, rec, w = run.cfg, run.records, run.work
    shape = (rec["batch"], rec["seq_len"], cfg["num_heads"], cfg["head_dim"])
    f1, b1 = w.flash_forward(*shape)
    f2, b2 = w.flash_backward(*shape)
    least, bound = w.roofline_seconds(
        (f1 + f2) * cfg["num_layers"], (b1 + b2) * cfg["num_layers"],
        run.peaks, cfg["dtype"])
    print(f"flash_roofline.train: bound by {bound}, least "
          f"{least * 1e3:.3f} ms, kernels {statistics.median(kernel_s) * 1e3:.3f} ms",
          file=sys.stderr)
    return 100.0 * least / statistics.median(kernel_s)
