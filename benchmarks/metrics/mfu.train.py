"""Whole-step model FLOP/s utilisation of training: the matrix-product and
attention FLOPs that forward and backward need (``work.train_step_flops``,
no recomputation) for every step of the window, over the window and the
chip's published bf16 peak, in percent."""


def read(run):
    rec = run.records
    if not rec.get("steps") or run.window_s <= 0:
        return None
    flops = run.work.train_step_flops(run.cfg, rec["batch"], rec["seq_len"])
    peak = run.peaks["flops_per_s"][run.cfg["dtype"]]
    return 100.0 * flops * rec["steps"] / run.window_s / peak
