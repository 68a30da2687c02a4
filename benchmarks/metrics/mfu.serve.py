"""Whole-step model FLOP/s utilisation of serving: forward FLOPs of every
prompt token prefilled and every token decoded in the window
(``work.forward_flops``: true tokens, not bucket padding), over the window
and the chip's published bf16 peak, in percent."""


def read(run):
    flops = run.records.get("forward_flops_in_window")
    if not flops or run.window_s <= 0:
        return None
    peak = run.peaks["flops_per_s"][run.cfg["dtype"]]
    return 100.0 * flops / run.window_s / peak
