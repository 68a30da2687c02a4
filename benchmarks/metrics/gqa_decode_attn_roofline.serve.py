"""Roofline share of the paged decode-attention kernel under grouped kv
heads, in a model whose layers are not all attention: the least time the
chip could take to read the K and V actually valid in every row, of the
KV heads only, and do 4 * kv_len * (query heads x head_dim) FLOPs a row
(``work_<family>.decode_attention``), in each ATTENTION layer
(``layer_counts(cfg)["attn"]``), summed over the decode steps of the
traced window, over the summed device time of the ``flash_decode_paged``
kernels inside the ``step`` program (the family's ``KERNEL_CLASSES``,
reduced by the driver), in percent.  The bound (expected: HBM) is printed
on standard error."""
import sys


def read(run):
    kernel_s = (run.records.get("kernel_class_s") or {}).get("decode_attn")
    attended = run.records.get("attended_in_trace")
    red = run.reduced
    prog = red and red.program(run.family.PROGRAMS["decode_step"])
    work = getattr(run, "family_work", None)    # the driver's, by family
    if not kernel_s or not attended or not prog or not work:
        return None
    cfg = run.cfg
    # the trace may hold one step more or fewer than the host counted
    scale = len(prog["durations_s"]) / max(run.records["steps_in_trace"], 1)
    flops, nbytes = work.decode_attention(attended * scale, cfg)
    layers = work.layer_counts(cfg)["attn"]
    least, bound = run.work.roofline_seconds(
        flops * layers, nbytes * layers, run.peaks, cfg["dtype"])
    print(f"gqa_decode_attn_roofline.serve: bound by {bound}, least "
          f"{least * 1e3:.2f} ms over {len(prog['durations_s'])} steps in "
          f"{layers} attention layers, kernels {kernel_s * 1e3:.2f} ms",
          file=sys.stderr)
    return 100.0 * least / kernel_s
