"""Load imbalance of the dropless expert layers in the window: the busiest
expert's rows over the mean expert's, from the program's counters
``moe.expert_rows_max`` (the busiest expert's rows, summed over layers and
steps) and ``moe.rows`` (all rows, summed alike): experts x rows_max /
rows.  1 is a perfectly even routing; the grouped products wait for the
busiest expert's tile.  A mean over the window weighted by rows: the
counters are drained every fourth step, so no per-step median exists."""


def read(run):
    rows = run.counters.get("moe.rows")
    if not rows:
        return None
    return run.cfg["num_experts"] * run.counters["moe.expert_rows_max"] / rows
