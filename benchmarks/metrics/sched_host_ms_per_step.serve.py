"""The scheduler's own host time per decode step, in ms: the self time of
every ``serve.step`` of the window and of everything under it that is not
a blocking read (``serve.sync``), summed, over the number of iterations
that dispatched a decode step (``serve.step{decode=1}``).  What is left
when the device's time is taken out: admission bookkeeping, dispatch,
the poll's completions, the telemetry drained in the poll.  The split by
child goes to standard error."""


def read(run):
    import spans
    sp = spans.load(run)
    steps = sp and sp.named("serve.step")
    decodes = sum(s.fields.get("decode", 0) for s in steps or ())
    if not decodes:
        return None
    split = {}
    for step in steps:
        for s in [step] + sp.descendants(step):
            if s.name != "serve.sync":
                split[s.name] = split.get(s.name, 0.0) + sp.self_ms(s)
    total = sum(split.values())
    spans.note("sched_host_ms_per_step.serve",
               f"{total:.1f} ms of host time over {len(steps)} iterations, "
               f"{decodes} with a decode step; self time by span: "
               + ", ".join(f"{k} {v:.1f} ms" for k, v in
                           sorted(split.items(), key=lambda kv: -kv[1])))
    return total / decodes
