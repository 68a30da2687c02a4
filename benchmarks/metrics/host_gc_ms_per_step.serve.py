"""Garbage collection inside the scheduler's iterations, in ms a decode
step: the sum of ``serve.step{gc_ms}`` (the collections that ended
inside the iteration, on any thread: the interpreter runs one at a time
and it stops them all) over the window, over the same decode steps as
``sched_host_ms_per_step.serve``.  0.0 where nothing was collected;
nothing where no ``serve.step`` carries ``gc_ms`` (a program that does
not put the collector on the record).  On standard error: the
collections that became ``host.gc`` spans (generation 2, or over 1 ms)
by generation, and the longest."""


def read(run):
    import spans
    sp = spans.load(run)
    steps = sp and sp.named("serve.step")
    decodes = sum(s.fields.get("decode", 0) for s in steps or ())
    if not decodes or not any("gc_ms" in s.fields for s in steps):
        return None
    total = sum(s.fields.get("gc_ms", 0.0) for s in steps)
    by_gen = {}
    for s in sp.named("host.gc"):
        rec = by_gen.setdefault(s.fields.get("gen"), [0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += spans.ms(s)
        rec[2] = max(rec[2], spans.ms(s))
    worst = max(steps, key=lambda s: s.fields.get("gc_ms", 0.0))
    spans.note("host_gc_ms_per_step.serve",
               f"{total:.1f} ms of collection inside {len(steps)} "
               f"iterations ({decodes} decode steps), at most "
               f"{worst.fields.get('gc_ms', 0.0):.2f} ms in one; host.gc "
               "spans by generation: " + (", ".join(
                   f"gen {g}: {n} taking {t:.1f} ms, the longest {m:.2f}"
                   for g, (n, t, m) in sorted(by_gen.items(),
                                              key=lambda kv: str(kv[0])))
                   or "none"))
    return total / decodes
