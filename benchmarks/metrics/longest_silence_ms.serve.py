"""The longest stretch without a span boundary inside one scheduler
iteration, in ms: over every ``serve.step`` of the window, the largest
distance between two consecutive stamps (a start or an end) of the step
and everything under it.  It comes from the spans' own stamps, so it
reads on a program without the stall watcher too.  A sound run reads one
poll's read or one long prefill (25-100 ms); a stalled one reads
seconds.  On standard error: the span the stretch lay in with its
fields, and the program's ``serve.stall`` event for it (what every
thread's stack showed, the garbage collector's part) where it left
one."""


def _stall_event(t0_ns, t1_ns):
    """The ``serve.stall`` the program recorded for this stretch: stamped
    where the stretch ended."""
    try:
        from paddle_tpu.core import flight_recorder as fr
        hits = [f for t, kind, f in fr.events() if kind == "serve.stall"
                and t0_ns < t <= t1_ns + 1_000_000]
    except Exception:
        return None
    return hits[-1] if hits else None


def read(run):
    import spans
    sp = spans.load(run)
    steps = sp and sp.named("serve.step")
    if not steps:
        return None
    worst = None                    # (ns, from, to, the step's spans)
    for step in steps:
        inside = [step] + sp.descendants(step)
        stamps = sorted({t for s in inside for t in (s.start_ns, s.end_ns)})
        for a, b in zip(stamps, stamps[1:]):
            if worst is None or b - a > worst[0]:
                worst = (b - a, a, b, inside)
    if worst is None:
        return None
    gap, a, b, inside = worst
    # the innermost span open through it: the last to start among those
    # that cover it
    inner = max((s for s in inside if s.start_ns <= a and s.end_ns >= b),
                key=lambda s: s.start_ns)
    text = (f"{gap / 1e6:.1f} ms inside {inner.name} {inner.fields} "
            f"({spans.ms(inner):.1f} ms), {(a - sp.t0_ns) / 1e9:.2f} s "
            f"into the window, over {len(steps)} iterations")
    stall = _stall_event(a, b)
    if stall:
        text += "; serve.stall: " + " ".join(
            f"{k}={stall[k]!r}" for k in
            ("ms", "gc_ms", "samples", "late_ms", "cpu_ms",
             "thread_cpu_ms", "top", "others", "stack") if k in stall)
    spans.note("longest_silence_ms.serve", text)
    return gap / 1e6
