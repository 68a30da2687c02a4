"""What a poll does for the monitor and not for the scheduler, in ms a
decode step: the duration of the window's ``serve.telemetry`` spans
(token latency, per-request cost attribution, goodput charge and flush,
cache and page occupancy, quantization clips, ``slo.tick()``) over the
same decode steps as ``sched_host_ms_per_step.serve``, of which it is a
part.  Nothing where the program has no such span.  On standard error:
the cost a poll."""


def read(run):
    import spans
    sp = spans.load(run)
    tele = sp and sp.named("serve.telemetry")
    decodes = sum(s.fields.get("decode", 0)
                  for s in (sp.named("serve.step") if sp else ()))
    if not tele or not decodes:
        return None
    total = sum(spans.ms(s) for s in tele)
    polls = len(sp.named("serve.poll")) or 1
    spans.note("telemetry_ms_per_step.serve",
               f"{total:.1f} ms in {len(tele)} spans over {polls} polls: "
               f"{total / polls:.3f} ms a poll, the longest "
               f"{max(spans.ms(s) for s in tele):.3f} ms; {decodes} "
               "decode steps")
    return total / decodes
