"""95th percentile of the program's own ``serve.prefill`` spans (the
request leaves the queue -> its first token is on the host) over the
requests submitted in the window, in ms.  Beside it on standard error:
the median, how many decode steps its sync waited behind on average
(``serve.sync{site=prefill}.steps_queued``), and the prefill program's
median device time from the trace: the wait is the device's queue, not
the prefill."""


def read(run):
    import spans
    from common import percentile
    sp = spans.load(run)
    if not sp:
        return None
    reqs = {s.fields.get("req") for s in sp.named("serve.queue_wait")}
    pre = [s for s in sp.all if s.name == "serve.prefill"
           and s.fields.get("req") in reqs]
    if not pre:
        return None
    waits = [spans.ms(s) for s in pre]
    ends = {s.end_ns for s in pre}
    behind = [s.fields.get("steps_queued", 0) for s in sp.all
              if s.name == "serve.sync" and s.end_ns in ends
              and s.fields.get("site") == "prefill"]
    device = run.program_median_ms("prefill")
    spans.note("prefill_wait_p95_ms.serve",
               f"p50 {percentile(waits, 50):.2f} ms over {len(waits)} "
               f"requests; its sync waited behind "
               f"{sum(behind) / max(len(behind), 1):.2f} decode steps on "
               f"average; the prefill program's device median "
               + (f"{device:.2f} ms" if device else "not in the trace"))
    return percentile(waits, 95)
