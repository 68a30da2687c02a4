"""95th percentile, over the requests due in the window, of the time from
when a request was due until the scheduler iteration that admitted it
began (ms).  The program stamps ``admitted_at`` only after the prefill, so
the harness's own ``engine.step`` span is the clock here."""


def read(run):
    waits = run.records.get("queue_waits_ms")
    if not waits:
        return None
    from common import percentile
    return percentile(waits, 95)
