"""95th percentile duration of ``serve.sync{site=poll}``, the scheduler
poll's blocking read of the lanes, over the polls of the window, in ms:
how long the one scheduler thread, and with it admission, is locked out
behind the decode steps already on the device."""


def read(run):
    import spans
    from common import percentile
    sp = spans.load(run)
    polls = sp and sp.named("serve.sync", site="poll")
    if not polls:
        return None
    waits = [spans.ms(s) for s in polls]
    behind = [s.fields.get("steps_queued", 0) for s in polls]
    spans.note("poll_sync_p95_ms.serve",
               f"p50 {percentile(waits, 50):.2f} ms over {len(waits)} "
               f"polls, behind {sum(behind) / len(behind):.2f} decode "
               "steps on average")
    return percentile(waits, 95)
