"""95th percentile of the program's own ``serve.queue_wait`` spans (submit
-> the request leaves the queue) over the requests submitted in the
window, in ms; a request that never left the queue counts its whole life.
The harness's ``queue_wait_p95_ms.serve`` times the same wait from outside
(due -> the admitting ``engine.step()`` call) and carries the generator's
lateness besides."""


def read(run):
    import spans
    from common import percentile
    sp = spans.load(run)
    waits = sp and [spans.ms(s) for s in sp.named("serve.queue_wait")]
    if not waits:
        return None
    spans.note("sched_queue_wait_p95_ms.serve",
               f"p50 {percentile(waits, 50):.2f} ms, max {max(waits):.2f} "
               f"ms over {len(waits)} requests")
    return percentile(waits, 95)
