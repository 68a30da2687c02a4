"""Median ``train.step`` span of the window, in ms: the host side of one
``TrainStep`` call (argument flattening, tracker, dispatch).  The device
runs on after it returns; once the dispatch queue is full the call blocks
until a slot frees, so a median near the device's step time means the
host is the one waiting."""


def read(run):
    import statistics
    import spans
    sp = spans.load(run)
    steps = sp and sp.named("train.step")
    if not steps:
        return None
    durs = [spans.ms(s) for s in steps]
    spans.note("dispatch_ms.train",
               f"max {max(durs):.2f} ms over {len(durs)} calls, "
               f"{sum(s.fields.get('compiled', 0) for s in steps)} of "
               "which built a program")
    return statistics.median(durs)
