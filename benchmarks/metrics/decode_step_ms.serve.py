"""Median device duration of one execution of the engine's decode ``step``
program, from the trace's module events (ms)."""


def read(run):
    return run.program_median_ms("decode_step")
