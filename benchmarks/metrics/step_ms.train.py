"""Median device duration of one execution of the train-step program,
from the trace's module events (ms)."""


def read(run):
    return run.program_median_ms("train_step")
