"""Median device duration of one execution of the engine's ``admit``
program, from the trace's module events (ms): the install of a prefill's
row into a freed slot (its pages and, where the cache holds per-lane
state beside them, that too), during which every lane waits."""


def read(run):
    return run.program_median_ms("admit")
