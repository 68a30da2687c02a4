"""Programs compiled for a new shape inside the measured window: the
program's ``jit.compile{cause=new_shape}`` counter, end minus start.
Should be 0."""


def read(run):
    return run.counters.get("compiles_in_window")
