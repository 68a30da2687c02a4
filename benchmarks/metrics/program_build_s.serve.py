"""Seconds a serving run spent building or loading programs before its
window opened: ``spans.program_build_s``."""


def read(run):
    import spans
    return spans.program_build_s(run)
