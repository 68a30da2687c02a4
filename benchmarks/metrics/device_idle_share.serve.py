"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, in percent."""


def read(run):
    return run.idle_share_pct()
