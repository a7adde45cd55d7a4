"""Share of the traced window in which no operation ran on the chip:
1 - (union of device op intervals) / window."""


def read(run):
    return 100.0 * run.trace.idle_share if run.trace is not None else None
