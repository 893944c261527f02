"""Share of the traced training window in which the device ran nothing."""


def read(run):
    s = run.trace.idle_share()
    return None if s is None else 100.0 * s
