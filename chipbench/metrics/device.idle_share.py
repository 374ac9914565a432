"""Percent of the traced timed loop in which no operation ran on the
device (1 - union of device-op intervals / window)."""


def read(run):
    if run.trace is None or run.busy_s is None:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
