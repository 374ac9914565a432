"""Seconds per timed window spent in observe of the fleet drift plane."""


def read(run):
    return run.span_per_window("drift")
