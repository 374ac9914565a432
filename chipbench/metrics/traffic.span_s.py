"""Seconds per timed window the benchmark's replay traffic spent
answering the controller's `sample` calls: the load generator's own
share of `window_s`."""


def read(run):
    return run.span_per_window("traffic")
