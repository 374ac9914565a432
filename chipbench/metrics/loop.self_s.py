"""Seconds per timed window in `run_window` outside every stage span:
the controller's own per-camera loops and bookkeeping."""


def read(run):
    return (run.elapsed - sum(run.spans.values())) / run.windows
