"""The fleet_drift kernel's share of its roofline: the least time the
chip could take for the calls' bytes and operations (flops.py) over the
device time of the `jit_fleet_drift` program in the trace."""
from chipbench import flops


def read(run):
    if run.trace is None or not run.drift_calls:
        return None
    secs, runs = run.trace.module_time("jit_fleet_drift", *run.window)
    if runs == 0 or secs <= 0:
        return None
    need = sum(flops.roofline_seconds(flops.fleet_drift_cost(*c), run.peak)
               for c in run.drift_calls)
    return 100.0 * need / secs
