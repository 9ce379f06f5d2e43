"""device_idle_share.att: percent of the traced sub-window in which no
operation ran on the device (profiler trace)."""

from benchmark import trace


def read(run):
    if run.reduced is None or not run.reduced.ops:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.reduced) / run.reduced.window_s)
