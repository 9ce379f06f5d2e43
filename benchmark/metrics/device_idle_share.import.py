"""device_idle_share.import: percent of the traced sub-window in which no
operation ran on the device (profiler trace); idle gaps are named by the
kind's bench.block_import spans. The sub-window (0.25 s from 2 s into the
window) falls in the first window block's signature batch, or in the next
block's sidecar checks after a tampered copy rejected early: a sub-window
with no device operation in it reads 100."""

from benchmark import trace


def read(run):
    if run.reduced is None or run.reduced.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.reduced) / run.reduced.window_s)
