"""host_s_per_msg.att: seconds of the window in which no device batch
verification was running, per message given a verdict in the window: the
gossip path's host work (decode, checks, committee lookup, signing roots,
signature decompression, fork choice, op pool) that the device does not
hide."""

from benchmark.trace import union


def read(run):
    if not run.in_window:
        return None
    inside = union([(c.start, c.end) for c in run.calls], run.t0, run.t_end)
    covered = sum(e - s for s, e in inside)
    return (run.seconds - covered) / run.in_window
