"""gossip_msgs_per_s: gossip messages given a verdict inside the measured
window, over the window's seconds (host clock)."""


def read(run):
    return run.in_window / run.seconds
