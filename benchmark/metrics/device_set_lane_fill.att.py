"""device_set_lane_fill.att: percent of the device verifier's set lanes
that carried a signature set, over the batch verifications that began in
the window: the `sets` and `lanes` (the set axis padded to its chunk) of
the program's `bls_device_verify` spans."""

from benchmark import program


def read(run):
    found = program.verify_calls(run)
    if found is None:
        return None
    calls, _ = found
    return 100.0 * sum(c.fields["sets"] for c in calls) / sum(c.fields["lanes"] for c in calls)
