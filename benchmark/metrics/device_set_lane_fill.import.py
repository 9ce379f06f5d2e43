"""device_set_lane_fill.import: percent of the device verifier's set
lanes that carried a signature set, over the batch verifications in the
signature batches of the blocks imported in the window: the `sets` and
`lanes` fields of the program's `bls_device_verify` spans."""

from benchmark import import_spans


def read(run):
    calls = import_spans.verify_calls(run)
    if calls is None:
        return None
    return 100.0 * sum(c.fields["sets"] for c in calls) / sum(c.fields["lanes"] for c in calls)
