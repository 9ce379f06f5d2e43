"""verify_wait_s_per_call.import: seconds one device batch verification
in the signature batch of a block imported in the window spent waiting
on the device: the `bls_device_wait` spans inside its
`bls_device_verify` span."""

from benchmark import import_spans


def read(run):
    calls = import_spans.verify_calls(run)
    if calls is None:
        return None
    return sum(import_spans.wait_s(c) for c in calls) / len(calls)
