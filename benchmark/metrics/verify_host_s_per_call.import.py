"""verify_host_s_per_call.import: host seconds of one device batch
verification in the signature batch of a block imported in the window:
a `bls_device_verify` span less the `bls_device_wait` spans inside it
(decoding, key aggregation, limb conversion, hashing and dispatch)."""

from benchmark import import_spans


def read(run):
    calls = import_spans.verify_calls(run)
    if calls is None:
        return None
    return sum(c.duration_s - import_spans.wait_s(c) for c in calls) / len(calls)
