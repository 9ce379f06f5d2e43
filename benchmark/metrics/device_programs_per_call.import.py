"""device_programs_per_call.import: device programs one batch
verification launched, over the calls in the signature batches of the
blocks imported in the window: the `programs` field the program sets on
each `bls_device_verify` span as the call ends. A program whose spans
lack the field reads None."""

from benchmark import import_spans


def read(run):
    calls = import_spans.verify_calls(run)
    if calls is None:
        return None
    counts = [c.fields.get("programs") for c in calls]
    if None in counts:
        return None
    return sum(counts) / len(counts)
