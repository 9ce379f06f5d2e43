"""device_calls_per_block.import: device batch verifications (the
program's `bls_device_verify` spans) inside the signature batch of each
block imported in the window, per block: the batch's sets in 32-set
chunks. The tampered block's calls, rejected, are not counted."""

from benchmark import import_spans


def read(run):
    calls = import_spans.verify_calls(run)
    if calls is None:
        return None
    return len(calls) / len(import_spans.imported(run))
