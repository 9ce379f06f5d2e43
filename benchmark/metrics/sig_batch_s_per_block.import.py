"""sig_batch_s_per_block.import: seconds of the block's one signature
batch (the program's `signature_batch_verify` span inside `block_import`),
per block imported in the window."""

from benchmark import import_spans


def read(run):
    return import_spans.per_block(run, ("signature_batch_verify",))
