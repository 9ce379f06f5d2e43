"""store_write_s_per_block.import: seconds of the import's store write
(block, state or its summary, sidecars: the program's `import_store_write`
span), per block imported in the window."""

from benchmark import import_spans


def read(run):
    return import_spans.per_block(run, ("import_store_write",))
