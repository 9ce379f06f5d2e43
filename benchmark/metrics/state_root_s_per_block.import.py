"""state_root_s_per_block.import: seconds of the post-state root the
block's state_root is checked against (the program's `import_state_root`
span), per block imported in the window."""

from benchmark import import_spans


def read(run):
    blocks = import_spans.imported(run)
    if not any(import_spans.program.within(b, "import_state_root") for b in blocks):
        return None
    return import_spans.per_block(run, ("import_state_root",))
