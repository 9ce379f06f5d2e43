"""head_update_s_per_block.import: seconds of fork choice in the import
(the program's `fork_choice_on_block` and `import_head_update` spans: the
block, its attestations, the head), per block imported in the window."""

from benchmark import import_spans


def read(run):
    return import_spans.per_block(run, ("fork_choice_on_block", "import_head_update"))
