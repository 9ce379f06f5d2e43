"""transition_host_s_per_block.import: host seconds of the state
transition outside its signature batch (the program's `state_transition`
span less its `signature_batch_verify`, inside `block_import`), per block
imported in the window; the post-state root is part of it."""

from benchmark import import_spans


def read(run):
    total = import_spans.per_block(run, ("state_transition",))
    if total is None:
        return None
    return total - import_spans.per_block(run, ("signature_batch_verify",))
