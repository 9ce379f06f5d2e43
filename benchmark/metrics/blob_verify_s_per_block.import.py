"""blob_verify_s_per_block.import: seconds of the gossip checks of one
block's sidecars (the program's `blob_sidecar_verify` spans: header
signature, then KZG proof) that began in the window: the mean check times
the block's sidecars."""

from benchmark import program


def read(run):
    checks = program.window_spans(run, ("blob_sidecar_verify",))["blob_sidecar_verify"]
    if not checks:
        return None
    return sum(c.duration_s for c in checks) / len(checks) * run.sidecars_per_block
