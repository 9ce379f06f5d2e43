"""setup_s: process start to the first timed submission (host clock):
loading the registry and the anchor state, booting the node, signing the
traffic, compile-cache loads and the warm-up batches."""


def read(run):
    return run.setup_s
