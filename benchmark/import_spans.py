"""What the per-layer metrics of block import read: the program's
`block_import` trees that began in the window and imported their block,
and the spans inside them (OBSERVABILITY.md). A block import is a
`block_import` tree with an `import_store_write` span: a block staged to
wait for its sidecars, or rejected, never writes. A program without these
spans reads None, never an error."""

from __future__ import annotations

from . import program


def imported(run) -> list:
    """The block_import trees of the window that imported a block."""
    roots = program.window_spans(run, ("block_import",))["block_import"]
    return [r for r in roots if program.within(r, "import_store_write")]


def per_block(run, names) -> float | None:
    """Seconds of the spans named `names` inside each imported block's
    tree, per block; None without such spans."""
    blocks = imported(run)
    if not blocks:
        return None
    return sum(s.duration_s for b in blocks for n in names for s in program.within(b, n)) / len(
        blocks
    )


def verify_calls(run) -> list | None:
    """The device batch verifications (`bls_device_verify` spans) inside
    the signature batches of the window's imported blocks: a rejected
    block's calls, and any outside a block's batch, are left out. None
    without such spans."""
    calls = [
        c
        for b in imported(run)
        for batch in program.within(b, "signature_batch_verify")
        for c in program.within(batch, "bls_device_verify")
    ]
    return calls or None


def wait_s(call) -> float:
    """Seconds a device batch verification waited on the device."""
    return sum(w.duration_s for w in program.within(call, "bls_device_wait"))
