"""verify_host_s_per_call.att: host seconds of one device batch
verification that began in the window, from the program's own spans: a
`bls_device_verify` span less the `bls_device_wait` spans inside it (the
host's decoding, limb conversion, message grouping, hashing and dispatch
of the device programs)."""

from benchmark import program


def read(run):
    found = program.verify_calls(run)
    if found is None:
        return None
    calls, waits = found
    return (sum(c.duration_s for c in calls) - sum(waits)) / len(calls)
