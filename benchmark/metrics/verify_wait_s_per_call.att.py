"""verify_wait_s_per_call.att: seconds one device batch verification that
began in the window spent waiting on the device, from the program's own
spans: the `bls_device_wait` spans (the subgroup check's read and the
pairing's answer) inside each `bls_device_verify` span."""

from benchmark import program


def read(run):
    found = program.verify_calls(run)
    if found is None:
        return None
    calls, waits = found
    return sum(waits) / len(calls)
