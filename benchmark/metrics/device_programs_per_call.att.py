"""device_programs_per_call.att: device programs one batch verification
launched, over the calls that began in the window: the `programs` field
the program sets on each `bls_device_verify` span as the call ends. A
program whose spans lack the field reads None."""

from benchmark import program


def read(run):
    found = program.verify_calls(run)
    if found is None:
        return None
    counts = [c.fields.get("programs") for c in found[0]]
    if None in counts:
        return None
    return sum(counts) / len(counts)
