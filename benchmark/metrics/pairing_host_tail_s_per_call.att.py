"""pairing_host_tail_s_per_call.att: host seconds one device batch
verification that began in the window spent finishing its pairing check,
from the program's own spans: the `bls_pairing_host_tail` spans (the
product of the Miller lanes and the final exponentiation, on the host)
inside each `bls_device_verify` span. A program that finishes the check
on the device records no such span, and reads nothing."""

from benchmark import program


def read(run):
    calls = program.window_spans(run, ("bls_device_verify",))["bls_device_verify"]
    tails = [t for c in calls for t in program.within(c, "bls_pairing_host_tail")]
    if not tails:
        return None
    return sum(t.duration_s for t in tails) / len(calls)
