"""gossip_host_s_per_msg.att: host seconds of the gossip batch path outside
the BLS verifier, per message, from the program's own spans under each
`attestation_batch` that began in the window: `attestation_prepare`
(checks, committees, signing roots, decompression), `attestation_fallback`
(per-item verification after a failed batch) and `attestation_apply`
(import lock, fork choice, op pool), over the messages of those batches,
each of which is given a verdict."""

from benchmark import program

STAGES = ("attestation_prepare", "attestation_fallback", "attestation_apply")


def read(run):
    batches = program.window_spans(run, ("attestation_batch",))["attestation_batch"]
    stages = [s for b in batches for name in STAGES for s in program.within(b, name)]
    if not any(s.name == "attestation_prepare" for s in stages):
        return None
    return sum(s.duration_s for s in stages) / sum(b.fields["n"] for b in batches)
